"""Zygote fork-server: pre-imports the worker stack once, forks workers in ms.

The reference's WorkerPool keeps worker *processes* warm (prestart + startup
tokens, src/ray/raylet/worker_pool.h:104,349,427) because forking a Python
interpreter that has already imported the runtime is two orders of magnitude
cheaper than exec'ing a fresh one: on a small host creating hundreds of
actors, cold spawns serialize on the CPU and cap actor creation at a few
per second.

One zygote process serves one node (it is env-configured for that node's
store/socket). Protocol over an authenticated Unix socket, one connection
per spawn:

    request:  {"env": {full worker environment}}
    reply:    {"pid": <forked worker pid>}  or  {"error": "..."}
    request:  {"type": "shutdown"}          -> zygote exits

The fork is safe by construction: the zygote's only thread is the accept
loop (no locks can be held across fork), and it never creates a jax backend
or touches the TPU — the worker of a chip lease needs the lease's
environment from interpreter start, so it always cold-spawns through
subprocess instead (node_manager.spawn_worker_process).

Forked workers are auto-reaped (SIGCHLD ignored in the zygote; the child
restores default handling so user code's subprocesses wait() normally).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Dict, Optional


def serve(socket_path: str, authkey: bytes) -> None:
    """Zygote main loop. Runs in a dedicated process.

    One PERSISTENT connection per client (request/reply in lockstep): the
    per-spawn cost is one small recv + fork + one small send, not a fresh
    socket connect + HMAC challenge (which costs more than the fork
    itself). Clients reconnect if the connection drops."""
    from multiprocessing.connection import Listener

    # preload everything a worker touches so forked children import nothing:
    # the worker module pulls in serialization (cloudpickle), the native shm
    # client, and the task executor machinery; numpy dominates user payloads.
    # The tail of lazy imports (cloudpickle, json, runtime_env, utils — all
    # touched on the first create_actor/exec) was measured at ~0.2s of
    # per-child CPU; importing them here moves that cost to zygote startup,
    # paid once.
    import dataclasses  # noqa: F401
    import json  # noqa: F401

    import cloudpickle  # noqa: F401
    import numpy  # noqa: F401

    from .. import runtime_env, serialization, utils  # noqa: F401
    from ..utils import actor_pool, queue, timeline  # noqa: F401
    from . import (  # noqa: F401
        device_store,
        placement_group,
        resources,
        scheduling_strategies,
        worker,
        worker_main,
    )

    # freeze the preloaded heap into gc's permanent generation: forked
    # children's collector then never scans (and so never copy-on-writes)
    # the module objects they inherited — the standard prefork-server gc
    # discipline for CPython
    import gc

    gc.collect()
    gc.freeze()

    signal.signal(signal.SIGCHLD, signal.SIG_IGN)  # auto-reap forked workers
    listener = Listener(socket_path, family="AF_UNIX", authkey=authkey)
    # Child baseline for the env-delta protocol. The CLIENT ships its
    # _base_env with each fresh connection ("base_env" key on the first
    # frame): children must reset to the exact dict deltas were computed
    # against. Neither the zygote's launch environ nor a serve-time
    # snapshot can stand in for it — a preloaded class's imports can
    # mutate os.environ after serve begins, and that drift must never
    # leak into workers. The startup snapshot below is only the fallback
    # for a client that never sent one (then deltas were computed against
    # the same launch env).
    base_env = {k: v for k, v in os.environ.items()
                if k != "RMT_ZYGOTE_AUTHKEY"}

    from ..utils.jax_backend import initialized_platforms

    # actor-class preload cache: the FIRST spawn carrying a given
    # cls_blob unpickles it HERE, once — every subsequent fork inherits
    # the loaded class via COW and skips the per-child cloudpickle.loads
    # (measured at a meaningful slice of the 2,000-actor burst's
    # per-child CPU). worker.create_actor checks this cache by cls_id.
    # Loading user code pre-fork risks the no-live-jax-backend invariant
    # (a blob whose import chain initializes a PJRT client would hand
    # every future child a fork-broken backend), so a load that trips
    # the guard below retires this zygote: the client cold-spawns the
    # current worker, blacklists the class, and starts a fresh zygote.
    def handle_one(req: dict) -> dict:
        """Serve one spawn request: preload (with the taint guard), fork,
        and — in the parent — return the reply dict. The forked child
        never returns (it becomes the worker and _exits)."""
        bootstrap = req.get("bootstrap")
        cls_cached = False
        if bootstrap is not None and not req.get("no_preload"):
            cls_id = bootstrap.get("cls_id")
            if cls_id is not None:
                if cls_id in worker.PRELOADED_CLASSES:
                    cls_cached = True
                elif bootstrap.get("cls_blob") is not None:
                    try:
                        worker.PRELOADED_CLASSES[cls_id] = \
                            cloudpickle.loads(bootstrap["cls_blob"])
                        cls_cached = True
                    except Exception:  # noqa: BLE001 — child loads
                        pass           # it from the blob as before
                    if initialized_platforms():
                        # the load initialized a backend in THIS
                        # process: forking now is unsafe. Retire.
                        worker.PRELOADED_CLASSES.pop(cls_id, None)
                        return {"cls_taint": True}
        try:
            pid = os.fork()
        except OSError as e:
            return {"error": repr(e)}
        if pid == 0:
            # --- child: become the worker ---------------------------------
            try:
                conn.close()
                listener.close()
                signal.signal(signal.SIGCHLD, signal.SIG_DFL)
                if "env" in req:
                    os.environ.clear()
                    os.environ.update(req["env"])
                else:
                    # delta protocol: the child resets to the FROZEN
                    # launch snapshot (the dict the client computed its
                    # delta against) — per spawn only the handful of
                    # per-worker vars cross the socket instead of the
                    # full ~3KB environment
                    os.environ.clear()
                    os.environ.update(base_env)
                    for k in req.get("env_removed") or ():
                        os.environ.pop(k, None)
                    os.environ.update(req.get("env_delta") or {})
                worker_main._bootstrap = bootstrap
                worker_main.main()
            except BaseException:  # noqa: BLE001 — never unwind into
                os._exit(1)        # the zygote's stack in a fork child
            os._exit(0)
        # --- parent -------------------------------------------------------
        # cls_cached acks the preload: the client then strips the
        # multi-KB cls_blob from subsequent spawns of this class
        return {"pid": pid, "cls_cached": cls_cached}

    while True:
        try:
            conn = listener.accept()
        except (OSError, EOFError):
            return
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                conn.close()
                break
            if msg.get("type") == "shutdown":
                conn.close()
                try:
                    listener.close()
                    os.unlink(socket_path)
                except OSError:
                    pass
                return
            if "base_env" in msg:
                base_env = {k: v for k, v in msg["base_env"].items()
                            if k != "RMT_ZYGOTE_AUTHKEY"}
            # batched spawns: concurrent client spawners combine into one
            # frame — a 2,000-actor burst pays one socket round trip (two
            # scheduling handoffs on a contended CPU) per BATCH of forks,
            # not per fork
            reqs = msg["spawns"] if "spawns" in msg else [msg]
            replies = []
            retire = False
            for req in reqs:
                rep = handle_one(req)
                replies.append(rep)
                if rep.get("cls_taint"):
                    retire = True  # unserved tail: client cold-spawns it
                    break
            out = {"replies": replies} if "spawns" in msg else replies[0]
            try:
                conn.send(out)
            except (OSError, BrokenPipeError):
                conn.close()
                break
            if retire:
                conn.close()
                try:
                    listener.close()
                    os.unlink(socket_path)
                except OSError:
                    pass
                return


class ForkedProc:
    """Popen-shaped facade over a worker forked by the zygote (we are not
    its parent, so liveness is a signal-0 probe and death is primarily
    detected by the runtime seeing the worker's pipe EOF — the same
    split RemoteProc uses for agent-spawned workers).

    PID-reuse guard: the kernel start time from /proc/<pid>/stat is
    recorded at creation; a recycled PID (worker died, auto-reaped, pid
    handed to an unrelated process) has a different start time, so poll()
    reports dead and terminate()/kill() refuse to signal the stranger."""

    __slots__ = ("pid", "returncode", "_starttime")

    def __init__(self, pid: int):
        self.pid = pid
        self.returncode: Optional[int] = None
        self._starttime = self._read_starttime(pid)
        if self._starttime is None:
            self.returncode = 1  # already gone before we looked

    @staticmethod
    def _read_starttime(pid: int) -> Optional[int]:
        try:
            with open(f"/proc/{pid}/stat", "rb") as f:
                # field 22, counting from 1, after the parenthesized comm
                return int(f.read().rsplit(b")", 1)[1].split()[19])
        except (OSError, IndexError, ValueError):
            return None

    def _alive(self) -> bool:
        st = self._read_starttime(self.pid)
        return st is not None and st == self._starttime

    def poll(self) -> Optional[int]:
        if self.returncode is not None:
            return self.returncode
        if not self._alive():
            self.returncode = 1
            return 1
        return None

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and time.monotonic() >= deadline:
                raise subprocess.TimeoutExpired(
                    f"forked-worker-{self.pid}", timeout)
            time.sleep(0.02)
        return self.returncode  # type: ignore[return-value]

    def terminate(self) -> None:
        if self.poll() is not None:
            return  # dead or pid recycled: never signal a stranger
        try:
            os.kill(self.pid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            self.returncode = self.returncode or 1

    def kill(self) -> None:
        if self.poll() is not None:
            return
        try:
            os.kill(self.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            self.returncode = self.returncode or 1


class _SpawnEntry:
    """One queued spawn request in the client's combining queue."""

    __slots__ = ("req", "reply", "done")

    def __init__(self, req: dict):
        self.req = req
        self.reply: Optional[dict] = None
        self.done = threading.Event()


class ZygoteClient:
    """Owns one zygote process and requests forks from it.

    ``spawn(env)`` returns a :class:`ForkedProc` or None (zygote not up /
    fork failed), in which case the caller falls back to a cold
    ``subprocess.Popen`` — the zygote is an accelerator, never a single
    point of failure."""

    def __init__(self, base_env: Dict[str, str], tag: str = "z"):
        self._authkey = os.urandom(16)
        self._socket_path = (
            f"/tmp/rmtZ_{os.getpid()}_{tag}_{os.urandom(3).hex()}.sock")
        env = dict(base_env)
        env["RMT_ZYGOTE_AUTHKEY"] = self._authkey.hex()
        # CPU platform, pinned: the zygote only ever forks CPU workers
        # (chip-leased workers always cold-spawn, spawn_worker_process),
        # and jax CAPTURES the platform list
        # at import — a class preload whose module chain imports jax
        # under any other value would poison every later child with a
        # platform no env reset can undo (the delta protocol resets
        # os.environ, not an already-imported jax's captured config)
        env["JAX_PLATFORMS"] = "cpu"
        # children inherit this exact dict; spawn() ships only the delta
        self._base_env = dict(env)
        self._proc = subprocess.Popen(
            [sys.executable, "-m",
             "ray_memory_management_tpu.core.zygote", self._socket_path],
            env=env, close_fds=True,
        )
        self._lock = threading.Lock()
        self._conn = None  # persistent request/reply connection
        self._ready = False
        # combining queue: concurrent spawners enqueue requests; whoever
        # holds the lock ships EVERY queued request as one batch frame
        self._q_mu = threading.Lock()
        self._q: list = []
        # actor classes the zygote confirmed preloaded (children inherit
        # them via COW): spawns of these ship WITHOUT the cls_blob
        self._cached_classes: set = set()
        # phase accounting for the scale bench (fork share of actor
        # creation): total forks requested, batch round trips made, and
        # seconds spent in them (seconds/forks = amortized per-fork RT)
        self.spawn_count = 0
        self.spawn_batches = 0
        self.spawn_seconds = 0.0

    def _connect(self, timeout: float = 10.0):
        from multiprocessing.connection import Client

        deadline = time.monotonic() + timeout
        while True:
            try:
                return Client(self._socket_path, family="AF_UNIX",
                              authkey=self._authkey)
            except (FileNotFoundError, ConnectionRefusedError, OSError):
                if (time.monotonic() >= deadline
                        or self._proc.poll() is not None):
                    return None
                time.sleep(0.02)

    def spawn(self, env: Dict[str, str],
              bootstrap: Optional[dict] = None) -> Optional[ForkedProc]:
        if self._proc.poll() is not None:
            return None
        base = self._base_env
        req: Dict[str, Any] = {
            "env_delta": {k: v for k, v in env.items()
                          if base.get(k) != v},
            "env_removed": [k for k in base
                            if k != "RMT_ZYGOTE_AUTHKEY"
                            and k not in env],
        }
        if bootstrap is not None:
            cls_id = bootstrap.get("cls_id")
            if cls_id is not None and cls_id in _taint_classes:
                # this class's preload once initialized a jax backend
                # inside a zygote: never preload it again
                req["no_preload"] = True
            elif cls_id is not None \
                    and cls_id in self._cached_classes \
                    and bootstrap.get("cls_blob") is not None:
                bootstrap = dict(bootstrap)
                del bootstrap["cls_blob"]  # zygote preloaded it
            req["bootstrap"] = bootstrap
        # combining: enqueue, then either become the leader (ship every
        # queued request as ONE batch frame) or wait for a leader to ship
        # ours. An actor burst's concurrent spawners pay one socket round
        # trip per batch instead of one per fork.
        entry = _SpawnEntry(req)
        with self._q_mu:
            self._q.append(entry)
        while not entry.done.is_set():
            if self._lock.acquire(timeout=0.02):
                try:
                    if not entry.done.is_set():
                        self._serve_batch_locked()
                finally:
                    self._lock.release()
            else:
                entry.done.wait(0.05)
        reply = entry.reply
        if reply is None:
            return None
        if reply.get("cls_taint"):
            # the zygote retired itself rather than fork with a live
            # backend; blacklist the class and cold-spawn this worker
            # (get_global() starts a fresh zygote on the next spawn)
            cid = bootstrap.get("cls_id") if bootstrap else None
            if cid is not None:
                _taint_classes.add(cid)
            return None
        pid = reply.get("pid")
        if pid and bootstrap is not None and reply.get("cls_cached"):
            cid = bootstrap.get("cls_id")
            if cid is not None:
                self._cached_classes.add(cid)
        return ForkedProc(pid) if pid else None

    def _serve_batch_locked(self) -> None:
        """With the leader lock held: ship every queued spawn request as
        one frame, distribute replies, wake the waiters. Entries the
        zygote did not serve (connection loss, taint retirement mid-
        batch, ANY unexpected error) resolve to None and their callers
        cold-spawn — a leader must never strand the spawners riding its
        batch, so nothing here may raise once the queue is drained."""
        with self._q_mu:
            batch = self._q
            self._q = []
        if not batch:
            return
        try:
            self._serve_batch(batch)
        finally:
            for e in batch:  # idempotent: already-served entries are set
                if not e.done.is_set():
                    e.reply = None
                    e.done.set()

    def _serve_batch(self, batch) -> None:
        t0 = time.monotonic()
        if self._proc.poll() is not None:
            return
        # first use waits for the zygote to finish its import preload
        frame = {"spawns": [e.req for e in batch]}
        if self._conn is None:
            try:
                self._conn = self._connect(
                    timeout=1.0 if self._ready else 15.0)
            except Exception:  # noqa: BLE001 — e.g. AuthenticationError
                self._conn = None
            if self._conn is None:
                return
            self._ready = True
            # fresh connection: ship the baseline the deltas are computed
            # against — the zygote's own environ can drift from it through
            # preload imports
            frame["base_env"] = self._base_env
        try:
            self._conn.send(frame)
            replies = self._conn.recv()["replies"]
        except Exception:  # noqa: BLE001 — conn loss, protocol drift:
            try:                          # reset; the batch cold-spawns
                self._conn.close()
            except OSError:
                pass
            self._conn = None
            return
        self.spawn_seconds += time.monotonic() - t0
        self.spawn_count += len(batch)
        self.spawn_batches += 1
        for i, e in enumerate(batch):
            e.reply = replies[i] if i < len(replies) else None
            e.done.set()

    def close(self) -> None:
        if self._proc.poll() is None:
            with self._lock:
                conn = self._conn if self._conn is not None \
                    else self._connect(timeout=0.5)
                self._conn = None
                if conn is not None:
                    try:
                        conn.send({"type": "shutdown"})
                    except (OSError, BrokenPipeError):
                        pass
                    try:
                        conn.close()
                    except OSError:
                        pass
            try:
                self._proc.wait(timeout=2.0)
            except subprocess.TimeoutExpired:
                self._proc.terminate()
        try:
            os.unlink(self._socket_path)
        except OSError:
            pass


# ---------------------------------------------------------------- singleton
# One zygote serves every node hosted by this OS process (the worker env is
# per-request, so the server is node-agnostic): the driver's head-local
# nodes share one, each node agent has its own in its own process.
_global: Optional[ZygoteClient] = None
_global_mu = threading.Lock()
# classes whose preload initialized a jax backend inside a zygote (which
# then retired itself): survives zygote replacement so the same class
# can never taint the successor
_taint_classes: set = set()


def peek_global() -> Optional[ZygoteClient]:
    """The current zygote if one is running — never starts one. For
    observers (bench phase accounting) that must not pay for, or gate on,
    a fork server the config may have disabled."""
    return _global


def get_global() -> Optional[ZygoteClient]:
    """The process-wide zygote, started on first use. None if disabled or
    its process died (callers then cold-spawn)."""
    global _global
    with _global_mu:
        if _global is not None and _global._proc.poll() is not None:
            _global = None  # zygote died: replace it
        if _global is None:
            from .node_manager import package_env

            try:
                _global = ZygoteClient(package_env())
            except Exception:  # noqa: BLE001 — never block worker spawn
                return None
        return _global


def shutdown_global() -> None:
    global _global
    with _global_mu:
        if _global is not None:
            _global.close()
            _global = None


def main(argv=None) -> int:
    socket_path = (argv or sys.argv[1:])[0]
    authkey = bytes.fromhex(os.environ.pop("RMT_ZYGOTE_AUTHKEY"))
    # die with the owning process: a head/agent that exits without a clean
    # shutdown (SIGKILL, crashed test) must not leak a forever-accepting
    # zygote. PDEATHSIG is cleared on fork, so workers are unaffected.
    try:
        import ctypes
        import signal as _sig

        PR_SET_PDEATHSIG = 1
        ctypes.CDLL("libc.so.6", use_errno=True).prctl(
            PR_SET_PDEATHSIG, _sig.SIGTERM, 0, 0, 0)
        if os.getppid() == 1:
            return 0  # parent already gone before prctl landed
    except Exception:  # noqa: BLE001 — non-Linux: rely on clean shutdown
        pass
    serve(socket_path, authkey)
    return 0


if __name__ == "__main__":
    sys.exit(main())
