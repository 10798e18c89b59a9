"""Peer-to-peer object transfer plane.

Every node — the head and each node agent — runs a :class:`TransferServer`
over its object store. All cross-node object movement is receiver-driven:
the destination dials the source's server and streams chunks STRAIGHT into
its own store allocation (``recv_bytes_into`` lands on the shm mapping, no
intermediate buffer). The head brokers only *locations* (who has the object,
where their server listens); payload bytes never transit the head.

This is the reference object manager's design (receiver-driven pulls over
dedicated gRPC streams, src/ray/object_manager/object_manager.h:114, chunked
per object_manager.proto:63-67) with three throughput refinements:

  * **Striped pulls** (wire protocol v2): objects at or above
    ``transfer_stripe_threshold`` are fetched as ``transfer_stripe_count``
    parallel range requests, each streaming a disjoint ``{oid, offset,
    length}`` slice of the SAME destination allocation over its own
    connection. The object is sealed once after every stripe lands; any
    stripe failure aborts the unsealed create so a retry re-allocates.
  * **Connection reuse**: the server runs a request LOOP per authenticated
    connection (idle-timeout bounded) instead of one request per
    connection, and clients keep idle connections in a
    :class:`ConnectionPool` keyed by (host, port, authkey). The
    challenge/response handshake — two round trips plus HMAC, the dominant
    cost of a metadata-sized pull — is paid once per pooled connection,
    not once per object.
  * **Admission per request**: the ``max_conns`` semaphore caps concurrent
    *serving* requests (the PullManager in-flight cap analog,
    pull_manager.h:47); idle pooled connections hold no slot.

Wire protocol v2 (authenticated ``multiprocessing.connection``; versioned by
config.WIRE_PROTOCOL_VERSION — mismatches are refused per request, naming
both versions):
    client -> server   {"oid": <bytes>, "proto": <int>,
                        "offset": <int>?, "length": <int>?,
                        "defer_above": <int>?, "trace": <list>?}
    ..."trace" is an additive optional (trace_id, span_id, parent) tuple
    naming the task the pull serves; the server records its serve span
    under it so stripe pulls and broadcast-tree hops land on the
    submitting task's causal chain in the timeline dump.
    server -> client   {"size": <span>, "total": <nbytes>}      (payload)
                  or   {"size": <nbytes>, "deferred": true}     (no payload)
                  or   {"error": <str>}
    ...full-object replies also carry "crc" (CRC32 of the whole payload,
    additive optional key — still protocol v2) when the serving store can
    produce it; clients verify at stripe completion / stream end and
    treat a mismatch as object loss (re-pull), never silent corruption.
    server -> client   raw chunk frames until ``size`` bytes are sent
    ...the connection then awaits the next request (idle timeout applies).

Codec negotiation (additive, still v2 — the same pattern as ``crc`` /
``defer_above``): a payload-bearing request MAY carry ``"codecs": (names
best-first)`` naming the lossless wire codecs the CLIENT can decode. A
codec-unaware server ignores the key and streams raw; a codec-aware
server picks the first name it also supports and — only when the span
clears ``compress_min_bytes`` AND a trial-block probe says the bytes are
actually compressible — answers with ``"codec": <name>`` and streams
CRC-PREFIXED COMPRESSED FRAMES (4-byte big-endian CRC32 of the
compressed chunk, then the chunk) instead of raw chunks. A codec-unaware
client never sends the key, so it never sees a compressed frame. Frame
CRCs are verified BEFORE decode (a wire bit flip never reaches the
decompressor); the decoded payload is still verified against the
full-object ``crc`` (verify after decode). Either failure is object loss
— abort the unsealed create and re-pull — never silent corruption.

``defer_above`` lets one request serve both sizes: a small object streams
immediately (single round trip); a large one answers with its size only so
the client can allocate once and fan the payload out as range requests.

The multi-destination distribution TREE (who pulls from whom when one
object resolves to many destinations) lives in runtime.py's
``_transfer_from`` gate — this module only moves bytes point to point.
"""

from __future__ import annotations

import os
import socket
import struct
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..utils import faults
from ..utils.integrity import crc32, crc32_combine
from ..utils.retry import RetryPolicy
from . import codec as wire_codec

_CONNECT_TIMEOUT = 20.0
# per-stripe progress deadline default (config: transfer_stripe_deadline_s):
# a stripe whose socket makes no progress for this long is declared dead
# and its range re-pulled from an alternate holder
_DEFAULT_STRIPE_DEADLINE = 30.0
# module defaults used when a caller passes no explicit striping config
# (unit-level callers); runtime/node_agent call sites pass their scoped
# Config values explicitly
_DEFAULT_STRIPE_THRESHOLD = 8 * 1024 * 1024
_DEFAULT_STRIPE_COUNT = 4
_MIN_STRIPE_BYTES = 1 << 20  # never split below 1 MiB per stripe
_DEFAULT_COMPRESS_MIN = 64 * 1024  # config: transfer_compress_min_bytes


def _observe_transfer(direction: str, nbytes: int, seconds: float) -> None:
    """Record one completed transfer in the size/latency histograms; never
    lets instrumentation fail a transfer."""
    try:
        from . import metrics_defs as mdefs

        tags = {"direction": direction}
        mdefs.transfer_bytes().observe(float(nbytes), tags=tags)
        mdefs.transfer_latency_seconds().observe(seconds, tags=tags)
    except Exception:  # noqa: BLE001
        pass


def _count(metric_accessor: str, n: int = 1) -> None:
    """Bump one metrics_defs counter by accessor name; never fails the
    transfer path."""
    try:
        from . import metrics_defs as mdefs

        getattr(mdefs, metric_accessor)().inc(n)
    except Exception:  # noqa: BLE001
        pass


def _store_crc(store, oid: bytes) -> Optional[int]:
    """Full-object CRC32 from the serving store's lazy checksum cache
    (NodeObjectStore.checksum); None when the store has no cache or the
    object vanished. Never fails the serve path."""
    fn = getattr(store, "checksum", None)
    if fn is None:
        return None
    try:
        return fn(oid)
    except Exception:  # noqa: BLE001
        return None


def _set_io_timeout(fd: int, seconds: float) -> None:
    """SO_RCVTIMEO/SO_SNDTIMEO on the connection's underlying socket
    (options live in the shared kernel socket, so setting them through a
    dup'd fd sticks; 0 clears)."""
    tv = struct.pack("ll", int(seconds), int((seconds % 1.0) * 1e6))
    s = socket.socket(fileno=os.dup(fd))
    try:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVTIMEO, tv)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_SNDTIMEO, tv)
    finally:
        s.close()


def _shutdown_fd(fd: int) -> None:
    """shutdown(SHUT_RDWR) the kernel socket behind ``fd``. A plain
    close() does NOT free a socket another thread is blocked in
    accept()/recv() on — the in-flight syscall holds a kernel reference,
    the listen port stays bound, and a same-port rebind fails. shutdown
    wakes the blocked syscall so the socket actually dies."""
    try:
        s = socket.socket(fileno=os.dup(fd))
    except OSError:
        return
    try:
        s.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    finally:
        s.close()


def _set_nodelay(fd: int) -> None:
    """TCP_NODELAY on both ends of every transfer connection: the
    request/reply exchanges are small frames, and Nagle + delayed ACK
    turns each into a ~40 ms stall — the entire latency budget of a
    metadata-sized pull (observed: 44 ms -> sub-ms p50 on loopback)."""
    try:
        s = socket.socket(fileno=os.dup(fd))
    except OSError:
        return  # e.g. an AF_UNIX test double
    try:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    except OSError:
        pass
    finally:
        s.close()


class TransferServer:
    """Serves one store's objects to peers. Spilled objects are served from
    the spill file (``store.read``) — serving never forces an allocation in
    a full store.

    Each accepted connection runs a REQUEST LOOP after its handshake: the
    ``max_conns`` semaphore is held only while a request is actively
    serving, so a pool of idle peer connections costs no admission slots.
    A connection idle past ``idle_timeout`` is closed (clients re-dial)."""

    def __init__(self, store, authkey: bytes, chunk_size: int,
                 bind_host: str = "0.0.0.0", max_conns: int = 32,
                 idle_timeout: float = 30.0, bind_port: int = 0,
                 compression: str = "auto",
                 compress_min_bytes: int = _DEFAULT_COMPRESS_MIN):
        from multiprocessing.connection import Listener

        self.store = store
        self.chunk_size = chunk_size
        self.idle_timeout = idle_timeout
        self._authkey = authkey
        # serve-side willingness to compress: "auto" honors whatever the
        # CLIENT offers (the puller drives, receiver-driven like
        # everything else here), a codec name pins that one, "off" never
        # compresses. The client-side knob is config.transfer_compression
        # (it decides whether a fetch OFFERS codecs at all).
        self.compress_min_bytes = int(compress_min_bytes)
        if compression == "off":
            self._codecs: Tuple[str, ...] = ()
        elif compression == "auto":
            self._codecs = wire_codec.available_codecs()
        else:
            self._codecs = (compression,) if (
                compression in wire_codec.available_codecs()) else ()
        # NO authkey on the Listener: accept() would run the challenge
        # handshake on the single accept thread, letting one stalled peer
        # wedge the whole server. The handshake runs per-connection on the
        # serve thread instead, under a socket IO timeout.
        self._listener = Listener((bind_host, bind_port))
        self.port: int = self._listener.address[1]
        self._sem = threading.BoundedSemaphore(max_conns)
        self._stop = threading.Event()
        self._conns_mu = threading.Lock()
        self._conns: set = set()  # live serving connections  # guarded-by: _conns_mu
        # observability (read by tests/bench; += is GIL-atomic enough for
        # monotonic counters)
        self.connections_accepted = 0
        self.requests_served = 0
        self.bytes_served = 0        # logical payload bytes (decoded)
        self.bytes_served_wire = 0   # bytes actually on the wire
        self.compressed_serves = 0
        threading.Thread(target=self._accept_loop, daemon=True,
                         name="xfer-accept").start()

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn = self._listener.accept()
            except Exception:  # noqa: BLE001 — closed listener
                if self._stop.is_set():
                    return
                continue
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True, name="xfer-serve").start()

    def _serve_conn(self, conn) -> None:
        """Handshake once, then serve requests until the peer hangs up or
        goes idle. Concurrency is capped per REQUEST by the semaphore so a
        burst of pulls cannot monopolize the host (admission control, the
        PullManager cap analog) while idle pooled connections stay free."""
        from multiprocessing.connection import (
            answer_challenge, deliver_challenge,
        )

        try:
            # bounded handshake: a peer that never answers times out the
            # recv instead of parking this thread forever (the accept
            # thread is already safe — it only spawns us). 30s matches
            # the client's per-operation budget: on a loaded single-core
            # host a BURST of concurrent handshakes contends for the GIL
            # and 10s was observed flaking a legitimate 8-way fetch.
            _set_io_timeout(conn.fileno(), 30.0)
            _set_nodelay(conn.fileno())
            deliver_challenge(conn, self._authkey)
            answer_challenge(conn, self._authkey)
        except Exception:  # noqa: BLE001 — bad key / timeout / EOF
            try:
                conn.close()
            except OSError:
                pass
            return
        self.connections_accepted += 1
        with self._conns_mu:
            self._conns.add(conn)
        try:
            while not self._stop.is_set():
                try:
                    # idle bound between requests: a pooled connection
                    # nobody uses must not hold a thread + fd forever
                    _set_io_timeout(conn.fileno(), self.idle_timeout)
                    req = conn.recv()
                except Exception:  # noqa: BLE001 — EOF / idle timeout
                    return
                with self._sem:
                    try:
                        # serve under a (longer) IO timeout: a peer that
                        # stalls mid-download would otherwise hold a
                        # semaphore slot and a store read ref forever —
                        # max_conns such peers would wedge this node's
                        # whole p2p plane
                        _set_io_timeout(conn.fileno(), 60.0)
                        if not self._serve_request(conn, req):
                            return
                    except (EOFError, OSError, KeyError, TypeError):
                        return
                    except Exception:  # noqa: BLE001 — a bad peer must
                        return  # not leak the slot or kill the server
        finally:
            with self._conns_mu:
                self._conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass

    def _serve_request(self, conn, req: dict) -> bool:
        """Serve one v2 request. Returns True when the connection stays
        usable for another request, False when it must close (protocol
        mismatch, or a failure mid-stream)."""
        from ..config import WIRE_PROTOCOL_VERSION

        # strict: a missing proto is a pre-versioning peer
        if req.get("proto") != WIRE_PROTOCOL_VERSION:
            conn.send({"error": (
                "wire protocol mismatch: server speaks "
                f"v{WIRE_PROTOCOL_VERSION}, peer spoke "
                f"v{req.get('proto')}")})
            return False
        # fault plane, serve side: drop vanishes mid-request (peer sees
        # EOF), stall delays the reply past the client's stripe deadline,
        # error answers with a refusal, corrupt flips a payload byte on
        # the wire BEFORE any encode (the decoded-payload crc catches
        # it), corrupt-compressed flips a byte inside a compressed frame
        # AFTER its frame crc is stamped (the pre-decode frame crc
        # catches it; a no-op on uncompressed serves). The store's copy
        # is NEVER touched.
        act = faults.fire("transfer.send")
        if act is not None:
            if act.mode == "stall":
                act.sleep()
            elif act.mode == "error":
                conn.send({"error": (
                    f"injected error at transfer.send (#{act.seq})")})
                return True
            elif act.mode == "drop":
                return False
        corrupt = act is not None and act.mode == "corrupt"
        corrupt_comp = act is not None and act.mode == "corrupt-compressed"
        oid = req["oid"]
        trace = req.get("trace")
        w0 = time.time()
        view = self.store.read(oid)
        if view is None:
            conn.send({"error": "object not in store"})
            return True
        try:
            n = len(view) if isinstance(view, bytes) else view.nbytes
            offset = int(req.get("offset") or 0)
            length = req.get("length")
            defer_above = req.get("defer_above")
            if length is None and defer_above is not None and n > defer_above:
                # size-only answer: the client allocates once, then fans
                # the payload out as parallel range requests. The full-
                # object crc rides here so the client can verify the
                # combined stripes against it.
                reply = {"size": n, "deferred": True}
                c = _store_crc(self.store, oid)
                if c is not None:
                    reply["crc"] = c
                conn.send(reply)
                self.requests_served += 1
                return True
            span = (n - offset) if length is None else int(length)
            if offset < 0 or span < 0 or offset + span > n:
                conn.send({"error": (
                    f"bad range [{offset}, {offset + span}) for "
                    f"{n}-byte object")})
                return True
            t0 = time.monotonic()
            reply = {"size": span, "total": n}
            if offset == 0 and span == n:
                c = _store_crc(self.store, oid)
                if c is not None:
                    reply["crc"] = c
            # codec negotiation: compress only when the client offered a
            # codec we speak, the span clears the threshold, AND the
            # trial-block probe says the bytes will actually shrink —
            # incompressible payloads (ciphertext, random floats) skip
            # encoding entirely so the worst case stays ~the raw path
            cname = None
            offered = req.get("codecs")
            if offered and self._codecs:
                if span < self.compress_min_bytes:
                    wire_codec.count_skip("below_threshold")
                else:
                    cname, skip = wire_codec.choose_codec(
                        offered, self._codecs, view, span, offset)
                    if cname is None:
                        wire_codec.count_skip(skip)
                    else:
                        reply["codec"] = cname
            conn.send(reply)
            mv = memoryview(view)
            wire_bytes = 0
            try:
                for off in range(offset, offset + span, self.chunk_size):
                    end = min(off + self.chunk_size, offset + span)
                    chunk = mv[off:end]
                    if corrupt and off == offset:
                        chunk = faults.corrupt_bytes(chunk)
                    if cname is None:
                        conn.send_bytes(chunk)
                        wire_bytes += end - off
                    else:
                        frame = wire_codec.encode_frame(chunk, cname)
                        if corrupt_comp and off == offset:
                            # flip a byte of the COMPRESSED payload after
                            # its crc was stamped — exactly a wire bit
                            # flip; the client's frame verify must catch
                            # it before the decoder runs
                            frame = frame[:4] + faults.corrupt_bytes(
                                frame[4:])
                        conn.send_bytes(frame)
                        wire_bytes += len(frame)
            finally:
                mv.release()
            # byte/codec counters first, requests_served LAST: the client's
            # fetch returns the instant the final chunk lands, so readers
            # (bench, tests) use requests_served as the barrier proving
            # this request's accounting is complete
            self.bytes_served_wire += wire_bytes
            self.bytes_served += span
            if cname is not None:
                self.compressed_serves += 1
            self.requests_served += 1
            if offset or (length is not None and span < n):
                _count("transfer_stripe_requests")
            _observe_transfer("serve", span, time.monotonic() - t0)
            if trace:
                # serve-side span in THIS process's ring (agents ship it
                # to the head on the keepalive pong), carrying the trace
                # of the task the pull serves
                try:
                    from ..utils import timeline, tracing

                    timeline.record_event(
                        f"serve::{oid.hex()[:8]}", "transfer", w0,
                        time.time(),
                        extra={"offset": offset, "length": span},
                        trace=tracing.from_wire(trace))
                except Exception:  # noqa: BLE001 — never fail a serve
                    pass
            return True
        finally:
            if isinstance(view, memoryview):
                self.store.release(oid)

    def close(self) -> None:
        self._stop.set()
        # wake the blocked accept() so the listen socket actually dies
        # (close() alone leaves it bound — see _shutdown_fd)
        sl = getattr(self._listener, "_listener", None)
        ls = getattr(sl, "_socket", None)
        if ls is not None:
            _shutdown_fd(ls.fileno())
        try:
            self._listener.close()
        except OSError:
            pass
        # tear down live serving connections too: an idle pooled peer
        # connection would otherwise pin a serve thread (blocked in
        # recv) and its socket for up to idle_timeout after shutdown
        with self._conns_mu:
            conns = list(self._conns)
            self._conns.clear()
        for c in conns:
            try:
                _shutdown_fd(c.fileno())
                c.close()
            except OSError:
                pass


def _dial(host: str, port: int, authkey: bytes, timeout: float,
          retry: Optional[RetryPolicy] = None):
    """Dial a TransferServer and run the handshake. Returns (conn, None)
    or (None, error_string). The connect/handshake phase retries under
    the unified RetryPolicy (default: 2 attempts, the pre-policy budget):
    nothing has streamed yet, and on a saturated host a GIL-starved peer
    can miss even a generous handshake budget (observed: a full-suite
    teardown starving an 8-way fetch's challenge past 30s).

    An authentication refusal returns a DISTINCT error string
    ("authentication failed ...") that retry loops classify as permanent
    — a wrong key is indistinguishable from peer death under the old
    generic "connect ... failed" message — and bumps its own counter."""
    from multiprocessing import AuthenticationError
    from multiprocessing.connection import (
        Connection, answer_challenge, deliver_challenge,
    )

    policy = retry if retry is not None else RetryPolicy(
        max_attempts=2, base_backoff_s=0.05, plane="transfer.dial")
    attempt = 0
    while True:
        conn = None
        try:
            act = faults.fire("transfer.dial")
            if act is not None:
                if act.mode == "stall":
                    act.sleep()
                else:  # drop / error / corrupt: the dial just fails
                    act.raise_()
            sock = socket.create_connection((host, port),
                                            timeout=_CONNECT_TIMEOUT)
            sock.settimeout(None)  # timeouts via SO_RCVTIMEO below
            conn = Connection(sock.detach())
            # per-operation bound: a healthy stream always progresses
            # within seconds; 30s of silence on any single recv means
            # the peer is gone
            _set_io_timeout(conn.fileno(), min(timeout, 30.0))
            _set_nodelay(conn.fileno())
            answer_challenge(conn, authkey)
            deliver_challenge(conn, authkey)
            return conn, None
        except Exception as e:  # noqa: BLE001 — peer down / auth refused
            if conn is not None:
                try:
                    conn.close()
                except OSError:
                    pass
            if isinstance(e, AuthenticationError):
                # a wrong key will not become right on retry
                _count("transfer_auth_failures")
                return None, (f"authentication failed dialing "
                              f"{host}:{port}: {e!r}")
            if not policy.backoff(attempt):
                return None, f"connect to {host}:{port} failed: {e!r}"
            attempt += 1


class ConnectionPool:
    """Authenticated transfer connections kept alive across pulls, keyed
    by (host, port, authkey). ``acquire`` hands back an idle pooled
    connection when one exists (a HIT — no dial, no handshake) or dials a
    fresh one (a MISS). ``release`` returns a healthy connection for
    reuse, capped at ``max_idle_per_peer`` idle connections per peer.

    Staleness is detected on use, not here: the fetch path discards a
    pooled connection whose first request errors (server restarted, idle
    timeout fired) and retries on a freshly dialed one."""

    def __init__(self, max_idle_per_peer: int = 8):
        self.max_idle_per_peer = max_idle_per_peer
        self._mu = threading.Lock()
        self._idle: Dict[tuple, List] = {}  # guarded-by: _mu
        self._closed = False  # guarded-by: _mu
        self.hits = 0
        self.misses = 0

    def acquire(self, host: str, port: int, authkey: bytes,
                timeout: float = 120.0):
        """Returns (conn, pooled, error): ``pooled`` True means the
        connection came from the pool and MAY be stale — the caller must
        retry its first request on a fresh connection if it errors."""
        key = (host, port, bytes(authkey))
        with self._mu:
            idle = self._idle.get(key)
            if idle:
                self.hits += 1
                conn = idle.pop()
                _count("transfer_pool_hits")
                return conn, True, None
            self.misses += 1
        _count("transfer_pool_misses")
        conn, err = _dial(host, port, authkey, timeout)
        return conn, False, err

    def release(self, host: str, port: int, authkey: bytes, conn) -> None:
        """Return a HEALTHY connection (request fully consumed) for reuse;
        closes it when the pool is full or shut down."""
        key = (host, port, bytes(authkey))
        with self._mu:
            if not self._closed and self.max_idle_per_peer > 0:
                idle = self._idle.setdefault(key, [])
                if len(idle) < self.max_idle_per_peer:
                    idle.append(conn)
                    return
        try:
            conn.close()
        except OSError:
            pass

    @staticmethod
    def discard(conn) -> None:
        """Drop a connection whose stream state is unknown (errored or
        abandoned mid-payload): never back into the pool."""
        try:
            conn.close()
        except OSError:
            pass

    def close(self) -> None:
        with self._mu:
            self._closed = True
            conns = [c for idle in self._idle.values() for c in idle]
            self._idle.clear()
        for c in conns:
            try:
                c.close()
            except OSError:
                pass


def create_or_wait(dst_store, oid: bytes, size: int, timeout: float = 30.0):
    """Allocate ``oid`` in ``dst_store``, handling the racing-fetch case:
    create() refuses while another fetch's copy of the SAME object is
    unsealed and in flight, and success is only real once the object is
    actually readable (the racer may die mid-stream and abort its
    partial copy — so create is RETRIED, not just waited out). Shared by
    the TCP pull and the same-host shm copy. Returns (buf, None) on a
    fresh allocation, (None, None) when the racing copy became readable,
    (None, error) on timeout.

    When the store exposes ``wait_for_object_change`` (NodeObjectStore's
    seal/delete condition), the wait wakes within microseconds of the
    racing copy sealing or aborting; a short poll tick remains only as
    the backstop for seals performed by ANOTHER process through the shm
    segment directly (no in-process notification exists for those)."""
    deadline = time.monotonic() + timeout
    waiter = getattr(dst_store, "wait_for_object_change", None)
    while True:
        try:
            return dst_store.create(oid, size), None
        except ValueError:
            pass
        if dst_store.contains(oid):
            return None, None
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            return None, "concurrent transfer of this object never completed"
        if waiter is not None:
            waiter(min(remaining, 0.05))
        else:
            time.sleep(0.05)


def _recv_exact(conn, sub) -> None:
    """Stream exactly ``sub.nbytes`` into the (shm) view ``sub``; the
    per-operation socket timeout bounds every recv. Split out so tests
    can fault-inject a mid-stripe connection kill.

    Fault plane, receive side: drop kills this connection under the
    in-flight stream (the next recv sees EOF), stall delays past the
    stripe deadline, error raises mid-receive, corrupt flips a byte in
    the landed buffer AFTER the stream (what a bad DIMM/NIC on the
    receive path does — only the checksum can catch it)."""
    act = faults.fire("transfer.recv")
    if act is not None:
        if act.mode == "stall":
            act.sleep()
        elif act.mode == "error":
            act.raise_()
        elif act.mode == "drop":
            _shutdown_fd(conn.fileno())
    size = sub.nbytes
    got = 0
    while got < size:
        got += conn.recv_bytes_into(sub[got:])
    if act is not None and act.mode == "corrupt" and size:
        sub[0:1] = bytes([sub[0] ^ 0xFF])


def _recv_compressed(conn, sub, cname: str,
                     verify_frames: bool = True) -> None:
    """Stream CRC-prefixed compressed frames into ``sub`` until its
    span is fully decoded. Each frame's CRC is verified BEFORE decode;
    a frame integrity or decode failure raises OSError so the caller
    discards the connection (the stream position is unknowable) and the
    fetch aborts its unsealed create and re-pulls — the same loss path
    a raw checksum mismatch takes, never sealing garbage.

    Fault plane: same receive-side physics as :func:`_recv_exact`
    (corrupt flips a landed byte AFTER decode — only the decoded-payload
    crc can catch that one)."""
    act = faults.fire("transfer.recv")
    if act is not None:
        if act.mode == "stall":
            act.sleep()
        elif act.mode == "error":
            act.raise_()
        elif act.mode == "drop":
            _shutdown_fd(conn.fileno())
    size = sub.nbytes
    got = 0
    while got < size:
        frame = conn.recv_bytes()
        try:
            # decode lands directly in the destination view (zrle's zero
            # blocks become one memset — no intermediate materialization)
            got += wire_codec.decode_frame_into(
                frame, cname, sub[got:], verify_crc=verify_frames)
        except (wire_codec.FrameIntegrityError,
                wire_codec.CodecError) as e:
            _count("transfer_checksum_mismatch")
            raise OSError(
                f"compressed frame ({cname}) failed integrity/decode: "
                f"{e}") from e
    if act is not None and act.mode == "corrupt" and size:
        sub[0:1] = bytes([sub[0] ^ 0xFF])


def _request_range(conn, oid: bytes, offset: int, length: int, sub,
                   proto: int, trace=None, codecs=None,
                   verify_checksum: bool = True) -> None:
    """One range request on an authenticated connection: header exchange,
    then stream the span straight into ``sub``. Raises on any mismatch
    or stream failure (caller aborts the whole fetch)."""
    req = {"oid": oid, "proto": proto, "offset": offset,
           "length": length}
    if trace:
        req["trace"] = tuple(trace)
    if codecs:
        req["codecs"] = tuple(codecs)
    conn.send(req)
    hdr = conn.recv()
    err = hdr.get("error")
    if err:
        raise OSError(f"range [{offset}, {offset + length}) refused: {err}")
    if hdr["size"] != length:
        raise OSError(f"range [{offset}, {offset + length}) answered "
                      f"{hdr['size']} bytes")
    cname = hdr.get("codec")
    if cname:
        _recv_compressed(conn, sub, cname, verify_frames=verify_checksum)
    else:
        _recv_exact(conn, sub)


def _stripe_ranges(total: int, stripe_count: int) -> List[Tuple[int, int]]:
    """Split ``total`` bytes into up to ``stripe_count`` contiguous
    (offset, length) ranges, each at least _MIN_STRIPE_BYTES."""
    n = max(1, min(stripe_count, total // _MIN_STRIPE_BYTES))
    base, extra = divmod(total, n)
    ranges = []
    off = 0
    for i in range(n):
        span = base + (1 if i < extra else 0)
        ranges.append((off, span))
        off += span
    return ranges


def fetch_object(host: str, port: int, authkey: bytes, oid: bytes,
                 dst_store, chunk_size: int,
                 timeout: float = 120.0,
                 pool: Optional[ConnectionPool] = None,
                 stripe_threshold: Optional[int] = None,
                 stripe_count: Optional[int] = None,
                 alt_sources: Optional[Callable] = None,
                 retry: Optional[RetryPolicy] = None,
                 verify_checksum: bool = True,
                 stripe_deadline: Optional[float] = None,
                 trace=None, codecs=None) -> Optional[str]:
    """Pull one object from a peer's TransferServer straight into
    ``dst_store``. Returns None on success, an error string on failure.

    ``codecs``: lossless wire codecs THIS client can decode, best-first
    (``codec.client_codecs(config)``); None (the default) sends no codec
    keys at all — indistinguishable on the wire from a codec-unaware v2
    peer, so every existing caller keeps today's raw path.

    The receive lands chunk-by-chunk in the store allocation itself
    (``recv_bytes_into`` on the shm view) — no full-object staging buffer
    anywhere, which is what keeps a GB-scale transfer O(chunk) in memory
    on both ends. Objects at or above ``stripe_threshold`` are fetched as
    ``stripe_count`` parallel range requests into disjoint slices of that
    one allocation, sealed once after all stripes land.

    Failure handling, innermost to outermost:

      * A failed/stalled STRIPE (socket silent past ``stripe_deadline``)
        re-pulls just its range from an alternate holder resolved via
        ``alt_sources`` into the same unsealed create — mid-pull holder
        failover, no lineage re-execution, no re-transfer of the ranges
        that already landed.
      * A payload whose CRC32 disagrees with the serving store's ("crc"
        in the reply) is aborted and counted, never sealed — the outer
        loop re-pulls it.
      * The whole fetch retries under ``retry`` (unified RetryPolicy;
        default 3 attempts with jittered backoff), rotating across
        ``alt_sources()`` so a dead source is abandoned, not hammered.
        Non-retryable failures (authentication, protocol mismatch)
        surface immediately.

    ``alt_sources``: zero-arg callable returning the CURRENT live holder
    list as (host, port) tuples — typically a closure over the GCS object
    directory, re-invoked at each failover so holders that died since the
    fetch began are excluded and new copies are found.

    ``pool``: a ConnectionPool amortizes the dial + challenge handshake
    across pulls (and serves stripe connections). Without one, every
    connection is fresh and closed after use (the v1 economics). A stale
    pooled connection (server restarted / idle-timed-out) is detected on
    the first request and transparently retried on a fresh dial.

    Every IO step is bounded: connect by _CONNECT_TIMEOUT, each recv/send
    by a per-operation socket timeout — a suspended or partitioned source
    fails the fetch instead of hanging the calling thread (and, on an
    agent, instead of pinning the oid unsealed forever, which would block
    the head's push fallback)."""
    policy = retry if retry is not None else RetryPolicy(
        max_attempts=3, base_backoff_s=0.05, plane="transfer")
    sources: List[Tuple[str, int]] = [(host, port)]
    attempt = 0
    while True:
        h, p = sources[attempt % len(sources)]
        err = _fetch_once(h, p, authkey, oid, dst_store, chunk_size,
                          timeout, pool, stripe_threshold, stripe_count,
                          alt_sources, verify_checksum, stripe_deadline,
                          trace=trace, codecs=codecs)
        if err is None:
            return None
        if not policy.is_retryable(err):
            return err
        if alt_sources is not None:
            # rotate to the CURRENT holder set, preferring anything that
            # is not the source that just failed
            try:
                alts = [tuple(s) for s in (alt_sources() or [])]
            except Exception:  # noqa: BLE001
                alts = []
            if alts:
                rest = [s for s in alts if s != (h, p)]
                sources = rest or alts
        if not policy.backoff(attempt):
            return err
        attempt += 1


def _fetch_once(host: str, port: int, authkey: bytes, oid: bytes,
                dst_store, chunk_size: int, timeout: float,
                pool: Optional[ConnectionPool],
                stripe_threshold: Optional[int],
                stripe_count: Optional[int],
                alt_sources: Optional[Callable],
                verify_checksum: bool,
                stripe_deadline: Optional[float],
                trace=None, codecs=None) -> Optional[str]:
    """One fetch attempt from one source (the pre-policy fetch_object
    body). Returns None on success, an error string on failure; never
    leaves an unsealed create behind."""
    from ..config import WIRE_PROTOCOL_VERSION

    if stripe_threshold is None:
        stripe_threshold = _DEFAULT_STRIPE_THRESHOLD
    if not stripe_count:  # None or 0 = auto: parallel stripes need cores
        stripe_count = min(_DEFAULT_STRIPE_COUNT, os.cpu_count() or 1)
    if stripe_count <= 1:
        stripe_threshold = 1 << 62  # one stream: never defer/stripe

    def _acquire():
        if pool is not None:
            return pool.acquire(host, port, authkey, timeout)
        conn, err = _dial(host, port, authkey, timeout)
        return conn, False, err

    def _release(conn):
        if pool is not None:
            pool.release(host, port, authkey, conn)
        else:
            try:
                conn.close()
            except OSError:
                pass

    # first request, with one stale-pooled-connection retry: a pooled
    # connection the server already dropped (restart, idle timeout) fails
    # here before any payload moved — discard it and redo on a fresh dial
    conn = None
    hdr = None
    for _attempt in range(2):
        conn, pooled, err = _acquire()
        if conn is None:
            return err
        try:
            # re-arm the per-operation timeout: a pooled connection keeps
            # whatever (possibly stripe-deadline-short) timeout its last
            # user set
            _set_io_timeout(conn.fileno(), min(timeout, 30.0))
            first_req = {"oid": oid, "proto": WIRE_PROTOCOL_VERSION,
                         "defer_above": stripe_threshold}
            if trace:
                first_req["trace"] = tuple(trace)
            if codecs:
                first_req["codecs"] = tuple(codecs)
            conn.send(first_req)
            hdr = conn.recv()
            break
        except Exception as e:  # noqa: BLE001 — dead pooled conn
            ConnectionPool.discard(conn)
            conn = None
            if not pooled:
                return f"transfer from {host}:{port} failed: {e!r}"
    if conn is None or hdr is None:
        return f"transfer from {host}:{port} failed: stale connection"

    t0 = time.monotonic()
    try:
        err = hdr.get("error")
        if err:
            _release(conn)
            conn = None
            return err
        size = hdr["size"]
        expect_crc = hdr.get("crc")
        buf, race_err = create_or_wait(dst_store, oid, size,
                                       timeout=min(timeout, 30.0))
        if not hdr.get("deferred"):
            # single stream: the payload is already on the wire
            if buf is None:
                # a racing copy won (or timed out): the stream on this
                # connection is now unconsumable — never pool it
                ConnectionPool.discard(conn)
                conn = None
                return race_err
            try:
                cname = hdr.get("codec")
                if cname:
                    _recv_compressed(conn, buf, cname,
                                     verify_frames=verify_checksum)
                else:
                    _recv_exact(conn, buf)
                if verify_checksum and expect_crc is not None \
                        and crc32(buf) != expect_crc:
                    _count("transfer_checksum_mismatch")
                    raise _ChecksumMismatch(
                        f"payload checksum mismatch pulling "
                        f"{oid.hex()[:12]} from {host}:{port}")
            except BaseException:
                # abort the unsealed create so retries can re-allocate.
                # delete() handles unsealed entries directly (obj_delete
                # "aborts an unsealed create", shmstore.cpp:379) — sealing
                # first would briefly publish the TRUNCATED object as
                # real, and a concurrent reader's ref could make that
                # permanent
                del buf
                try:
                    dst_store.delete(oid)
                except Exception:  # noqa: BLE001
                    pass
                raise
            dst_store.seal(oid)
            _release(conn)
            conn = None
            _observe_transfer("pull", size, time.monotonic() - t0)
            return None

        # deferred header: no payload pending, the connection is clean
        if buf is None:
            _release(conn)
            conn = None
            return race_err
        first_conn, conn = conn, None  # ownership moves to the striped path
        return _striped_fetch(host, port, authkey, oid, dst_store, buf,
                              size, stripe_count, first_conn, pool,
                              _release, timeout, t0,
                              alt_sources=alt_sources,
                              expect_crc=expect_crc,
                              verify_checksum=verify_checksum,
                              stripe_deadline=stripe_deadline,
                              trace=trace, codecs=codecs)
    except _ChecksumMismatch as e:
        # the stream was fully consumed before the verify — the
        # connection stays usable, but the payload is poison
        return str(e)
    except (EOFError, OSError) as e:
        return f"transfer from {host}:{port} failed: {e!r}"
    except Exception as e:  # noqa: BLE001 — store full after wait, etc.
        return repr(e)
    finally:
        if conn is not None:
            ConnectionPool.discard(conn)


class _ChecksumMismatch(Exception):
    """Internal: a fully-received payload failed its CRC verify."""


def _striped_fetch(host: str, port: int, authkey: bytes, oid: bytes,
                   dst_store, buf, total: int, stripe_count: int,
                   first_conn, pool: Optional[ConnectionPool], _release,
                   timeout: float, t0: float,
                   alt_sources: Optional[Callable] = None,
                   expect_crc: Optional[int] = None,
                   verify_checksum: bool = True,
                   stripe_deadline: Optional[float] = None,
                   trace=None, codecs=None) -> Optional[str]:
    """Fan ``total`` bytes out as parallel range requests into disjoint
    slices of ``buf`` (the already-created, unsealed allocation).
    ``first_conn`` carries stripe 0; each other stripe acquires its own
    connection (pooled when available). Owns ``buf``: seals on success,
    aborts the create on any failure.

    A stripe that errors or stalls past ``stripe_deadline`` does NOT
    abort the fetch: its missing range is re-pulled from the alternate
    holders ``alt_sources()`` resolves at that moment — into the same
    unsealed allocation, leaving the stripes that already landed in
    place. Each stripe's CRC is computed in its own thread (overlapped
    with the other stripes' socket reads) and combined via
    ``crc32_combine`` against the serving store's full-object crc."""
    from ..config import WIRE_PROTOCOL_VERSION

    if stripe_deadline is None or stripe_deadline <= 0:
        stripe_deadline = _DEFAULT_STRIPE_DEADLINE
    ranges = _stripe_ranges(total, stripe_count)
    crcs: Dict[int, int] = {}  # offset -> crc32 of that landed range
    errors: List[str] = []
    mu = threading.Lock()

    def pull_range(offset: int, span: int, conn, release_fn,
                   src: Tuple[str, int]) -> bool:
        sub = buf[offset:offset + span]
        try:
            # the per-stripe progress deadline: silence on this socket
            # past it means the holder is stalled/dead — fail the stripe
            # (NOT the fetch) so its range can fail over
            _set_io_timeout(conn.fileno(),
                            min(stripe_deadline, timeout))
            _request_range(conn, oid, offset, span, sub,
                           WIRE_PROTOCOL_VERSION, trace=trace,
                           codecs=codecs, verify_checksum=verify_checksum)
            # crc over the DECODED stripe — the verify-after-decode half
            # of the integrity story (the frame crc already covered the
            # compressed bytes pre-decode)
            c = crc32(sub) if verify_checksum else 0
        except BaseException as e:  # noqa: BLE001
            ConnectionPool.discard(conn)
            with mu:
                errors.append(f"stripe [{offset}, {offset + span}) from "
                              f"{src[0]}:{src[1]} failed: {e!r}")
            return False
        finally:
            sub.release()
        with mu:
            crcs[offset] = c
        release_fn(conn)
        return True

    def pull_range_fresh(offset: int, span: int) -> None:
        if pool is not None:
            conn, _pooled, err = pool.acquire(host, port, authkey, timeout)
        else:
            conn, err = _dial(host, port, authkey, timeout)
        if conn is None:
            with mu:
                errors.append(err)
            return
        pull_range(offset, span, conn, _release, (host, port))

    threads = []
    for offset, span in ranges[1:]:
        t = threading.Thread(target=pull_range_fresh, args=(offset, span),
                             daemon=True, name="xfer-stripe")
        t.start()
        threads.append(t)
    pull_range(ranges[0][0], ranges[0][1], first_conn, _release,
               (host, port))
    for t in threads:
        t.join()

    missing = [(o, s) for (o, s) in ranges if o not in crcs]
    if missing and alt_sources is not None:
        # mid-pull holder failover: re-resolve LIVE holders and re-pull
        # only the missing ranges into the same unsealed create — the
        # landed stripes are kept, nothing re-runs lineage
        try:
            alts = [tuple(s) for s in (alt_sources() or [])]
        except Exception:  # noqa: BLE001
            alts = []
        alts = [s for s in alts if s != (host, port)]
        for offset, span in missing:
            for ah, ap in alts:
                if pool is not None:
                    conn, _pooled, err = pool.acquire(ah, ap, authkey,
                                                      timeout)
                else:
                    conn, err = _dial(ah, ap, authkey, timeout)
                if conn is None:
                    with mu:
                        errors.append(err)
                    continue

                def rel(c, _h=ah, _p=ap):
                    if pool is not None:
                        pool.release(_h, _p, authkey, c)
                    else:
                        try:
                            c.close()
                        except OSError:
                            pass

                if pull_range(offset, span, conn, rel, (ah, ap)):
                    _count("transfer_failovers")
                    break
        missing = [(o, s) for (o, s) in ranges if o not in crcs]

    if missing:
        # unrecoverable: abort the unsealed create (all stripe threads
        # are done, their subviews released) so a retry can re-allocate
        del buf
        try:
            dst_store.delete(oid)
        except Exception:  # noqa: BLE001
            pass
        return errors[0] if errors else (
            f"striped pull of {oid.hex()[:12]} left ranges {missing}")

    if verify_checksum and expect_crc is not None:
        combined = 0
        for offset, span in ranges:
            combined = crc32_combine(combined, crcs[offset], span)
        if combined != expect_crc:
            _count("transfer_checksum_mismatch")
            del buf
            try:
                dst_store.delete(oid)
            except Exception:  # noqa: BLE001
                pass
            return (f"payload checksum mismatch pulling "
                    f"{oid.hex()[:12]} from {host}:{port} (striped)")
    dst_store.seal(oid)
    _count("transfer_striped_fetches")
    _observe_transfer("pull", total, time.monotonic() - t0)
    return None


# --------------------------------------------------------------------------
# ICI-first device transfer plane
#
# When producer and consumer sit on the SAME mesh — the same process, or
# processes joined into one jax distributed mesh — a device object moves
# device-to-device over the interconnect (a jitted transfer compiled per
# (shape, dtype, src, dst)) instead of paying device→host copy, host
# serialization, and the shm/DCN wire. Everything else falls back to the
# v2 striped host path above; the decision is made where the directory
# already resolves holders (runtime._ensure_device_materialized /
# _batch_locality). On CPU-backed jax (tier-1) every process is its own
# single-device mesh, so the decision logic and the fallback path are
# exercised end-to-end while the compiled move degrades to an identity
# jit on the one local device.

_ici_lock = threading.Lock()
_ici_moves: Dict[tuple, Callable] = {}  # guarded-by: _ici_lock
_ici_fingerprint: Optional[tuple] = None  # guarded-by: _ici_lock
_PROCESS_TOKEN = os.urandom(8).hex()


def mesh_fingerprint() -> Optional[tuple]:
    """Identity of the mesh THIS process's devices belong to. Processes
    with equal fingerprints can move device objects over the
    interconnect without a host hop. A process inside a multi-process
    jax distributed mesh is identified by the global device set; a lone
    process (CPU tier-1, single-host dev) is its OWN mesh — a random
    process token keeps two unrelated CPU processes from aliasing.
    None when jax is unavailable or uninitialized."""
    global _ici_fingerprint
    with _ici_lock:
        if _ici_fingerprint is not None:
            return _ici_fingerprint
    try:
        import jax

        platform = jax.default_backend()
        if jax.process_count() > 1:
            fp = (platform, jax.device_count(), "distributed")
        else:
            fp = (platform,
                  tuple(d.id for d in jax.local_devices()),
                  _PROCESS_TOKEN)
    except Exception:  # noqa: BLE001 — no jax, no device plane
        return None
    with _ici_lock:
        _ici_fingerprint = fp
    return _ici_fingerprint


def same_mesh(a: Optional[tuple], b: Optional[tuple]) -> bool:
    """True when two processes' device sets share one interconnect
    domain (fingerprints match). The ICI route is only taken when this
    holds; otherwise the host wire path is authoritative."""
    if a is None or b is None:
        return False
    return tuple(a) == tuple(b)


def _source_device(arr):
    try:
        devs = getattr(arr, "devices", None)
        if callable(devs):
            ds = list(devs())
            if ds:
                return ds[0]
        return getattr(arr, "device", None)
    except Exception:  # noqa: BLE001
        return None


def ici_move(arr, dst_device, donate: bool = False):
    """Move a device array to ``dst_device`` with a jitted
    device-to-device transfer, compiled once per (shape, dtype, src,
    dst) and cached — steady-state handoffs pay only the interconnect
    copy. ``donate`` releases the source buffer into the move (the
    consuming side of a last-reader handoff); donation is skipped on
    CPU where XLA does not honor it. Counts
    ``rmt_device_ici_transfers_total``."""
    import jax

    src = _source_device(arr)
    if src is not None and dst_device is not None and src == dst_device:
        _count("device_ici_transfers")
        return arr  # already home: the zero-length transfer
    key = (tuple(getattr(arr, "shape", ())), str(getattr(arr, "dtype", "")),
           getattr(src, "id", None), getattr(dst_device, "id", None),
           bool(donate))
    with _ici_lock:
        fn = _ici_moves.get(key)
    if fn is None:
        from jax.sharding import SingleDeviceSharding

        kwargs = {"out_shardings": SingleDeviceSharding(dst_device)}
        if donate and jax.default_backend() != "cpu":
            kwargs["donate_argnums"] = (0,)
        fn = jax.jit(lambda x: x, **kwargs)
        with _ici_lock:
            _ici_moves[key] = fn
    out = fn(arr)
    out.block_until_ready()
    _count("device_ici_transfers")
    return out


def ici_allgather_move(arr, mesh_devices, dst_index: int):
    """One-hot psum transfer across an explicit device list: each
    non-source position contributes zeros and the psum lands the payload
    on every mesh position, from which ``dst_index`` keeps its shard —
    the collective spelling of a point-to-point move for backends where
    direct device_put between chips bounces through the host. Falls
    back to :func:`ici_move` when the mesh is a single device."""
    if len(mesh_devices) < 2:
        return ici_move(arr, mesh_devices[dst_index])
    try:
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(list(mesh_devices), ("x",))

        def _relay(x):
            return jax.lax.psum(x, "x")

        moved = jax.shard_map(_relay, mesh=mesh, in_specs=P(),
                              out_specs=P(),
                              check_vma=False)(jnp.asarray(arr))
        out = jax.device_put(moved, mesh_devices[dst_index])
        out.block_until_ready()
        _count("device_ici_transfers")
        return out
    except Exception:  # noqa: BLE001 — collective spelling is best-effort
        return ici_move(arr, mesh_devices[dst_index])
