"""BackendExecutor + WorkerGroup: driver-side machinery behind a Trainer.

Mirrors the reference's train/_internal/backend_executor.py:42 and
worker_group.py:91 — create a placement group for the gang (:137), start one
actor per worker (:178,335), run the backend's on_start hook (:127) (here:
objstore collective-group formation — the jax.distributed /
_setup_torch_process_group analog, train/torch/config.py:54), ship the user
loop (:275,356-360), and drain per-worker result queues
(train/_internal/session.py:144 → get_next_results, backend_executor.py:362).

TPU mapping: each TrainWorker is a host-process actor; ``chips_per_worker``
TPU chips are leased to it (its process is spawned with exactly those in
TPU_VISIBLE_CHIPS and exits with the gang), and inside the loop the user
builds meshes over the worker's local chips with parallel.make_mesh. Data
parallelism ACROSS workers rides the collective group exposed via
``session_collective_group_name``.
"""

from __future__ import annotations

import time
import uuid
from typing import Any, Callable, Dict, List, Optional

from .. import api
from ..exceptions import (ActorError, NodeDeadError, RmtError, TaskError,
                          WorkerCrashedError)
from .checkpoint import Checkpoint


class TrainingFailedError(RmtError):
    pass


class ElasticResize(RmtError):
    """Raised out of BackendExecutor.run when the elastic world watcher
    wants a DIFFERENT world size (capacity grew back after a downsize).
    Not a failure: the trainer rebuilds the group at ``target_world`` and
    resumes from the latest checkpoint without consuming failure budget."""

    def __init__(self, target_world: int):
        super().__init__(f"elastic resize to world={target_world}")
        self.target_world = target_world


def placeable_world_size(bundle: Dict[str, Any], cap: int,
                         runtime=None) -> int:
    """How many copies of ``bundle`` the cluster can place RIGHT NOW
    (greedy first-fit over alive nodes' available resources), capped at
    ``cap``. This is the elastic trainer's sizing signal after a node
    death — rebuild the gang at whatever the surviving nodes can hold —
    and its recovery signal once the autoscaler replaces the node."""
    from .. import _worker_context
    from ..core.resources import Resources

    rt = runtime or _worker_context.get_runtime()
    req = Resources(dict(bundle) or {"CPU": 1})
    with rt._lock:
        nodes = [nm for nm in rt.nodes.values() if nm.alive]
        frees = [Resources.from_fixed(nm.resources.available.fixed())
                 for nm in nodes]
    count = 0
    while count < cap:
        for i, free in enumerate(frees):
            if req.fits_in(free):
                frees[i] = free - req
                count += 1
                break
        else:
            break
    return count


# libtpu's process grid and per-process slice for W worker processes of c
# chips each that share one four-chip host (the layouts jax's own
# multi-process tests use): (W, c) -> (TPU_PROCESS_BOUNDS,
# TPU_CHIPS_PER_PROCESS_BOUNDS)
_ONE_HOST_WORLDS = {(4, 1): ("2,2,1", "1,1,1"), (2, 2): ("2,1,1", "1,2,1")}


def _one_host_tpu_world(leases: List[dict]) -> List[Optional[Dict[str, str]]]:
    """Per-rank libtpu environment for an xla world whose workers leased
    chips. A lease makes each worker an isolated one-process slice
    (node_manager.chip_lease_env); to form ONE world the processes must
    instead be told the grid they make up and where to find each other.
    Workers without leases (CPU worlds) need nothing. Anything but the
    layouts in ``_ONE_HOST_WORLDS`` has not been brought up and raises."""
    if not any(l["chips"] for l in leases):
        return [None] * len(leases)
    counts = {len(l["chips"].split(",")) if l["chips"] else 0
              for l in leases}
    shape = (len(leases), counts.pop()) if len(counts) == 1 else None
    if shape not in _ONE_HOST_WORLDS or \
            len({l["node_id"] for l in leases}) != 1:
        raise TrainingFailedError(
            "an xla world over leased TPU workers is brought up only for "
            f"{sorted(_ONE_HOST_WORLDS)} (workers, chips each) on one "
            f"four-chip host; got leases {[l['chips'] for l in leases]} on "
            f"{len({l['node_id'] for l in leases})} node(s). Use one "
            "worker that leases all the host's chips")
    process_bounds, chip_bounds = _ONE_HOST_WORLDS[shape]
    addresses = ",".join(f"localhost:{l['port']}" for l in leases)
    return [{"TPU_PROCESS_BOUNDS": process_bounds,
             "TPU_CHIPS_PER_PROCESS_BOUNDS": chip_bounds,
             "TPU_PROCESS_ADDRESSES": addresses,
             "TPU_PROCESS_PORT": str(l["port"]),
             "CLOUD_TPU_TASK_ID": str(rank)}
            for rank, l in enumerate(leases)]


class _TrainWorkerImpl:
    """The per-worker actor (RayTrainWorker analog, worker_group.py:335)."""

    def __init__(self, rank: int, world_size: int, group_name: str):
        import os

        self.rank = rank
        self.world_size = world_size
        self.group_name = group_name
        os.environ["RMT_TRAIN_RANK"] = str(rank)
        os.environ["RMT_TRAIN_WORLD"] = str(world_size)
        os.environ["RMT_TRAIN_GROUP"] = group_name

    def _rmt_init_collective(self, world_size, rank, backend, group_name):
        from ..collective import init_collective_group

        init_collective_group(world_size, rank, backend, group_name)
        return True

    def _rmt_require_tpu(self) -> str:
        """``ScalingConfig(use_tpu=True)`` asked for the chip: a worker
        whose default backend is anything else fails here, before the
        user's loop can run on the CPU by accident."""
        import jax

        backend = jax.default_backend()
        if backend != "tpu":
            import os

            raise RuntimeError(
                f"train worker {self.rank} was asked to use the TPU but "
                f"jax's default backend is {backend!r} (JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS')!r}, TPU_VISIBLE_CHIPS="
                f"{os.environ.get('TPU_VISIBLE_CHIPS')!r})")
        return jax.devices()[0].device_kind

    def _rmt_chip_lease(self) -> dict:
        """What this worker leased and where, plus a free port on its host
        for libtpu's own process rendezvous (``_one_host_tpu_world``)."""
        import os
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        return {"node_id": os.environ.get("RMT_NODE_ID", ""),
                "chips": os.environ.get("TPU_VISIBLE_CHIPS"), "port": port}

    def _rmt_pick_coordinator(self) -> str:
        """Rank-0 hook: choose the jax.distributed coordinator address on
        THIS worker's host (the reference's rank-0 addr/port selection for
        torch process groups, train/torch/config.py:108-156)."""
        import socket

        s = socket.socket()
        s.bind(("0.0.0.0", 0))
        port = s.getsockname()[1]
        s.close()
        # routable address of this host (agents may live on other machines);
        # a UDP connect learns the outbound interface without sending
        try:
            probe = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            probe.connect(("8.8.8.8", 53))
            host = probe.getsockname()[0]
            probe.close()
        except OSError:
            host = "127.0.0.1"
        return f"{host}:{port}"

    def _rmt_init_jax_world(self, coordinator: str, world: int,
                            rank: int,
                            tpu_env: Optional[Dict[str, str]] = None) -> int:
        """Form one global jax world across the worker processes
        (jax.distributed.initialize — the NCCLUniqueID-rendezvous /
        _setup_torch_process_group analog, SURVEY §2.3). Must run before
        this process initializes any jax backend; afterwards jax.devices()
        is the GLOBAL device list and one jit program spans every worker.
        ``tpu_env`` is this rank's place among the processes that share a
        TPU host; libtpu reads it when the backend is created."""
        import os

        from ..utils.jax_backend import initialized_platforms

        os.environ.update(tpu_env or {})

        live = initialized_platforms()
        if live:
            raise RuntimeError(
                f"jax backends {live} already initialized in this worker; "
                "xla cross-worker mode requires a fresh process")
        import jax

        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=world, process_id=rank)
        return jax.device_count()

    def run_loop(self, loop_blob: bytes, config: Optional[dict],
                 checkpoint_blob: Optional[bytes], dataset_shard,
                 rank_state_blob: Optional[bytes] = None) -> bool:
        """Execute the user's train_loop_per_worker to completion. Runs on
        one actor thread while next_results() is served on another
        (max_concurrency=2 — the reference pairs a train thread with the
        session queue the same way)."""
        import pickle

        import cloudpickle

        from . import session as session_mod

        rank_state = (pickle.loads(rank_state_blob)
                      if rank_state_blob else None)
        # init the session before anything that can fail or block, so a
        # concurrent next_results() poll never mistakes "not started yet"
        # for "finished" (it reports None only after s.finished is set)
        s = session_mod.init_session(
            world_rank=self.rank, world_size=self.world_size,
            checkpoint=None, dataset_shard=dataset_shard,
            rank_state=rank_state,
        )
        try:
            loop = cloudpickle.loads(loop_blob)
            s.loaded_checkpoint = (
                Checkpoint.from_bytes(checkpoint_blob)
                if checkpoint_blob else None
            )
            if config is not None:
                loop(config)
            else:
                loop()
            return True
        except BaseException as e:
            s.error = e
            raise
        finally:
            s.finished.set()

    def next_results(self, timeout_s: float = 1.0) -> Optional[List[dict]]:
        """Drain queued session.report() payloads; None once the loop has
        finished and the queue is empty. Checkpoints travel as bytes."""
        import queue as queue_mod

        from . import session as session_mod

        try:
            s = session_mod.get_session()
        except RuntimeError:
            return []  # run_loop hasn't started yet — poll again
        out: List[dict] = []
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                item = s.queue.get(timeout=max(0.0, deadline -
                                               time.monotonic()))
            except queue_mod.Empty:
                break
            ckpt = item.get("checkpoint")
            item["checkpoint"] = ckpt.to_bytes() if ckpt else None
            out.append(item)
            if not s.queue.empty():
                continue
            break
        if not out and s.finished.is_set() and s.queue.empty():
            return None
        return out


class WorkerGroup:
    def __init__(self, num_workers: int, resources_per_worker: Dict[str, Any],
                 placement_strategy: str = "PACK"):
        from ..core.placement_group import placement_group

        self.num_workers = num_workers
        self.group_name = f"train_{uuid.uuid4().hex[:8]}"
        bundle = dict(resources_per_worker) or {"CPU": 1}
        self.pg = placement_group([bundle] * num_workers,
                                  strategy=placement_strategy)
        if not self.pg.wait(60):
            raise TrainingFailedError(
                f"placement group for {num_workers} workers "
                f"({bundle} each) could not be scheduled"
            )
        cls = api.remote(_TrainWorkerImpl)
        self.actors = []
        for rank in range(num_workers):
            self.actors.append(
                cls.options(
                    max_concurrency=2,
                    num_cpus=resources_per_worker.get("CPU", 1),
                    num_tpus=resources_per_worker.get("TPU", 0),
                    placement_group=self.pg,
                    placement_group_bundle_index=rank,
                ).remote(rank, num_workers, self.group_name)
            )

    def setup_collective(self) -> None:
        from ..collective import create_collective_group

        create_collective_group(
            self.actors, self.num_workers, list(range(self.num_workers)),
            backend="objstore", group_name=self.group_name,
        )

    def setup_xla_world(self) -> int:
        """Cross-worker XLA mode: every worker process joins one
        jax.distributed world so the user loop jits over ONE global mesh —
        gradients sync through XLA collectives (ICI/DCN), never the object
        plane. Returns the global device count. On a TPU host each
        worker sees the chips it leased (``use_tpu``, ``chips_per_worker``)
        and no others; a worker without a lease is a CPU process."""
        tpu_envs = _one_host_tpu_world(api.get(
            [a._rmt_chip_lease.remote() for a in self.actors], timeout=120))
        coordinator = api.get(
            self.actors[0]._rmt_pick_coordinator.remote(), timeout=120)
        counts = api.get(
            [a._rmt_init_jax_world.remote(coordinator, self.num_workers, r,
                                          tpu_envs[r])
             for r, a in enumerate(self.actors)],
            timeout=300,
        )
        if len(set(counts)) != 1:
            raise TrainingFailedError(
                f"workers disagree on global device count: {counts}")
        return counts[0]

    def shutdown(self) -> None:
        from ..core.placement_group import remove_placement_group

        for a in self.actors:
            try:
                api.kill(a)
            except Exception:
                pass
        try:
            from ..collective.coordinator import destroy_coordinator

            destroy_coordinator(self.group_name)
        except Exception:
            pass
        remove_placement_group(self.pg)


class BackendExecutor:
    def __init__(self, num_workers: int,
                 resources_per_worker: Optional[Dict[str, Any]] = None,
                 placement_strategy: str = "PACK",
                 use_collective: bool = True,
                 collective_backend: str = "objstore",
                 use_tpu: bool = False):
        self.num_workers = num_workers
        self.resources_per_worker = resources_per_worker or {"CPU": 1}
        self.placement_strategy = placement_strategy
        self.use_collective = use_collective and num_workers > 1
        self.collective_backend = collective_backend
        self.use_tpu = use_tpu
        self.group: Optional[WorkerGroup] = None

    def start(self) -> None:
        self.group = WorkerGroup(
            self.num_workers, self.resources_per_worker,
            self.placement_strategy,
        )
        if self.use_collective:
            if self.collective_backend == "xla":
                self.group.setup_xla_world()
            else:
                self.group.setup_collective()
        if self.use_tpu:
            # after the xla world, which must form before any backend
            api.get([a._rmt_require_tpu.remote() for a in self.group.actors],
                    timeout=300)

    def run(
        self,
        train_loop: Callable,
        config: Optional[dict],
        checkpoint: Optional[Checkpoint],
        dataset_shards: Optional[List[Any]] = None,
        on_report: Optional[Callable[[List[dict]], None]] = None,
        poll_interval_s: float = 0.2,
        rank_states: Optional[Dict[int, bytes]] = None,
        world_watcher: Optional[Callable[[], Optional[int]]] = None,
    ) -> None:
        """Ship the loop to every worker and drain reports until all loops
        complete. Raises TrainingFailedError on worker failure (a dead
        worker, actor, or NODE — the PR-3 agent-death plumbing surfaces
        all three as errors on the polled refs) and ElasticResize when
        ``world_watcher`` returns a different target world size.

        ``rank_states`` hands each rank its restored loader-state shard
        (session.get_rank_state()); ranks absent from the dict start
        fresh."""
        from ..serialization import dumps_function

        assert self.group is not None, "call start() first"
        loop_blob = dumps_function(train_loop)
        ckpt_blob = checkpoint.to_bytes() if checkpoint else None
        shards = dataset_shards or [None] * self.num_workers
        states = rank_states or {}
        done_refs = [
            a.run_loop.remote(loop_blob, config, ckpt_blob, shards[i],
                              states.get(i))
            for i, a in enumerate(self.group.actors)
        ]
        live = set(range(self.num_workers))
        batches: List[dict] = []

        def flush() -> None:
            # deliver everything collected this round before any error can
            # propagate — a healthy worker's checkpoint must not be lost
            # because a peer died mid-round
            if batches and on_report is not None:
                on_report(list(batches))
            batches.clear()

        try:
            while live:
                if world_watcher is not None:
                    target = world_watcher()
                    if target is not None and target != self.num_workers:
                        flush()
                        raise ElasticResize(target)
                refs = [
                    (i, self.group.actors[i].next_results.remote(0.5))
                    for i in sorted(live)
                ]
                for i, ref in refs:
                    res = api.get(ref, timeout=120)
                    if res is None:
                        live.discard(i)
                    elif res:
                        batches.extend(res)
                    else:
                        # empty batch: either the loop hasn't started or it
                        # died before init_session (e.g. a shard failed to
                        # deserialize). If run_loop already finished, a final
                        # drain is safe and prevents polling forever.
                        ready, _ = api.wait([done_refs[i]], timeout=0)
                        if ready:
                            api.get(done_refs[i])  # surfaces loop errors
                            final = api.get(
                                self.group.actors[i].next_results.remote(0.0),
                                timeout=120,
                            )
                            if final:
                                batches.extend(final)
                            live.discard(i)
                flush()
                if live:
                    time.sleep(poll_interval_s)
            # surface loop errors (worker finished exceptionally)
            api.get(done_refs, timeout=60)
        except (ActorError, TaskError, WorkerCrashedError,
                NodeDeadError) as e:
            flush()
            raise TrainingFailedError(str(e)) from e

    def shutdown(self) -> None:
        if self.group is not None:
            self.group.shutdown()
            self.group = None
