"""Typed, env-overridable configuration flags.

Mirrors the reference's ``RAY_CONFIG(type, name, default)`` macro system
(src/ray/common/ray_config.h:46-58, defaults in src/ray/common/ray_config_def.h):
every flag has a type, a default, and an environment override spelled
``RMT_<NAME>``. Unlike the reference's C++ singleton, this is a plain Python
dataclass-like registry so tests can construct scoped configs.
"""

from __future__ import annotations

import os
from typing import Any, Dict

_FLAG_DEFS: Dict[str, tuple] = {}


def _flag(name: str, typ, default, doc: str = ""):
    _FLAG_DEFS[name] = (typ, default, doc)
    return default


# --- object store / data plane (reference: ray_config_def.h) -----------------
_flag("max_direct_call_object_size", int, 100 * 1024,
      "Objects <= this are inlined in task replies / the in-process memory "
      "store instead of the shared-memory store (ray_config_def.h:181).")
_flag("task_rpc_inlined_bytes_limit", int, 10 * 1024 * 1024,
      "Total bytes of args inlined into a task submission (ray_config_def.h:424).")
_flag("object_store_memory", int, 512 * 1024 * 1024,
      "Per-node shared-memory store capacity in bytes.")
_flag("object_store_fallback_directory", str, "/tmp/rmt_spill",
      "Directory for spilled objects (external storage).")
_flag("min_spilling_size", int, 1 * 1024 * 1024,
      "Spill batches of at least this many bytes (ray_config_def.h:495; the "
      "reference default is 100 MiB, scaled down for single-host stores).")
_flag("object_spilling_threshold", float, 0.8,
      "Start spilling when the store passes this fraction full "
      "(ray_config_def.h:499).")
_flag("object_store_full_timeout_s", float, 5.0,
      "How long an allocation waits for reader refs / pins to drain when "
      "nothing is spillable before raising ObjectStoreFullError (the plasma "
      "CreateRequestQueue blocks clients the same way, "
      "create_request_queue.h:32).")
_flag("push_pressure_retry_s", float, 30.0,
      "Total budget a pressured push to a remote store keeps retrying "
      "(with backoff) while the sender holds its read ref. The receiver "
      "nacks 'retryable' when transiently full instead of failing the "
      "transfer — pressure causes slowness, never object loss (the "
      "reference's pull-manager admission control + queued plasma "
      "creates, pull_manager.h:47, create_request_queue.h:32).")
_flag("max_io_workers", int, 2,
      "Concurrent spill/restore IO threads (ray_config_def.h:489; default 4).")
_flag("object_manager_chunk_size", int, 5 * 1024 * 1024,
      "Chunk size for inter-node object push/pull (ray_config_def.h:300).")
_flag("transfer_max_conns", int, 32,
      "Concurrent serving REQUESTS per TransferServer (the PullManager "
      "in-flight cap analog, pull_manager.h:47). Must comfortably exceed "
      "transfer_stripe_count: one striped peer alone opens that many "
      "parallel range requests.")
_flag("transfer_stripe_threshold", int, 8 * 1024 * 1024,
      "Objects >= this many bytes are pulled as parallel stripes over "
      "multiple connections; smaller objects use one stream (the v2 "
      "range-request wire protocol).")
_flag("transfer_stripe_count", int, 0,
      "Parallel connections per striped pull; each stripe receives a "
      "disjoint range of the same destination allocation. 0 = auto "
      "(min(4, cpu_count)): on a single-core host parallel stripes only "
      "add GIL/context-switch overhead (measured 1.16 -> 0.75 GB/s at 4 "
      "stripes), so auto degrades to one stream there.")
_flag("transfer_pool_size", int, 8,
      "Idle authenticated connections kept per (host, port) peer by the "
      "transfer-plane connection pool, amortizing the challenge/response "
      "handshake across pulls. 0 disables pooling.")
_flag("transfer_idle_timeout_s", float, 30.0,
      "Server-side idle timeout on a pooled transfer connection: a "
      "connection with no request for this long is closed (the client "
      "pool transparently re-dials on next use).")
_flag("transfer_broadcast_fanout", int, 2,
      "Max concurrent pulls of ONE object per holding node during a "
      "multi-destination distribution. Later fetchers wait for an "
      "in-flight copy to land and pull from the new holder, turning an "
      "n-destination broadcast from source-bottlenecked O(n*size) into a "
      "pipelined O(size*log n) tree. 0 disables the gate.")

# --- device (HBM) object tier ------------------------------------------------
_flag("device_store_capacity_bytes", int, 0,
      "HBM budget for the per-process device object store; putting past "
      "it demotes least-recently-used UNPINNED device objects to the "
      "host shm tier (which spills below itself as usual). 0 = auto, "
      "resolved by the first device put in the process that holds the "
      "device: 60% of the memory limit that device reports, or 1 GiB "
      "for a device that reports none (CPU-backed arrays). Negative "
      "disables eviction entirely (unbounded pinning).")
_flag("device_demote_precision", str, "f32",
      "Dtype-aware downcast applied when a float32 device object is "
      "demoted to host: 'f32' keeps the exact bytes; 'bf16' writes the "
      "PR 7 quantize envelope (half the host/spill bytes, values "
      "round-tripped through bf16 truncation — rel err <= 2^-8). "
      "Non-f32 payloads always demote exact.")
_flag("device_promote_on_read", bool, True,
      "Re-promote a demoted device object back into the device store on "
      "its next device-side read (LRU re-entry; it can be demoted "
      "again under pressure). Off leaves demoted objects host-resident.")
_flag("device_ici_transfer", bool, True,
      "Move device objects device-to-device with a jitted transfer "
      "(compiled per shape/dtype/src/dst) when producer and consumer "
      "sit on the same mesh, instead of bouncing through host "
      "serialization; cross-mesh readers always fall back to the "
      "striped host wire path.")

# --- scheduling --------------------------------------------------------------
_flag("scheduler_spread_threshold", float, 0.5,
      "Hybrid policy: pack onto the local/low-index nodes until utilization "
      "passes this, then spread (hybrid_scheduling_policy.h:48).")
_flag("scheduler_locality_weight", float, 1.0,
      "Soft data-locality score weight: among fitting nodes, prefer the "
      "holder of the most argument bytes, traded off against utilization "
      "and dispatch-queue depth (the owner-side locality-aware lease "
      "policy, locality_aware_scheduling in the direct task transport). "
      "0 disables locality scoring entirely. Always subordinate to hard "
      "NodeAffinity / placement-group strategies and to spillback when "
      "the holder is saturated.")
_flag("locality_min_bytes", int, 256 * 1024,
      "Locality scoring engages only when some fitting node holds at "
      "least this many argument bytes — tiny args are cheaper to move "
      "than a placement distortion is to absorb (inlined args never "
      "count: they ship in the exec message).")
_flag("argument_prefetch", bool, True,
      "Pipelined argument prestage: when placement lands on a non-holder, "
      "submit the task to the node's dispatch queue immediately and pull "
      "its args concurrently, overlapping the transfer with queue wait "
      "instead of serializing it in front of execution. Prestaged pulls "
      "ride the broadcast-gate admission; a worker that wins the race "
      "simply blocks on its arg get until the same copy lands "
      "(create_or_wait dedupes). Off restores transfer-then-submit.")
_flag("worker_prestart_count", int, 2,
      "Workers to prestart per node at startup (worker_pool.h prestart).")
_flag("max_workers_per_node", int, 8,
      "Upper bound on pooled workers per node.")
_flag("worker_lease_timeout_s", float, 30.0,
      "How long a task waits for a worker lease before erroring.")
_flag("log_to_driver", bool, True,
      "Stream worker stdout/stderr to the driver, prefixed with the worker "
      "identity (the reference's log monitor tails worker logs to the "
      "driver, services.py:1126; here the lines ride the worker pipe).")
_flag("max_tasks_in_flight_per_worker", int, 10,
      "Pipelining depth: tasks whose resource request matches a busy "
      "worker's held lease queue on its pipe instead of waiting for the "
      "owner round trip (the reference's small-task pipelining knob, "
      "max_tasks_in_flight_per_worker in the direct task transport).")
_flag("worker_fork_server", bool, True,
      "Fork CPU-platform workers from a pre-warmed zygote process (ms "
      "spawns) instead of exec'ing a fresh interpreter (the reference's "
      "WorkerPool prestart/reuse economics, worker_pool.h:104,349,427). "
      "The worker of a chip lease always cold-spawns.")

# --- multi-host plane --------------------------------------------------------
_flag("enable_node_listener", bool, True,
      "Listen for node agents joining over TCP (the head side of the "
      "multi-host plane; node_agent.py is the raylet-process analog).")
_flag("node_listener_host", str, "127.0.0.1",
      "Interface the node listener binds. Use 0.0.0.0 to accept agents "
      "from other hosts.")
_flag("node_listener_port", int, 0,
      "Node listener port; 0 picks an ephemeral port.")

_flag("gcs_storage_path", str, "",
      "Durable GCS table storage (sqlite file). Empty = in-memory tables "
      "that die with the driver; set a path and detached actors + cluster "
      "KV survive head restarts (the Redis-FT analog, "
      "redis_store_client.h:28).")

# --- decentralized control plane ---------------------------------------------
_flag("gcs_directory_shards", int, 0,
      "Lock-striped shards for the GCS object directory (locations / "
      "sizes / tiers) and the head's refcount tables, keyed by object id "
      "so directory updates and free batches from different nodes never "
      "contend on one lock (the reference shards its GCS tables the same "
      "way, gcs_table_storage.h). 0 = auto (cpu_count, clamped to "
      "[4, gcs_directory_shards_max]).")
_flag("gcs_directory_shards_max", int, 64,
      "Upper clamp for AUTO directory-shard resolution. 64 shards stop "
      "paying off around 8 virtual nodes; pod-scale runs (64-256 node "
      "memberships) raise this so add/locate traffic from hundreds of "
      "agent channels keeps striping instead of re-serializing.")
_flag("gcs_directory_hot_max_rows", int, 1_000_000,
      "Hot-row budget for the GCS object directory, split evenly across "
      "shards. Rows beyond the per-shard share spill COLD (LRU within "
      "shard): holder set / size / tier map serialize in batches to the "
      "gcs_storage blob surface and fault back in transparently on "
      "locate, so head RSS stays bounded at millions of rows instead of "
      "growing ~1KB per live object. <=0 disables spilling (every row "
      "stays RAM-resident).")
_flag("gcs_directory_cold_s", float, 5.0,
      "A directory row is a spill candidate once it has not been "
      "located, renewed, or mutated for this long. The hard hot-row cap "
      "wins over recency: an over-budget shard spills its LRU tail even "
      "if some of it is younger than this.")
_flag("leaf_lease_batch", int, 64,
      "Max leaf-lease grants coalesced into one lease_batch frame per "
      "node per scheduling pass. The leaf fast path buffers grants "
      "head-side and flushes one frame per node instead of one frame "
      "per task, so per-node control ingress is O(flushes), not "
      "O(tasks). 1 disables coalescing (every grant ships alone, the "
      "pre-batching wire behavior).")
_flag("leaf_lease_slots", int, 0,
      "Execution-lease credits granted in bulk per node for LEAF tasks "
      "(no placement group / affinity / runtime_env, <=1 CPU, no TPU): "
      "the head places these round-robin without consulting the cluster "
      "scheduler, and node agents dispatch them onto their own workers, "
      "spilling back to the head router only when saturated (the raylet "
      "two-level lease protocol, raylet_client.h:398). 0 = auto "
      "(2x the node's CPU count); negative disables leaf leasing.")
# --- multi-tenant job plane --------------------------------------------------
_flag("job_watchdog_interval_s", float, 0.5,
      "Cadence of the cluster server's job watchdog: jobs whose client "
      "connection closed but whose disconnect notification was dropped "
      "(the job.detach fault site) are found and swept at this interval. "
      "<=0 disables the watchdog (dropped detaches then leak until "
      "shutdown — chaos-test territory only).")
_flag("job_sweep_retry_s", float, 1.0,
      "Delay before a job-death sweep that hit an error (the job.sweep "
      "fault site, or a transient runtime error mid-step) is re-run by "
      "the heartbeat loop. Sweeps are idempotent; retrying is always "
      "safe.")
_flag("reply_flush_window_s", float, 0.001,
      "Adaptive coalescing window for worker->head done replies: after "
      "the first queued reply the drain thread waits up to this long for "
      "more completions before writing one batch frame (flushes early on "
      "reply_flush_max or an urgent frame). 0 restores write-asap.")
_flag("reply_flush_max", int, 32,
      "Flush the worker reply batch as soon as it reaches this many "
      "frames, regardless of the adaptive window.")
_flag("sealed_wal_max_bytes", int, 32 * 1024,
      "With durable gcs_storage_path set, sealed object values up to "
      "this size are written to a sealed-object WAL so a head restart "
      "loses no sealed small objects (larger values stay recoverable "
      "through lineage / spill as before). 0 disables the WAL.")

# --- cloud storage credentials -----------------------------------------------
_flag("cloud_storage_access_key", str, "",
      "Access key id for the s3:// external-storage backend. Resolution "
      "order: this flag (incl. RMT_cloud_storage_access_key), then the "
      "AWS_ACCESS_KEY_ID environment variable, then the SDK default "
      "chain (instance profile, ~/.aws).")
_flag("cloud_storage_secret_key", str, "",
      "Secret access key paired with cloud_storage_access_key.")
_flag("cloud_storage_endpoint", str, "",
      "Endpoint URL override for the s3:// backend (minio, GCS interop "
      "mode). Empty uses the SDK default endpoint; also honors "
      "AWS_ENDPOINT_URL.")
_flag("cloud_storage_region", str, "",
      "Region for the s3:// backend; falls back to AWS_DEFAULT_REGION "
      "then the SDK default.")
_flag("cloud_storage_credentials_file", str, "",
      "Service-account JSON for the gs:// backend; falls back to "
      "GOOGLE_APPLICATION_CREDENTIALS then the SDK default chain.")

# --- fault tolerance ---------------------------------------------------------
_flag("fault_injection_spec", str, "",
      "Deterministic fault-injection plane spec (utils/faults.py): "
      "';'-separated 'site:mode[:p=P][:after=N][:max=N][:stall=S]' rules "
      "over the registered sites (transfer.send/recv/dial, spill.write/"
      "read, control.dispatch, worker.exec). Empty disables injection. "
      "Propagates to node agents and workers via RMT_fault_injection_spec.")
_flag("fault_injection_seed", int, 0,
      "Seed for the fault plane's per-site RNG streams: same seed + spec "
      "=> the same injection schedule, replayable across runs.")
_flag("transfer_retry_attempts", int, 3,
      "Max attempts per transfer-plane operation (dial, fetch) under the "
      "unified RetryPolicy before the failure is surfaced.")
_flag("transfer_retry_backoff_s", float, 0.05,
      "Base exponential backoff between transfer retries (jittered).")
_flag("transfer_stripe_deadline_s", float, 30.0,
      "Per-stripe progress deadline on a striped pull: a stripe that "
      "stalls past this re-resolves live holders and re-pulls its range "
      "from an alternate source (mid-pull holder failover) instead of "
      "hanging the whole fetch.")
_flag("transfer_verify_checksum", bool, True,
      "Verify the CRC32 carried in transfer replies / spill metadata at "
      "every materialization boundary (stripe completion, restore). A "
      "mismatch is treated as object loss — re-pull or reconstruct — "
      "never silent corruption.")
_flag("transfer_compression", str, "off",
      "Wire compression for the transfer plane (fetches, broadcast "
      "tree, spill write/restore). 'off' sends raw bytes (today's "
      "path, and what a codec-unaware v2 peer always gets); 'auto' "
      "negotiates the best codec both ends support (lz4 when "
      "available, else zlib); or name one codec ('zlib', 'lz4') to "
      "pin it. Negotiation is additive inside wire protocol v2 — a "
      "peer without the feature simply ignores the request key and "
      "replies raw.")
_flag("transfer_compress_min_bytes", int, 64 * 1024,
      "Payloads below this many bytes are never compressed (the "
      "syscall+CRC already dominates small pulls). Above it, a "
      "trial-block probe still skips encoding for incompressible "
      "payloads so the worst case stays within ~2% of the raw path.")
_flag("transfer_compress_level", int, 1,
      "zlib compression level for the wire codec (1 = fastest; the "
      "wire wants throughput, not archival ratio).")
_flag("collective_precision", str, "f32",
      "Default precision for quantized collectives when neither the "
      "op call nor the group names one: f32 (bit-exact, the default "
      "— quantization is strictly opt-in), bf16 (half the wire "
      "bytes), or int8 (block-wise scales, ~quarter the wire bytes); "
      "dequantize+accumulate always happens at f32 (EQuARX-style).")
_flag("spill_retry_attempts", int, 3,
      "Max attempts per spill/restore IO operation under the RetryPolicy.")
_flag("spill_retry_backoff_s", float, 0.1,
      "Base exponential backoff between spill IO retries (jittered).")
_flag("spill_degraded_backoff_s", float, 30.0,
      "After spill IO exhausts its retries, the store degrades to keeping "
      "objects in memory under backpressure (loud SPILL_DEGRADED event, "
      "not a crash) and re-probes the storage backend at this period.")
_flag("unsealed_create_deadline_s", float, 300.0,
      "Unsealed creates older than this are swept and aborted (the "
      "fetching process died mid-pull and leaked the allocation). Must "
      "comfortably exceed every bounded transfer timeout so a live "
      "in-flight pull is never swept out from under its writer.")
_flag("num_heartbeats_timeout", int, 30,
      "Missed heartbeats before a node is declared dead "
      "(gcs_heartbeat_manager.cc:29).")
_flag("heartbeat_interval_s", float, 0.5, "Node heartbeat period.")
_flag("task_max_retries", int, 4,
      "Default retries for normal tasks (remote_function.py:161-166).")
_flag("actor_max_restarts", int, 0, "Default actor restarts.")

# --- serve data plane --------------------------------------------------------
_flag("serve_backpressure_timeout_s", float, 60.0,
      "How long a Router.assign call waits for a replica slot to drain "
      "before shedding the request (raises BackpressureTimeout and bumps "
      "rmt_serve_shed_total{reason=backpressure_timeout}).")
_flag("kv_page_tokens", int, 64,
      "KV-cache page size in tokens for the serve engine's paged "
      "device cache: a request reserves pages of this many positions "
      "from one resident pool instead of max_seq up front, and the "
      "decode step fetches only the pages a row's length reaches.")
_flag("serve_kv_pool_bytes", int, 0,
      "Per-replica KV page-pool budget in bytes. 0 sizes the pool to "
      "max_slots x max_seq positions, so every slot can hold a request "
      "of the model's full length at once; exhaustion causes admission "
      "backpressure, never an allocation failure.")
_flag("serve_shed_queue_factor", float, 2.0,
      "HTTP proxy load-shed threshold as a multiple of the deployment's "
      "total capacity (replicas x max_concurrent_queries): when the "
      "known queue depth exceeds it the proxy answers 429 instead of "
      "queueing the request.")

# --- misc --------------------------------------------------------------------
_flag("memory_monitor_interval_s", float, 0.0,
      "Node OOM-monitor check period (memory_monitor.h analog). 0 "
      "disables it; when enabled, host memory above the threshold kills "
      "the newest running task's worker (it retries under its budget).")
_flag("memory_usage_threshold", float, 0.95,
      "Fraction of host memory use that triggers the OOM kill "
      "(ray_config_def.h memory_usage_threshold analog).")
_flag("event_stats", bool, True,
      "Collect per-handler event-loop stats (src/ray/common/event_stats.cc).")

# --- observability: profiling plane ------------------------------------------
_flag("profile_hz", float, 11.0,
      "Continuous wall-clock stack-sampling rate (samples/s) for the "
      "profiling plane's always-on sampler in every process (worker, "
      "agent, head). Low by design: the acceptance contract is <= 5% "
      "tasks/s overhead on the chatty fan-out. 0 disables the continuous "
      "sampler (burst capture stays available); RMT_PROFILE=0 disables "
      "the whole plane.")
_flag("profile_burst_hz", float, 97.0,
      "Sampling rate for on-demand burst captures (rmt profile --hz "
      "default, and the RMT_WORKER_PROFILE deprecation alias). Bursts "
      "are short and opt-in, so this trades overhead for resolution.")

# --- observability: health plane ---------------------------------------------
_flag("metrics_max_series_per_name", int, 256,
      "Cardinality guard: max distinct tag-value combinations a single "
      "metric name may hold in the registry. The first write past the "
      "cap folds into an all-__other__ overflow series (counted by "
      "rmt_metrics_series_overflow_total{metric}) so an unbounded "
      "job_id/deployment tag space cannot grow the registry or the "
      "Prometheus exposition forever. 0 disables the cap.")
_flag("tsdb_raw_points", int, 600,
      "Per-series raw ring size in the head's time-series store. At the "
      "0.5s heartbeat tick, 600 points ~= 5 minutes of tick-resolution "
      "history; the ring is a fixed-size deque, so head RSS is bounded "
      "by construction.")
_flag("tsdb_downsample_every", int, 10,
      "Every N raw samples the tsdb folds them into one aggregate "
      "(min/max/last/count) point in the downsampled ring, trading "
      "resolution for horizon (10 ticks at 0.5s = one 5s point).")
_flag("tsdb_downsample_points", int, 720,
      "Per-series downsampled ring size: 720 aggregate points at one "
      "per 5s ~= 1 hour of coarse history behind the raw window.")
_flag("tsdb_max_series_per_name", int, 64,
      "Per-name series cap inside the tsdb (tighter than the registry "
      "guard: the store keeps history per series, not one float). "
      "Samples for tag combos past the cap fold into an all-__other__ "
      "bucket and are counted by rmt_tsdb_dropped_total{reason}. "
      "0 disables the cap.")


def _coerce(typ, raw: str):
    if typ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return typ(raw)


# Version of every cross-process wire schema (node registration, thin-client
# requests, transfer-plane fetches — the reference versions its protobuf
# schemas the same way, src/ray/protobuf/). Strict equality: a mixed-version
# cluster fails LOUDLY at the handshake with both versions named, instead of
# mis-parsing a frame mid-run. Bump on ANY incompatible message change.
# v2: transfer-plane range requests ({oid, offset, length}) + per-connection
# request loops (connection reuse) replaced v1's one-full-object-per-
# connection fetch.
WIRE_PROTOCOL_VERSION = 2


class Config:
    """A scoped snapshot of all flags, with ``RMT_<NAME>`` env overrides
    applied at construction time (the reference reads ``RAY_<name>`` once at
    process start, ray_config.h:58)."""

    def __init__(self, **overrides: Any):
        for name, (typ, default, _doc) in _FLAG_DEFS.items():
            env = os.environ.get(f"RMT_{name}")
            value = _coerce(typ, env) if env is not None else default
            setattr(self, name, value)
        for k, v in overrides.items():
            if k not in _FLAG_DEFS:
                raise ValueError(f"unknown config flag: {k}")
            setattr(self, k, v)

    def to_dict(self) -> Dict[str, Any]:
        return {name: getattr(self, name) for name in _FLAG_DEFS}

    @staticmethod
    def flag_docs() -> Dict[str, str]:
        return {name: doc for name, (_t, _d, doc) in _FLAG_DEFS.items()}


_global_config: Config | None = None


def global_config() -> Config:
    global _global_config
    if _global_config is None:
        _global_config = Config()
    return _global_config


def set_global_config(cfg: Config) -> None:
    global _global_config
    _global_config = cfg
