"""The bench evidence chain: the driver captures only the TAIL of
bench.py's stdout, so the last line must stay compact (<1 KB) no matter
how many rows the suites emit, and a TPU section that could not be measured
is an error that says why, never numbers from another run.
"""

import importlib.util
import json
import os
import sys
import time

import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("rmt_bench", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bloated_inputs():
    results = {"single_client_put_gigabytes": 9.64,
               **{f"row_{i}": 123.4 for i in range(40)}}
    stats = {k: {"median": 11912.5267891, "min": 10991.1877,
                 "max": 12835.6629, "trials": 3}
             for k in ("single_client_tasks_sync",
                       "single_client_tasks_async",
                       "single_client_put_gigabytes",
                       *(f"row_{i}" for i in range(40)))}
    ratios = {k: 3.0 for k in results}
    scale = {"many_actors_per_s": 86.54, "many_tasks_per_s": 3635.1,
             "many_pgs_per_s": 29890.64, "broadcast_gbps": 5.37,
             "cross_node_gbps": 3.65, "head_peak_rss_mb": 762.6,
             "stats": {k: {"median": 1.0, "min": 0.5, "max": 2.0}
                       for k in range(20)}}
    tpu = {"train_mfu": 0.532, "train_tokens_per_s": 101786.0,
           "train_rows": {
               "llama-1b S=2048": {"tokens_per_s": 17356.0,
                                   "mfu": 0.4795},
               "gpt2-small S=4096": {"tokens_per_s": 61818.0,
                                     "mfu": 0.377}},
           "flash_speedup": {"1024": 1.1, "4096": 1.8, "8192": 2.4},
           "device": {"platform": "tpu", "kind": "TPU v5 lite",
                      "count": 1}}
    return results, stats, ratios, scale, tpu


def test_headline_line_stays_under_1kb(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu)
    assert len(payload) <= 1000
    line = json.loads(payload)
    # the mandated fields the driver must see
    assert line["vs_baseline"] == 3.02
    assert line["hw"]["memcpy_gbps"] == 11.56
    assert line["hw"]["put_vs_memcpy_ceiling"] == round(9.64 / 11.56, 3)
    assert line["tpu"]["train_mfu"] == 0.532
    assert line["tpu"]["llama1b_mfu"] == 0.4795
    assert line["tpu"]["flash_speedup_8192"] == 2.4
    assert line["tpu"]["device_kind"] == "TPU v5 lite"
    assert line["scale"]["many_actors_per_s"] == 86.54
    assert line["micro"]["single_client_tasks_async"] == 11912.5


def test_tpu_section_without_chip_is_an_error(bench, monkeypatch):
    """No chip: the TPU section is an error naming the reason (nothing is
    merged in from an earlier run), and the headline carries it, short."""
    from ray_memory_management_tpu import api

    monkeypatch.setattr(api, "_detect_tpu_chips", lambda: 0)
    tpu = bench._tpu_suite()
    assert set(tpu) == {"error"} and "no TPU chip" in tpu["error"]
    results, stats, ratios, scale, _ = _bloated_inputs()
    payload = bench.headline_line(
        results, stats, ratios, 3.02, 11.56, scale,
        {"error": tpu["error"] + "x" * 500})
    assert len(payload) <= 1000
    assert "no TPU chip" in json.loads(payload)["tpu"]["error"]


def test_transfer_microbench_reports_required_fields(bench):
    """The transfer suite must emit every field the BENCH_DETAIL.json
    contract names (stripe counters, pool hit rate, chain egress) — run a
    mini-sized pass so CI proves the real code path, not a fixture."""
    from ray_memory_management_tpu.utils.transfer_bench import (
        run_transfer_microbench,
    )

    out = run_transfer_microbench(small_pulls=25, payload_mb=16, n_dests=2)
    missing = [k for k in bench.REQUIRED_TRANSFER_FIELDS if k not in out]
    assert not missing, missing
    assert out["stripe_requests"] >= 1
    assert 0.0 <= out["pool_hit_rate"] <= 1.0
    # the distribution-tree egress property, in bytes: naive serves every
    # copy off one node; the chain caps any single node at ~one copy
    assert out["naive_source_bytes"] == 2 * out["chain_max_source_bytes"]


def test_headline_line_carries_transfer_summary(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    transfer = {"pool_speedup": 3.07, "small_pull_p50_us_pooled": 113.5,
                "small_pull_p50_us_fresh": 348.0, "pool_hit_rate": 0.99,
                "naive_source_bytes": 4 << 30,
                "chain_max_source_bytes": 1 << 30}
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, transfer)
    assert len(payload) <= 1000
    line = json.loads(payload)
    if "transfer" in line:  # may be popped only by the <1KB guard
        assert line["transfer"]["pool_speedup"] == 3.07
        assert line["transfer"]["egress_flatten"] == 4.0


def test_locality_suite_reports_required_fields(bench, monkeypatch):
    """The locality suite must emit every field the BENCH_DETAIL.json
    contract names (on/off tasks-per-s, bytes moved, locality counters,
    prestage overlap) — run a mini-sized pass so CI proves the real code
    path, not a fixture."""
    from ray_memory_management_tpu.core.runtime import Runtime
    from ray_memory_management_tpu.utils.locality_bench import (
        run_locality_suite,
    )

    pull = Runtime._transfer_from

    def cross_host_pull(self, *a, **kw):
        # a same-host copy of 4 MB lands in about 2 ms, at times before the
        # router has stamped SCHEDULED on the task it handed on; a pull
        # from another host takes longer, and 50 ms of it makes the pull
        # certain to outlast the hand-off to the dispatch queue
        time.sleep(0.05)
        return pull(self, *a, **kw)

    monkeypatch.setattr(Runtime, "_transfer_from", cross_host_pull)
    out = run_locality_suite(n_nodes=2, n_tasks=4, arg_mb=4, trials=1)
    missing = [k for k in bench.REQUIRED_LOCALITY_FIELDS if k not in out]
    assert not missing, missing
    assert out["locality_on_tasks_per_s"] > 0
    assert out["locality_off_tasks_per_s"] > 0
    assert out["locality_bytes_avoided_mb"] > 0
    # the prestage proof: a forced non-holder placement pulled its arg
    # while the task rode the dispatch queue
    assert out["prefetch_completed"] >= 1
    assert out["prefetch_overlap_ms"] > 0


def test_headline_line_carries_locality_summary(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    locality = {"locality_speedup": 8.2, "locality_bytes_avoided_mb": 384.0,
                "prefetch_overlap_ms": 11.3}
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, None, locality)
    assert len(payload) <= 1000
    line = json.loads(payload)
    if "locality" in line:  # may be popped only by the <1KB guard
        assert line["locality"]["speedup"] == 8.2
        assert line["locality"]["prefetch_overlap_ms"] == 11.3


def test_tracing_suite_reports_required_fields(bench):
    """The tracing suite must emit every field the BENCH_DETAIL.json
    contract names (on/off tasks-per-s, overhead pct) — run a mini-sized
    pass so CI proves the real code path, not a fixture."""
    from ray_memory_management_tpu.utils.tracing_bench import (
        run_tracing_suite,
    )

    out = run_tracing_suite(n_tasks=16, trials=1)
    missing = [k for k in bench.REQUIRED_TRACING_FIELDS if k not in out]
    assert not missing, missing
    assert out["tracing_on_tasks_per_s"] > 0
    assert out["tracing_off_tasks_per_s"] > 0


def test_headline_line_carries_tracing_summary(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    tracing = {"tracing_overhead_pct": 2.4}
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, None, None, tracing)
    assert len(payload) <= 1000
    line = json.loads(payload)
    if "tracing" in line:  # may be popped only by the <1KB guard
        assert line["tracing"]["overhead_pct"] == 2.4


def test_logging_suite_reports_required_fields(bench):
    """The logging suite must emit every field the BENCH_DETAIL.json
    contract names (on/off tasks-per-s, overhead pct) — run a mini-sized
    pass so CI proves the real code path, not a fixture."""
    from ray_memory_management_tpu.utils.logging_bench import (
        run_logging_suite,
    )

    out = run_logging_suite(n_tasks=16, trials=1)
    missing = [k for k in bench.REQUIRED_LOGGING_FIELDS if k not in out]
    assert not missing, missing
    assert out["logging_on_tasks_per_s"] > 0
    assert out["logging_off_tasks_per_s"] > 0


def test_headline_line_carries_logging_summary(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    logging_out = {"logging_overhead_pct": 1.8}
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, logging=logging_out)
    assert len(payload) <= 1000
    line = json.loads(payload)
    if "logging" in line:  # may be popped only by the <1KB guard
        assert line["logging"]["overhead_pct"] == 1.8


def test_profile_suite_reports_required_fields(bench):
    """The profiling suite must emit every field the BENCH_DETAIL.json
    contract names (on/off tasks-per-s, overhead pct) — run a mini-sized
    pass so CI proves the real code path, not a fixture."""
    from ray_memory_management_tpu.utils.profile_bench import (
        run_profile_suite,
    )

    out = run_profile_suite(n_tasks=16, trials=1)
    missing = [k for k in bench.REQUIRED_PROFILE_FIELDS if k not in out]
    assert not missing, missing
    assert out["profile_on_tasks_per_s"] > 0
    assert out["profile_off_tasks_per_s"] > 0


def test_headline_line_carries_profile_summary(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    profile = {"profile_overhead_pct": 2.1}
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, profile=profile)
    assert len(payload) <= 1000
    line = json.loads(payload)
    if "profile" in line:  # may be popped only by the <1KB guard
        assert line["profile"]["overhead_pct"] == 2.1


def test_bench_detail_snapshot_has_profile_section(bench):
    """An existing BENCH_DETAIL.json snapshot (written by a full bench
    run) must carry the profile section with the required fields."""
    path = os.path.join(os.path.dirname(_BENCH), "BENCH_DETAIL.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_DETAIL.json snapshot in repo")
    with open(path) as f:
        detail = json.load(f)
    profile = detail.get("profile")
    if profile is None:
        pytest.skip("snapshot predates the profile section")
    if "error" not in profile:
        missing = [k for k in bench.REQUIRED_PROFILE_FIELDS
                   if k not in profile]
        assert not missing, missing


def test_health_suite_reports_required_fields(bench):
    """The health suite must emit every field the BENCH_DETAIL.json
    contract names (on/off tasks-per-s, overhead pct, pod-scale store
    footprint) — run a mini-sized pass so CI proves the real code path,
    not a fixture."""
    from ray_memory_management_tpu.utils.health_bench import (
        run_health_suite,
    )

    out = run_health_suite(n_tasks=16, trials=1, sim_nodes=16, n_rules=3)
    missing = [k for k in bench.REQUIRED_HEALTH_FIELDS if k not in out]
    assert not missing, missing
    assert out["health_on_tasks_per_s"] > 0
    assert out["health_off_tasks_per_s"] > 0
    assert out["rule_eval_ms"] >= 0
    assert out["store_points"] > 0  # the rings actually filled


def test_headline_line_carries_health_summary(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    health = {"health_overhead_pct": 1.4}
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, health=health)
    assert len(payload) <= 1000
    line = json.loads(payload)
    if "health" in line:  # may be popped only by the <1KB guard
        assert line["health"]["overhead_pct"] == 1.4


def test_bench_detail_snapshot_has_health_section(bench):
    """An existing BENCH_DETAIL.json snapshot (written by a full bench
    run) must carry the health section with the required fields."""
    path = os.path.join(os.path.dirname(_BENCH), "BENCH_DETAIL.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_DETAIL.json snapshot in repo")
    with open(path) as f:
        detail = json.load(f)
    health = detail.get("health")
    if health is None:
        pytest.skip("snapshot predates the health section")
    if "error" not in health:
        missing = [k for k in bench.REQUIRED_HEALTH_FIELDS
                   if k not in health]
        assert not missing, missing


def test_elastic_suite_reports_required_fields(bench):
    """The elastic-training suite must emit every field the
    BENCH_DETAIL.json contract names (steps/s off/sync/async, blocking
    split, recovery) — run a mini-sized pass so CI proves the real code
    path, not a fixture."""
    from ray_memory_management_tpu.utils.train_elastic_bench import (
        run_elastic_suite,
    )

    out = run_elastic_suite(n_steps=6, checkpoint_every=2, payload_kb=8,
                            save_trials=3)
    missing = [k for k in bench.REQUIRED_ELASTIC_FIELDS if k not in out]
    assert not missing, missing
    assert out["steps_per_s_ckpt_off"] > 0
    assert out["steps_per_s_ckpt_sync"] > 0
    assert out["steps_per_s_ckpt_async"] > 0
    assert out["blocking_ms_sync"] > 0
    # the acceptance property: async blocks the step for a small
    # fraction of the sync write (the ISSUE caps it at 10%)
    assert out["async_blocking_vs_sync_pct"] < 50


def test_compression_bench_reports_required_fields(bench):
    """The compressed-movement-plane suite must emit every field the
    BENCH_DETAIL.json contract names (per-corpus ratio + BOTH raw and
    effective GB/s plus the same-run uncompressed control, the
    incompressible overhead bound, the broadcast chain, and the
    per-precision allreduce accuracy) — run a mini-sized pass so CI
    proves the real code path, not a fixture."""
    from ray_memory_management_tpu.utils.transfer_bench import (
        run_compression_bench,
    )

    out = run_compression_bench(payload_mb=8, n_dests=2, trials=1,
                                overhead_trials=1)
    missing = [k for k in bench.REQUIRED_COMPRESSION_FIELDS
               if k not in out]
    assert not missing, missing
    for name in out["corpora"]:
        assert out["corpus_effective_gbps"][name] > 0, name
        assert out["corpus_raw_gbps"][name] > 0, name
        assert out["corpus_uncompressed_gbps"][name] > 0, name
        assert out["corpus_ratio"][name] >= 1.0, name
    # the sparse gradient corpus must actually compress on the wire
    assert out["corpus_ratio"]["sparse-grad"] > 2.0
    assert out["corpus_codec"]["random"] is None  # probe skipped it
    assert out["broadcast_effective_gbps"] > 0
    # per-precision accuracy: f32 bit-exact, sub-f32 within envelope
    assert out["allreduce_err"]["f32"] == 0.0
    assert 0 < out["allreduce_err"]["bf16"] <= 2.0 ** -7
    assert 0 < out["allreduce_err"]["int8"] <= 1.5 / 127.0
    assert out["allreduce_wire_factor"]["bf16"] == pytest.approx(2.0)
    assert out["allreduce_wire_factor"]["int8"] > 3.0


def test_headline_line_carries_compression_summary(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    compression = {
        "broadcast_corpus": "sparse-grad",
        "corpus_effective_gbps": {"zeros": 0.7, "random": 0.5},
        "corpus_uncompressed_gbps": {"zeros": 0.35, "random": 0.5},
        "broadcast_effective_gbps": 0.4,
        "broadcast_uncompressed_gbps": 0.2,
        "incompressible_overhead_pct": 1.1,
        "allreduce_err": {"f32": 0.0, "bf16": 0.002, "int8": 0.005},
    }
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, None, None, None, None,
                                  compression)
    assert len(payload) <= 1000
    line = json.loads(payload)
    if "compression" in line:  # may be popped only by the <1KB guard
        assert line["compression"]["best_corpus"] == "zeros"
        assert line["compression"]["vs_uncompressed"] == 2.0
        assert line["compression"]["chain_vs_uncompressed"] == 2.0
        assert line["compression"]["int8_err"] == 0.005


def test_bench_detail_snapshot_has_compression_section(bench):
    """An existing BENCH_DETAIL.json snapshot (written by a full bench
    run) must carry the compression section with the required fields."""
    path = os.path.join(os.path.dirname(_BENCH), "BENCH_DETAIL.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_DETAIL.json snapshot in repo")
    with open(path) as f:
        detail = json.load(f)
    compression = detail.get("compression")
    if compression is None:
        pytest.skip("snapshot predates the compression section")
    if "error" not in compression:
        missing = [k for k in bench.REQUIRED_COMPRESSION_FIELDS
                   if k not in compression]
        assert not missing, missing


def test_headline_line_carries_elastic_summary(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    elastic = {"async_blocking_vs_sync_pct": 4.2, "recovery_s": 1.7}
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, None, None, None, elastic)
    assert len(payload) <= 1000
    line = json.loads(payload)
    if "elastic" in line:  # may be popped only by the <1KB guard
        assert line["elastic"]["async_vs_sync_pct"] == 4.2
        assert line["elastic"]["recovery_s"] == 1.7


def test_bench_detail_snapshot_has_elastic_section(bench):
    """An existing BENCH_DETAIL.json snapshot (written by a full bench
    run) must carry the elastic section with the required fields."""
    path = os.path.join(os.path.dirname(_BENCH), "BENCH_DETAIL.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_DETAIL.json snapshot in repo")
    with open(path) as f:
        detail = json.load(f)
    elastic = detail.get("elastic")
    if elastic is None:
        pytest.skip("snapshot predates the elastic section")
    if "error" not in elastic:
        missing = [k for k in bench.REQUIRED_ELASTIC_FIELDS
                   if k not in elastic]
        assert not missing, missing


def test_bench_detail_snapshot_has_tracing_section(bench):
    """An existing BENCH_DETAIL.json snapshot (written by a full bench
    run) must carry the tracing section with the required fields."""
    path = os.path.join(os.path.dirname(_BENCH), "BENCH_DETAIL.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_DETAIL.json snapshot in repo")
    with open(path) as f:
        detail = json.load(f)
    tracing = detail.get("tracing")
    if tracing is None:
        pytest.skip("snapshot predates the tracing section")
    if "error" not in tracing:
        missing = [k for k in bench.REQUIRED_TRACING_FIELDS
                   if k not in tracing]
        assert not missing, missing


def test_bench_detail_snapshot_has_logging_section(bench):
    """An existing BENCH_DETAIL.json snapshot (written by a full bench
    run) must carry the logging section with the required fields."""
    path = os.path.join(os.path.dirname(_BENCH), "BENCH_DETAIL.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_DETAIL.json snapshot in repo")
    with open(path) as f:
        detail = json.load(f)
    logging_out = detail.get("logging")
    if logging_out is None:
        pytest.skip("snapshot predates the logging section")
    if "error" not in logging_out:
        missing = [k for k in bench.REQUIRED_LOGGING_FIELDS
                   if k not in logging_out]
        assert not missing, missing


def test_bench_detail_snapshot_has_locality_section(bench):
    """An existing BENCH_DETAIL.json snapshot (written by a full bench
    run) must carry the locality section with the required fields."""
    path = os.path.join(os.path.dirname(_BENCH), "BENCH_DETAIL.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_DETAIL.json snapshot in repo")
    with open(path) as f:
        detail = json.load(f)
    locality = detail.get("locality")
    assert locality, "BENCH_DETAIL.json lacks the locality section"
    if "error" not in locality:
        missing = [k for k in bench.REQUIRED_LOCALITY_FIELDS
                   if k not in locality]
        assert not missing, missing


def test_bench_detail_snapshot_has_transfer_section(bench):
    """An existing BENCH_DETAIL.json snapshot (written by a full bench
    run) must carry the transfer section with the required fields."""
    path = os.path.join(os.path.dirname(_BENCH), "BENCH_DETAIL.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_DETAIL.json snapshot in repo")
    with open(path) as f:
        detail = json.load(f)
    transfer = detail.get("transfer")
    assert transfer, "BENCH_DETAIL.json lacks the transfer section"
    if "error" not in transfer:
        missing = [k for k in bench.REQUIRED_TRANSFER_FIELDS
                   if k not in transfer]
        assert not missing, missing


def test_device_suite_reports_required_fields(bench):
    """The device-tier suite must emit every field the BENCH_DETAIL.json
    contract names (zero-copy vs shm round trip, demotion, ICI vs host,
    eviction sweep) — run a mini-sized pass so CI proves the real code
    path, not a fixture."""
    from ray_memory_management_tpu.utils.device_bench import (
        run_device_suite,
    )

    out = run_device_suite(payload_mb=4, trials=1, sweep_mb=(1,))
    missing = [k for k in bench.REQUIRED_DEVICE_FIELDS if k not in out]
    assert not missing, missing
    assert out["zero_copy_gbps"] > 0
    assert out["shm_roundtrip_gbps"] > 0
    # the zero-copy proof: the read skipped serialization outright
    assert out["bytes_avoided_mb"] > 0
    assert out["demotion_evictions"] >= 1
    assert out["eviction_sweep"] and out["eviction_sweep"][0]["evictions"] > 0


def test_headline_line_carries_device_summary(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    device = {"zero_copy_gbps": 31.0, "zero_copy_speedup": 14.2,
              "bytes_avoided_mb": 192.0, "demotion_gbps": 3.1,
              "ici_vs_host_speedup": 88.0}
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, device=device)
    assert len(payload) <= 1000
    line = json.loads(payload)
    if "device" in line:  # may be popped only by the <1KB guard
        assert line["device"]["zero_copy_speedup"] == 14.2
        assert line["device"]["bytes_avoided_mb"] == 192.0


def test_bench_detail_snapshot_has_device_section(bench):
    """An existing BENCH_DETAIL.json snapshot (written by a full bench
    run) must carry the device section with the required fields."""
    path = os.path.join(os.path.dirname(_BENCH), "BENCH_DETAIL.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_DETAIL.json snapshot in repo")
    with open(path) as f:
        detail = json.load(f)
    device = detail.get("device")
    assert device, "BENCH_DETAIL.json lacks the device section"
    if "error" not in device:
        missing = [k for k in bench.REQUIRED_DEVICE_FIELDS
                   if k not in device]
        assert not missing, missing


def test_headline_line_carries_scale_curve_summary(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    scale_curve = {
        "nodes": [1, 2, 4, 8],
        "many_tasks_per_s": {"1": 2850.4, "2": 3105.2, "4": 3320.8,
                             "8": 3290.1},
        "many_actors_per_s": {"1": 3.1, "2": 4.2, "4": 5.0, "8": 4.8},
        "tasks_scaling_1_to_4": 1.165,
        "actors_scaling_1_to_4": 1.613,
        "stats": {"many_tasks_per_s": {
            str(n): {"median": 1.0, "min": 0.5, "max": 2.0, "trials": 3}
            for n in (1, 2, 4, 8)}},
    }
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, scale_curve=scale_curve)
    assert len(payload) <= 1000
    line = json.loads(payload)
    if "scale_curve" in line:  # may be popped only by the <1KB guard
        assert line["scale_curve"]["tasks_per_s"]["4"] == 3320.8
        assert line["scale_curve"]["tasks_scaling_1_to_4"] == 1.165
        # per-point keys are strings so the dotted perf-gate lookup
        # (scale_curve.tasks_per_s.4) resolves after a JSON round trip
        assert all(isinstance(k, str)
                   for k in line["scale_curve"]["tasks_per_s"])


def test_headline_line_drops_errored_scale_curve(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu,
                                  scale_curve={"error": "boom"})
    assert "scale_curve" not in json.loads(payload)


@pytest.mark.slow
def test_scale_curve_required_fields(bench):
    """A tiny two-point curve run end-to-end: every REQUIRED field
    present, per-point stats keyed by stringified node count."""
    from ray_memory_management_tpu.utils.scale_bench import run_scale_curve

    out = run_scale_curve(node_counts=(1, 2), per_node_cpus=1,
                          n_tasks=100, n_actors=2, trials=1)
    missing = [k for k in bench.REQUIRED_SCALE_CURVE_FIELDS
               if k not in out]
    assert not missing, missing
    assert out["nodes"] == [1, 2]
    assert set(out["many_tasks_per_s"]) == {"1", "2"}
    assert all(v > 0 for v in out["many_tasks_per_s"].values())
    assert all(v > 0 for v in out["many_actors_per_s"].values())
    # only 1 and 4-node points define the 1->4 factor; a 2-point run
    # leaves it None rather than inventing a ratio
    assert out["tasks_scaling_1_to_4"] is None
    row = out["stats"]["many_tasks_per_s"]["1"]
    assert {"median", "min", "max", "trials"} <= set(row)


def test_headline_line_carries_pod_curve_summary(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    pod = {
        "nodes": [8, 64, 128, 256],
        "tasks_per_s": {"8": 2900.0, "64": 2400.0, "128": 2100.0,
                        "256": 1800.0},
        "dir_p50_us": {"8": 4.0, "64": 5.0, "128": 6.0, "256": 8.0},
        "dir_p99_us": {"8": 20.0, "64": 40.0, "128": 80.0, "256": 160.0},
        "head_rss_mb": {"8": 210.0, "64": 240.0, "128": 280.0,
                        "256": 340.0},
        "tasks_scaling_first_to_last": 0.62,
        "rows": {"target": 1_000_000, "total": 1_000_192, "hot": 200_000,
                 "cold": 800_192, "rss_mb_at_rows": 410.0, "faults": 12,
                 "spills": 900, "resyncs": 0, "full_pongs": 0,
                 "delta_pongs": 5120, "churn_rows_shipped": 19984},
    }
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, pod=pod)
    assert len(payload) <= 1000
    line = json.loads(payload)
    if "pod_curve" in line:  # may be popped only by the <1KB guard
        # first/last points carry the perf-gate field names verbatim
        assert line["pod_curve"]["nodes_max"] == 256
        assert line["pod_curve"]["tasks_per_s_8"] == 2900.0
        assert line["pod_curve"]["tasks_per_s_256"] == 1800.0
        assert line["pod_curve"]["dir_p99_us_256"] == 160.0
        assert line["pod_curve"]["head_rss_mb_256"] == 340.0
        assert line["pod_curve"]["rows_total"] == 1_000_192
        assert line["pod_curve"]["rows_rss_mb"] == 410.0
        assert line["pod_curve"]["rows_full_pongs"] == 0


def test_headline_line_drops_errored_pod_curve(bench):
    results, stats, ratios, scale, tpu = _bloated_inputs()
    payload = bench.headline_line(results, stats, ratios, 3.02, 11.56,
                                  scale, tpu, pod={"error": "boom"})
    assert "pod_curve" not in json.loads(payload)


def test_bench_detail_snapshot_has_pod_section(bench):
    """An existing BENCH_DETAIL.json snapshot (written by a full bench
    run) must carry the pod section with the required fields."""
    path = os.path.join(os.path.dirname(_BENCH), "BENCH_DETAIL.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_DETAIL.json snapshot in repo")
    with open(path) as f:
        detail = json.load(f)
    pod = detail.get("pod")
    if pod is None:
        pytest.skip("snapshot predates the pod section")
    if "error" not in pod:
        missing = [k for k in bench.REQUIRED_POD_FIELDS if k not in pod]
        assert not missing, missing


@pytest.mark.slow
def test_pod_curve_required_fields(bench):
    """A mini pod curve end-to-end (real sim agents over real channels,
    real row flood against the bounded directory): every REQUIRED field
    present, per-point dicts keyed by stringified node count, and the
    flood's convergence/bound evidence populated."""
    from ray_memory_management_tpu.utils.pod_bench import run_pod_curve

    out = run_pod_curve(node_counts=(2, 4), tasks_per_point=80,
                        rows_target=3000, hot_max_rows=512,
                        rows_per_agent_chunk=250)
    missing = [k for k in bench.REQUIRED_POD_FIELDS if k not in out]
    assert not missing, missing
    assert out["nodes"] == [2, 4]
    assert set(out["tasks_per_s"]) == {"2", "4"}
    assert all(v > 0 for v in out["tasks_per_s"].values())
    assert all(v > 0 for v in out["dir_p99_us"].values())
    assert all(v > 0 for v in out["head_rss_mb"].values())
    rows = out["rows"]
    assert rows["total"] >= rows["target"] == 3000
    assert rows["cold"] > 0  # the hot cap engaged during the flood
    assert rows["rss_mb_at_rows"] > 0
