"""The serve engine measured from inside (``ContinuousBatcher``): loop
phases that add up to the engine thread's wall time, counts that only grow
and read consistently from other threads, the seconds of each phase in
which nothing the engine dispatched was unread (``starved_s``) and the
iterations that stalled (``stalls``), a request's queue / prefill / decode
spans under the caller's trace id, and the phases as ``rmt.engine.*`` spans
on the profiler's own clock.

CPU, toy preset: what is checked is the accounting, not a speed.
"""

import glob
import sys
import threading
import time

import pytest

import ray_memory_management_tpu as rmt
from ray_memory_management_tpu import serve
from ray_memory_management_tpu.serve.llm import (
    ENGINE_PHASES, ContinuousBatcher, llm_deployment,
)
from ray_memory_management_tpu.utils import (
    faults, profiling, timeline, tracing,
)

MAX_SLOTS, PAGE = 4, 16


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_memory_management_tpu.models import gpt

    cfg = gpt.PRESETS["test"]
    return cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def whole_model():
    """A model that offers no ``mixed_step``: its prompts are prefilled
    whole (``_iterate``, ``_admit``)."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import latent_moe

    cfg = latent_moe.LatentMoEConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=4,
        v_head_dim=16, d_ff=128, moe_d_ff=32, n_routed_experts=8,
        n_shared_experts=1, experts_per_tok=2, routed_scaling_factor=1.8,
        max_seq=128, dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, latent_moe.init_params(jax.random.PRNGKey(7), cfg)


@pytest.fixture(params=["chunk", "whole"])
def either_model(request, model, whole_model):
    """Both of the engine's paths: it chooses by ``mixed_step``'s presence."""
    picked = model if request.param == "chunk" else whole_model
    return request.param, picked


def engine(model):
    cfg, params = model
    return ContinuousBatcher(
        params, cfg, max_slots=MAX_SLOTS, max_new_tokens=24,
        pad_multiple=PAGE, steps_per_iter=4, kv_page_tokens=PAGE)


def wave(eng, n=12):
    """``n`` concurrent requests of mixed lengths; returns when all are
    answered."""
    errors = []

    def one(i):
        try:
            out = eng.submit(list(range(2, 8 + 3 * i)),
                             max_new_tokens=6 + 2 * (i % 8), timeout=120)
            assert len(out) == 6 + 2 * (i % 8)
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(150)
    assert not errors and not any(t.is_alive() for t in threads), errors


def close(eng):
    eng.close()
    eng._thread.join(10)
    assert not eng._thread.is_alive()


# ------------------------------------------------------------- (a) accounting
def test_phases_add_up_to_the_engine_threads_wall_time(model):
    eng = engine(model)
    t0 = time.perf_counter()  # the constructor started the thread
    try:
        while eng.engine_stats()["iterations"] < 50:
            wave(eng)
    finally:
        close(eng)
    wall = time.perf_counter() - t0
    st = eng.engine_stats()
    assert set(st["phase_s"]) == set(st["phase_cpu_s"]) == set(ENGINE_PHASES)
    assert st["iterations"] >= 50
    # every instant of the thread lies in one phase: what is outside (loop
    # control between two phases, the exit path) stays under 1%
    assert sum(st["phase_s"].values()) == pytest.approx(wall, rel=0.01)
    for name in ENGINE_PHASES:
        assert 0.0 <= st["phase_cpu_s"][name] <= st["phase_s"][name] + 0.05
    # the step is waited for, not spun on; the glue is the host's own work
    assert st["phase_cpu_s"]["step_wait"] < st["phase_s"]["step_wait"]
    assert st["phase_s"]["prefill"] > 0 and st["phase_s"]["emit"] > 0
    assert st["admitted"] == len(st["recent"]) > 0
    assert all(q >= 0 and p > 0 for q, p in st["recent"])


def test_counts_only_grow_and_read_whole_from_eight_threads(model):
    cfg, _ = model
    eng = engine(model)
    stop, errors, reads = threading.Event(), [], [0] * 8

    def reader(k):
        last = eng.engine_stats()
        try:
            while not stop.is_set():
                st = eng.engine_stats()
                reads[k] += 1
                for key in ("iterations", "slab_positions",
                            "live_positions", "admitted",
                            "prefill_positions",
                            "prefill_kernel_positions", "mixed_steps",
                            "chunk_positions_live"):
                    assert st[key] >= last[key], key
                assert st["admitted"] * PAGE <= st["prefill_positions"]
                assert st["prefill_kernel_positions"] <= st[
                    "prefill_positions"]
                for name in ENGINE_PHASES:
                    assert st["phase_s"][name] >= last["phase_s"][name]
                    assert st["starved_s"][name] >= last["starved_s"][name]
                    # of one copy: a block adds both before it is published
                    assert st["starved_s"][name] <= st["phase_s"][name]
                assert len(st["stalls"]) <= 16
                # one copy, from one instant: counts that move together
                # are never seen apart
                assert len(st["recent"]) == min(st["admitted"], 512)
                assert st["live_positions"] <= st["slab_positions"]
                # the live rows' pages up to their last step's length
                assert st["slab_positions"] % PAGE == 0
                # once an iteration, of either kind; one that carries
                # chunks is the only one that can find no row live
                assert (st["iterations"] - st["mixed_steps"]) * PAGE <= st[
                    "slab_positions"] <= st["iterations"] * MAX_SLOTS \
                    * cfg.max_seq
                st["phase_s"].clear()  # the caller's own copy
                st["starved_s"].clear()
                st["stalls"].clear()
                last = eng.engine_stats()
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(k,)) for k in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        for t in threads:
            t.start()
        for _ in range(2):
            wave(eng)
    finally:
        stop.set()
        for t in threads:
            t.join(30)
        sys.setswitchinterval(interval)
        close(eng)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads) and min(reads) > 0
    st = eng.engine_stats()
    assert st["admitted"] == 24 and st["iterations"] > 0


@pytest.mark.parametrize("attention", ["auto", "flash-interpret"])
def test_prefill_positions_grow_by_the_bucket_an_admission(model, attention):
    """``prefill_positions`` adds each admission's bucket length, and
    ``prefill_kernel_positions`` those of buckets whose program holds the
    flash kernel: none off the TPU, all where the configuration asks for
    the kernel under the interpreter by name."""
    import dataclasses

    cfg, params = model
    eng = engine((dataclasses.replace(cfg, attention=attention), params))
    try:
        seen = []

        def published():  # the answer leaves in ``emit``, the copy after it
            st = eng.engine_stats()
            return st if st["admitted"] > len(seen) else None

        for n_prompt in (3, 16, 17, 40, 16):
            eng.submit(list(range(2, 2 + n_prompt)), max_new_tokens=2,
                       timeout=120)
            st = _poll(published)
            seen.append((st["admitted"], st["prefill_positions"],
                         st["prefill_kernel_positions"]))
    finally:
        close(eng)
    buckets = [16, 16, 32, 48, 16]
    totals = [sum(buckets[:i + 1]) for i in range(5)]
    assert [a for a, _, _ in seen] == [1, 2, 3, 4, 5]
    assert [p for _, p, _ in seen] == totals
    assert [k for _, _, k in seen] == (
        totals if attention == "flash-interpret" else [0] * 5)


# ------------------------------------- (a') seconds without a program in flight
def settled(eng, admitted, timeout=60.0):
    """The copy published after the iteration that answered the
    ``admitted``-th request and every row's retirement (an answer leaves in
    ``emit``, the copy after it)."""
    seen = eng.engine_stats()["phase_s"]["idle_wait"]

    def idle():
        st = eng.engine_stats()
        done = st["admitted"] >= admitted and all(
            p is None for p in eng._slot_pending)
        return st if done and st["phase_s"]["idle_wait"] > seen else None

    st = _poll(idle, timeout)
    assert st, "the engine never settled"
    return st


def grown(after, before, key):
    return {k: v - before[key][k] for k, v in after[key].items()}


def test_starved_seconds_lie_inside_each_phases_wall(either_model):
    path, model = either_model
    eng = engine(model)
    assert eng._mixed == (path == "chunk")
    try:
        while eng.engine_stats()["iterations"] < 50:
            wave(eng)
    finally:
        close(eng)
    st = eng.engine_stats()
    assert set(st["starved_s"]) == set(st["phase_s"]) == set(ENGINE_PHASES)
    for name in ENGINE_PHASES:
        assert 0.0 <= st["starved_s"][name] <= st["phase_s"][name], name
    # nothing is in flight between an iteration's readback and its first
    # dispatch: these blocks count whole, on the wall's own clock reads
    for name in ("gate", "assemble", "emit"):
        assert st["starved_s"][name] == pytest.approx(
            st["phase_s"][name], rel=1e-9, abs=1e-9), name
        assert st["phase_s"][name] > 0
    assert st["starved_s"]["idle_wait"] == 0.0  # no work, so nobody starves
    # the wait counts from the readback's return alone
    assert st["starved_s"]["step_wait"] < st["phase_s"]["step_wait"]
    assert 0 < st["starved_s"]["prefill"] <= st["phase_s"]["prefill"]
    if path == "whole":
        # the decode program is every iteration's first call after the
        # admissions' readbacks: its dispatch is starved up to the call's
        # return, a clock's read before the block's end
        assert st["starved_s"]["step_dispatch"] == pytest.approx(
            st["phase_s"]["step_dispatch"], abs=2e-5 * st["iterations"])
    work = sum(st["phase_s"].values()) - st["phase_s"]["idle_wait"]
    assert 0 < sum(st["starved_s"].values()) < work


def test_of_three_chunks_an_iteration_one_is_starved(model):
    """The chunk path: the first chunk's program is running while the host
    makes the next two's arguments, so one ``prefill`` block of the three
    is starved (up to its call's return) and the other two are not."""
    eng = engine(model)
    prompt = list(range(2, 2 + 2 * PAGE + 8))  # three chunks of 16
    try:
        eng.submit(prompt, max_new_tokens=2, timeout=120)  # compiles
        before = settled(eng, 1)
        call = eng._mixed_step

        def slow_call(*a):  # 30 ms on the host inside each chunk's call
            time.sleep(0.03)
            return call(*a)

        eng._mixed_step = slow_call
        eng.submit(prompt, max_new_tokens=2, timeout=120)
        after = settled(eng, 2)
    finally:
        close(eng)
    assert after["mixed_steps"] - before["mixed_steps"] == 3
    wall = grown(after, before, "phase_s")["prefill"]
    starved = grown(after, before, "starved_s")["prefill"]
    assert wall >= 0.09
    assert 0.03 <= starved < 0.06, (starved, wall)


def test_an_admission_is_starved_up_to_its_programs_call(whole_model):
    """The whole-prompt path: ``prefill`` is starved from its start to the
    prefill program's call returning and not while it waits for the first
    token, so the two differ by the wait."""
    eng = engine(whole_model)

    class Late:  # a first token whose readback takes 20 ms
        def __init__(self, first):
            self.first = first

        def __int__(self):
            time.sleep(0.02)
            return int(self.first)

    try:
        eng.submit(list(range(2, 20)), max_new_tokens=2, timeout=120)
        before = settled(eng, 1)
        build = eng._paged_prefill_fn

        def late_first(bucket):
            def fn(*a):
                pool, first = build(bucket)(*a)
                return pool, Late(first)
            return fn

        eng._paged_prefill_fn = late_first
        for _ in range(3):
            eng.submit(list(range(2, 20)), max_new_tokens=2, timeout=120)
        after = settled(eng, 4)
    finally:
        close(eng)
    assert after["admitted"] - before["admitted"] == 3
    wall = grown(after, before, "phase_s")["prefill"]
    starved = grown(after, before, "starved_s")["prefill"]
    assert 0 < starved < wall
    assert wall - starved >= 3 * 0.02  # the first-token wait between them


# --------------------------------------------- (a'') the iterations that stalled
def test_a_planted_stall_leaves_exactly_one_row(either_model):
    path, model = either_model
    eng = engine(model)
    try:
        while eng.engine_stats()["iterations"] < 40:
            wave(eng)
        n = settled(eng, 1)["admitted"]
        t0 = time.time()
        before = eng.engine_stats()
        faults.configure("serve.admit:stall:stall=1.0:max=1", seed=3)
        try:
            eng.submit([3, 4, 5, 6], max_new_tokens=2, timeout=120)
        finally:
            faults.reset()
        after = settled(eng, n + 1)
        t1 = time.time()
    finally:
        close(eng)
    assert len(after["stalls"]) <= 16
    rows = [r for r in after["stalls"] if t0 <= r["t_end"] <= t1]
    assert len(rows) == 1, after["stalls"]
    row, = rows
    assert row not in before["stalls"]
    assert set(row) == {"t_end", "wall_s", "median_s", "phase_s",
                        "phase_cpu_s", "starved_s", "rows", "token_steps"}
    assert row["wall_s"] > 4 * row["median_s"] > 0
    assert row["wall_s"] == pytest.approx(
        sum(row["phase_s"].values()), rel=1e-9)
    assert row["phase_s"]["idle_wait"] == 0.0
    for key in ("phase_s", "phase_cpu_s", "starved_s"):
        assert set(row[key]) == set(ENGINE_PHASES)
    assert max(row["phase_s"], key=row["phase_s"].get) == "prefill"
    assert row["phase_s"]["prefill"] >= 1.0
    # the thread slept: it was off a core, and the chip had no work
    assert sum(row["phase_cpu_s"].values()) < 0.1 * row["wall_s"]
    assert row["starved_s"]["prefill"] >= 1.0
    assert row["rows"] == 1 and row["token_steps"] >= 1
    # the iteration's own seconds: the accumulators' difference holds them
    assert grown(after, before, "phase_s")["prefill"] >= row[
        "phase_s"]["prefill"]


def test_the_ring_keeps_the_newest_sixteen_stalls(model):
    eng = engine(model)
    try:
        while eng.engine_stats()["iterations"] < 40:
            wave(eng)
        n = settled(eng, 1)["admitted"]
        t0 = time.time()
        # every admission stalls; a request is three iterations, so the
        # median of the last 32 stays a plain iteration's
        faults.configure("serve.admit:stall:stall=0.3", seed=3)
        try:
            for i in range(19):
                eng.submit([3, 4, 5 + i], max_new_tokens=9, timeout=120)
        finally:
            faults.reset()
        after = settled(eng, n + 19)
    finally:
        close(eng)
    rows = after["stalls"]
    assert len(rows) == 16
    ends = [r["t_end"] for r in rows]
    assert ends == sorted(ends) and ends[0] > t0  # oldest first, the newest
    # the planted ones (a plain iteration that the machine held up may sit
    # among them)
    assert sum(r["phase_s"]["prefill"] >= 0.3 for r in rows) >= 12


# ------------------------------------------------------ (b) a request's spans
SPANS = ("serve.engine.queue", "serve.engine.prefill", "serve.engine.decode")


def _poll(pred, timeout=30.0):
    """Replica-side spans ride the worker's 1 s profile flush."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        out = pred()
        if out:
            return out
        time.sleep(0.2)
    return pred()


@pytest.fixture(scope="module")
def traced_request():
    """One request sent through ``serve.run`` and a handle under a root
    context of the test's own: (the context, the trace's slices as the
    head's ring holds them)."""
    rmt.init(num_cpus=4, ignore_reinit_error=True)
    timeline.clear()
    try:
        serve.start(http_port=None)
        handle = serve.run(llm_deployment(
            "test", max_new_tokens=8, max_batch_size=2, pad_multiple=16))
        rmt.get(handle.remote({"tokens": [1, 2, 3]}), timeout=300)  # compile
        root = tracing.new_root()
        token = tracing.set_current(root)
        try:
            out = rmt.get(handle.remote(
                {"tokens": [5, 6, 7, 8, 9], "max_new_tokens": 6}),
                timeout=120)
        finally:
            tracing.reset(token)
        assert len(out["tokens"]) == 6

        def spans():
            evs = timeline.chrome_trace_events(trace_id=root[0],
                                               flows=False)
            return evs if {e["name"] for e in evs} >= set(SPANS) else None

        evs = _poll(spans)
        assert evs, "the engine's spans never reached the head"
        # the filter the CLI and /api/timeline use
        filtered = timeline.chrome_trace_events(
            trace_id=root[0], cat="serve", flows=False)
        yield root, evs, filtered
    finally:
        serve.shutdown()
        rmt.shutdown()
        timeline.clear()


@pytest.mark.parametrize("span", SPANS)
def test_a_request_carries_its_trace_into_the_engine(traced_request, span):
    root, evs, filtered = traced_request
    by_name = {e["name"]: e for e in evs if e["cat"] == "serve"}
    assert set(by_name) == set(SPANS)
    assert sorted(e["name"] for e in filtered) == sorted(SPANS)  # once each
    ev = by_name[span]
    assert ev["args"]["trace_id"] == root[0]
    assert ev["args"]["prompt_tokens"] == 5 and ev["args"]["bucket"] == 16
    assert ev["args"]["output_tokens"] == 6 and ev["args"]["row"] in (0, 1)
    # the parent chain: engine span -> the replica's exec span -> the
    # caller's context
    execs = [e for e in evs if e["cat"] == "task"
             and e["args"].get("span_id") == ev["args"]["parent_span_id"]]
    assert execs and "handle_request" in execs[0]["name"]
    assert execs[0]["args"]["parent_span_id"] == root[1]
    assert execs[0]["ts"] <= ev["ts"] + 1e3  # us; same host clock
    assert ev["ts"] + ev["dur"] <= execs[0]["ts"] + execs[0]["dur"] + 1e3
    # queue, prefill, decode follow each other without a hole
    q, p, d = (by_name[n] for n in SPANS)
    assert q["ts"] + q["dur"] == pytest.approx(p["ts"], abs=1.0)
    assert p["ts"] + p["dur"] == pytest.approx(d["ts"], abs=1.0)


def test_a_request_without_a_context_records_spans_without_ids(model):
    timeline.clear()
    eng = engine(model)
    try:
        assert tracing.get_current() is None
        eng.submit([3, 4, 5], max_new_tokens=5, timeout=120)
    finally:
        close(eng)
    evs = [e for e in timeline.chrome_trace_events(cat="serve", flows=False)]
    timeline.clear()
    assert sorted(e["name"] for e in evs) == sorted(SPANS)
    assert all("trace_id" not in e["args"] for e in evs)
    assert all(e["args"]["output_tokens"] == 5 for e in evs)


# ------------------------------------------- (c) on the profiler's own clock
@pytest.fixture(scope="module")
def engine_line(model, tmp_path_factory):
    """The host-plane line of a profiler capture that holds the engine
    thread's spans, as (its events, every event's (start, end) of the
    trace)."""
    from jax.profiler import ProfileData

    logdir = str(tmp_path_factory.mktemp("xprof"))
    eng = engine(model)
    try:
        wave(eng, 6)  # compile outside the capture
        with profiling.xprof_trace(logdir):
            wave(eng, 6)
    finally:
        close(eng)
    path, = glob.glob(logdir + "/plugins/profile/*/*.xplane.pb")
    lines, extent = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            extent += [(a, b) for _, a, b in evs]
            if any(n.startswith("rmt.engine.") for n, _, _ in evs):
                lines.append((plane.name, [e for e in evs if e[0].startswith(
                    "rmt.engine.")]))
    return lines, extent


@pytest.mark.parametrize("name", ["step_wait", "assemble", "emit"])
def test_phases_are_spans_on_one_line_of_the_host_plane(engine_line, name):
    lines, extent = engine_line
    # one thread, one line: all of the engine's spans lie together
    assert len(lines) == 1, [p for p, _ in lines]
    plane, evs = lines[0]
    assert plane.startswith("/host:")
    mine = [e for e in evs if e[0] == "rmt.engine." + name]
    assert mine
    lo, hi = min(a for a, _ in extent), max(b for _, b in extent)
    assert all(lo <= a <= b <= hi for _, a, b in mine)
    assert all(b > a for _, a, b in mine)
    # no two phases overlap: each instant belongs to one of them
    ordered = sorted(evs, key=lambda e: e[1])
    for (_, _, end), (nxt, start, _) in zip(ordered, ordered[1:]):
        assert end <= start, nxt
    # in an iteration, assemble comes before the wait and the emit after
    names = [n.rsplit(".", 1)[1] for n, _, _ in ordered]
    at = names.index("step_wait")
    assert "assemble" in names[:at] and names[at + 1] == "emit"
