"""Serving data plane: paged KV-cache accounting, admission backpressure,
load shedding, trace propagation, and the serve fault matrix.

The paged engine's memory contract is tested at the accounting layer
(pages and pinned device bytes move with admit/retire, exhaustion defers
admission instead of OOMing) and at the routing layer (typed, counted
shed errors; proxy 429s; every HTTP response carries the root trace id).
Fault-matrix entries: ``serve.admit`` errors fail ONLY the admitted
request, ``replica.exec`` errors surface to the caller — the engine and
the replica keep serving afterwards.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_memory_management_tpu as rmt
from ray_memory_management_tpu import serve
from ray_memory_management_tpu.config import Config, global_config
from ray_memory_management_tpu.core import metrics_defs as mdefs
from ray_memory_management_tpu.utils import faults


@pytest.fixture(autouse=True)
def _clean_fault_plane():
    yield
    os.environ.pop("RMT_fault_injection_spec", None)
    os.environ.pop("RMT_fault_injection_seed", None)
    faults.reset()


@pytest.fixture
def engine_setup():
    import jax

    from ray_memory_management_tpu.models import gpt

    cfg = gpt.TransformerConfig(vocab_size=128, n_layers=2, n_heads=2,
                                d_model=32, max_seq=128)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    yield gpt, cfg, params


# --- paged KV accounting -----------------------------------------------------

def _stopped_engine(llm_mod, params, cfg, **kw):
    """An engine whose thread has exited: the test drives admit, step and
    retire itself, so the assertions bracket them deterministically."""
    eng = llm_mod.ContinuousBatcher(params, cfg, **kw)
    eng.close()
    eng._thread.join(30)
    assert not eng._thread.is_alive()
    return eng


def _admit(eng, llm_mod, row, tokens, budget):
    """Reserve ``row``'s pages and run the iteration that admits the
    request (the engine's own, driven by hand): the prompt's chunks, each
    beside a token-step of the rows already live."""
    p = llm_mod._Pending((list(tokens), budget))
    need = eng._need_tokens(p)
    assert eng.kv_pool.reserve(row, need)
    eng._iterate_mixed([(p, row)])
    return p, need


class TestPagedKV:
    def test_retire_frees_pages_and_the_pools_bytes_stay(self, engine_setup):
        """The memory contract of the resident pool: its arrays are
        allocated at the first admission and not before, a request holds
        page ids while it lives, the page gauge
        (rmt_serve_kv_pages_in_use) falls back to zero at retire, and the
        pool's bytes are the same before and after: what tracks live
        requests is pages, not bytes."""
        from ray_memory_management_tpu.serve import llm as llm_mod

        gpt, cfg, params = engine_setup
        eng = _stopped_engine(llm_mod, params, cfg, max_slots=2,
                              max_new_tokens=24, pad_multiple=8,
                              steps_per_iter=4, kv_page_tokens=16)
        pool = eng.kv_pool
        assert eng._pool is None and eng.kv_stats()["store_bytes"] == 0
        assert (pool.table == pool.sink_page).all()

        # the admitting iteration makes the first of its 24 tokens
        p, need = _admit(eng, llm_mod, 0, [5, 9, 17, 3], 24)
        assert not p.event.is_set() and len(eng._slot_out[0]) == 1
        pages = pool.pages_for(need)
        assert pool.pages_in_use == pages
        assert mdefs.serve_kv_pages_in_use().get() == float(pages)
        owned = pool.table[0, :pages]
        assert (owned != pool.sink_page).all() and len(set(owned)) == pages
        assert (pool.table[0, pages:] == pool.sink_page).all()
        assert (pool.table[1] == pool.sink_page).all()
        # K and V, every budgeted page and the sink
        whole = (pool.capacity_pages + 1) * pool.page_bytes
        assert eng._pool["k"].nbytes + eng._pool["v"].nbytes == whole
        kv = eng.kv_stats()
        assert kv["store_bytes"] == kv["peak_store_bytes"] == whole
        assert kv["bytes_in_use"] == pages * pool.page_bytes

        eng._retire(0)
        assert p.event.is_set() and p.result  # request completed
        assert pool.pages_in_use == 0
        assert mdefs.serve_kv_pages_in_use().get() == 0.0
        assert (pool.table == pool.sink_page).all()
        kv = eng.kv_stats()
        assert kv["store_bytes"] == kv["peak_store_bytes"] == whole
        assert kv["bytes_in_use"] == 0

    @pytest.mark.parametrize("kv_heads", [None, 1], ids=["mha", "gqa"])
    @pytest.mark.parametrize("page", [8, 16])
    def test_interleaved_on_shuffled_pages_is_token_exact(
            self, engine_setup, kv_heads, page):
        """Two requests decode side by side, their pages handed out in a
        shuffled order (so neither row's pages are contiguous or
        ascending): each answer is the one ``gpt.generate`` gives alone."""
        import random

        import jax
        import numpy as np

        from ray_memory_management_tpu.serve.llm import ContinuousBatcher

        gpt, cfg, params = engine_setup
        if kv_heads is not None:
            cfg = gpt.TransformerConfig(
                vocab_size=128, n_layers=2, n_heads=2, n_kv_heads=kv_heads,
                d_model=32, max_seq=128)
            params = gpt.init_params(jax.random.PRNGKey(1), cfg)
        eng = ContinuousBatcher(params, cfg, max_slots=2, max_new_tokens=24,
                                pad_multiple=8, steps_per_iter=4,
                                kv_page_tokens=page)
        seen = {}
        reserve = eng.kv_pool.reserve

        def spy(row, tokens):
            ok = reserve(row, tokens)
            seen[row] = list(eng.kv_pool.table[row])
            return ok

        try:
            # the engine thread is idle until the first submit
            random.Random(page).shuffle(eng.kv_pool._free)
            eng.kv_pool.reserve = spy
            prompts = [list(range(3, 3 + 21)), list(range(40, 40 + 9))]
            budgets, res = [24, 17], [None, None]

            def go(i):
                res[i] = eng.submit(prompts[i], max_new_tokens=budgets[i])

            ts = [threading.Thread(target=go, args=(i,)) for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(300)
            for i in range(2):
                ref = np.asarray(gpt.generate(
                    params, cfg, np.asarray([prompts[i]], np.int32),
                    steps=budgets[i]))
                assert res[i] == ref[0, len(prompts[i]):].tolist(), i
        finally:
            eng.close()
        sink = eng.kv_pool.sink_page
        owned = [[pg for pg in seen[r] if pg != sink] for r in (0, 1)]
        assert len(owned[0]) >= 2 and not set(owned[0]) & set(owned[1])
        assert any(sorted(o) != o or o[-1] - o[0] != len(o) - 1
                   for o in owned)  # really shuffled

    def test_overshoot_lands_in_the_sink_not_in_a_neighbour(
            self, engine_setup):
        """Row 0's budget ends two tokens into an iteration of eight; its
        reservation ends with them. The six positions it decodes past
        that go to the sink: row 1's pages, row 0's own history and every
        free page are bit for bit what they were."""
        import numpy as np

        from ray_memory_management_tpu.serve import llm as llm_mod

        gpt, cfg, params = engine_setup
        K, page = 8, 8
        eng = _stopped_engine(llm_mod, params, cfg, max_slots=3,
                              max_new_tokens=16, pad_multiple=8,
                              steps_per_iter=K, kv_page_tokens=page)
        pool = eng.kv_pool
        # two chunks, the first token in the second
        _admit(eng, llm_mod, 1, range(20, 31), 16)
        # prompt 5 + budget 3: one page, full after the two decode steps;
        # row 1 rode its chunk
        p0, _ = _admit(eng, llm_mod, 0, range(2, 7), 3)
        assert pool.pages_for(eng.kv_pool.row_tokens(0)) == 1
        assert eng._slot_offset.tolist() == [5, 11 + 1, 0]
        assert not p0.event.is_set() and eng._slot_budget[0] == 2
        before = {n: np.asarray(a, np.float32)
                  for n, a in eng._pool.items()}
        off = eng._slot_offset.copy()
        sink, mine = pool.sink_page, pool.table[0, 0]
        theirs = [pg for pg in pool.table[1] if pg != sink]
        free = [pg for pg in range(pool.capacity_pages)
                if pg != mine and pg not in theirs]
        steps = eng.steps
        eng._iterate_mixed([])  # no chunk waits: the decode program
        assert eng.steps - steps == K and p0.event.is_set()
        assert p0.result == np.asarray(gpt.generate(
            params, cfg, np.asarray([list(range(2, 7))], np.int32),
            steps=3))[0, 5:].tolist()
        for name, was in before.items():
            now = np.asarray(eng._pool[name], np.float32)
            # row 1 wrote its own K positions and nothing else of its pages
            flat = lambda a: a[:, :, theirs].reshape(  # noqa: E731
                a.shape[0], a.shape[1], -1, a.shape[-1])
            wrote = slice(int(off[1]), int(off[1]) + K)
            keep = np.ones(len(theirs) * page, bool)
            keep[wrote] = False
            np.testing.assert_array_equal(flat(now)[:, :, keep],
                                          flat(was)[:, :, keep])
            assert np.abs(flat(now)[:, :, wrote]).min(axis=-1).max() > 0
            # row 0: its history stands, its page took positions 5, 6, 7
            np.testing.assert_array_equal(now[:, :, mine, :off[0]],
                                          was[:, :, mine, :off[0]])
            assert (now[:, :, mine, off[0]:] != was[:, :, mine, off[0]:]
                    ).any(axis=(0, 1, 3)).all()
            np.testing.assert_array_equal(now[:, :, free], was[:, :, free])
            # the overshoot (and the idle row 2) went to the sink
            assert (now[:, :, sink] != was[:, :, sink]).any()

    def test_one_decode_program_and_one_mixed_step(self):
        """``stats()["compile"]``: the first request builds the mixed step
        and the decode program, a prompt of more chunks nothing more; after
        that no length, budget, page count or mix of
        live rows asks the compiler for anything, and no whole-prompt
        prefill is ever built."""
        from ray_memory_management_tpu.serve.llm import LLMServer

        srv = LLMServer(preset="test", max_batch_size=4, max_new_tokens=24,
                        pad_multiple=16, steps_per_iter=4,
                        kv_page_tokens=16)
        eng = srv._engine
        try:
            def programs():
                return srv.stats()["compile"]["programs"]

            srv.generate(list(range(2, 12)), max_new_tokens=9)  # one chunk
            assert eng._mixed_step._cache_size() == 1
            warm = programs()
            srv.generate(list(range(2, 22)), max_new_tokens=9)  # two
            # the second chunk rides the same program, and no new decode
            assert programs() == warm

            def go(i):
                srv.generate(list(range(2, 4 + 3 * i)),
                             max_new_tokens=3 + 2 * (i % 9))

            ts = [threading.Thread(target=go, args=(i,)) for i in range(10)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(300)
            assert not any(t.is_alive() for t in ts)
            assert srv.stats()["requests"] == 12
            assert programs() == warm
            assert not eng._prefill_cache
            assert eng._paged_step._cache_size() == 1
            assert eng._mixed_step._cache_size() == 1
        finally:
            eng.close()

    def test_pool_exhaustion_backpressures_never_fails(self, engine_setup):
        """More concurrent requests than the page pool fits: admissions
        DEFER (kv_backpressure counts them) and every request still
        completes exactly — exhaustion is queueing, never an allocation
        failure."""
        import numpy as np

        from ray_memory_management_tpu.serve.kv_cache import row_token_bytes
        from ray_memory_management_tpu.serve.llm import ContinuousBatcher

        gpt, cfg, params = engine_setup
        # room for exactly 2 one-page reservations; 4 slots want pages
        pool_bytes = 2 * 16 * row_token_bytes(cfg)
        eng = ContinuousBatcher(
            params, cfg, max_slots=4, max_new_tokens=8, pad_multiple=8,
            steps_per_iter=4, kv_page_tokens=16, kv_pool_bytes=pool_bytes)
        try:
            prompts = [[2 + i, 5, 7, 11] for i in range(6)]
            res = [None] * 6

            def go(i):
                res[i] = eng.submit(prompts[i])

            ts = [threading.Thread(target=go, args=(i,)) for i in range(6)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(300)
            assert all(r is not None for r in res)
            for i, prompt in enumerate(prompts):
                ref = np.asarray(gpt.generate(
                    params, cfg, np.asarray([prompt], np.int32), steps=8))
                assert res[i] == ref[0, len(prompt):].tolist(), i
            assert eng.kv_backpressure >= 1  # the pool really saturated
            assert eng.kv_pool.pages_in_use == 0  # all freed at retire
        finally:
            eng.close()

    def test_impossible_request_fails_fast_not_forever(self, engine_setup):
        """A request that cannot fit even an EMPTY pool must fail with a
        descriptive error immediately — backpressuring it would spin
        forever with no retiring slot to free pages."""
        from ray_memory_management_tpu.serve.kv_cache import row_token_bytes
        from ray_memory_management_tpu.serve.llm import ContinuousBatcher

        gpt, cfg, params = engine_setup
        eng = ContinuousBatcher(
            params, cfg, max_slots=2, max_new_tokens=8, pad_multiple=8,
            kv_page_tokens=16,
            kv_pool_bytes=16 * row_token_bytes(cfg))  # one page total
        try:
            with pytest.raises(RuntimeError, match="pool capacity"):
                eng.submit(list(range(2, 32)), timeout=30)
        finally:
            eng.close()

    @staticmethod
    def _resident_and_queued(eng, prompts, budget):
        """Two callers on a one-slot engine, parked in ``submit``; returns
        their threads and what each raised, once the first is in the slot
        and the second in the queue."""
        raised = [None, None]

        def go(i):
            try:
                eng.submit(prompts[i], max_new_tokens=budget, timeout=300)
            except BaseException as e:  # noqa: BLE001 (the test reads it)
                raised[i] = e

        ts = [threading.Thread(target=go, args=(i,)) for i in range(2)]
        ts[0].start()
        deadline = time.time() + 120
        while eng._slot_pending[0] is None and time.time() < deadline:
            time.sleep(0.005)
        ts[1].start()
        while not eng._q and time.time() < deadline:
            time.sleep(0.005)
        assert eng._slot_pending[0] is not None and len(eng._q) == 1
        return ts, raised

    @staticmethod
    def _wrap_program(eng, which, wrap):
        """Lay ``wrap(program) -> callable`` over the decode program
        (``which`` "decode") or over the mixed step ("mixed": the program
        that carries a prompt's chunk)."""
        name = "_paged_step" if which == "decode" else "_mixed_step"
        setattr(eng, name, wrap(getattr(eng, name)))

    @pytest.mark.parametrize("which", ["decode", "mixed"])
    def test_failed_decode_program_fails_all_and_the_engine_recovers(
            self, engine_setup, which):
        """The decode program (or the mixed step that carries the resident
        caller's chunk) raises once: the resident caller and the queued one
        both get that error, every page goes back, the pool's arrays are
        dropped (a failed program may have consumed them), and the next
        request is token-exact on a pool allocated anew."""
        import numpy as np

        from ray_memory_management_tpu.serve.llm import ContinuousBatcher

        gpt, cfg, params = engine_setup
        eng = ContinuousBatcher(params, cfg, max_slots=1, max_new_tokens=8,
                                pad_multiple=8, steps_per_iter=4,
                                kv_page_tokens=16)
        calls, go_on = [], threading.Event()

        def falls_over_once(step):
            def call(*args):
                calls.append(1)
                if len(calls) == 1:
                    go_on.wait(120)  # until the second caller is queued
                    raise RuntimeError("decode program fell over")
                return step(*args)
            return call

        self._wrap_program(eng, which, falls_over_once)
        try:
            prompts = [[5, 9, 17, 3], [2, 4, 6, 8, 10]]
            ts, raised = self._resident_and_queued(eng, prompts, 8)
            assert eng.kv_pool.pages_in_use > 0
            go_on.set()
            for t in ts:
                t.join(60)
            assert not any(t.is_alive() for t in ts)
            for e in raised:
                assert isinstance(e, RuntimeError)
                assert "decode program fell over" in str(e)
            assert eng.kv_pool.pages_in_use == 0 and eng._pool is None
            assert not eng._prefilling
            assert eng._thread.is_alive()  # it keeps serving
            out = eng.submit(prompts[1], timeout=120)
            ref = np.asarray(gpt.generate(
                params, cfg, np.asarray([prompts[1]], np.int32), steps=8))
            assert out == ref[0, len(prompts[1]):].tolist()
            assert eng.kv_pool.pages_in_use == 0
        finally:
            eng.close()

    @pytest.mark.parametrize("which", ["decode", "mixed"])
    def test_close_fails_resident_and_queued_callers_promptly(
            self, engine_setup, which):
        """``close()`` while one request decodes (or its chunk rides a
        mixed step that takes seconds) and one waits: both get "engine
        closed" within seconds of it, not after their submit timeout of
        300 s, the engine thread exits, and no page stays reserved."""
        from ray_memory_management_tpu.serve.llm import ContinuousBatcher

        gpt, cfg, params = engine_setup
        eng = ContinuousBatcher(params, cfg, max_slots=1,
                                max_new_tokens=100, pad_multiple=8,
                                steps_per_iter=1, kv_page_tokens=16)
        # 99 decode steps: resident for five seconds; or three in the
        # mixed step alone
        nap = {"decode": 0.05, "mixed": 3.0}[which]

        def slow(step):
            def call(*args):
                time.sleep(nap)
                return step(*args)
            return call

        self._wrap_program(eng, which, slow)
        try:
            ts, raised = self._resident_and_queued(
                eng, [[5, 9, 17, 3], [2, 4, 6, 8, 10]], 100)
            t0 = time.monotonic()
            eng.close()
            for t in ts:
                t.join(30)
            eng._thread.join(30)
            assert time.monotonic() - t0 < 30
            assert not any(t.is_alive() for t in ts)
            assert not eng._thread.is_alive()
            for e in raised:
                assert isinstance(e, RuntimeError)
                assert "engine closed" in str(e)
            assert eng.kv_pool.pages_in_use == 0 and eng._pool is None
            with pytest.raises(RuntimeError, match="engine closed"):
                eng.submit([1, 2, 3])
        finally:
            eng.close()


# --- config knob + typed shed errors -----------------------------------------

def test_backpressure_timeout_knob_registered():
    assert Config().serve_backpressure_timeout_s == 60.0
    assert Config(serve_backpressure_timeout_s=3.0) \
        .serve_backpressure_timeout_s == 3.0
    os.environ["RMT_serve_backpressure_timeout_s"] = "7.5"
    try:
        assert Config().serve_backpressure_timeout_s == 7.5
    finally:
        os.environ.pop("RMT_serve_backpressure_timeout_s")


def test_backpressure_timeout_typed_and_counted(rmt_start_regular,
                                                monkeypatch):
    """Routing past a saturated deployment raises the TYPED
    BackpressureTimeout (not a bare RuntimeError) after
    serve_backpressure_timeout_s, and counts the shed by reason."""
    from ray_memory_management_tpu.serve.handle import BackpressureTimeout

    serve.start(http_port=None)
    try:
        @serve.deployment(max_concurrent_queries=1)
        def snooze(x=None):
            time.sleep(2.5)
            return "ok"

        h = serve.run(snooze)
        monkeypatch.setattr(global_config(),
                            "serve_backpressure_timeout_s", 0.5)
        slow = threading.Thread(
            target=lambda: rmt.get(h.remote(1), timeout=60), daemon=True)
        slow.start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:  # wait until it holds the slot
            if h._router.queue_depth() >= 1:
                break
            time.sleep(0.02)
        before = mdefs.serve_shed().get(tags={"reason":
                                              "backpressure_timeout"})
        with pytest.raises(BackpressureTimeout,
                           match="backpressure timeout routing to"):
            h.remote(2)
        assert mdefs.serve_shed().get(
            tags={"reason": "backpressure_timeout"}) == before + 1
        slow.join(60)
    finally:
        serve.shutdown()


def test_http_sheds_429_with_trace_id(rmt_start_regular):
    """HTTP ingress under saturation: the overflow request gets 429 (a
    'retry later', not a 500), and EVERY response — shed or served —
    carries the root x-rmt-trace-id header that stitches the
    proxy→router→replica spans together."""
    from ray_memory_management_tpu.serve.api import _ctrl
    from ray_memory_management_tpu.serve.http_proxy import start_proxy

    os.environ["RMT_serve_backpressure_timeout_s"] = "1.0"
    from ray_memory_management_tpu import config as cfgmod
    cfgmod.set_global_config(Config())
    serve.start(http_port=0)
    try:
        @serve.deployment(max_concurrent_queries=1)
        def plod(x=None):
            time.sleep(3.0)
            return {"ok": True}

        serve.run(plod)
        port = start_proxy(_ctrl(), 0)
        results = {}

        def first():
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/plod",
                data=json.dumps(1).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                results["status"] = resp.status
                results["trace"] = resp.headers.get("x-rmt-trace-id")

        t = threading.Thread(target=first, daemon=True)
        t.start()
        time.sleep(0.8)  # first request is mid-service, slot held
        req2 = urllib.request.Request(
            f"http://127.0.0.1:{port}/plod",
            data=json.dumps(2).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req2, timeout=60)
        assert exc.value.code == 429
        shed_trace = exc.value.headers.get("x-rmt-trace-id")
        assert shed_trace and int(shed_trace, 16) >= 0  # hex trace id
        t.join(60)
        assert results.get("status") == 200
        served_trace = results.get("trace")
        assert served_trace and int(served_trace, 16) >= 0
        assert served_trace != shed_trace  # one root trace per request
    finally:
        serve.shutdown()
        os.environ.pop("RMT_serve_backpressure_timeout_s", None)
        cfgmod.set_global_config(Config())


# --- serve fault matrix ------------------------------------------------------

def test_admit_fault_fails_only_that_request(engine_setup):
    """An injected serve.admit error fails ONLY the request being
    admitted (its page reservation rolls back); the engine thread
    survives and serves the next request exactly."""
    import numpy as np

    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    gpt, cfg, params = engine_setup
    faults.configure("serve.admit:error:max=1", seed=3)
    eng = ContinuousBatcher(params, cfg, max_slots=2, max_new_tokens=4,
                            pad_multiple=8, kv_page_tokens=16)
    try:
        with pytest.raises(faults.FaultInjected):
            eng.submit([5, 9, 17, 3], timeout=60)
        assert eng.kv_pool.pages_in_use == 0  # reservation rolled back
        out = eng.submit([5, 9, 17, 3], timeout=120)
        ref = np.asarray(gpt.generate(
            params, cfg, np.asarray([[5, 9, 17, 3]], np.int32), steps=4))
        assert out == ref[0, 4:].tolist()
        assert mdefs.faults_injected().get(
            tags={"site": "serve.admit", "mode": "error"}) >= 1
    finally:
        eng.close()


def test_replica_exec_fault_surfaces_and_replica_survives():
    """An injected replica.exec error surfaces to the caller as a task
    error (propagated via the env spec — the child-process path); the
    replica is NOT torn down and the next request succeeds."""
    os.environ["RMT_fault_injection_spec"] = "replica.exec:error:max=1"
    os.environ["RMT_fault_injection_seed"] = "17"
    faults.reset()  # in-process plane re-discovers the env spec too
    rmt.init(num_cpus=4, ignore_reinit_error=True)
    try:
        serve.start(http_port=None)
        try:
            @serve.deployment
            def echo(x):
                return {"x": x}

            h = serve.run(echo)
            with pytest.raises(Exception, match="injected"):
                rmt.get(h.remote(1), timeout=60)
            assert rmt.get(h.remote(2), timeout=60) == {"x": 2}
        finally:
            serve.shutdown()
    finally:
        rmt.shutdown()
