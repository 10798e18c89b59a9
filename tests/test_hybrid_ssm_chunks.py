"""The hybrid state-space model's prompts on the decode step in chunks
(``hybrid_ssm.mixed_step``): what the chunks leave in the pool against the
whole prefill, and the engine that takes them against the engine that
prefills whole prompts. The toy model, its weights and the helpers are
tests/test_hybrid_ssm.py's.
"""

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_memory_management_tpu.models import gpt, hybrid_ssm
from ray_memory_management_tpu.ops import ssm

# ``params`` is the module-scoped fixture of the toy's weights, found by name
from test_hybrid_ssm import (CFG, PAGE, _empty_pool, _greedy, _prefill,
                             _server, _together, params)


# ------------------------------------- a prompt's chunks on the decode step
# the scan kernel tiles whole lanes of channels and of state and chunks of
# 128: a second toy whose state-space branch it takes (in interpret mode)
KCFG = hybrid_ssm.HybridSSMConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, kv_heads=2,
    head_dim=16, d_ff=96, ssm_heads=2, ssm_head_dim=128, ssm_state=128,
    ssm_groups=1, ssm_conv=4, ssm_in_multiplier=0.7,
    ssm_multipliers=(0.9, 0.8, 1.2, 0.7, 1.3), max_seq=512,
    dtype=jnp.float32, param_dtype=jnp.float32)
# form -> (configuration, chunk, page): a chunk is two pages
FORMS = {"plain": (CFG, 32, 16), "scan-kernel": (KCFG, 128, 64)}


@pytest.fixture(scope="module")
def kparams():
    out = hybrid_ssm.init_params(jax.random.PRNGKey(11), KCFG)
    for i, layer in enumerate(out["layers"]):
        layer["conv_b"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(i), layer["conv_b"].shape)
    return out


def _chunked_beside_riders(params, cfg, C, page, n_prompt, *, idle=True):
    """A prompt of ``n_prompt`` tokens through ``mixed_step``, chunk by
    chunk, into slot 1 and pages of its own, beside two rows that decode
    (slots 0 and 2) and an idle slot 3; every slot's entry starts as junk an
    earlier request left. ``idle`` false plants a fault: the row is live
    among the decode rows while its chunks ride. Returns what the chunks
    left (pool, the last chunk's logits), the same riders stepped by
    ``paged_decode`` alone, and the whole prompt through ``prefill_row``."""
    rng = np.random.default_rng(n_prompt)
    n_chunks, per = -(-n_prompt // C), C // page
    sink, width = 4 * per + 8, 4 * per
    table = np.full((4, width), sink, np.int32)
    table[0, :2], table[2, :2] = [sink - 2, 1], [sink - 5, 3]
    mine = np.full(4 * per, sink, np.int32)
    mine[:n_chunks * per] = [sink - 1, 0, sink - 3, 2, sink - 4, 4,
                             sink - 6, 5][:n_chunks * per]
    riders = {0: rng.integers(2, cfg.vocab_size, 13).tolist(),
              2: rng.integers(2, cfg.vocab_size, 6).tolist()}
    pool, last = _empty_pool(4, sink, 3.0, cfg, page), [1, 1, 1, 1]
    for r, p in riders.items():
        logits, pool = _prefill(params, p, -(-len(p) // page) * page, pool,
                                table[r], r, cfg, page)
        last[r] = int(jnp.argmax(logits))
    prompt = rng.integers(2, cfg.vocab_size, n_prompt)
    toks = np.full(n_chunks * C, 9, np.int32)   # the junk tail is not token 0
    toks[:n_prompt] = prompt
    step = jax.jit(functools.partial(hybrid_ssm.mixed_step, cfg=cfg))
    alone = jax.jit(functools.partial(hybrid_ssm.paged_decode, cfg=cfg))
    mixed, plain = pool, pool
    m_last = p_last = jnp.asarray(last, jnp.int32)
    off = np.asarray([13, 0, 6, 0], np.int32)
    for index in range(n_chunks):
        ends = index == n_chunks - 1
        lengths = off + index * (off > 0)
        if not idle:  # the fault: live at the positions its chunks filled
            lengths[1], table[1] = index * C, mine[:width]
        lengths = jnp.asarray(lengths)
        logits, mixed, counts = step(
            params, mixed, jnp.asarray(toks[index * C:(index + 1) * C]),
            jnp.asarray(mine), jnp.int32(n_prompt - 1 - index * C if ends
                                         else C - 1),
            m_last, lengths, lengths, jnp.asarray(table),
            chunk_index=jnp.int32(index), slot=jnp.int32(1))
        assert logits.shape == (5, cfg.vocab_size)
        live = int((np.asarray(lengths) > 0).sum())
        assert int(counts["mixed_state_rows_stepped"]) == cfg.n_layers * live
        # no count under the decode program's names: its readers count
        # token-steps of the decode program alone
        assert set(counts) == {"mixed_state_rows_stepped"}
        if idle:
            ref, plain, _ = alone(params, p_last, plain, lengths, lengths,
                                  jnp.asarray(table))
            np.testing.assert_allclose(logits[:4][np.asarray([0, 2])],
                                       ref[np.asarray([0, 2])], atol=2e-4)
            p_last = jnp.argmax(ref, axis=-1)
        m_last = jnp.argmax(logits[:4], axis=-1)
    want_logits, whole = _prefill(params, prompt, n_chunks * C,
                                  _empty_pool(4, sink, 3.0, cfg, page), mine,
                                  1, cfg, page)
    return mixed, logits[4], plain, want_logits, whole, mine[:n_chunks * per]


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("tail", [1, 2, 3, -1, "one", "two", "three"])
def test_chunks_leave_what_the_whole_prefill_leaves(params, kparams, form,
                                                    tail, monkeypatch):
    """Prompts of 1, 2, 3 and ``C - 1`` positions into their last chunk (a
    convolution tail that reaches back into the chunk before, or into the
    zeros before position 0) and of exactly one, two and three chunks: the
    chunks leave the pages, the state, the tail and the last position's
    logits of ``prefill_row``, whatever junk the slot held; the riders get
    ``paged_decode``'s logits and state, and the idle slot keeps its junk to
    the bit. Once with the recurrence in plain ``jax.numpy`` and once in the
    scan kernel (interpret mode), each chunk after the first from ``h0``."""
    cfg, C, page = FORMS[form]
    if form == "scan-kernel":
        scan = ssm.ssd_scan
        monkeypatch.setattr(ssm, "ssd_scan", lambda *a, **k: scan(
            *a, chunk=128, use_pallas="interpret", **k))
    whole = {"one": C, "two": 2 * C, "three": 3 * C}
    n_prompt = whole[tail] if tail in whole else C + tail % C
    mixed, logits, plain, want_logits, whole, pages = _chunked_beside_riders(
        params if cfg is CFG else kparams, cfg, C, page, n_prompt)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=2e-4)
    for name in ("k", "v"):  # the prompt's positions, and nothing past them
        got, want = (np.asarray(a[name][:, :, pages]) for a in (mixed, whole))
        np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mixed["ssm"][:, 1]),
                               np.asarray(whole["ssm"][:, 1]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(mixed["conv"][:, :, 1]),
                               np.asarray(whole["conv"][:, :, 1]), atol=1e-5)
    for r in (0, 2):  # the riders moved as they move alone
        np.testing.assert_allclose(np.asarray(mixed["ssm"][:, r]),
                                   np.asarray(plain["ssm"][:, r]), atol=1e-4)
        np.testing.assert_allclose(np.asarray(mixed["conv"][:, :, r]),
                                   np.asarray(plain["conv"][:, :, r]),
                                   atol=1e-5)
    assert bool(jnp.all(mixed["ssm"][:, 3] == 3.0))
    assert bool(jnp.all(mixed["conv"][:, :, 3] == 3.0))


@pytest.mark.parametrize("fault", ["none", "live_in_the_decode_half",
                                   "carries_at_the_first_chunk_too"])
def test_only_the_chunks_move_the_prefilling_rows_state(params, fault,
                                                        monkeypatch):
    """Three chunks of a row that is idle among the decode rows leave the
    whole prefill's state. Were the row live there, the decode half would
    move its state between its chunks; were a first chunk to start from
    the slot's entry, it would carry on from an earlier request's: both
    faults show, so the comparison can fail."""
    if fault == "carries_at_the_first_chunk_too":
        monkeypatch.setattr(hybrid_ssm, "_carried", lambda entry, first: entry)
    mixed, _, _, _, whole, _ = _chunked_beside_riders(
        params, CFG, 32, 16, 70, idle=fault != "live_in_the_decode_half")
    gap = float(jnp.max(jnp.abs(mixed["ssm"][:, 1] - whole["ssm"][:, 1])))
    assert (gap < 1e-4) if fault == "none" else (gap > 1e-3), gap


@pytest.fixture(scope="module")
def whole_srv():
    """The engine as it was before the model offered ``mixed_step``: the
    engine chooses when it is built, by what the model offers then."""
    with pytest.MonkeyPatch.context() as mp:
        mp.delattr(hybrid_ssm, "mixed_step")
        srv = _server()
    assert not srv._engine._mixed
    yield srv
    srv._engine.close()


@pytest.fixture(scope="module")
def chunk_srv():
    srv = _server()
    assert srv._engine._mixed and srv._engine._chunk == 32
    yield srv
    srv._engine.close()


def _arrivals(srv, prompts, budgets):
    """``prompts[0]`` is decoding when the others arrive together."""
    first, eng = [None], srv._engine
    steps = eng.steps

    def resident():
        first[0] = srv.generate(prompts[0], max_new_tokens=budgets[0])

    t = threading.Thread(target=resident)
    t.start()
    deadline = time.monotonic() + 120
    while eng.steps == steps and time.monotonic() < deadline:
        time.sleep(0.001)  # until its first iteration has run
    rest = _together(srv, prompts[1:], budgets[1:])
    t.join(300)
    return [first[0]] + rest


@pytest.mark.parametrize("budget", [1, 3, 4, 5, 11])
@pytest.mark.parametrize("n_prompt", [5, 101],
                         ids=["one-chunk", "four-chunks"])
def test_arrivals_while_others_decode_are_the_whole_prefill_engines_tokens(
        chunk_srv, whole_srv, budget, n_prompt):
    """A long answer is decoding when a request of ``budget`` tokens (to,
    on and past an iteration of four) and one of another shape arrive: the
    engine whose prompts ride the decode step in chunks returns, token for
    token, what the engine that prefills whole prompts returns, and what
    the whole forward puts first."""
    rng = np.random.default_rng(100 * budget + n_prompt)
    prompts = [rng.integers(2, CFG.vocab_size, n).tolist()
               for n in (19, n_prompt, 33)]
    budgets = [24, budget, 7]
    got = _arrivals(chunk_srv, prompts, budgets)
    want = _arrivals(whole_srv, prompts, budgets)
    for p, b, out, ref in zip(prompts, budgets, got, want):
        assert len(out) == b and out == ref \
            == _greedy(chunk_srv.params, p, out), len(p)
    e = chunk_srv.stats()["engine"]
    assert e["mixed_steps"] > 0 and e["mixed_state_rows_stepped"] > 0
    assert whole_srv.stats()["engine"]["mixed_steps"] == 0
    assert chunk_srv._engine.kv_pool.pages_in_use == 0


@pytest.mark.parametrize("fault", ["none", "carries_at_the_first_chunk_too"])
def test_a_slot_admitted_again_never_reads_what_the_first_request_left(
        fault, monkeypatch):
    """One slot, so that every request inherits its forerunner's entry, and
    nobody clears it: a long request, then short ones, whose answers are a
    fresh engine's. With a ``mixed_step`` that takes the slot's state and
    tail at a prompt's first chunk too, they are not."""
    rng = np.random.default_rng(2)
    long = rng.integers(2, CFG.vocab_size, 50).tolist()
    shorts = [rng.integers(2, CFG.vocab_size, n).tolist() for n in (2, 1, 7)]
    fresh = [_server(max_batch_size=1) for _ in shorts]
    if fault != "none":
        monkeypatch.setattr(hybrid_ssm, "_carried", lambda entry, first: entry)
    used = _server(max_batch_size=1)
    try:
        assert used._engine._mixed
        want = [f.generate(p, max_new_tokens=10)
                for f, p in zip(fresh, shorts)]
        used.generate(long, max_new_tokens=13)
        assert bool(jnp.any(used._engine._pool["ssm"][:, 0] != 0))
        got = [used.generate(p, max_new_tokens=10) for p in shorts]
        if fault == "none":
            assert got == want
        else:
            assert all(g != w for g, w in zip(got, want)), (got, want)
    finally:
        for srv in [used] + fresh:
            srv._engine.close()


@pytest.mark.parametrize("path", ["chunks", "whole", "dense-chunks"])
def test_positions_are_summed_once_an_iteration_on_both_paths(path,
                                                              monkeypatch):
    """``live_positions`` and ``slab_positions`` grow once an iteration, at
    assembly, whether the iteration carries chunks or runs the decode
    program: their quotient by ``iterations`` is a mean a token-step's
    assembly in both kinds."""
    from ray_memory_management_tpu.serve.llm import (ContinuousBatcher,
                                                     LLMServer)

    calls = []
    count = ContinuousBatcher._count_positions

    def counted(self, offsets, steps):
        calls.append((offsets.tolist(), steps))
        count(self, offsets, steps)

    monkeypatch.setattr(ContinuousBatcher, "_count_positions", counted)
    if path == "whole":
        monkeypatch.delattr(hybrid_ssm, "mixed_step")
    srv = _server() if path != "dense-chunks" else LLMServer(
        config=gpt.PRESETS["test"], max_batch_size=3, max_new_tokens=24,
        pad_multiple=32, steps_per_iter=4, kv_page_tokens=PAGE, seed=7)
    try:
        assert srv._engine._mixed == (path != "whole")
        rng = np.random.default_rng(4)
        vocab = srv.cfg.vocab_size
        prompts = [rng.integers(2, vocab, n).tolist() for n in (40, 3, 70)]
        _arrivals(srv, prompts, [24, 9, 6])
        e = srv.stats()["engine"]
    finally:
        srv._engine.close()
    assert len(calls) == e["iterations"] > 0
    assert (e["mixed_steps"] > 0) == (path != "whole")
    assert e["live_positions"] == sum(sum(o) for o, _ in calls)
    assert e["slab_positions"] == sum(
        -(-(o + k) // PAGE) * PAGE for offs, k in calls for o in offs)
    # an iteration's token-steps: its chunks (at most four), or the four of
    # the decode program
    assert {k for _, k in calls} <= {1, 2, 3, 4}
    assert e["live_positions"] <= e["slab_positions"]
