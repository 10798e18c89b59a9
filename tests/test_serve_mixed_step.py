"""A prompt's chunks ride the decode step (``gpt.mixed_step`` and the
engine's chunk path, serve/llm.py::ContinuousBatcher._iterate_mixed).

(a) the model's part against its oracles: ``mixed_step`` chained over a
    prompt's chunks leaves the pages and the first token ``gpt.prefill_row``
    leaves, and the decode rows that ride in those steps what
    ``gpt.paged_decode`` alone gives them;
(b) the engine on a CPU: requests that arrive while others decode return
    ``gpt.generate``'s greedy tokens, whatever their budget is to K and
    however many chunks their prompt has;
(c) what an iteration costs the host: one readback, no key split, counts
    that add up, and an injected ``serve.admit`` fault that fails one
    request;
(d) a model without ``mixed_step`` keeps the whole-prompt path, and the
    layer-pattern model, which offers one, rides the chunk path through the
    same engine (the hybrid state-space model's chunk path is
    tests/test_hybrid_ssm.py's).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_memory_management_tpu.models import gpt, latent_moe, nemotron_h
from ray_memory_management_tpu.serve.llm import ContinuousBatcher
from ray_memory_management_tpu.utils import faults

PAGE, C, K = 8, 16, 4  # a chunk is two pages


def _cfg(attention="auto"):
    return gpt.TransformerConfig(
        vocab_size=128, n_layers=2, n_heads=4, n_kv_heads=2, d_model=32,
        max_seq=128, attention=attention)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(jax.random.PRNGKey(0), _cfg())


# ------------------------------------------------------- (a) the model's part
def _empty_pool(cfg, pages):
    shape = (cfg.n_layers, cfg.kv_heads, pages + 1, PAGE, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def _row_pages(pool, pages):
    """The positions a table row's ``pages`` hold: [L, Hkv, positions, Dh]."""
    return {n: np.asarray(a[:, :, pages]).reshape(
        a.shape[0], a.shape[1], -1, a.shape[-1]) for n, a in pool.items()}


def _prefilled(params, cfg, pool, prompt, pages):
    """``prompt`` in ``pages`` as ``gpt.prefill_row`` leaves it; its first
    token."""
    n = -(-len(prompt) // PAGE) * PAGE
    toks = np.ones((1, n), np.int32)
    toks[0, :len(prompt)] = prompt
    logits, row = gpt.prefill_row(params, jnp.asarray(toks), cfg, n,
                                  len(prompt))
    pool = {name: pool[name].at[:, :, pages[:n // PAGE]].set(
        row[name].reshape(row[name].shape[:2] + (n // PAGE, PAGE, -1)))
        for name in pool}
    return pool, int(jnp.argmax(logits))


@pytest.mark.parametrize("attention", ["flash-interpret", "ref"])
@pytest.mark.parametrize("n_prompt", [11, 16, 48, 41],
                         ids=["one-chunk", "one-full", "exact", "ragged"])
def test_chunks_leave_what_the_whole_prefill_leaves(params, attention,
                                                    n_prompt):
    """Chained over a prompt's chunks beside two decoding rows: the chunked
    row's pages and first token are ``prefill_row``'s of the whole prompt,
    and the riders' tokens and pages ``paged_decode``'s alone."""
    cfg = _cfg(attention)
    assert gpt.prefill_takes_kernel(cfg, C) == (attention != "ref")
    rng = np.random.default_rng(n_prompt)
    sink, width = 24, 8
    table = np.full((3, width), sink, np.int32)
    table[0, :3], table[1, :2] = [5, 1, 9], [14, 3]
    mine = np.asarray([7, 20, 11, 2, 17, 13, 0, 22], np.int32)
    riders = [rng.integers(2, 128, n).tolist() for n in (13, 6)]
    pool, last = _empty_pool(cfg, sink), [1, 1, 1]
    for r, prompt in enumerate(riders):
        pool, last[r] = _prefilled(params, cfg, pool, prompt, table[r])
    off = np.asarray([13, 6, 0], np.int32)  # row 2 is the chunked one: idle
    prompt = rng.integers(2, 128, n_prompt)
    n_chunks = -(-n_prompt // C)
    toks = np.ones(n_chunks * C, np.int32)
    toks[:n_prompt] = prompt

    step = jax.jit(gpt.mixed_step, static_argnames=("cfg",))
    alone = jax.jit(gpt.paged_decode, static_argnames=("cfg",))
    programs = step._cache_size()
    mixed, plain = pool, pool
    m_last = p_last = jnp.asarray(last, jnp.int32)
    for index in range(n_chunks):
        ends = index == n_chunks - 1
        lengths = jnp.asarray(off + index * (off > 0))
        logits, mixed, counts = step(
            params, mixed, jnp.asarray(toks[index * C:(index + 1) * C]),
            jnp.asarray(mine), jnp.int32(n_prompt - 1 - index * C if ends
                                         else 0),
            m_last, lengths, lengths, jnp.asarray(table), cfg=cfg,
            chunk_index=jnp.int32(index))
        assert logits.shape == (4, 128) and counts == {}
        ref, plain, _ = alone(params, p_last, plain, lengths, lengths,
                              jnp.asarray(table), cfg=cfg)
        np.testing.assert_allclose(logits[:2], ref[:2], atol=2e-5)
        m_last = jnp.argmax(logits[:3], axis=-1)
        p_last = jnp.argmax(ref, axis=-1)
        assert m_last[:2].tolist() == p_last[:2].tolist()
    # one program, whatever the chunk (the cache is the function's, and an
    # earlier case of the same configuration may have filled it)
    assert step._cache_size() <= programs + 1
    whole, first = _prefilled(params, cfg, _empty_pool(cfg, sink), prompt,
                              mine)
    assert int(jnp.argmax(logits[3])) == first
    got, want = _row_pages(mixed, mine), _row_pages(whole, mine)
    for name in got:  # the prompt's positions; past them the two pad alike
        np.testing.assert_allclose(got[name][:, :, :n_chunks * C],
                                   want[name][:, :, :n_chunks * C],
                                   atol=2e-6)
    for r in (0, 1):  # the riders wrote one position a step, as alone
        a, b = _row_pages(mixed, table[r]), _row_pages(plain, table[r])
        for name in a:
            np.testing.assert_allclose(a[name], b[name], atol=2e-5)
            assert np.abs(a[name][:, :, off[r] + n_chunks - 1]).max() > 0
    # and nothing of theirs, nor of the idle row, in the chunked row's pages
    for name in got:
        assert not got[name][:, :, n_chunks * C:].any()


# ------------------------------------------------------ (b) the engine, whole
@pytest.fixture(scope="module")
def engine(params):
    eng = ContinuousBatcher(params, _cfg(), max_slots=3, max_new_tokens=24,
                            pad_multiple=C, steps_per_iter=K,
                            kv_page_tokens=PAGE)
    yield eng
    eng.close()


def _greedy(params, prompt, budget):
    out = np.asarray(gpt.generate(params, _cfg(), np.asarray(
        [prompt], np.int32), steps=budget))
    return out[0, len(prompt):].tolist()


def _together(eng, prompts, budgets):
    res = [None] * len(prompts)

    def go(i):
        res[i] = eng.submit(prompts[i], max_new_tokens=budgets[i],
                            timeout=300)

    ts = [threading.Thread(target=go, args=(i,)) for i in range(len(prompts))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(300)
    return res


@pytest.mark.parametrize("budget", [1, K - 1, K, K + 1, 2 * K + 3])
@pytest.mark.parametrize("n_prompt", [5, (K + 2) * C - 3],
                         ids=["one-chunk", "more-chunks-than-K"])
def test_arrivals_while_others_decode_are_token_exact(engine, params, budget,
                                                      n_prompt):
    """A long answer is decoding when a request of ``budget`` tokens and one
    of another shape arrive: each returns ``gpt.generate``'s greedy tokens
    of its own prompt alone."""
    assert engine._mixed and engine._chunk == C
    rng = np.random.default_rng(100 * budget + n_prompt)
    prompts = [rng.integers(2, 128, n).tolist() for n in (19, n_prompt, 33)]
    budgets = [24, budget, 7]
    first, steps = [None], engine.steps

    def resident():
        first[0] = engine.submit(prompts[0], max_new_tokens=24, timeout=300)

    t = threading.Thread(target=resident)
    t.start()
    deadline = time.monotonic() + 120
    while engine.steps == steps and time.monotonic() < deadline:
        time.sleep(0.001)  # until its first iteration has run
    rest = _together(engine, prompts[1:], budgets[1:])
    t.join(300)
    for out, prompt, b in zip([first[0]] + rest, prompts, budgets):
        assert out == _greedy(params, prompt, b)
    assert engine.kv_pool.pages_in_use == 0


def test_sampled_decoding_splits_its_key_on_the_device(params):
    """With a temperature the chained programs draw from keys they split
    themselves: answers of the asked length, different for different
    seeds, the same for the same seed."""
    outs = []
    for seed in (3, 3, 4):
        eng = ContinuousBatcher(params, _cfg(), max_slots=2,
                                max_new_tokens=12, temperature=1.0,
                                pad_multiple=C, steps_per_iter=K,
                                kv_page_tokens=PAGE, seed=seed)
        try:
            outs.append(eng.submit(list(range(2, 40)), timeout=300))
        finally:
            eng.close()
    assert all(len(o) == 12 for o in outs)
    assert outs[0] == outs[1] != outs[2]


# --------------------------------------------- (c) what an iteration costs
def test_one_readback_an_iteration_and_counts_add_up(engine, params,
                                                     monkeypatch):
    """On a warmed engine: one ``device_get`` an iteration and no other
    readback of a token, no ``jax.random.split`` dispatched by the host
    (greedy), and ``mixed_steps``, ``steps``, ``prefill_positions`` and
    ``chunk_positions_live`` are what the prompts and the programs run add
    up to."""
    lengths, budgets = [3, 40, 16, 90, 17], [1, 9, 5, 2, 12]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 128, n).tolist() for n in lengths]
    want = [_greedy(params, p, b) for p, b in zip(prompts, budgets)]
    assert _together(engine, prompts, budgets) == want  # programs built
    calls = {"device_get": 0, "split": 0, "scan": 0}
    real_get, real_split, scan = jax.device_get, jax.random.split, \
        engine._paged_step

    def counted(name, fn):
        def wrapper(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return wrapper

    monkeypatch.setattr(jax, "device_get", counted("device_get", real_get))
    monkeypatch.setattr(jax.random, "split", counted("split", real_split))
    monkeypatch.setattr(engine, "_paged_step", counted("scan", scan))
    before, steps = _settled(engine, 0), engine.steps
    assert _together(engine, prompts, budgets) == want
    after = _settled(engine, before["admitted"] + len(prompts))
    grew = {k: after[k] - before[k] for k in (
        "iterations", "mixed_steps", "prefill_positions",
        "chunk_positions_live", "admitted")}
    assert calls["device_get"] == grew["iterations"] > 0
    assert calls["split"] == 0
    chunks = sum(-(-n // C) for n in lengths)
    assert grew["mixed_steps"] == chunks
    assert grew["prefill_positions"] == chunks * C
    assert grew["chunk_positions_live"] == sum(lengths)
    assert grew["admitted"] == len(prompts)
    # token-steps: a chunk each, and K a call of the decode program
    assert engine.steps - steps == chunks + K * calls["scan"]
    # fewer than K chunks waiting is the only reason to run it
    assert 0 < calls["scan"] <= grew["iterations"]


def _settled(eng, admitted, timeout=30.0):
    """The published copy once it holds ``admitted`` admissions (an answer
    leaves in ``emit``, the copy after it)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = eng.engine_stats()
        if st["admitted"] >= admitted and not any(
                p is not None for p in eng._slot_pending):
            time.sleep(0.05)
            return eng.engine_stats()
        time.sleep(0.01)
    raise AssertionError("the engine did not settle")


def test_admit_fault_fails_one_request_on_the_chunk_path(engine, params):
    faults.configure("serve.admit:error:max=1", seed=3)
    try:
        prompt = list(range(2, 40))
        with pytest.raises(faults.FaultInjected):
            engine.submit(prompt, timeout=60)
        assert engine.kv_pool.pages_in_use == 0  # reservation rolled back
        assert not engine._prefilling
        assert engine.submit(prompt, max_new_tokens=6, timeout=120) \
            == _greedy(params, prompt, 6)
    finally:
        faults.reset()


def test_a_prefilling_row_is_idle_in_the_decode_half(params):
    """What each mixed step is handed: the row being prefilled has length 0
    and a table row of sink entries among the decode rows, its real pages
    only as the chunk's; from the step after its last chunk it is live at
    its prompt's length."""
    eng = ContinuousBatcher(params, _cfg(), max_slots=2, max_new_tokens=8,
                            pad_multiple=C, steps_per_iter=K,
                            kv_page_tokens=PAGE)
    seen, step = [], eng._mixed_step

    def spy(params, pool, chunk_tokens, chunk_pages, chunk_index, chunk_last,
            first_row, last, offsets, table, key):
        seen.append((int(chunk_index), np.asarray(chunk_pages),
                     int(chunk_last), int(first_row), np.asarray(offsets),
                     np.asarray(table)))
        return step(params, pool, chunk_tokens, chunk_pages, chunk_index,
                    chunk_last, first_row, last, offsets, table, key)

    eng._mixed_step = spy
    try:
        prompt = list(range(2, 2 + 2 * C + 5))  # three chunks
        assert eng.submit(prompt, timeout=300) == _greedy(params, prompt, 8)
    finally:
        eng.close()
    sink = eng.kv_pool.sink_page
    assert [s[0] for s in seen] == [0, 1, 2]
    for index, pages, at, first_row, lengths, table in seen:
        assert (table == sink).all() and not lengths.any()
        assert (pages[:(index + 1) * C // PAGE] != sink).all()
        assert first_row == (0 if index == 2 else -1)
        # the head's row is also the last of the chunk's real positions
        assert at == (len(prompt) - 1 - 2 * C if index == 2 else C - 1)


# ----------------------------------- (d) a model that offers no mixed_step
PATTERN = nemotron_h.NemotronHConfig(
    vocab_size=512, d_model=64, pattern="ME*M", n_heads=4, kv_heads=2,
    head_dim=16, ssm_heads=8, ssm_head_dim=16, ssm_state=8, ssm_groups=2,
    moe_latent=32, moe_d_ff=48, shared_d_ff=96, n_routed_experts=16,
    n_held_experts=8, first_held_expert=0, experts_per_tok=4,
    routed_scaling_factor=5.0, max_seq=128, dtype=jnp.float32,
    param_dtype=jnp.float32)
MOE = latent_moe.LatentMoEConfig(
    vocab_size=512, d_model=64, n_layers=2, n_heads=4, q_lora_rank=24,
    kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    d_ff=128, moe_d_ff=32, n_routed_experts=8, n_shared_experts=1,
    experts_per_tok=2, routed_scaling_factor=1.8, max_seq=128,
    dtype=jnp.float32, param_dtype=jnp.float32)


def test_a_model_without_mixed_step_prefills_whole():
    """The engine chooses by what the model offers: the latent model offers
    no ``mixed_step``, so its prompts go through ``_paged_prefill_fn`` and
    no mixed program is ever built."""
    assert not hasattr(latent_moe, "mixed_step") \
        and hasattr(gpt, "mixed_step")
    eng = ContinuousBatcher(latent_moe.init_params(jax.random.PRNGKey(1),
                                                   MOE),
                            MOE, max_slots=2, max_new_tokens=6,
                            pad_multiple=16, steps_per_iter=K,
                            kv_page_tokens=16)
    try:
        assert not eng._mixed
        out = eng.submit(list(range(2, 22)), timeout=300)
        st = eng.engine_stats()
    finally:
        eng.close()
    assert len(out) == 6
    assert set(eng._prefill_cache) == {32}
    assert not hasattr(eng, "_mixed_step")
    assert st["mixed_steps"] == st["chunk_positions_live"] == 0
    assert st["prefill_positions"] == 32 and st["admitted"] == 1


@pytest.fixture(scope="module")
def pattern_engine():
    params = nemotron_h.init_params(jax.random.PRNGKey(1), PATTERN)
    eng = ContinuousBatcher(params, PATTERN, max_slots=3, max_new_tokens=12,
                            pad_multiple=16, steps_per_iter=K,
                            kv_page_tokens=16)
    yield eng, params
    eng.close()


@pytest.mark.parametrize("lengths,budgets", [
    ((3, 17, 40, 16), (5, 12, 1, 9)), ((70, 2, 33, 21, 9), (3, 8, 12, 2, 6))],
    ids=["four-on-three-slots", "five-with-a-long-one"])
def test_the_layer_pattern_model_rides_the_chunk_path(pattern_engine,
                                                      lengths, budgets):
    """A model whose layers are of kinds (Mamba-2, experts, attention) and
    that offers ``mixed_step``: more requests than slots, prompts of one to
    five chunks that end inside a chunk, budgets to, on and past an
    iteration of K. Each answer is greedy decoding by the whole ``forward``,
    every chunk was a mixed step, the counts come under the mixed step's own
    names, and no prefill program was built."""
    eng, params = pattern_engine
    assert eng._mixed and eng._chunk == 16
    rng = np.random.default_rng(sum(lengths))
    prompts = [rng.integers(2, 512, n).tolist() for n in lengths]
    before = _settled(eng, 0)
    got = _together(eng, prompts, list(budgets))
    after = _settled(eng, before["admitted"] + len(prompts))
    for prompt, budget, out in zip(prompts, budgets, got):
        seq = list(prompt)
        for _ in range(budget):
            logits = nemotron_h.forward(params, jnp.asarray([seq]),
                                        PATTERN)[0, -1]
            seq.append(int(jnp.argmax(logits)))
        assert out == seq[len(prompt):], len(prompt)
    chunks = sum(-(-n // 16) for n in lengths)
    assert after["mixed_steps"] - before["mixed_steps"] == chunks
    assert after["chunk_positions_live"] - before["chunk_positions_live"] \
        == sum(lengths)
    # two Mamba layers' update kernel and one expert layer a mixed step
    assert after["mixed_ssm_layer_steps"] == 2 * after["mixed_steps"]
    assert after["mixed_expert_layer_steps"] == after["mixed_steps"]
    assert after["mixed_expert_assignments_held"] \
        >= after["mixed_experts_touched"] > 0
    assert not eng._prefill_cache and eng.kv_pool.pages_in_use == 0
