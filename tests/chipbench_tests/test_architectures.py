"""The architecture seam of chipbench, rehearsed off the chip.

A configuration of another architecture arrives as new files: one under
``chipbench/configs/``, one module under ``chipbench/architectures/``, one
reference under ``chipbench/reference/``, and entries in ``BENCHMARK.json``.
The stub under ``tests/chipbench_tests/stub/`` is such a set of files (latent
attention and sparse experts at sizes a hand can count), laid beside the
real ones for these tests by extending the two packages' search paths; no
file under ``chipbench/`` knows of it. Also here: the seam held shut, and
the closed-loop arm of the serve driver at toy size. No time read here is a
device number.
"""

import importlib
import json
import os
import re
import sys
import time

import numpy as np
import pytest

import chipbench.architectures
import chipbench.reference
from chipbench import architectures, flops, harness, manifest
from chipbench.drivers import serve as serve_driver
from chipbench_config_checks import check_config_file

HERE = os.path.dirname(os.path.abspath(__file__))
STUB = os.path.join(HERE, "stub")
SEED = 2 ** 31 + 9  # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def stub():
    """The stub's configuration, its files found as a real architecture's
    would be."""
    packages = {chipbench.architectures: "architectures",
                chipbench.reference: "reference"}
    for pkg, folder in packages.items():
        pkg.__path__.append(os.path.join(STUB, folder))
    importlib.invalidate_caches()
    try:
        with open(os.path.join(STUB, "configs", "latent-moe-stub.json")) as f:
            yield json.load(f)
    finally:
        for pkg, folder in packages.items():
            pkg.__path__.remove(os.path.join(STUB, folder))
            sys.modules.pop(pkg.__name__ + ".latent_moe_stub", None)


# ---------------------------------------------------------------- the stub
def test_stub_loads_through_the_checks_of_the_real_files(stub):
    assert stub["architecture"] == "latent_moe_stub"
    arch = architectures.of(stub)
    assert arch.__name__ == "chipbench.architectures.latent_moe_stub"
    # the key set of the catalog's latent-attention and expert rows
    assert "head_dim" not in stub
    assert stub["hidden_size"] % stub["num_attention_heads"]
    assert {"kv_lora_rank", "qk_rope_head_dim", "n_routed_experts",
            "num_experts_per_tok", "first_k_dense_replace"} <= set(stub)
    check_config_file(stub)
    # a width may not be cut, here as in any file
    with pytest.raises(AssertionError):
        check_config_file(dict(stub, reduced=["kv_lora_rank"]))
    with pytest.raises(AssertionError):
        check_config_file(dict(stub, num_experts_per_tok=1))
    # and the dense decoder's keys are not asked of it
    dense = architectures.of({})
    assert dense.__name__ == "chipbench.architectures.dense_gqa"
    assert not set(dense.WIDTHS) <= set(stub)


def test_stub_sizes_a_pool_from_its_own_bytes_a_token(stub):
    arch = architectures.of(stub)
    # 3 layers x (16 latent + 4 rotary) x 2 bytes
    assert arch.cache_token_bytes(stub) == 3 * 20 * 2 == 120
    mix = {"prompt_tokens": {"max": 40},
           "engine": {"max_batch_size": 4, "kv_page_tokens": 16,
                      "max_new_tokens": 8, "max_concurrent_queries": 9}}
    e = serve_driver.engine_kwargs(stub, mix)
    assert e["kv_pool_bytes"] == 4 * 48 * 120   # 40 + 8, page aligned
    assert "max_concurrent_queries" not in e
    # a mix that gives the pool is taken at its word, whatever the keys
    given = dict(mix, engine=dict(mix["engine"], kv_pool_bytes=12345))
    assert serve_driver.engine_kwargs(stub, given)["kv_pool_bytes"] == 12345
    # the dense decoder's, for the same slots: K and V of 8 heads of 128
    m = manifest.config("mistral-7b-d16")
    assert architectures.of(m).cache_token_bytes(m) == 2 * 16 * 8 * 128 * 2


def test_stub_counts_flops_of_the_parameters_a_token_uses(stub):
    arch = architectures.of(stub)
    attn = 100 * 24 + 24 * 6 * 16 + 100 * 20 + 16 * 6 * 20 + 6 * 8 * 100
    assert attn == 13_424
    dense_mlp, expert, router = 3 * 100 * 64, 3 * 100 * 10, 100 * 8
    head = 100 * 50
    held = 3 * attn + dense_mlp + 2 * (9 * expert + router)
    layer, got_head = arch.matmul_params(stub)
    assert (layer * 3, got_head) == (held, head)
    norms = 3 * (2 * 100 + 24 + 16) + 100
    assert arch.n_params(stub) == held + 2 * 8 + norms + 2 * head
    # a token uses 2 routed experts and the shared one of the 9 held
    used = 3 * attn + dense_mlp + 2 * (3 * expert + router) + head
    pair = 2 * 6 * (16 + 8)
    assert arch.forward_flops(stub, 1, 10) == 2 * used + 10 * 3 * pair
    assert arch.forward_flops(stub, 7, 28) == 7 * 2 * used + 28 * 3 * pair
    # not of the 6 experts a layer that are only held
    assert 2 * (held + head) - arch.forward_flops(stub, 1, 0) \
        == 2 * 2 * 6 * expert
    # what flops.py keeps is no architecture's: a train step of any forward
    assert flops.train_flops_per_step(stub, 2, 8) == 3.0 * (
        16 * 2 * used + 2 * 36 * 3 * pair)
    assert arch.attention_shape(stub) == (6, 16)
    # the shapes its parameters really have add up to the count
    import jax

    params = jax.eval_shape(lambda: arch.reference().init_params(
        jax.random.PRNGKey(0), stub))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) \
        == arch.n_params(stub)


def test_check_reaches_the_stubs_reference(stub):
    import jax
    import jax.numpy as jnp

    model = architectures.of(stub).reference()
    assert model.__name__ == "chipbench.reference.latent_moe_stub"
    params = model.init_params(jax.random.PRNGKey(SEED), stub)
    # causal: a row padded to 32 reads the same at the positions before
    padded = jax.jit(lambda t: model.logits(params, t, stub))
    samples = []
    for n_prompt, n_out in ((9, 6), (20, 11)):
        seq = np.random.default_rng(n_prompt).integers(
            2, stub["vocab_size"], n_prompt).tolist()
        for _ in range(n_out):  # greedy, by the reference itself
            row = jnp.asarray(seq + [1] * (32 - len(seq)))
            seq.append(int(jnp.argmax(padded(row)[len(seq) - 1])))
        samples.append({"prompt": seq[:n_prompt], "served": seq[n_prompt:]})
    got = serve_driver.check_samples(stub, SEED, samples, control="fp8")
    assert got["requests"] == 2 and got["tokens"] == 17
    assert got["gap_max"] < 1e-4 < got["control_gap_max"]
    # the control is read where `correct` reads, at the served positions:
    # no more places put another token first than tokens were served
    rows = serve_driver.check_samples(stub, SEED, samples, "fp8",
                                      detail=True)["per_sample"]
    assert [r[:2] for r in rows] == [[9, 6], [20, 11]]
    assert all(0 <= r[5] <= r[1] and r[4] <= r[3] for r in rows)
    assert max(r[3] for r in rows) == got["control_gap_max"]
    # other weights, or one token altered, and the gap opens
    assert serve_driver.check_samples(stub, SEED + 1, samples)["gap_max"] > .1
    samples[1]["served"][5] = (samples[1]["served"][5] + 1) % 50
    assert serve_driver.check_samples(stub, SEED, samples)["gap_max"] > 1e-3
    # every routed expert is reached by some position, and no token by all
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 100))
    moe = jax.tree.map(lambda w: w[0].astype(jnp.float32), params["moe"])
    score = jax.nn.sigmoid(x @ moe["router"])
    _, chosen = jax.lax.top_k(score, stub["num_experts_per_tok"])
    assert set(np.asarray(chosen).ravel()) == set(range(8))


def test_the_deployed_class_lays_the_bridges_over_the_architectures_server():
    from ray_memory_management_tpu.serve.llm import LLMServer

    cls = serve_driver.chip_server(manifest.config("mistral-7b-d16"))
    assert cls.__mro__[1:3] == (serve_driver.ChipServer, LLMServer)
    for bridge in ("snapshot", "trace_start", "trace_stop", "free", "check",
                   "reseed"):
        assert getattr(cls, bridge) is getattr(serve_driver.ChipServer,
                                               bridge)
    assert cls.__call__ is LLMServer.__call__


# ------------------------------------------------------- the seam held shut
SOURCES = sorted(
    os.path.relpath(os.path.join(folder, f), manifest.HERE)
    for folder, _, files in os.walk(manifest.HERE) for f in files
    if f.endswith(".py"))
# an architecture's own files: its module, and its reference
OWN = re.compile(r"^(architectures/|reference/(?!train\.py$))")
FORBIDDEN = re.compile(
    r"ray_memory_management_tpu\.models|ray_memory_management_tpu import "
    r"models|chipbench\.reference\.model\b|chipbench\.reference import "
    r"model\b|\[\s*[\"'](head_dim|num_key_value_heads)[\"']\s*\]")


@pytest.mark.parametrize("path", [p for p in SOURCES if not OWN.match(p)])
def test_nothing_outside_an_architecture_knows_its_layers(path):
    with open(os.path.join(manifest.HERE, path)) as f:
        text = f.read()
    found = [m.group(0) for m in FORBIDDEN.finditer(text)]
    assert not found, (path, found)


def test_the_seam_test_sees_what_it_forbids():
    assert len(SOURCES) > 30 and "drivers/serve.py" in SOURCES
    own = {p for p in SOURCES if OWN.match(p)}
    assert own >= {"architectures/__init__.py", "architectures/dense_gqa.py",
                   "reference/model.py"}
    assert "reference/train.py" not in own and "flops.py" not in own
    for line in ('from ray_memory_management_tpu.models import gpt',
                 'from chipbench.reference import model',
                 'import chipbench.reference.model as m',
                 'x = cfg["head_dim"] * 2', "cfg[ 'num_key_value_heads' ]"):
        assert FORBIDDEN.search(line), line
    for line in ('from chipbench.reference import train as ref_train',
                 'def flash_call(kind, batch_heads, seq, head_dim):',
                 'from ray_memory_management_tpu.parallel import make_mesh'):
        assert not FORBIDDEN.search(line), line


# --------------------------------------------- the closed loop, at toy size
TOY = dict(name="toy", num_hidden_layers=2, hidden_size=64,
           intermediate_size=128, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, vocab_size=512,
           rope_theta=1e4, rms_norm_eps=1e-6, max_position_embeddings=128,
           param_dtype="bfloat16", activation_dtype="bfloat16")
TOY_BATCH = {
    "name": "toy-batch", "kind": "serve-closed", "clients": 4,
    "requests_per_client": 256, "order_block": 4, "schedule_seed": 3,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.35,
                      "min": 24, "max": 64},
    "output_tokens": {"dist": "uniform", "min": 2, "max": 8},
    "engine": {"max_batch_size": 2, "steps_per_iter": 4, "pad_multiple": 16,
               "kv_page_tokens": 16, "max_new_tokens": 8},
    "trace_seconds": 1.0, "check": {"requests": 4, "gap_limit": 0.15}}


@pytest.fixture(scope="module")
def toy_batch():
    return serve_driver.run({"name": "toy", "chips": 1}, TOY, TOY_BATCH,
                            seed=SEED, seconds=3.0, trace=True,
                            started=time.time(), expect_platform="cpu")


@pytest.mark.parametrize("trace", [False, True])
def test_closed_loop_at_toy_size_ends_in_the_cells_line(toy_batch, trace):
    r = toy_batch
    assert r["correct"], r["comparisons"]
    assert r["attempted"] >= 8 and r["failed"] == 0
    line = json.loads(json.dumps(
        harness.result_line("longprompt-batch", trace, r)))
    assert list(line)[-1] == "compared"
    if not trace:
        # a closed loop has no due instants of its own: no tail
        # nor the open loop's tokens/s: here the reading is capacity, a
        # metric of its own with a bound of its own
        assert set(line["metrics"]) == {"setup_s",
                                        "serve.capacity_tokens_per_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
        return
    got = set(line["metrics"])
    assert got >= {"serve.closed.engine_prefill_share",
                   "serve.closed.engine_host_share",
                   "serve.closed.slab_live_share",
                   "serve.closed.tokens_per_decode_step",
                   "serve.closed.compiles_in_window",
                   "runtime.lease_to_device_s", "compile.setup_compile_s"}
    # what moves the open loop's metrics is not read here
    assert not got & {"loadgen.late_p99_ms", "serve.queue_wait_p90_ms",
                      "serve.engine_ttft_p90_ms", "serve.step_mfu",
                      "serve.engine_headroom_share", "serve.engine_host_share",
                      "serve.engine_host_cpu_share",
                      "serve.compiles_in_window"}
    assert line["metrics"]["serve.closed.compiles_in_window"]["value"] == 0
    assert 0 < line["metrics"][
        "serve.closed.engine_prefill_share"]["value"] < 100


def test_closed_loop_keeps_the_engine_busy_and_says_when_it_cannot(
        toy_batch):
    r = toy_batch
    assert r["context"]["clocks"]["requests_in_window"] >= 8
    assert r["comparisons"]["clients_out_of_work"] == [0, 0]
    # more callers than slots: the engine always had a request waiting
    b, a = r["context"]["before"]["engine"], r["context"]["after"]["engine"]
    idle = a["phase_s"]["idle_wait"] - b["phase_s"]["idle_wait"]
    assert idle < 0.2 * r["context"]["clocks"]["window_s"]
    # a client that has sent all the mix gives it is counted, not hidden
    load = serve_driver.Load(None, None, lambda: 0.0)
    load.send = lambda req, due, **tags: None
    loop = serve_driver.ClosedLoop(load, [[{"tokens": [2]}], []])
    for c in (0, 1, 0):
        loop._send(c)
    assert loop.nxt == [1, 0] and loop.dry == 2
