"""chipbench, rehearsed off the chip.

What a CPU run can show: that the manifest and every data file agree, that
the generators are deterministic and keep their clips, that the FLOP counts
match hand-worked ones, that the trace reduction adds up on a recorded trace,
that each driver runs end to end at toy size (platform named ``cpu``) to a
last line with the contract's keys, that the output check fails when the
timed path is broken or computed in a lower precision, and that the two
largest programs compile for a described v5e chip inside its memory. No time
read here is a device number.

The topology is described inside a module-scoped fixture only (every xdist
worker imports this file; only the one that runs it may load the TPU library).
"""

import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import architectures, flops, harness, manifest, traffic
from chipbench.drivers import serve as serve_driver
from chipbench.drivers import train as train_driver
from chipbench.trace import xplane
from chipbench_config_checks import NAME, check_config_file

HERE = os.path.dirname(os.path.abspath(__file__))
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILES = {d: sorted(f for f in os.listdir(os.path.join(manifest.HERE, d))
                   if f.endswith(".json"))
         for d in ("configs", "traffic", "metrics")}

TOY = dict(name="toy", num_hidden_layers=2, hidden_size=64,
           intermediate_size=128, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, vocab_size=512,
           rope_theta=1e4, rms_norm_eps=1e-6, max_position_embeddings=128,
           param_dtype="bfloat16", activation_dtype="bfloat16")
TOY_CHAT = {
    "name": "toy-chat", "kind": "serve-open", "rate_per_s": 6.0,
    "arrivals": {"dist": "exponential"},
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.9,
                      "min": 4, "max": 48},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                      "min": 2, "max": 24},
    "engine": {"max_batch_size": 4, "steps_per_iter": 4, "pad_multiple": 16,
               "kv_page_tokens": 16, "max_new_tokens": 24,
               "max_concurrent_queries": 64},
    "trace_seconds": 1.0, "check": {"requests": 4, "gap_limit": 0.15}}
TOY_TRAIN = {
    "name": "toy-train", "kind": "train", "batch": 2, "seq": 64,
    "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                  "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4},
    "attention": "flash", "remat": True, "unroll_layers": True,
    "report_every": 3, "trace_steps": 3,
    "check": {"steps": 3, "loss_limit": 0.003, "grad_limit": 0.03,
              "change_limit": 0.03}}
CELL = {"name": "toy", "chips": 1}
SEED = 2 ** 31 + 5  # the driver's seeds pass 32 signed bits


# ------------------------------------------------------------------ manifest
def test_manifest_and_files_agree():
    bench = manifest.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    files = manifest.metric_files()
    for group in (bench["configs"], bench["workloads"], bench["end_to_end"],
                  bench["per_layer"]):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        data = manifest.config(c["name"])
        assert data["source"] == c["source"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
        manifest.traffic(w["traffic"])
        reported = [m["name"] for m in
                    manifest.metrics_for(w["name"], "end_to_end")]
        assert "setup_s" in reported and len(reported) >= 2
        assert manifest.metrics_for(w["name"], "per_layer")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(cells) // 4)
    for m in bench["per_layer"]:
        f = files[m["name"]]
        assert {k: f[k] for k in m} == m  # the metric's file says the same
        assert callable(manifest.reader(f["reader"]))
        # every cell that reads the metric reports what it moves
        for w in cells:
            if m in manifest.metrics_for(w, "per_layer"):
                assert e2e[m["moves"]] in manifest.metrics_for(
                    w, "end_to_end"), (m["name"], w)
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"], []).append(m["name"])
    assert all("\n" not in k and len(k) <= 200 for k in layers)


@pytest.mark.parametrize("folder,name", [(d, f) for d in FILES
                                         for f in FILES[d]])
def test_every_data_file_loads(folder, name):
    with open(os.path.join(manifest.HERE, folder, name)) as f:
        data = json.load(f)
    assert data["name"] == name[:-len(".json")] and NAME.match(data["name"])
    if folder == "configs":
        check_config_file(data)
    elif folder == "traffic":
        assert len(data["why"]) <= 200 and data["who"]
        # a kind is served by the driver of its first word, found by name
        assert callable(harness.driver_for(data).run)
    else:
        assert UNIT.match(data["unit"])
        assert callable(manifest.reader(data["reader"]))


# ------------------------------------------------------------------- traffic
@pytest.mark.parametrize("name", [f[:-5] for f in FILES["traffic"]])
def test_generators_are_deterministic_and_keep_their_clips(name):
    mix = manifest.traffic(name)
    if mix["kind"] == "train":
        a = traffic.train_batch(mix, SEED, 7, 1000)
        b = traffic.train_batch(mix, SEED, 7, 1000)
        c = traffic.train_batch(mix, SEED, 8, 1000)
        assert np.array_equal(a["tokens"], b["tokens"])
        assert not np.array_equal(a["tokens"], c["tokens"])
        assert a["tokens"].shape == (mix["batch"], mix["seq"])
        assert np.array_equal(a["tokens"][:, 1:], a["targets"][:, :-1])
        assert len({r.tobytes() for r in a["tokens"]}) == mix["batch"]
        return
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    if mix["kind"] == "serve-closed":
        n = int(mix["requests_per_client"])
        one = traffic.closed_clients(mix, SEED, n, 1000)
        two = traffic.closed_clients(mix, SEED, n, 1000)
        other = traffic.closed_clients(mix, SEED + 1, n, 1000)
        assert one == two and one != other
        assert len(one) == mix["clients"] and {len(c) for c in one} == {n}
        flat = [r for c in one for r in c]
        for r in flat:
            assert p["min"] <= len(r["tokens"]) <= p["max"]
            assert o["min"] <= r["max_new_tokens"] <= o["max"]
            assert "due" not in r and min(r["tokens"]) >= 2
        # every seed offers each client the same sizes in the same order
        size = lambda cs: [[(len(r["tokens"]), r["max_new_tokens"])  # noqa
                            for r in c] for c in cs]
        assert (size(one) == size(other)) == ("schedule_seed" in mix)
        assert sorted(sum(size(one), [])) == sorted(sum(size(other), []))
        med = sorted(len(r["tokens"]) for r in flat)[len(flat) // 2]
        assert abs(med - p["median"]) <= 0.1 * p["median"]
        return
    mix = dict(mix, rate_per_s=3.0)
    one = traffic.open_schedule(mix, SEED, 40.0, 1000)
    two = traffic.open_schedule(mix, SEED, 40.0, 1000)
    other = traffic.open_schedule(mix, SEED + 1, 40.0, 1000)
    assert one == two and one != other
    for r in one:
        assert p["min"] <= len(r["tokens"]) <= p["max"]
        assert o["min"] <= r["max_new_tokens"] <= o["max"]
        assert 0 <= r["due"] < 40.0 and min(r["tokens"]) >= 2
    assert abs(len(one) - 120) <= 2
    # every seed offers the same sizes, in another order
    size = lambda rs: sorted((len(r["tokens"]), r["max_new_tokens"])  # noqa
                             for r in rs)
    assert sorted(len(r["tokens"]) for r in one) == \
        sorted(len(r["tokens"]) for r in other) or \
        abs(len(one) - len(other)) <= 2
    med = sorted(len(r["tokens"]) for r in one)[len(one) // 2]
    assert abs(med - p["median"]) <= 0.1 * p["median"]


@pytest.mark.parametrize("name,mid,pairs", [("chat-online", 100, 12),
                                            ("longprompt-batch", 40, 14)])
def test_warm_up_waves_reach_every_program_of_the_mix(name, mid, pairs):
    mix = manifest.traffic(name)
    e = mix["engine"]
    up = lambda x, m: -(-x // m) * m  # noqa: E731
    reach = set()
    for p in range(mix["prompt_tokens"]["min"],
                   mix["prompt_tokens"]["max"] + 1):
        for b in (mix["output_tokens"]["min"], mid,
                  mix["output_tokens"]["max"]):
            bucket = up(p, e["pad_multiple"])
            reach.add((bucket, up(max(bucket, p + b), e["kv_page_tokens"])))
    waves = serve_driver.warm_up_waves(mix)
    got = set()
    for wave in waves:
        assert len(wave) <= e["max_batch_size"]
        caps = []
        for w in wave:
            bucket = up(w["prompt"], e["pad_multiple"])
            cap = up(max(bucket, w["prompt"] + w["budget"]),
                     e["kv_page_tokens"])
            got.add((bucket, cap))
            caps.append(cap)
        # the wave's slab length sits beside every shorter capacity
        assert set(caps) >= {c for _, c in reach if c <= max(caps)}
    assert got == reach and len(reach) == pairs
    # one prefill program a bucket: every bucket of the mix is among them
    assert {b for b, _ in got} == set(range(
        up(mix["prompt_tokens"]["min"], e["pad_multiple"]),
        up(mix["prompt_tokens"]["max"], e["pad_multiple"]) + 1,
        e["pad_multiple"]))


# --------------------------------------------------------------------- flops
def test_flops_against_hand_worked_counts():
    m = manifest.config("mistral-7b-d16")
    # one layer: q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    arch = architectures.of(m)
    assert arch.matmul_params(m) == (layer, 4096 * 32000)
    assert arch.n_params(m) == 16 * (layer + 2 * 4096) + 4096 \
        + 2 * 4096 * 32000
    assert abs(arch.n_params(m) / 1e9 - 3.75) < 0.01
    # one token attending to 1000 positions
    want = 2 * (16 * layer + 4096 * 32000) + 4 * 1000 * 16 * 32 * 128
    assert arch.forward_flops(m, 1, 1000) == want
    i = manifest.config("internlm2-1.8b-d6")
    assert abs(architectures.of(i).n_params(i) / 1e6 - 757) < 1
    step = flops.train_flops_per_step(i, 2, 4096)
    layer_i = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192
    dense = 2 * 8192 * (6 * layer_i + 2048 * 92544)
    attn = 4 * 2 * (4096 * 4097 // 2) * 6 * 16 * 128
    assert step == 3.0 * (dense + attn)
    assert 29e12 < step < 31e12  # the issue's 30 TFLOP a step
    assert flops.causal_pairs(4) == 10 and flops.causal_pairs(4, 2) == 7
    f, b = flops.flash_call("fwd", 32, 4096, 128)
    assert f == 2 * 2 * (4096 * 4097 / 2) * 128 * 32
    assert b == 4 * 32 * 4096 * 128 * 2 + 32 * 4096 * 4
    assert flops.roofline_seconds(f, b, "TPU v5 lite")[1] == "compute"
    with pytest.raises(KeyError):
        flops.peak("some other chip")


# --------------------------------------------------------------------- trace
def test_trace_reduction_on_hand_made_rows():
    dev = "/device:TPU:0"
    rows = [
        {"plane": dev, "line": "XLA Modules", "name": "jit_a(1)",
         "start_ns": 0, "dur_ns": 4_000},
        {"plane": dev, "line": "XLA Modules", "name": "jit_b(2)",
         "start_ns": 10_000, "dur_ns": 5_000},
        {"plane": dev, "line": "XLA Ops", "name": "fusion.1",
         "start_ns": 0, "dur_ns": 3_000},
        {"plane": dev, "line": "XLA Ops", "name": "copy.2",
         "start_ns": 2_000, "dur_ns": 2_000},   # overlaps the fusion
        {"plane": dev, "line": "XLA Ops", "name": "fusion.1",
         "start_ns": 10_000, "dur_ns": 5_000},
    ]
    out = xplane.reduce_events(rows)
    assert out["planes"] == 1
    assert out["busy_s"] == pytest.approx(9e-6)
    assert out["span_s"] == pytest.approx(15e-6)
    assert out["programs"] == {"jit_a": pytest.approx(4e-6),
                               "jit_b": pytest.approx(5e-6)}
    assert out["ops"]["fusion.1"] == pytest.approx(8e-6)
    assert out["op_calls"]["fusion.1"] == 2
    assert out["idle_gaps"] == [["before jit_b", pytest.approx(6e-6)]]
    assert xplane.reduce_events([])["planes"] == 0


RECORDED = sorted(f for f in os.listdir(os.path.join(
    os.path.dirname(xplane.__file__), "recorded")) if f.endswith(".jsonl")) \
    if os.path.isdir(os.path.join(os.path.dirname(xplane.__file__),
                                  "recorded")) else []


@pytest.mark.parametrize("name", RECORDED)
def test_trace_reduction_on_the_recorded_trace(name):
    folder = os.path.join(os.path.dirname(xplane.__file__), "recorded")
    with open(os.path.join(folder, name)) as f:
        rows = [json.loads(line) for line in f]
    with open(os.path.join(folder, name[:-len(".jsonl")] + ".expect.json")) \
            as f:
        expect = json.load(f)
    out = xplane.reduce_events(rows)
    assert out["planes"] == expect["planes"]
    assert out["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["span_s"]
    for k, v in expect["programs"].items():
        assert out["programs"][k] == pytest.approx(v, rel=1e-9)
    assert out["device_ops"][0][0] == expect["top_program"]
    # busy time is at most the sum of the operations' durations
    assert out["busy_s"] <= sum(out["ops"].values()) * (1 + 1e-9)


def test_flash_roofline_reader_on_the_recorded_train_step():
    """One traced step of `pretrain-1chip` (my chip run, PR 24): six layers,
    so six dq and six dk/dv calls and twelve forward calls (remat runs the
    forward kernel again in the backward pass)."""
    from chipbench.readers import flash_roofline

    folder = os.path.join(os.path.dirname(xplane.__file__), "recorded")
    with open(os.path.join(folder, "train_step.jsonl")) as f:
        t = xplane.reduce_events([json.loads(line) for line in f])
    cfg = manifest.config("internlm2-1.8b-d6")
    mix = manifest.traffic("pretrain-4k")
    calls, spent = {}, 0.0
    for name, seconds in t["ops"].items():
        kind = flash_roofline.kind_of(t["op_text"][name], 32, 4096, 128)
        if kind:
            calls[kind] = calls.get(kind, 0) + t["op_calls"][name]
            spent += seconds
    assert calls == {"fwd": 12, "dq": 6, "dkv": 6}
    pairs, peak = 4096 * 4097 / 2, 197e12
    least = (12 * 2 + 6 * 3 + 6 * 4) * 2 * pairs * 128 * 32 / peak
    ctx = {"trace": t, "cfg": cfg, "mix": mix,
           "device": {"kind": "TPU v5 lite"}}
    share = flash_roofline.read(ctx)
    assert share == pytest.approx(100 * least / spent, rel=1e-9)
    assert 30 < share < 40
    assert flash_roofline.read(dict(ctx, trace=None)) is None
    assert flash_roofline.read(dict(ctx, trace=xplane.reduce_events([]))) \
        is None


# ------------------------------------------------------------------- drivers
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def toy_serve():
    return serve_driver.run(CELL, TOY, TOY_CHAT, seed=SEED, seconds=4.0,
                            trace=True, started=time.time(),
                            expect_platform="cpu", control="fp8")


@pytest.fixture(scope="module")
def toy_train():
    return train_driver.run(
        CELL, dict(TOY, param_dtype="float32"), TOY_TRAIN, seed=SEED,
        seconds=2.0, trace=True, started=time.time(), expect_platform="cpu",
        attention="flash-interpret", control="bf16")


@pytest.mark.parametrize("trace", [False, True])
def test_serve_driver_at_toy_size_ends_in_a_contract_line(toy_serve, trace):
    r = toy_serve
    assert r["correct"], r["comparisons"]
    assert r["attempted"] >= 20 and r["failed"] == 0
    line = json.loads(json.dumps(
        harness.result_line("chat-online", trace, r)))
    assert set(line) - {"compared", "breakdown", "trace_cost", "check_s"} \
        == CONTRACT_KEYS
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        # the CPU has no device plane: the trace readers find nothing to
        # read and their metrics are left out, never reported as 0
        assert "device_idle_share.serve" not in line["metrics"]
        assert line["metrics"]["serve.compiles_in_window"]["value"] == 0
        assert 0 < line["metrics"]["serve.tokens_per_decode_step"][
            "value"] <= TOY_CHAT["engine"]["max_batch_size"]
        assert line["metrics"]["loadgen.late_p99_ms"]["value"] < 250
        assert "runtime.lease_to_device_s" in line["metrics"]
    else:
        assert set(line["metrics"]) == {"setup_s", "serve.tokens_per_s",
                                        "serve.norm_latency_p90_ms"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("trace", [False, True])
def test_train_driver_at_toy_size_ends_in_a_contract_line(toy_train, trace):
    r = toy_train
    assert r["correct"], r["comparisons"]
    line = json.loads(json.dumps(
        harness.result_line("pretrain-1chip", trace, r)))
    assert set(line) - {"compared", "breakdown", "trace_cost", "check_s"} \
        == CONTRACT_KEYS
    assert list(line)[-1] == "compared"
    if trace:
        assert line["metrics"]["train.step_ms_median"]["value"] > 0
        assert "train.flash_roofline" not in line["metrics"]
        assert "train.step_mfu" not in line["metrics"]  # cpu: no peak
    else:
        assert set(line["metrics"]) == {"setup_s", "train.tokens_per_s"}
        assert r["attempted"] * 2 * 64 == r["context"]["clocks"]["tokens"]


def test_window_summary_credits_tokens_by_time_in_service():
    done = [
        {"due": 1.0, "sent": 1.001, "finished": 3.0, "prompt": [5] * 10,
         "served": [7] * 10, "budget": 10},              # whole: 20 tokens
        {"due": 8.0, "sent": 8.0, "finished": 12.0, "prompt": [5] * 30,
         "served": [7] * 10, "budget": 10},              # half in: 20 of 40
        {"due": 9.0, "sent": 9.0, "finished": 70.0, "prompt": [5] * 4,
         "budget": 10, "error": "no answer"},
    ]
    s = serve_driver.summarize(
        {"done": done, "seconds": 10.0, "window_s": 10.0}, TOY, TOY_CHAT)
    assert s["tokens"] == pytest.approx(40.0)
    assert s["failed"] == 1 and s["requests_in_window"] == 1
    assert s["latency_ms_per_token"] == pytest.approx(
        [200.0, 400.0, (10.0 + serve_driver.DRAIN_S - 9.0) / 10 * 1e3])
    assert s["late_ms"][0] == pytest.approx(1.0)
    assert serve_driver.percentile(list(range(1, 113)), 90) == 101


def test_the_lower_precision_controls_read_above_the_program(toy_serve,
                                                             toy_train):
    """The control is the reference put in the program's place one
    precision down (fp8 for the bf16 serve cell, bf16 parameters and
    moments for the f32 train cell); on the chip, at the cells' own sizes,
    it reads above each limit (PERF.md has those readings)."""
    c = toy_serve["comparisons"]
    assert c["control_logit_gap_max"][0] > 3 * c["served_logit_gap_max"][0]
    assert c["control_logit_gap_max"][0] > TOY_CHAT["check"]["gap_limit"]
    t = toy_train["comparisons"]
    worst = t["readings"][0]["control"][
        "param_change_norm_gap_worst_leaf"]["gap"]
    assert worst > 3 * t["param_change_norm_gap_worst_leaf"][0]
    assert worst > TOY_TRAIN["check"]["change_limit"]


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_a_broken_timed_path_comes_out_not_correct(fault):
    """The harness's look for a chip is skipped (platform named ``cpu``)
    and the rest of a run is driven with the timed path broken underneath."""
    if fault == "token_altered":
        r = serve_driver.run(CELL, TOY, TOY_CHAT, seed=SEED + 1,
                             seconds=2.0, trace=False, started=time.time(),
                             expect_platform="cpu", fault=fault)
        assert r["comparisons"]["served_logit_gap_max"][0] \
            > TOY_CHAT["check"]["gap_limit"]
    else:
        r = train_driver.run(
            CELL, dict(TOY, param_dtype="float32"), TOY_TRAIN, seed=SEED + 1,
            seconds=1.0, trace=False, started=time.time(),
            expect_platform="cpu", attention="flash-interpret", fault=fault)
    assert r["correct"] is False, r["comparisons"]


def test_reference_against_gpt_forward_at_toy_size():
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import gpt

    cfg = dict(TOY, param_dtype="float32")
    arch = architectures.of(cfg)
    model = arch.reference()
    tc = arch.program_config(
        dict(cfg, activation_dtype="float32"), attention="ref")
    key = jax.random.PRNGKey(SEED)
    ours, theirs = model.init_params(key, cfg), gpt.init_params(key, tc)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree.leaves(ours), jax.tree.leaves(theirs)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0, 512)
    want = gpt.forward(theirs, tokens, tc)
    got = jnp.stack([model.logits(ours, t, cfg) for t in tokens])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    low = jnp.stack([model.logits(ours, t, cfg, "fp8") for t in tokens])
    assert float(jnp.max(jnp.abs(low - want))) > 0.1


def test_command_line_refuses_a_machine_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", "chat-online",
         "--seed", "3", "--seconds", "1"], cwd=manifest.ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "platform 'cpu'" in proc.stderr


LEAVES_BEHIND = """
import os, subprocess, sys
from chipbench import harness

harness.adopt_orphans()
sleeper = "import time; print('up', flush=True); time.sleep(120)"
# a worker that started a child of its own and went, as a killed worker does
subprocess.Popen([sys.executable, "-c",
                  "import subprocess, sys; subprocess.Popen("
                  "[sys.executable, '-c', %r])" % sleeper]).wait()
# and one that takes no notice of being told to stop
deaf = subprocess.Popen(
    [sys.executable, "-c", "import signal; signal.signal(signal.SIGTERM, "
     "signal.SIG_IGN); " + sleeper], stdout=subprocess.PIPE)
deaf.stdout.readline()
before = harness.below(os.getpid())
stopped = harness.stop_processes(grace_s=0.3, kill_s=1.0)
print(len(before), sorted(how for _, _, how in stopped),
      len(harness.below(os.getpid())))
"""


def test_a_run_waits_for_every_process_below_it():
    proc = subprocess.run([sys.executable, "-c", LEAVES_BEHIND],
                          cwd=manifest.ROOT, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split("\n")[-2] == "2 ['SIGKILL', 'SIGTERM'] 0"


# ------------------------------------------------ described-chip compilation
@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("program,mix_name", [
    ("decode", "chat-online"), ("train", "pretrain-4k"),
    ("prefill", "longprompt-batch"), ("decode", "longprompt-batch")],
    ids=["decode", "train", "prefill", "decode-longprompt"])
def test_the_largest_programs_fit_a_described_v5e(one_chip, program,
                                                  mix_name, monkeypatch):
    import jax
    import jax.numpy as jnp

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hbm = flops.peak("TPU v5 lite")["hbm_bytes"]
    if program in ("decode", "prefill"):
        from ray_memory_management_tpu.ops import paged_attention as pa
        from ray_memory_management_tpu.serve.llm import ContinuousBatcher

        # the kernel's dispatch asks where computation lands: steer it here
        monkeypatch.setattr(pa, "_on_tpu", lambda: True)
        cfg = manifest.config("mistral-7b-d16")
        arch = architectures.of(cfg)
        mix = manifest.traffic(mix_name)
        e = serve_driver.engine_kwargs(cfg, mix)
        tc = arch.program_config(cfg)
        slots, page = e["max_batch_size"], e["kv_page_tokens"]
        # one slot's share of the pool, in positions: 16 x 1,536 are 1.5
        # GiB, 8 x 4,096 are 2 GiB
        longest = e["kv_pool_bytes"] // slots // arch.cache_token_bytes(cfg)
        assert (e["kv_pool_bytes"], longest) == {
            "chat-online": (3 * 2 ** 29, 1536),
            "longprompt-batch": (2 ** 31, 4096)}[mix_name]
        params = shaped(jax.eval_shape(
            lambda: arch.init_program_params(jax.random.PRNGKey(0), tc)))
        eng = ContinuousBatcher(
            None, tc, max_slots=slots, max_new_tokens=e["max_new_tokens"],
            pad_multiple=e["pad_multiple"],
            steps_per_iter=e["steps_per_iter"],
            kv_page_tokens=page, kv_pool_bytes=e["kv_pool_bytes"])
        try:
            pool = shaped(jax.eval_shape(eng.kv_pool.allocate))
            assert pool["k"].shape[2] == slots * longest // page + 1  # sink
            width = eng.kv_pool.table_width
            if program == "decode":
                # the one decode program of the engine's lifetime
                compiled = eng._paged_step.lower(
                    params, pool, arr((slots,)), arr((slots,)),
                    arr((slots, width)), arr((2,), jnp.uint32)).compile()
            else:
                # the mix's largest prefill bucket
                bucket = -(-mix["prompt_tokens"]["max"] // e[
                    "pad_multiple"]) * e["pad_multiple"]
                assert bucket == 3584
                compiled = eng._paged_prefill_fn(bucket).lower(
                    params, pool, arr((1, bucket)), arr((width,)),
                    arr(()), arr((2,), jnp.uint32)).compile()
        finally:
            eng.close()
        assert compiled.as_text().count("tpu_custom_call") == (
            1 if program == "decode" else 0)
        # 7.0 GiB of weights and the pool in each (1.5 GiB, longprompt-batch
        # 2 GiB); by the compiler 9.13 GiB and 9.65 GiB in the decode
        # programs (PERF.md), 12.58 GiB in the prefill of 3,584 positions
        # (its row cache, activations, [32, S, S] scores and logits)
        lo, hi = {("decode", "chat-online"): (8.4, 9.6),
                  ("decode", "longprompt-batch"): (8.9, 10.1),
                  ("prefill", "longprompt-batch"): (12.0, 13.2)}[
                      program, mix_name]
        assert lo * 2 ** 30 < _total_bytes(compiled) < hi * 2 ** 30
    else:
        import optax
        from jax.sharding import Mesh

        from ray_memory_management_tpu.parallel import make_train_step

        cfg = manifest.config("internlm2-1.8b-d6")
        arch = architectures.of(cfg)
        mix = manifest.traffic(mix_name)
        tc = arch.program_config(
            cfg, attention=mix["attention"], remat=mix["remat"],
            max_seq=mix["seq"], scan_unroll=cfg["num_hidden_layers"])
        mesh = Mesh(np.array([one_chip._device]), ("dp",))
        params = shaped(jax.eval_shape(
            lambda: arch.init_program_params(jax.random.PRNGKey(0), tc)))
        opt = optax.adamw(mix["optimizer"]["learning_rate"])
        state = shaped(jax.eval_shape(opt.init, params))
        step = make_train_step(
            lambda p, b: arch.program_loss(p, b, tc, mesh), opt, mesh)
        shape = (mix["batch"], mix["seq"])
        compiled = step.lower(params, state, {
            "tokens": arr(shape), "targets": arr(shape)}).compile()
        assert compiled.as_text().count("tpu_custom_call") > 0
        # the issue's reckoning: 13.1 GiB
        assert 12.5 * 2 ** 30 < _total_bytes(compiled) < 14 * 2 ** 30
    assert _total_bytes(compiled) < hbm
