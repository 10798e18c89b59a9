"""The train driver: ``JaxTrainer.fit`` with one worker on a leased chip.

The loop is user code, as it is for any user of ``JaxTrainer``: it builds the
step with ``parallel.make_train_step`` and the loss of the configuration's
architecture (``gpt.loss_fn`` for the dense decoder), feeds a new
seeded batch from the host every step and reports through
``session.report``. Set-up builds the one compiled step with its state,
drives it through its first steps (which the reference follows) and hands
that same object to the window.
"""

from __future__ import annotations

import gc
import shutil
import statistics
import tempfile
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from chipbench import flops, traffic

FIT_TIMEOUT_S = 1100


def _leaf_paths(tree) -> Dict[str, Any]:
    import jax

    return {"/".join(str(getattr(k, "key", k)) for k in path): x
            for path, x in jax.tree_util.tree_leaves_with_path(tree)}


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float],
                   skip=()) -> Dict[str, Any]:
    """The widest gap between the program's norm and the reference's over
    the leaves, each measured against the reference's norm of that leaf or
    of the median leaf, whichever is larger."""
    median = statistics.median(ref.values())
    worst, at = 0.0, None
    for k, r in ref.items():
        if k in skip:
            continue
        gap = abs(got[k] - r) / max(r, median)
        if gap >= worst:
            worst, at = gap, k
    return {"gap": worst, "leaf": at}


def compare(got: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers the output check holds to limits: the widest relative
    gap of a step's loss, and by the worst leaf the first gradient's norm
    and the norm of the parameters' change after the first steps. Leaves
    whose gradient is nought to rounding in the reference (under a
    thousandth of the median leaf's) move under Adam by round-off alone and
    are left out of the change by that rule."""
    ref = got["reference"]
    g_med = statistics.median(ref["grad_norms"].values())
    still = [k for k, g in ref["grad_norms"].items() if g < 1e-3 * g_med]

    def against(x):
        return {
            "loss_gap_max": max(abs(a - b) / abs(b) for a, b in
                                zip(x["losses"], ref["losses"])),
            "first_grad_norm_gap_worst_leaf": worst_leaf_gap(
                x["grad_norms"], ref["grad_norms"]),
            "param_change_norm_gap_worst_leaf": worst_leaf_gap(
                x["change_norms"], ref["change_norms"], still)}

    out = dict(against(got), leaves_left_out=len(still))
    if got.get("control"):
        out["control"] = against(got["control"])
    return out


def train_loop(config: Dict[str, Any]) -> None:
    """Runs in the leased worker. Everything it reports the benchmark
    process reads from ``Result.metrics_history``."""
    t_loop = time.time()
    import jax
    import jax.numpy as jnp
    import optax

    from chipbench import architectures
    from chipbench.drivers.serve import device_fields, memory_peak
    from chipbench.reference import train as ref_train
    from chipbench.trace import xplane
    from ray_memory_management_tpu.parallel import (
        make_mesh, make_train_step, param_pspecs, shard_pytree)
    from ray_memory_management_tpu.train import session
    from ray_memory_management_tpu.utils.compile_cache import CompileCounter

    compiles = CompileCounter()
    device = device_fields(jax, config["expect_platform"], config["chips"])
    t_device = time.time()
    cfg, mix, seed = config["cfg"], config["mix"], config["seed"]
    fault = config.get("fault")
    B, S, V = mix["batch"], mix["seq"], cfg["vocab_size"]
    o = mix["optimizer"]
    arch = architectures.of(cfg)
    tc = arch.program_config(
        cfg, attention=config.get("attention", mix["attention"]),
        remat=mix["remat"], max_seq=S,
        scan_unroll=cfg["num_hidden_layers"] if mix["unroll_layers"] else 1)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    opt = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                      eps=o["eps"], weight_decay=o["weight_decay"])

    def loss_fn(p, b):
        if fault == "half_batch":  # a test's planted fault
            b = jax.tree.map(lambda x: x[: B // 2], b)
        return arch.program_loss(p, b, tc, mesh)

    step = make_train_step(loss_fn, opt, mesh)
    if fault == "state_unchanged":
        real = step

        def step(p, s, b):  # noqa: F811 — a test's planted fault
            keep = jax.tree.map(jnp.copy, (p, s))
            _, _, loss = real(p, s, b)
            return keep[0], keep[1], loss

    norms = jax.jit(lambda tree: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        tree))
    change = jax.jit(lambda p, k: jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p,
        arch.init_program_params(k, tc)))

    def feed(i: int, seed=seed):
        batch = traffic.train_batch(mix, seed, i, V)
        return {k: jax.device_put(v) for k, v in batch.items()}

    n_check = int(mix["check"]["steps"])

    def first_steps(seed):
        """State from the seed, driven through its first steps by the
        window's own call and feed; what the comparison reads of them."""
        key = jax.random.PRNGKey(seed)
        params = arch.init_program_params(key, tc)
        params = shard_pytree(params, mesh, param_pspecs(params, mesh, "dp"))
        opt_state = opt.init(params)
        got = {"losses": []}
        for i in range(n_check):
            params, opt_state, loss = step(params, opt_state, feed(i, seed))
            got["losses"].append(float(loss))
            if i == 0:  # mu after one step is (1 - b1) x the first gradient
                got["grad_norms"] = {
                    k: float(v) / (1.0 - o["b1"]) for k, v in
                    _leaf_paths(norms(opt_state[0].mu)).items()}
        got["change_norms"] = {k: float(v) for k, v in
                               _leaf_paths(change(params, key)).items()}
        return params, opt_state, loss, got

    def reference(seed):
        batches = [{k: jnp.asarray(v) for k, v in
                    traffic.train_batch(mix, seed, j, V).items()}
                   for j in range(n_check)]
        out = {"reference": ref_train.follow(seed, cfg, o, batches)}
        if config.get("control"):
            out["control"] = ref_train.follow(
                seed, cfg, o, batches, compute="bf16",
                state_dtype="bfloat16")
        return out

    params, opt_state, loss, got = first_steps(seed)
    setup_compile = compiles.snapshot()

    # the window: the same step and state go on from step n_check
    seconds, every = config["seconds"], int(mix["report_every"])
    steps, last_sync, i = 0, 0, n_check
    intervals = []
    t0 = t_mark = time.time()
    while True:
        params, opt_state, loss = step(params, opt_state, feed(i))
        i += 1
        steps += 1
        now = time.time()
        if steps % every == 0 or now - t0 >= seconds:
            value = float(loss)  # the readback closes the steps so far
            now = time.time()
            intervals.append((now - t_mark) / (steps - last_sync))
            session.report({"step": i, "loss": value, "t": now - t0})
            t_mark, last_sync = now, steps
            if now - t0 >= seconds:
                break
    window_s = time.time() - t0
    reduced = None
    if config["trace"]:
        # the same loop goes on for a few steps under the profiler, past
        # the window's close, so that the window's own numbers are clean
        n_traced = int(mix["trace_steps"])
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        try:
            jax.profiler.start_trace(trace_dir)
            trace_t0 = time.time()
            for _ in range(n_traced):
                params, opt_state, loss = step(params, opt_state, feed(i))
                i += 1
            float(loss)
            t_stop = time.time()
            jax.profiler.stop_trace()
            t_read = time.time()
            reduced = xplane.reduce_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        reduced.update(window_s=t_stop - trace_t0, steps=n_traced,
                       collect_s=t_read - t_stop,
                       reduce_s=time.time() - t_read)
    in_window = compiles.snapshot()
    peak = memory_peak(jax)

    # free the program's state, then the reference follows the first steps
    del params, opt_state, loss
    gc.collect()
    got.update(reference(seed))
    # the limits tool reads more seeds under the same compiled step
    more = []
    for other in config.get("more_seeds", []):
        state = first_steps(other)
        extra = state[3]
        del state
        gc.collect()
        extra.update(reference(other), seed=other)
        more.append(extra)
    session.report({
        "summary": True, "device": device, "memory_peak_bytes": peak,
        "times": {"loop_start": t_loop, "device_ready": t_device,
                  "window_start": t0},
        "window_s": window_s, "steps": steps, "tokens": steps * B * S,
        "step_s_intervals": intervals, "first_steps": got, "more": more,
        "compile_setup": setup_compile, "compile_window": in_window,
        "trace": reduced,
    })


def run(cell: Dict[str, Any], cfg: Dict[str, Any], mix: Dict[str, Any], *,
        seed: int, seconds: float, trace: bool, started: float,
        expect_platform: str = "tpu", fault: Optional[str] = None,
        control: Optional[str] = None, attention: Optional[str] = None,
        more_seeds=()) -> Dict[str, Any]:
    """One run of a train cell; returns what the harness prints."""
    import ray_memory_management_tpu as rmt
    from ray_memory_management_tpu.train import (JaxTrainer, RunConfig,
                                                 ScalingConfig)

    chips = cell["chips"]
    if expect_platform == "tpu":
        scaling = ScalingConfig(num_workers=1, use_tpu=True,
                                chips_per_worker=chips)
    else:  # a rehearsal off the chip: the same lease without use_tpu
        scaling = ScalingConfig(num_workers=1,
                                resources_per_worker={"TPU": chips})
    config = {"cfg": cfg, "mix": mix, "seed": seed, "seconds": seconds,
              "chips": chips,
              "trace": trace, "expect_platform": expect_platform,
              "fault": fault, "control": control,
              "more_seeds": list(more_seeds)}
    if attention:
        config["attention"] = attention
    rmt.init(num_cpus=2, num_tpus=chips)
    try:
        run_dir = tempfile.mkdtemp(prefix="chipbench_fit_")
        try:
            t_fit = time.time()
            trainer = JaxTrainer(
                train_loop, train_loop_config=config,
                scaling_config=scaling,
                run_config=RunConfig(name="chipbench", storage_path=run_dir))
            done: Dict[str, Any] = {}
            worker = threading.Thread(
                target=lambda: done.update(result=trainer.fit()),
                daemon=True)
            worker.start()
            worker.join(FIT_TIMEOUT_S)
            if worker.is_alive():
                raise TimeoutError(
                    f"trainer.fit() still running after {FIT_TIMEOUT_S} s")
            result = done["result"]
        finally:
            shutil.rmtree(run_dir, ignore_errors=True)
    finally:
        rmt.shutdown()
    if result.error is not None:
        raise result.error
    s = next(m for m in result.metrics_history if m.get("summary"))

    compared = compare(s["first_steps"])
    lim = mix["check"]
    comparisons = {
        "platform": [s["device"]["platform"], expect_platform],
        "loss_gap_max": [compared["loss_gap_max"], lim["loss_limit"]],
        "first_grad_norm_gap_worst_leaf": [
            compared["first_grad_norm_gap_worst_leaf"]["gap"],
            lim["grad_limit"]],
        "param_change_norm_gap_worst_leaf": [
            compared["param_change_norm_gap_worst_leaf"]["gap"],
            lim["change_limit"]],
        "worst_leaves": [
            compared["first_grad_norm_gap_worst_leaf"]["leaf"],
            compared["param_change_norm_gap_worst_leaf"]["leaf"]],
        "leaves_left_out": [compared["leaves_left_out"], 0],
    }
    correct = s["steps"] > 0 and all(
        np.isfinite(v[0]) and v[0] <= v[1] for k, v in comparisons.items()
        if k.endswith(("_max", "_leaf")))
    if control is not None or s["more"]:
        # the limits tool's readings: every seed's numbers, the control's
        # beside them
        comparisons["readings"] = [dict(compared, seed=seed)] + [
            dict(compare(m), seed=m["seed"]) for m in s["more"]]
    setup_s = s["times"]["window_start"] - started
    values = {"setup_s": setup_s,
              "train.tokens_per_s": s["tokens"] / s["window_s"]}
    device = dict(s["device"], memory_peak_bytes=s["memory_peak_bytes"])
    clocks = {
        "setup_s": setup_s, "window_s": s["window_s"], "tokens": s["tokens"],
        "steps": s["steps"], "step_s_intervals": s["step_s_intervals"],
        "model_flops": s["steps"] * flops.train_flops_per_step(
            cfg, mix["batch"], mix["seq"]),
        "lease_to_device_s": s["times"]["device_ready"] - t_fit,
    }
    return {"correct": bool(correct), "attempted": s["steps"], "failed": 0,
            "values": values, "device": device, "comparisons": comparisons,
            "context": {"kind": "train", "cfg": cfg, "mix": mix,
                        "before": {"compile": s["compile_setup"]},
                        "after": {"compile": s["compile_window"]},
                        "clocks": clocks, "trace": s["trace"],
                        "device": device}}
