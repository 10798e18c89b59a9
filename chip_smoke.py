#!/usr/bin/env python3
"""Chip smoke: the runtime's leased-chip path, once, on a real TPU.

    python chip_smoke.py            # one chip: device plane, train, serve
    python chip_smoke.py --chips 4  # four chips: the sharded train path only

Layout: this driver stays off the chip (its own jax is pinned to the CPU
platform before any backend exists) and exactly one worker process at a time
holds it, through a ``num_tpus`` lease. Each phase ends with its chip-holding
process gone, and the next phase's lease succeeding is what shows the chip
was freed; nothing sleeps in between. Every phase checks, inside the leased
worker, that jax's platform is ``tpu``: there is no way to accept a CPU from
the command line (tests/test_chip_smoke.py calls the phases as functions at
toy size and names the platform it expects).

The last line of stdout is the verdict, one JSON object; every reading goes
on an earlier line. Times printed here are smoke readings, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import signal
import sys
import tempfile
import threading
import time

MIB = 1 << 20
GIB = 1 << 30
DEADLINE_S = 1150  # the driver allows 1200 s, compilation included


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def pin_driver_to_cpu() -> None:
    """Keep this process off the chip. Done through jax's config, not the
    environment: a leased worker inherits ``JAX_PLATFORMS`` from the
    driver's environment (unset on the chip machine, so jax finds the TPU
    there; ``cpu`` where the operator hides the chip, and then the worker
    reports ``cpu`` and the run fails)."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def require_platform(jax, expect: str) -> dict:
    """The device fields of the verdict line, as jax reports them in this
    (leased) process; any platform but the expected one is an error."""
    first = jax.devices()[0]
    dev = {"platform": first.platform, "kind": first.device_kind,
           "count": len(jax.devices())}
    if dev["platform"] != expect:
        raise RuntimeError(
            f"leased worker computes on platform {dev['platform']!r} "
            f"({dev['kind']}), not {expect!r}: JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r} TPU_VISIBLE_CHIPS="
            f"{os.environ.get('TPU_VISIBLE_CHIPS')!r}")
    return dev


def seeded_array(seed: int, index: int, n_elems: int):
    """f32 values in [0, 1) that every backend computes to the same bits:
    threefry integers, an exact int->float conversion and a power-of-two
    scale. The driver regenerates them on its CPU backend to check a
    cross-process read value for value."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.PRNGKey(seed), index)
    bits = jax.random.bits(key, (n_elems,), jnp.uint32)
    return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


# ------------------------------------------------------- phase 1: device plane
class DevicePlane:
    """Runs inside a ``num_tpus=1`` actor: pins arrays in the device tier
    past its budget, reads them back every way the tier offers, and
    reports what the store and the device counted."""

    def __init__(self, expect_platform: str, seed: int):
        import jax

        from ray_memory_management_tpu.utils.compile_cache import (
            CompileCounter,
        )

        self.compiles = CompileCounter()
        self.device = require_platform(jax, expect_platform)
        self.seed = seed
        self.refs = []

    @staticmethod
    def _counters() -> dict:
        import jax

        from ray_memory_management_tpu.core import metrics_defs as mdefs

        stats = jax.devices()[0].memory_stats() or {}
        return {
            "device_bytes_in_use": stats.get("bytes_in_use"),
            "store_pinned_bytes": int(sum(
                mdefs.device_bytes_pinned().series().values())),
            "demotions": int(sum(
                mdefs.device_evictions().series().values())),
            "zero_copy_hits": int(sum(
                mdefs.device_zero_copy_hits().series().values())),
        }

    def run(self, n_arrays: int, n_elems: int) -> dict:
        import jax
        import jax.numpy as jnp

        import ray_memory_management_tpu as rmt

        out = {"device": self.device, "before": self._counters()}
        # the budget sits below the sum, so the last put demotes the
        # least recently used array (index 0) to the shm tier; only the
        # store keeps the arrays alive, so a demotion frees device memory
        last = None
        for i in range(n_arrays):
            last = seeded_array(self.seed, i, n_elems)
            self.refs.append(rmt.put(last, device=True))
        out["after_puts"] = self._counters()
        check(out["after_puts"]["demotions"] >= 1,
              f"no demotion after {n_arrays} puts: {out['after_puts']}")

        # zero-copy: the live buffer itself comes back
        check(rmt.get(self.refs[-1]) is last,
              "same-process get copied the array")
        del last

        # re-promotion: the demoted array is read from shm, lands back on
        # the device and is pinned again (which demotes the next victim)
        back = rmt.get(self.refs[0])
        check(bool(jnp.array_equal(
            back, seeded_array(self.seed, 0, n_elems))),
            "demoted array came back with other values")
        check(rmt.get(self.refs[0]) is back,
              "re-promoted array is not pinned again")
        del back
        out["after_repromotion"] = self._counters()
        check(out["after_repromotion"]["demotions"]
              > out["after_puts"]["demotions"],
              "re-pinning array 0 past the budget demoted nothing")

        # donation: the last reader takes the buffer out of the store and
        # a donating jit consumes it
        taken = rmt.get(self.refs[-2], consume=True)
        step = jax.jit(lambda v: v * 2.0 + 1.0, donate_argnums=(0,))
        result = step(taken)
        check(taken.is_deleted(), "the consumed buffer was not donated")
        check(bool(jnp.array_equal(
            result,
            seeded_array(self.seed, n_arrays - 2, n_elems) * 2.0 + 1.0)),
            "donated computation gave other values")
        del result, taken
        out["after_donation"] = self._counters()
        out["compile"] = self.compiles.snapshot()
        return out

    def ref(self, index: int):
        return self.refs[index]


def device_plane_phase(rmt, *, expect_platform: str, n_arrays: int,
                       array_bytes: int, capacity_bytes: int, seed: int,
                       timeout_s: float) -> dict:
    """``put(device=True)`` past the tier's budget in a leased actor;
    then this (other) process reads one array through the shm tier."""
    import numpy as np

    n_elems = array_bytes // 4
    store_bytes = (n_arrays + 2) * array_bytes
    free = os.statvfs("/dev/shm")
    check(free.f_bavail * free.f_frsize > store_bytes,
          f"/dev/shm has {free.f_bavail * free.f_frsize} bytes free; the "
          f"demotions need a {store_bytes}-byte object store")
    # workers read their config from RMT_<flag>; this one is the tier's
    # budget in the leased worker
    os.environ["RMT_device_store_capacity_bytes"] = str(capacity_bytes)
    rmt.init(num_cpus=2, num_tpus=1, object_store_memory=store_bytes)
    try:
        t0 = time.monotonic()
        actor = rmt.remote(DevicePlane).options(num_tpus=1).remote(
            expect_platform, seed)
        out = rmt.get(actor.run.remote(n_arrays, n_elems), timeout=timeout_s)
        # cross-process: the owner materialises the array to shm, this
        # process rebuilds it on its own (CPU) backend
        index = n_arrays - 1
        got = np.asarray(rmt.get(rmt.get(actor.ref.remote(index)),
                                 timeout=timeout_s))
        want = np.asarray(seeded_array(seed, index, n_elems))
        check(got.dtype == want.dtype and got.shape == want.shape
              and np.array_equal(got, want),
              "cross-process read differs from the seeded values")
        out["cross_process_read"] = {"bytes": int(got.nbytes),
                                     "value_exact": True}
        out["seconds"] = round(time.monotonic() - t0, 1)
        rmt.kill(actor)
    finally:
        rmt.shutdown()
        del os.environ["RMT_device_store_capacity_bytes"]
    say("device_plane", array_bytes=array_bytes, n_arrays=n_arrays,
        capacity_bytes=capacity_bytes, **out)
    return out


# --------------------------------------------------------------- phase 2: train
def _train_setup(config: dict, devices, axes: dict, strategy: str):
    """Model, optimizer state and the jitted step on a mesh of ``devices``;
    shared by the one-chip loop, the four-chip loop and the one-chip run
    the four-chip loop is compared with."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_memory_management_tpu.models import gpt
    from ray_memory_management_tpu.parallel import (
        make_mesh, make_train_step, param_pspecs, shard_pytree,
    )

    preset = gpt.PRESETS[config["preset"]]
    # unrolled layer loop: at gpt2-small B=16 S=1024 the scanned loop's
    # step needs 16.75 GiB by the compiler's memory analysis, the
    # unrolled one 10.98 GiB (described v5e compile, PR 21)
    cfg = dataclasses.replace(
        preset, attention=config["attention"], max_seq=config["seq"],
        scan_unroll=preset.n_layers)
    mesh = make_mesh(axes, devices=devices)
    key = jax.random.PRNGKey(config["seed"])
    params = gpt.init_params(key, cfg)
    params = shard_pytree(params, mesh, param_pspecs(params, mesh, strategy))
    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.fold_in(key, 1),
                                (config["batch"], config["seq"]), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    step = make_train_step(
        lambda p, b: gpt.loss_fn(p, b, cfg, mesh=mesh), opt, mesh)
    return cfg, mesh, params, opt_state, batch, step


def train_loop(config: dict) -> None:
    """One chip: ``steps`` steps of fwd+bwd+AdamW on a fixed seeded batch,
    each reported through ``session.report``."""
    import jax

    from ray_memory_management_tpu.models import gpt
    from ray_memory_management_tpu.train import session
    from ray_memory_management_tpu.utils.compile_cache import CompileCounter

    compiles = CompileCounter()
    device = require_platform(jax, config["expect_platform"])
    cfg, mesh, params, opt_state, batch, step = _train_setup(
        config, jax.devices()[:1], {"dp": 1}, "dp")

    # the same first step's loss under the jnp reference, on this chip
    ref_cfg = dataclasses.replace(cfg, attention="ref")
    loss_ref = float(jax.jit(
        lambda p, b: gpt.loss_fn(p, b, ref_cfg))(params, batch))
    kernel_calls = step.lower(params, opt_state, batch).as_text().count(
        "tpu_custom_call")

    seconds = []
    for i in range(config["steps"]):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        loss = float(loss)  # the readback is the completion barrier
        seconds.append(time.perf_counter() - t0)
        session.report({"step": i, "loss": loss})
    steady = sorted(seconds[1:])[len(seconds[1:]) // 2]
    session.report({
        "summary": True, "device": device, "loss_ref": loss_ref,
        "mosaic_custom_calls": kernel_calls,
        "first_step_s_with_compile": round(seconds[0], 2),
        "step_ms_median": round(steady * 1e3, 2),
        "tokens_per_s": round(config["batch"] * config["seq"] / steady, 1),
        "n_params": gpt.count_params(params),
        "compile": compiles.snapshot(),
        "device_bytes_in_use":
            (jax.devices()[0].memory_stats() or {}).get("bytes_in_use"),
    })


def _fit(rmt, loop, config: dict, chips: int, timeout_s: float) -> list:
    """Run ``loop`` in one ``JaxTrainer`` worker that leases ``chips``
    chips; returns everything it reported."""
    from ray_memory_management_tpu.train import (
        JaxTrainer, RunConfig, ScalingConfig,
    )

    if config["expect_platform"] == "tpu":
        scaling = ScalingConfig(num_workers=1, use_tpu=True,
                                chips_per_worker=chips)
    else:
        # a rehearsal off the chip: the same lease, without use_tpu, whose
        # workers refuse any backend but the TPU before the loop starts
        scaling = ScalingConfig(num_workers=1,
                                resources_per_worker={"TPU": chips})
    rmt.init(num_cpus=2, num_tpus=chips)
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as run_dir:
            trainer = JaxTrainer(
                loop, train_loop_config=config, scaling_config=scaling,
                run_config=RunConfig(name="chip_smoke",
                                     storage_path=run_dir))
            done = {}
            worker = threading.Thread(
                target=lambda: done.update(result=trainer.fit()),
                daemon=True)
            worker.start()
            worker.join(timeout_s)
            check(not worker.is_alive(),
                  f"trainer.fit() still running after {timeout_s} s")
            result = done["result"]
    finally:
        rmt.shutdown()
    if result.error is not None:
        raise result.error
    return result.metrics_history


def _check_losses(losses: list, what: str) -> None:
    check(all(math.isfinite(x) for x in losses),
          f"{what}: non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"{what}: loss did not fall: {losses}")


def train_phase(rmt, *, expect_platform: str, preset: str, batch: int,
                seq: int, steps: int, attention: str, seed: int,
                timeout_s: float) -> dict:
    history = _fit(rmt, train_loop, {
        "expect_platform": expect_platform, "preset": preset,
        "batch": batch, "seq": seq, "steps": steps,
        "attention": attention, "seed": seed}, 1, timeout_s)
    losses = [m["loss"] for m in history if "loss" in m]
    summary = next(m for m in history if m.get("summary"))
    check(len(losses) == steps, f"{len(losses)} of {steps} steps reported")
    _check_losses(losses, "train")
    # bf16 activations: the kernel and the reference round differently
    check(abs(losses[0] - summary["loss_ref"])
          <= 2e-2 * abs(summary["loss_ref"]),
          f"first loss {losses[0]} vs attention='ref' "
          f"{summary['loss_ref']}")
    if attention == "flash":
        check(summary["mosaic_custom_calls"] > 0,
              "the lowered step holds no Mosaic custom call: the kernel "
              "did not run")
    out = {"losses": losses, **{k: v for k, v in summary.items()
                               if k != "summary"}}
    say("train", preset=preset, batch=batch, seq=seq, attention=attention,
        reading="smoke, not a benchmark", **out)
    return out


# --------------------------------------------------------------- phase 3: serve
def serve_phase(rmt, *, expect_platform: str, preset: str, prompt_len: int,
                budgets: list, max_new_tokens: int, seed: int,
                timeout_s: float) -> dict:
    """The default engine (continuous batching over paged KV) behind
    ``serve.run``, its replica on a ``num_tpus=1`` lease: all requests
    arrive together, then the first prompt is decoded again alone."""
    import numpy as np

    from ray_memory_management_tpu import serve
    from ray_memory_management_tpu.models import gpt
    from ray_memory_management_tpu.serve.llm import llm_deployment

    vocab = gpt.PRESETS[preset].vocab_size
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, vocab, prompt_len).tolist() for _ in budgets]
    rmt.init(num_cpus=4, num_tpus=1)
    try:
        t0 = time.monotonic()
        serve.start(http_port=None)
        handle = serve.run(llm_deployment(
            preset, ray_actor_options={"num_tpus": 1},
            max_new_tokens=max_new_tokens, max_batch_size=len(budgets)))
        cold = rmt.get(handle.stats.remote(), timeout=timeout_s)
        require = cold["device"]["platform"]
        check(require == expect_platform,
              f"the replica computes on platform {require!r} "
              f"({cold['device']['kind']}), not {expect_platform!r}")
        ready_s = time.monotonic() - t0

        results = [None] * len(budgets)
        errors = []

        def one(i: int) -> None:
            try:
                results[i] = rmt.get(handle.remote(
                    {"tokens": prompts[i], "max_new_tokens": budgets[i]}),
                    timeout=timeout_s)["tokens"]
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        t1 = time.monotonic()
        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(len(budgets))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout_s)
        if errors:
            raise errors[0]
        together_s = time.monotonic() - t1
        check(all(r is not None for r in results),
              "a request never returned")
        got = [len(r) for r in results]
        check(got == list(budgets),
              f"token counts {got} are not the budgets {list(budgets)}")
        warm = rmt.get(handle.stats.remote(), timeout=timeout_s)

        solo = rmt.get(handle.remote(
            {"tokens": prompts[0], "max_new_tokens": budgets[0]}),
            timeout=timeout_s)["tokens"]
        check(solo == results[0],
              "one prompt decoded alone and inside the batch gave "
              "different token ids")
        final = rmt.get(handle.stats.remote(), timeout=timeout_s)
        kv = final["kv"]
        check(kv["peak_store_bytes"] > 0,
              f"the KV pool was never allocated on the device: {kv}")
        out = {
            "device": cold["device"], "requests": len(budgets),
            "budgets": list(budgets), "solo_equals_batched": True,
            "replica_ready_s": round(ready_s, 1),
            "requests_together_s": round(together_s, 1),
            "compilations_by_the_requests":
                warm["compile"]["programs"] - cold["compile"]["programs"],
            "compilations_by_the_solo_repeat":
                final["compile"]["programs"] - warm["compile"]["programs"],
            "compile": final["compile"],
            "kv_peak_pinned_bytes": kv["peak_store_bytes"],
            "kv_page_bytes": kv["page_bytes"],
            "decode_steps": final["batches"],
        }
        serve.shutdown()
    finally:
        rmt.shutdown()
    say("serve", preset=preset, prompt_len=prompt_len,
        reading="smoke, not a benchmark", **out)
    return out


# ------------------------------------------------------------- four-chip path
def sharded_train_loop(config: dict) -> None:
    """One process, four chips: a dp=2 x tp=2 mesh, the flash kernel inside
    the tp-sharded jit, two steps; then the same two steps on one of the
    chips, and one allreduce over all four."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_memory_management_tpu.collective.mesh_group import (
        MeshCollectives,
    )
    from ray_memory_management_tpu.train import session

    device = require_platform(jax, config["expect_platform"])
    devices = jax.devices()
    check(len(devices) == 4, f"the lease shows {len(devices)} devices")

    def run(devs, axes, strategy):
        cfg, mesh, params, opt_state, batch, step = _train_setup(
            config, devs, axes, strategy)
        leaves = jax.tree.leaves(params)
        spread = min(len(x.sharding.device_set) for x in leaves)
        n_split = sum(not x.sharding.is_fully_replicated for x in leaves)
        in_use = None
        losses = []
        for _ in range(config["steps"]):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
            if in_use is None:
                in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                          for d in devices]
        calls = step.lower(params, opt_state, batch).as_text().count(
            "tpu_custom_call")
        return losses, spread, n_split, in_use, calls

    sharded, spread, n_split, in_use, calls = run(
        devices, {"dp": 2, "tp": 2}, "tp")
    single, _, _, _, _ = run(devices[:1], {"dp": 1}, "dp")

    group = MeshCollectives(devices)
    x = np.arange(8, dtype=np.float32) + 1.0
    reduced = np.asarray(group.allreduce(
        group.shard_ranks(jnp.tile(x, (4, 1)))))
    session.report({
        "summary": True, "device": device, "losses_sharded": sharded,
        "losses_one_chip": single, "param_min_device_set": spread,
        "params_split_over_devices": n_split,
        "bytes_in_use_per_device": in_use, "mosaic_custom_calls": calls,
        "allreduce_exact": bool(np.array_equal(reduced,
                                               np.tile(4.0 * x, (4, 1)))),
    })


def sharded_train_phase(rmt, *, expect_platform: str, preset: str,
                        batch: int, seq: int, steps: int, attention: str,
                        seed: int, timeout_s: float) -> dict:
    history = _fit(rmt, sharded_train_loop, {
        "expect_platform": expect_platform, "preset": preset,
        "batch": batch, "seq": seq, "steps": steps,
        "attention": attention, "seed": seed}, 4, timeout_s)
    out = next(m for m in history if m.get("summary"))
    out = {k: v for k, v in out.items() if k != "summary"}
    _check_losses(out["losses_sharded"], "sharded train")
    for a, b in zip(out["losses_sharded"], out["losses_one_chip"]):
        check(abs(a - b) <= 2e-2 * abs(b),
              f"sharded losses {out['losses_sharded']} vs one chip "
              f"{out['losses_one_chip']}")
    check(out["param_min_device_set"] == 4,
          "a parameter lives on fewer than four devices")
    check(out["params_split_over_devices"] > 0, "no parameter is split")
    if expect_platform == "tpu":  # the CPU backend reports no memory stats
        check(all(b for b in out["bytes_in_use_per_device"]),
              f"a device holds nothing: {out['bytes_in_use_per_device']}")
    check(out["allreduce_exact"], "allreduce did not return n x the input")
    if attention == "flash":
        check(out["mosaic_custom_calls"] > 0,
              "the sharded step holds no Mosaic custom call")
    say("sharded_train", preset=preset, batch=batch, seq=seq,
        attention=attention, mesh="dp=2 x tp=2", **out)
    return out


# ------------------------------------------------------------------------ main
def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    def out_of_time(signum, frame):
        raise TimeoutError(f"chip_smoke passed its {DEADLINE_S} s deadline")

    signal.signal(signal.SIGALRM, out_of_time)
    signal.alarm(DEADLINE_S)
    # the phases share small programs (PRNG set-up, parameter init); with
    # no compile-time floor they are cached too, which is what lets a later
    # phase show hits on entries an earlier one wrote
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    try:
        pin_driver_to_cpu()
        import ray_memory_management_tpu as rmt
        from ray_memory_management_tpu.utils import compile_cache

        placed_from_outside = compile_cache.ENV_VAR in os.environ
        say("start", chips=args.chips, seed=args.seed,
            compile_cache_dir=compile_cache.adopt(),
            compile_cache_dir_from_environment=placed_from_outside)
        common = dict(expect_platform="tpu", seed=args.seed)
        if args.chips == 4:
            last = sharded_train_phase(
                rmt, preset="gpt2-small", batch=16, seq=1024, steps=2,
                attention="flash", timeout_s=900, **common)
        else:
            first = device_plane_phase(
                rmt, n_arrays=4, array_bytes=GIB,
                capacity_bytes=3 * GIB + GIB // 2, timeout_s=300, **common)
            second = train_phase(
                rmt, preset="gpt2-small", batch=16, seq=1024, steps=5,
                attention="flash", timeout_s=600, **common)
            last = serve_phase(
                rmt, preset="gpt2-small", prompt_len=128,
                budgets=[64, 16] * 4, max_new_tokens=64, timeout_s=420,
                **common)
            say("compile_cache",
                device_plane_wrote=first["compile"]["cache_writes"],
                train_hit=second["compile"]["cache_hits"],
                train_wrote=second["compile"]["cache_writes"],
                serve_hit=last["compile"]["cache_hits"],
                later_phase_hit_earlier_entries=(
                    second["compile"]["cache_hits"] > 0
                    or last["compile"]["cache_hits"] > 0))
            for other in (first, second):
                check(other["device"] == last["device"],
                      "the phases saw different devices")
        device = last["device"]
        check(device["platform"] == "tpu" and device["count"] == args.chips,
              f"ran on {device}, wanted {args.chips} tpu device(s)")
    except BaseException as e:  # noqa: BLE001 — the verdict line names it
        import traceback

        traceback.print_exc()
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1
    finally:
        signal.alarm(0)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
