"""TPU compute benchmarks: train-step MFU, flash-attention kernel, and
collective bus-bandwidth.

Measures the north-star rows of BASELINE.md ("match A100 DDP/NCCL") that the
reference never publishes (its release tests assert completion, not
throughput — release/release_logs/): the numbers must be measured, so this
module measures them on whatever TPU is attached.

Methodology note: every timed region (a) runs its whole loop inside ONE
jitted dispatch via ``lax.scan``/``fori_loop``, and (b) ends with a tiny
device→host readback as its completion barrier.

Who holds the chip: ``train_step_mfu``, ``flash_attention_bench``,
``allreduce_busbw`` and ``rl_learner_bench`` compute in the calling process,
which therefore holds the chip (call them from a ``num_tpus`` task, or from
a driver that starts no chip-leasing worker).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Optional

import numpy as np

# bf16 peak FLOPs/s per chip by device kind (public spec sheets)
PEAK_BF16: Dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops(device) -> float:
    """bf16 peak of ``device`` from the table. A device the table does not
    know is an error: a utilisation against a guessed peak is no number."""
    kind = device.device_kind
    for name, peak in PEAK_BF16.items():
        if kind.startswith(name):
            return peak
    raise ValueError(
        f"no bf16 peak on record for device kind {kind!r}; add it to "
        f"PEAK_BF16 with its source (known: {sorted(PEAK_BF16)})")


def _readback(x) -> float:
    """Force completion: pull one scalar to the host."""
    import jax

    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(np.asarray(leaf).ravel()[0])


def train_step_mfu(preset: str = "gpt2-small", batch_size: int = 8,
                   seq_len: int = 1024, steps: int = 8,
                   remat: bool = False,
                   bf16_params: bool = False) -> Dict[str, float]:
    """Single-chip TransformerLM train step: tokens/s and model FLOPs
    utilisation. Full fwd+bwd+AdamW, ``steps`` steps inside one dispatch.

    Tuned for the chip: params/opt-state DONATED (buffers reused in
    place), layer scan fully unrolled (drops the scan-carry
    dynamic-update-slice traffic — worth ~8% step time at gpt2-small),
    flash attention. ``bf16_params`` stores params and Adam moments in
    bf16 (with bf16 grads) — what lets a ~1B-param model + optimizer fit
    a single 16 GB chip; ``remat`` checkpoints each block for long-S
    activation memory."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    from ..models import gpt

    over = {"attention": "flash", "max_seq": seq_len, "remat": remat,
            "scan_unroll": gpt.PRESETS[preset].n_layers}
    if bf16_params:
        over["param_dtype"] = jnp.bfloat16
    cfg = dataclasses.replace(gpt.PRESETS[preset], **over)
    key = jax.random.PRNGKey(0)
    params = gpt.init_params(key, cfg)
    if bf16_params:
        opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    else:
        opt = optax.adamw(3e-4)
    opt_state = opt.init(params)
    tokens = jax.random.randint(key, (batch_size, seq_len), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(params, opt_state, batch):
        def step(carry, _):
            p, s = carry
            loss, grads = jax.value_and_grad(
                lambda p_: gpt.loss_fn(p_, batch, cfg))(p)
            updates, s = opt.update(grads, s, p)
            p = optax.apply_updates(p, updates)
            return (p, s), loss

        (p, s), losses = lax.scan(step, (params, opt_state), None,
                                  length=steps)
        return p, s, losses

    params, opt_state, losses = run(params, opt_state, batch)  # compile
    _readback(losses)
    n_params = gpt.count_params(params)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        params, opt_state, losses = run(params, opt_state, batch)
        final_loss = _readback(losses[-1:])
        best = min(best, time.perf_counter() - t0)
    dt = best

    tokens_per_s = batch_size * seq_len * steps / dt
    # PaLM-appendix accounting: 6N per token (fwd+bwd matmuls) plus causal
    # attention 6*L*S*d_model per token (12*L*S*d non-causal, halved)
    flops_per_token = 6 * n_params + 6 * cfg.n_layers * seq_len * cfg.d_model
    mfu = tokens_per_s * flops_per_token / peak_flops(jax.devices()[0])
    return {
        "tokens_per_s": tokens_per_s,
        "mfu": mfu,
        "n_params": n_params,
        "loss": final_loss,
        "step_ms": dt / steps * 1e3,
    }


def flash_attention_bench(seq_lens=(1024, 4096, 8192), bh: int = 4,
                          head_dim: int = 128,
                          iters: int = 8) -> Dict[int, Dict[str, float]]:
    """Flash kernel vs jnp reference, fwd+bwd, per sequence length.
    Returns {S: {flash_ms, ref_ms, speedup}}."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..ops.flash_attention import flash_attention, reference_attention

    out: Dict[int, Dict[str, float]] = {}
    for S in seq_lens:
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (bh, S, head_dim), jnp.bfloat16)
        k = jax.random.normal(kk, (bh, S, head_dim), jnp.bfloat16)
        v = jax.random.normal(kv, (bh, S, head_dim), jnp.bfloat16)

        def timed(attn_fn, n):
            def loss(q_, k_, v_):
                return jnp.sum(attn_fn(q_, k_, v_).astype(jnp.float32) ** 2)

            grad = jax.grad(loss, argnums=(0, 1, 2))

            @jax.jit
            def run(q, k, v):
                def body(i, carry):
                    q_, acc = carry
                    dq, dk, dv = grad(q_, k, v)
                    # data-dependence across iterations so nothing is hoisted
                    return (q_ + 1e-6 * dq.astype(q_.dtype),
                            acc + jnp.sum(dv.astype(jnp.float32)))

                return lax.fori_loop(0, n, body, (q, jnp.float32(0.0)))

            _readback(run(q, k, v)[1])  # compile + warm
            t0 = time.perf_counter()
            _readback(run(q, k, v)[1])
            return (time.perf_counter() - t0) / n * 1e3

        n_ref = max(2, iters // 4) if S >= 8192 else iters
        flash_ms = timed(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, use_pallas="on"),
            iters)
        ref_ms = timed(
            lambda q_, k_, v_: reference_attention(q_, k_, v_), n_ref)
        out[S] = {"flash_ms": flash_ms, "ref_ms": ref_ms,
                  "speedup": ref_ms / flash_ms}
    return out


def rl_learner_bench(n_workers: int = 2, iters: int = 4,
                     train_batch: int = 4096, fragment: int = 512,
                     num_sgd_iter: int = 6,
                     minibatch: int = 512) -> Dict[str, float]:
    """RL throughput with the learner ON THE CHIP: PPO through the full
    stack — CPU rollout actors sample CartPole fragments in worker
    processes, the driver-side learner runs donated-state minibatch SGD
    on the TPU (make_ppo_update donate=True: params/opt-state update in
    place in HBM). The north-star row BASELINE.md names ("RLlib
    PPO/IMPALA with TPU learner — env steps/s"); the reference's analog
    keeps learner threads off the rollout path
    (rllib/execution/multi_gpu_learner_thread.py).

    Reports overall env_steps_per_s (sample+learn, the headline),
    learner-only learner_env_steps_per_s, and learner_ms per jit'd
    minibatch update."""
    import ray_memory_management_tpu as rmt
    from ray_memory_management_tpu.rllib.ppo import PPOConfig

    rmt.init(num_cpus=max(2, n_workers))
    try:
        algo = (PPOConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 200})
                .rollouts(num_rollout_workers=n_workers,
                          rollout_fragment_length=fragment)
                .training(train_batch_size=train_batch, lr=3e-4,
                          num_sgd_iter=num_sgd_iter,
                          sgd_minibatch_size=minibatch,
                          donate_learner_state=True)
                .debugging(seed=0)
                .build())
        try:
            algo.train()  # warm: compiles the update, forks the workers
            steps = 0
            sample_s = learn_s = 0.0
            updates = 0
            t0 = time.perf_counter()
            for _ in range(iters):
                r = algo.train()
                steps += r["num_env_steps_sampled"]
                sample_s += r["sample_time_s"]
                learn_s += r["learn_time_s"]
                updates += num_sgd_iter * max(
                    1, r["num_env_steps_sampled"] // minibatch)
            dt = time.perf_counter() - t0
            return {
                "env_steps_per_s": steps / dt,
                "learner_env_steps_per_s": steps / max(learn_s, 1e-9),
                "learner_ms": learn_s / max(updates, 1) * 1e3,
                "sample_s": sample_s, "learn_s": learn_s,
                "algo": "ppo", "n_workers": n_workers,
                # episode_reward_mean is None when no episode completed
                # in the window — keep the persisted row JSON-numeric
                "final_reward": r.get("episode_reward_mean") or 0.0,
            }
        finally:
            algo.stop()
    finally:
        rmt.shutdown()


def allreduce_busbw(size_mb: int = 64,
                    iters: int = 8) -> Optional[Dict[str, float]]:
    """Bus bandwidth of a psum allreduce over all local TPU devices.
    Returns None when fewer than 2 devices are attached (a single chip has
    no interconnect to measure)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        return None
    mesh = Mesh(np.array(devs), ("x",))
    elems = size_mb * (1 << 20) // 4
    x = jnp.ones((n, elems), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("x", None)))

    @jax.jit
    def run(x):
        def body(i, y):
            f = jax.shard_map(lambda a: lax.psum(a, "x"), mesh=mesh,
                              in_specs=P("x", None),
                              out_specs=P("x", None), check_vma=False)
            return f(y) / n  # keep magnitudes bounded

        return lax.fori_loop(0, iters, body, x)

    _readback(run(x))
    t0 = time.perf_counter()
    _readback(run(x))
    dt = (time.perf_counter() - t0) / iters
    bytes_moved = size_mb * (1 << 20)
    # ring-allreduce bus bytes: 2*(n-1)/n per byte of payload
    busbw = bytes_moved * 2 * (n - 1) / n / dt
    return {"busbw_gbps": busbw / 1e9, "world": n,
            "alg_bw_gbps": bytes_moved / dt / 1e9}
