"""Operation and byte counts from shapes, and the table of peaks.

Every count here is what the algorithm needs, not what the program happens
to execute: recomputation (remat) is not counted in a model's FLOPs, and a
kernel's bytes are its operands read once and its results written once.
Configurations are the dicts of ``chipbench/configs/<name>.json``; a
model's own counts (its parameters, its forward FLOPs) are its
architecture's (``chipbench/architectures/``), and what is here is no
architecture's: the peaks, causal pairs, a train step of any forward, the
flash kernels, the roofline.
"""

from __future__ import annotations

from typing import Dict, Tuple

from chipbench import architectures

# Published peaks of one chip, keyed by jax's ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a device not in the table is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def causal_pairs(length: int, start: int = 0) -> int:
    """(query, key) pairs of positions start..length-1, each attending to
    itself and everything before it."""
    return (length * (length + 1) - start * (start + 1)) // 2


def train_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Forward plus backward of one step without recomputation: the
    backward pass costs twice the forward."""
    return 3.0 * architectures.of(cfg).forward_flops(
        cfg, batch * seq, batch * causal_pairs(seq))


# ---------------------------------------------------------------- flash kernels
# matmuls of one (block_q, block_k) tile, in units of 2*bq*bk*head_dim FLOPs:
# fwd: QK^T, PV; dq: QK^T, dO V^T, dS K; dkv: QK^T, dO V^T, P^T dO, dS^T Q
_KERNEL_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call(kind: str, batch_heads: int, seq: int, head_dim: int,
               itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) one causal call of the ``fwd``, ``dq`` or ``dkv``
    kernel needs over [batch_heads, seq, head_dim] operands: causal
    attention needs half of the seq x seq tile work; operands are read
    once and results written once (q, k, v, o / do, lse, delta)."""
    pairs = seq * (seq + 1) / 2.0
    flops = _KERNEL_MATMULS[kind] * 2.0 * pairs * head_dim * batch_heads
    tensor = batch_heads * seq * head_dim * itemsize
    vector = batch_heads * seq * 4  # lse / delta, f32
    n_tensors = {"fwd": 4, "dq": 5, "dkv": 6}[kind]
    n_vectors = {"fwd": 1, "dq": 2, "dkv": 2}[kind]
    return flops, float(n_tensors * tensor + n_vectors * vector)


def roofline_seconds(flops: float, nbytes: float,
                     device_kind: str) -> Tuple[float, str]:
    """The least time the chip could take, and which bound holds."""
    pk = peak(device_kind)
    t_flops = flops / pk["bf16_flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
