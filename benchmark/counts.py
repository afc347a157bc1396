"""Operations and bytes the algorithm needs, from shapes, and the chip's
published peaks: the part of the yardstick's arithmetic that no model
family owns. What follows from a configuration file's sizes (parameters,
FLOPs of a trained token and of a served forward, the cache's widths) is
the family's: `families/<model_type>/counts.py`. None of it is the
program's.

Conventions: a multiply-add is 2 FLOPs; a causal attention needs half of
the s*s score matrix; recomputed work (the flash backward's score
recompute, remat) does not count; backward is twice forward.
"""

from __future__ import annotations

#: per chip, keyed by `jax.devices()[0].device_kind`. Source: Google Cloud
#: documentation, "TPU v5e" system architecture table (197 TFLOP/s bf16,
#: 819 GB/s HBM bandwidth, 16 GB HBM). A kind that is not here is an error.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            f"to benchmark/counts.py:PEAKS with its source")
    return PEAKS[device_kind]


def flash_fwd(batch: int, heads: int, seq: int, head_dim: int,
              itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one causal flash-attention forward call needs: two
    matmuls over the lower triangle; q, k, v read and o written once."""
    flops = 2 * 2.0 * batch * heads * seq * seq * head_dim / 2
    byts = 4.0 * batch * heads * seq * head_dim * itemsize
    return flops, byts


def flash_bwd(batch: int, heads: int, seq: int, head_dim: int,
              itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of the backward (dKV and dQ kernels together): the
    four needed matmuls dV=P^T dO, dP=dO V^T, dQ=dS K, dK=dS^T Q over the
    lower triangle; the recompute of the scores in either kernel does not
    count. q, k, v, o, dO read once and dq, dk, dv written once."""
    flops = 4 * 2.0 * batch * heads * seq * seq * head_dim / 2
    byts = 8.0 * batch * heads * seq * head_dim * itemsize
    return flops, byts


def paged_attention(q_width: int, kv_bytes_per_token: float, attended: int,
                    context_read: int) -> tuple[float, float]:
    """(FLOPs, bytes) ONE layer's paged-attention calls need: the K and V of
    the live contexts read once a call (``context_read`` cached tokens in
    all, ``kv_bytes_per_token`` each; q and the output are small beside
    them), and 4 FLOPs a unit of ``q_width`` (heads x head_dim) for each of
    the ``attended`` pairs of a query token and a key it attends.
    Memory-bound at decode: a few FLOPs a byte."""
    return 4.0 * q_width * attended, kv_bytes_per_token * context_read


def roofline_seconds(flops: float, byts: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s."""
    p = peaks(device_kind)
    return max(flops / p["flops"], byts / p["hbm_bytes_per_s"])
