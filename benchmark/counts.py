"""Operations and bytes the algorithm needs, from shapes, and the chip's
published peaks. The yardstick's arithmetic lives here, not in the program:
`utils/flops.transformer_flops_per_token` keeps the full 4*L*s*d attention
term for a causal model and takes 2*N over every parameter, so an MFU from
it can read high.

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


def dims(cfg: dict) -> tuple[int, int, int, int, int]:
    """(layers, d_model, d_ff, heads, vocab as run) of a GPT-2 config file."""
    d = cfg["n_embd"]
    return (cfg["n_layer"], d, cfg.get("n_inner") or 4 * d, cfg["n_head"],
            cfg["vocab_size"])


def matmul_params(cfg: dict, head: bool = True) -> int:
    """Parameters that multiply every token: q, k, v, out, the two MLP
    matrices of each layer, and (``head``) the tied vocab projection.
    Embedding look-ups, biases and LayerNorms do no matmul work."""
    L, d, f, _, V = dims(cfg)
    return L * (4 * d * d + 2 * d * f) + (V * d if head else 0)


def param_count(cfg: dict) -> int:
    L, d, f, _, V = dims(cfg)
    per_layer = 4 * d * d + 4 * d + 2 * d * f + f + d + 4 * d
    return V * d + cfg["n_positions"] * d + L * per_layer + 2 * d + V


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Needed forward+backward FLOPs per trained token of a causal LM:
    6 per matmul parameter, plus attention: forward QK^T and PV are
    2*2*s*d per token and layer in full, half of it causal; times 3."""
    L, d, *_ = dims(cfg)
    attn_fwd = L * 2 * seq_len * d
    return 6.0 * matmul_params(cfg) + 3.0 * attn_fwd


def forward_flops(cfg: dict, n_tokens: int, ctx_sum: int,
                  n_logits: int) -> float:
    """Needed forward FLOPs of serving ``n_tokens`` positions whose attended
    context lengths add up to ``ctx_sum`` and of which ``n_logits`` feed a
    sampled token (only those need the vocab projection)."""
    L, d, _, _, V = dims(cfg)
    return (2.0 * matmul_params(cfg, head=False) * n_tokens
            + 4.0 * L * d * ctx_sum + 2.0 * V * d * n_logits)


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


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of K and V that ONE layer holds for one cached token."""
    _, d, *_ = dims(cfg)
    return 2.0 * d * itemsize


def paged_attention(cfg: dict, ctx_lens, q_tokens: int,
                    itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one layer's paged-attention call needs for slots whose
    live contexts are ``ctx_lens``: the K and V of those contexts read once
    (q and the output are small beside them), and 4*d FLOPs per attended
    position and query token. Memory-bound at decode: a few FLOPs a byte."""
    _, d, *_ = dims(cfg)
    ctx = float(sum(ctx_lens))
    return 4.0 * d * ctx * q_tokens, kv_bytes_per_token(cfg, itemsize) * ctx


def roofline_seconds(flops: float, byts: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s."""
    p = peaks(device_kind)
    return max(flops / p["flops"], byts / p["hbm_bytes_per_s"])
