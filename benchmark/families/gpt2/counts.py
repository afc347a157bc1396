"""What a GPT-2 configuration file's sizes need, from shapes alone: the
family's half of the yardstick's arithmetic (`benchmark/counts.py` has the
conventions and the half that no family owns). The program's own
`utils/flops.transformer_flops_per_token` keeps the full 4*L*s*d attention
term for a causal model and takes 2*N over every parameter, so an MFU from
it can read high."""

from __future__ import annotations


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


def attention_shape(cfg: dict) -> tuple[int, int]:
    """(heads, head_dim) of one attention call: what the flash kernels'
    rooflines are reckoned at."""
    _, d, _, heads, _ = dims(cfg)
    return heads, d // heads


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of K and V that ONE layer holds for one cached token."""
    _, d, *_ = dims(cfg)
    return 2.0 * d * itemsize


def cache_layers(cfg: dict) -> int:
    """Layers that keep K and V in the paged cache: every one."""
    return dims(cfg)[0]
