"""What an Olmo-Hybrid configuration file's sizes need, from shapes alone:
the family's half of the yardstick's arithmetic (`benchmark/counts.py` has
the conventions). Two kinds of layer: a full-attention layer costs its
projections a token and 4 x width FLOPs an attended key; a linear-attention
(gated delta rule) layer costs its projections and a fixed amount of work a
token whatever the context, and keeps no K or V."""

from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"


def dims(cfg: dict) -> dict:
    H = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kinds = list(cfg["layer_types"])
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "H": H, "dk": dk, "dv": dv,
            "K": H * dk, "U": H * dv, "taps": cfg["linear_conv_kernel_dim"],
            "heads": cfg["num_attention_heads"],
            "n_linear": kinds.count(LINEAR), "n_full": kinds.count(FULL)}


def layer_matmul_params(cfg: dict) -> tuple[int, int]:
    """Parameters that multiply every token in one (linear, full) layer:
    the mixer's projections and the gated MLP's three matrices. The per-head
    gates' projections count (they are matmuls); norm scales, convolution
    taps and gate offsets do no matmul work."""
    z = dims(cfg)
    mlp = 3 * z["d"] * z["f"]
    linear = (z["d"] * (2 * z["K"] + 2 * z["U"] + 2 * z["H"])
              + z["U"] * z["d"] + mlp)
    full = 4 * z["d"] * z["d"] + mlp
    return linear, full


def param_count(cfg: dict) -> int:
    z = dims(cfg)
    linear, full = layer_matmul_params(cfg)
    linear += (z["taps"] * (2 * z["K"] + z["U"]) + 2 * z["H"] + z["dv"]
               + 2 * z["d"])
    full += 4 * z["d"]
    return (z["n_linear"] * linear + z["n_full"] * full
            + 2 * z["V"] * z["d"] + z["d"])


def gated_delta_work(cfg: dict, tokens: int, calls: int, slots_per_call: int,
                     itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) ONE linear layer's gated-delta-rule calls need for
    ``tokens`` tokens in ``calls`` calls that each touch the state of
    ``slots_per_call`` slots: 6 dk dv FLOPs a token and head (the three
    products S k, the rank-one update, S q); q, k, v read and o written
    once in ``itemsize`` bytes; the float32 state read and written once a
    call and slot. The same whatever implements the kernel."""
    z = dims(cfg)
    flops = 6.0 * z["dk"] * z["dv"] * z["H"] * tokens
    io = (2 * z["K"] + 2 * z["U"]) * itemsize * tokens
    state = 2.0 * 4 * z["H"] * z["dk"] * z["dv"] * calls * slots_per_call
    return flops, io + state


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    raise ValueError("the olmo_hybrid family has no training cell")


def forward_flops(cfg: dict, n_tokens: int, ctx_sum: int,
                  n_logits: int) -> float:
    """Needed forward FLOPs of serving ``n_tokens`` positions whose attended
    context lengths add up to ``ctx_sum`` and of which ``n_logits`` feed a
    sampled token: every layer's projections and the linear layers' rule a
    token, the full layers' attention an attended key, the head a sampled
    token."""
    z = dims(cfg)
    linear, full = layer_matmul_params(cfg)
    rule, _ = gated_delta_work(cfg, 1, 0, 0)
    return (n_tokens * (z["n_linear"] * (2.0 * linear + rule)
                        + z["n_full"] * 2.0 * full)
            + 4.0 * z["n_full"] * z["d"] * ctx_sum
            + 2.0 * z["V"] * z["d"] * n_logits)


def attention_shape(cfg: dict) -> tuple[int, int]:
    """(heads, head_dim) of a full-attention layer's call."""
    z = dims(cfg)
    return z["heads"], z["d"] // z["heads"]


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of K and V that ONE full-attention layer holds for one cached
    token."""
    return 2.0 * cfg["hidden_size"] * itemsize


def cache_layers(cfg: dict) -> int:
    """Layers that keep K and V in the paged cache: the full-attention ones
    only."""
    return dims(cfg)["n_full"]
