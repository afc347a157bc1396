"""What a GigaChat 3.5 configuration file's sizes need, from shapes alone:
the family's half of the yardstick's arithmetic (`benchmark/counts.py` has
the conventions). Layers of two mixers (latent attention, gated delta rule)
and two feed-forwards (dense, experts). A latent-attention layer costs its
projections a token and, absorbed, 2 x heads x (latent row + latent) FLOPs
an attended key; a linear layer its projections and a fixed amount of work a
token. The routed experts' work is the run's: `moe_work` takes the counts
of local assignments and of expert calls that the program counted, and
nothing here assumes how the router spreads tokens."""

from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"


def dims(cfg: dict) -> dict:
    n = cfg["num_hidden_layers"]
    full = set(cfg["full_attention_layers"])
    H = cfg["num_attention_heads"]
    Hv, Hk = cfg["linear_num_value_heads"], cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    n_dense = cfg["first_k_dense_replace"]
    return {"d": cfg["hidden_size"], "F": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"], "V": cfg["vocab_size"],
            "E": cfg["n_routed_experts"],
            "X": cfg["expert_share"]["router_experts"],
            "heads": H, "Q": cfg["q_lora_rank"], "R": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dvh": cfg["v_head_dim"], "H": Hv, "Hk": Hk, "dk": dk, "dv": dv,
            "K": Hk * dk, "U": Hv * dv, "taps": cfg["linear_conv_kernel_dim"],
            "n_full": len(full), "n_linear": n - len(full),
            "n_dense": n_dense, "n_moe": n - n_dense}


def mixer_matmul_params(cfg: dict) -> tuple[int, int]:
    """Parameters that multiply every token in one (linear, latent) mixer.
    The latent layer's are counted as the expanded form has them (W_ukv
    multiplies each token's latent once): the absorbed form's per-key work
    is `latent_attention_work`'s."""
    z = dims(cfg)
    linear = (z["d"] * (2 * z["K"] + 2 * z["U"] + 2 * z["H"])
              + z["U"] * z["d"])
    latent = (z["d"] * z["Q"] + z["Q"] * z["heads"] * (z["dn"] + z["dr"])
              + z["d"] * (z["R"] + z["dr"])
              + z["R"] * z["heads"] * (z["dn"] + z["dvh"])
              + 2 * z["d"] * z["heads"] * z["dvh"])
    return linear, latent


def expert_params(cfg: dict) -> int:
    """One expert's (routed or shared) SwiGLU matrices."""
    z = dims(cfg)
    return 3 * z["d"] * z["f"]


def param_count(cfg: dict) -> int:
    """Every parameter of the cut: mixers with their norms, gates and taps,
    the dense layers' SwiGLU, the expert layers' router, correction bias,
    shared expert and held experts, embedding, head and final norm."""
    z = dims(cfg)
    linear, latent = mixer_matmul_params(cfg)
    linear += (z["taps"] * (2 * z["K"] + z["U"]) + 2 * z["H"] + z["dv"]
               + 2 * z["d"])
    latent += z["Q"] + z["R"] + 2 * z["d"]
    dense = 3 * z["d"] * z["F"] + 2 * z["d"]
    moe = (z["d"] * z["X"] + z["X"] + (z["E"] + 1) * expert_params(cfg)
           + 2 * z["d"])
    return (z["n_linear"] * linear + z["n_full"] * latent
            + z["n_dense"] * dense + z["n_moe"] * moe
            + 2 * z["V"] * z["d"] + z["d"])


def gated_delta_work(cfg: dict, tokens: int, calls: int, slots_per_call: int,
                     itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) ONE linear layer's gated-delta-rule calls need for
    ``tokens`` tokens in ``calls`` calls that each touch the state of
    ``slots_per_call`` slots: 6 dk dv FLOPs a token and VALUE head; q and k
    (one a value head: the kernels take them so), v read and o written once
    in ``itemsize`` bytes; the float32 state read and written once a call
    and slot."""
    z = dims(cfg)
    flops = 6.0 * z["dk"] * z["dv"] * z["H"] * tokens
    io = (2 * z["H"] * z["dk"] + 2 * z["U"]) * itemsize * tokens
    state = 2.0 * 4 * z["H"] * z["dk"] * z["dv"] * calls * slots_per_call
    return flops, io + state


def latent_attention_work(cfg: dict, q_tokens: int, attended: int,
                          context_read: int) -> tuple[float, float]:
    """(FLOPs, bytes) ONE latent-attention layer's calls need, absorbed:
    for each of the ``attended`` pairs of a query token and a cached key,
    every head's score over the latent row (latent + rotary lanes) and its
    weighted latent (2 FLOPs a multiply-add each); the cached rows of the
    live contexts read once a call (``context_read`` in all, bfloat16), the
    query rows (bfloat16) and the float32 result of each query token once."""
    z = dims(cfg)
    row, latent = z["R"] + z["dr"], z["R"]
    flops = 2.0 * z["heads"] * (row + latent) * attended
    byts = (2.0 * row * context_read
            + q_tokens * z["heads"] * (2.0 * row + 4.0 * latent))
    return flops, byts


def moe_work(cfg: dict, assignments: int,
             experts_called: int) -> tuple[float, float]:
    """(FLOPs, bytes) the routed experts' grouped matmuls need for
    ``assignments`` local token-to-expert assignments and ``experts_called``
    calls of a held expert that received any (both summed over the expert
    layers, as the program counts them): the three SwiGLU products of each
    assignment; each called expert's weights read once (bfloat16), each
    assignment's row in (bfloat16), its SwiGLU output written and read
    (bfloat16) and its result written (float32)."""
    z = dims(cfg)
    flops = 2.0 * expert_params(cfg) * assignments
    byts = (2.0 * expert_params(cfg) * experts_called
            + assignments * (2.0 * z["d"] + 4.0 * z["f"] + 4.0 * z["d"]))
    return flops, byts


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    raise ValueError("the gigachat3_5 family has no training cell")


def forward_flops(cfg: dict, n_tokens: int, ctx_sum: int,
                  n_logits: int) -> float:
    """Needed forward FLOPs of serving ``n_tokens`` positions whose attended
    context lengths add up to ``ctx_sum`` and of which ``n_logits`` feed a
    sampled token: every layer's mixer projections, the linear layers' rule
    a token, the latent layers' absorbed attention an attended key, the
    dense layers' SwiGLU, the expert layers' router and shared expert, the
    head a sampled token. The routed experts are left out: their work is
    the run's (`moe_work`), some thousandths of the rest at this cut."""
    z = dims(cfg)
    linear, latent = mixer_matmul_params(cfg)
    rule, _ = gated_delta_work(cfg, 1, 0, 0)
    attend, _ = latent_attention_work(cfg, 0, 1, 0)
    per_token = (z["n_linear"] * (2.0 * linear + rule)
                 + z["n_full"] * 2.0 * latent
                 + z["n_dense"] * 6.0 * z["d"] * z["F"]
                 + z["n_moe"] * 2.0 * (z["d"] * z["X"] + expert_params(cfg)))
    return (n_tokens * per_token + z["n_full"] * attend * ctx_sum
            + 2.0 * z["V"] * z["d"] * n_logits)


def attention_shape(cfg: dict) -> tuple[int, int]:
    """(heads, width of a head's absorbed query row)."""
    z = dims(cfg)
    return z["heads"], z["R"] + z["dr"]


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """Bytes that ONE latent layer caches for one token: the latent and the
    rotary key, shared by every head (576 x 2 = 1152)."""
    z = dims(cfg)
    return float((z["R"] + z["dr"]) * itemsize)


def cache_layers(cfg: dict) -> int:
    """Layers that keep rows in the paged cache: the latent ones only."""
    return dims(cfg)["n_full"]
