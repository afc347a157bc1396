"""Plain GigaChat 3.5 (ai-sage/GigaChat3.5-432B-A28B `config.json`), the
benchmark's share of it (the configuration file's `reduced`, `published`
and `expert_share`), in float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: no kernel, no cache, no
batching, the full forward over every position, and nothing imported from
the program. It decides `correct`, so it follows the equations as the
configuration's `assumed` writes them down:

Norms: N(x) = x / sqrt(mean(x^2) + eps) * (1 + w) everywhere (zero-centred).
Block: h = x + N2(mix(N1 x)); out = h + N4(ffn(N3 h)); a final N before the
untied head.
MLA layer (expanded, as trained): c_q = N(x W_dq); q_h = c_q W_uq_h =
[q_nope_h || q_r_h] (128 || 64); [c_kv || k_r] = x W_dkv, c_kv = N(c_kv),
k_r = RoPE(k_r) (one head for all); k_h = [c_kv W_uk_h || k_r], v_h = c_kv
W_uv_h; q_r_h = RoPE(q_r_h); scores q_h.k_h * 192^-1/2 * m^2, m = 0.1 ln 8
+ 1, causal softmax; o = [o_h] * 2 sigmoid(x W_g), then W_o. RoPE on
interleaved pairs, theta 1e5, YaRN inverse frequencies (factor 8, beta_fast
32, beta_slow 1, original 32768).
Linear layer (Gated DeltaNet): q~, k~, v~ = x W_q, x W_k, x W_v through a
causal depth-wise convolution of 4 taps, then SiLU; q, k of unit length by
q/k head (32), q / sqrt(128); q/k head j serves value heads 2j and 2j + 1;
beta = sigmoid(x W_b); g = -exp(A_log) softplus(x W_a + dt_bias); S_t =
e^g S + beta (v - e^g S k) k^T, o = S q, a plain scan over tokens; y =
W_o [N(o_h) * 2 sigmoid(x W_z)_h] (one norm weight of 128 for every head).
SwiGLU (dense, shared, routed): W_down(silu(min(x W_gate, 10)) *
clip(x W_up, -10, 10)).
Expert layer: s = sigmoid(x W_r) over all 256 experts (float32); the top 8
by s + b; w = s_top / sum(s_top) * 2.5; y = shared(x) + sum over the chosen
experts that this share holds of w_e E_e(x): expert by expert, each over
the tokens that chose it.

The weights are the model's own bfloat16 weights (4.7 B of them would not
fit a chip in float32); a layer's matrices are upcast when its turn comes,
an expert at a time, and the sequence's position-wise work runs in blocks of
rows (the linear layer's in blocks of time, its state carried), so that
the reference fits beside the weights. `quant="int8"` / `"fp8"` is the
control: the operands of every weight matmul rounded to 8 bits (one scale a
tensor), the nearest precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LINEAR, FULL = "linear_attention", "full_attention"

#: stack -> leaf -> (shape code, kind); `kind` picks the random law
LINEAR_LEAVES = {
    "wq": ("dK", "matrix"), "wk": ("dK", "matrix"), "wv": ("dU", "matrix"),
    "wg": ("dU", "matrix"), "wo": ("Ud", "matrix"),
    "wb": ("dH", "matrix"), "wa": ("dH", "matrix"),
    "a_log": ("H", "a_log"), "dt_bias": ("H", "dt_bias"),
    "conv_q": ("cK", "conv"), "conv_k": ("cK", "conv"),
    "conv_v": ("cU", "conv"), "o_norm": ("v", "norm"),
    "norm1": ("d", "norm"), "norm2": ("d", "norm"),
}
MLA_LEAVES = {
    "wq_a": ("dQ", "matrix"), "q_a_norm": ("Q", "norm"),
    "wq_b": ("QA", "matrix"), "wkv_a": ("dL", "matrix"),
    "kv_a_norm": ("R", "norm"), "wkv_b": ("RB", "matrix"),
    "wg": ("dO", "matrix"), "wo": ("Od", "matrix"),
    "norm1": ("d", "norm"), "norm2": ("d", "norm"),
}
DENSE_LEAVES = {
    "w_gate": ("dF", "matrix"), "w_up": ("dF", "matrix"),
    "w_down": ("Fd", "matrix"), "norm3": ("d", "norm"), "norm4": ("d", "norm"),
}
MOE_LEAVES = {
    "router": ("dX", "router"), "bias": ("X", "bias"),
    "w_gate": ("Edf", "matrix"), "w_up": ("Edf", "matrix"),
    "w_down": ("Efd", "matrix"),
    "s_gate": ("df", "matrix"), "s_up": ("df", "matrix"),
    "s_down": ("fd", "matrix"), "norm3": ("d", "norm"), "norm4": ("d", "norm"),
}
TOP_LEAVES = {"embed": ("Vd", "matrix"), "head": ("dV", "matrix"),
              "final_norm": ("d", "norm")}
STACKS = {"linear": LINEAR_LEAVES, "mla": MLA_LEAVES, "dense": DENSE_LEAVES,
          "moe": MOE_LEAVES}
#: kinds kept in float32 on the device (small, and they steer the gates)
F32_KINDS = ("a_log", "dt_bias", "norm", "conv", "router", "bias")

#: rows of the position-wise blocks, tokens of the linear layer's blocks
ROWS, TIME = 2048, 256


def sizes(cfg: dict) -> dict:
    """The configuration's sizes under short names, and the layer plan."""
    n = cfg["num_hidden_layers"]
    full = set(cfg["full_attention_layers"])
    kinds = [FULL if i in full else LINEAR for i in range(n)]
    share = cfg["expert_share"]
    if cfg.get("tie_word_embeddings") or cfg.get("attention_bias"):
        raise ValueError("the reference has an untied head and no biases")
    if cfg["hidden_act"] != "silu" or cfg["n_shared_experts"] != 1:
        raise ValueError("the reference's feed-forwards are SiLU-gated, with "
                         "one shared expert")
    if cfg["rope_scaling"]["type"] != "yarn" or cfg["n_group"] != 1:
        raise ValueError("the reference has YaRN rotary and ungrouped routing")
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return {"d": cfg["hidden_size"], "F": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"], "V": cfg["vocab_size"],
            "E": cfg["n_routed_experts"], "X": share["router_experts"],
            "first": share["first_held"], "k": cfg["num_experts_per_tok"],
            "scale": cfg["routed_scaling_factor"],
            "limit": cfg["swiglu_limit"], "heads": H, "dn": dn, "dr": dr,
            "dvh": cfg["v_head_dim"], "Q": cfg["q_lora_rank"],
            "R": cfg["kv_lora_rank"], "L": cfg["kv_lora_rank"] + dr,
            "A": H * (dn + dr), "B": H * (dn + cfg["v_head_dim"]),
            "O": H * cfg["v_head_dim"],
            "Hk": Hk, "H": Hv, "kd": dk, "v": dv, "K": Hk * dk, "U": Hv * dv,
            "c": cfg["linear_conv_kernel_dim"], "kinds": kinds,
            "n_dense": cfg["first_k_dense_replace"],
            "eps": cfg["rms_norm_eps"],
            "std": cfg.get("initializer_range", 0.02)}


CFG_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "vocab_size", "n_routed_experts",
            "num_experts_per_tok", "routed_scaling_factor", "swiglu_limit",
            "first_k_dense_replace", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim", "rms_norm_eps",
            "rope_theta", "initializer_range", "hidden_act",
            "n_shared_experts", "n_group")


def cfg_key(cfg: dict) -> tuple:
    """The configuration as a hashable static argument."""
    return (tuple((k, cfg.get(k)) for k in CFG_KEYS)
            + (("full_attention_layers", tuple(cfg["full_attention_layers"])),
               ("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),
               ("expert_share", tuple(sorted(cfg["expert_share"].items())))))


def _cfg(items) -> dict:
    cfg = dict(items)
    cfg["full_attention_layers"] = list(cfg["full_attention_layers"])
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    cfg["expert_share"] = dict(cfg["expert_share"])
    return cfg


def seed_key(seed: int):
    """A key from any non-negative whole number (a run's `--seed` may pass
    2**31, which a 32-bit PRNGKey argument cannot hold)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, shape, kind, std):
    """One leaf, float32. Matrices and the router N(0, std^2); norm weights
    (zero-centred: the gain's offset from 1) and the correction bias
    N(0, std^2) too; convolution taps N(0, 1/taps); the decay gates as the
    published Gated DeltaNet layer draws them (A uniform in (0, 16), dt
    log-uniform in (1e-3, 1e-1), `dt_bias` its inverse softplus)."""
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-4, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "conv":
        return x / np.sqrt(shape[0])
    return std * x


def layer_counts(sz: dict) -> dict:
    """Layers in each stack."""
    return {"linear": sz["kinds"].count(LINEAR), "mla": sz["kinds"].count(FULL),
            "dense": sz["n_dense"], "moe": len(sz["kinds"]) - sz["n_dense"]}


#: the last weights made: the program serves the reference's own arrays,
#: and the comparison after the window asks for the same seed again
_LAST = {}


def make_weights(cfg: dict, seed: int, sharding=None, stacked: bool = True):
    """Every weight of the model from ``seed``, made on the device in one
    jitted call: matrices bfloat16, norms, gates, taps, router and bias
    float32, in the program's tree (`{"linear", "mla", "dense", "moe": {leaf:
    [layers, ...]}, "embed", "head", "final_norm"}`), each leaf of a stack
    drawn a layer (an expert) at a time. ``stacked`` is accepted for the
    drivers' sake."""
    del stacked
    key = (cfg_key(cfg), int(seed), sharding)
    if key in _LAST:
        return _LAST[key]
    _LAST.clear()  # the old weights go before the new ones are made
    sz = sizes(cfg)
    count = layer_counts(sz)

    def shape(code):
        return tuple(sz[c] for c in code)

    def draw(k, code, kind):
        dtype = jnp.float32 if kind in F32_KINDS else jnp.bfloat16
        return _leaf(k, shape(code), kind, sz["std"]).astype(dtype)

    def stack(k, leaves, n):
        out = {}
        for name, kk in zip(sorted(leaves), jax.random.split(k, len(leaves))):
            code, kind = leaves[name]
            if code.startswith("E"):     # experts: one a draw
                flat = jax.lax.map(lambda e, code=code, kind=kind: draw(
                    e, code[1:], kind), jax.random.split(kk, n * sz["E"]))
                out[name] = flat.reshape(n, sz["E"], *flat.shape[1:])
            else:
                out[name] = jax.lax.map(
                    lambda e, code=code, kind=kind: draw(e, code, kind),
                    jax.random.split(kk, n))
        return out

    def build(k):
        ks = jax.random.split(k, len(STACKS) + 1)
        out = {name: stack(kk, STACKS[name], count[name])
               for name, kk in zip(sorted(STACKS), ks)}
        for name, kk in zip(sorted(TOP_LEAVES),
                            jax.random.split(ks[-1], len(TOP_LEAVES))):
            out[name] = draw(kk, *TOP_LEAVES[name])
        return out

    _LAST[key] = jax.jit(build, out_shardings=sharding)(seed_key(seed))
    return _LAST[key]


# ---------------------------------------------------------------------------
# forward: one sequence [S], float32
# ---------------------------------------------------------------------------


def fake_int8(x):
    """Round to 255 levels with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def fake_fp8(x):
    """Round to float8 e4m3 after scaling the tensor's largest magnitude to
    the format's."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant):
    w = w.astype(jnp.float32)
    if quant == "int8":
        a, w = fake_int8(a), fake_int8(w)
    elif quant == "fp8":
        a, w = fake_fp8(a), fake_fp8(w)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(a, w, precision=HI)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (1 + w)


def _l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _swiglu(x, wg, wu, wd, limit, quant):
    g, u = _mm(x, wg, quant), _mm(x, wu, quant)
    return _mm(jax.nn.silu(jnp.minimum(g, limit)) * jnp.clip(u, -limit, limit),
               wd, quant)


def _rows(fn, *xs):
    """``fn`` over blocks of ``ROWS`` rows of the [S, ...] arrays ``xs``."""
    S = xs[0].shape[0]
    r = math.gcd(S, ROWS)
    out = jax.lax.map(lambda b: fn(*b), tuple(
        x.reshape(S // r, r, *x.shape[1:]) for x in xs))
    return out.reshape(S, *out.shape[2:])


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """YaRN (arXiv 2309.00071) as DeepSeek-V3 computes it: for rotary pair
    i, the original frequency 1 / theta^(2i/dim) for the pairs that turn
    more than `beta_fast` times over the original context, that frequency
    over `factor` for those that turn fewer than `beta_slow` times, and a
    linear blend by pair index between."""
    dim, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    y = cfg["rope_scaling"]
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def at(rot):   # the pair index that turns ``rot`` times
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(at(y["beta_fast"])), 0)
    high = min(math.ceil(at(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    interp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (1.0 / pos) * (1.0 - interp) + (1.0 / (y["factor"] * pos)) * interp


def mscale(cfg: dict) -> float:
    """YaRN's attention scale m = 0.1 * mscale_all_dim * ln(factor) + 1; the
    scores are scaled by m^2 (the rotary's own factor m / m is 1)."""
    y = cfg["rope_scaling"]
    return 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0


def _rope(x, pos, inv):
    """Rotary on interleaved pairs: x [..., S, (h,) dr], pos [S]."""
    ang = pos[:, None].astype(jnp.float32) * inv                  # [S, dr/2]
    if x.ndim == 3:
        ang = ang[:, None]
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, a * s + b * c], -1).reshape(x.shape)


def gated_delta_recurrence(q, k, v, g, beta, state):
    """The recurrence as written, one token at a time. q, k [T, H, kd]
    (normalised, q scaled), v [T, H, dv], g, beta [T, H]; state [H, dv,
    kd]. Returns (o [T, H, dv], state)."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        decayed = jnp.exp(g_t)[:, None, None] * S
        err = v_t - jnp.einsum("hvk,hk->hv", decayed, k_t, precision=HI)
        S = decayed + jnp.einsum("hv,hk->hvk", b_t[:, None] * err, k_t,
                                 precision=HI)
        return S, jnp.einsum("hvk,hk->hv", S, q_t, precision=HI)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def _linear_mixer(x, p, sz, quant):
    """h = x + N2(gated delta rule layer(N1 x)), in blocks of ``TIME``
    tokens with the state and the convolution's window carried."""
    S, H, Hk, kd, dv, c = (x.shape[0], sz["H"], sz["Hk"], sz["kd"], sz["v"],
                           sz["c"])
    T = math.gcd(S, TIME)
    taps = jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]], 1)

    def block(carry, xb):
        state, window = carry
        xn = _norm(xb, p["norm1"], sz["eps"])
        u = jnp.concatenate([_mm(xn, p["wq"], quant), _mm(xn, p["wk"], quant),
                             _mm(xn, p["wv"], quant)], axis=1)
        ext = jnp.concatenate([window, u], axis=0)
        y = jax.nn.silu(sum(taps[j] * ext[j:j + T] for j in range(c)))
        q, k, v = jnp.split(y, [sz["K"], 2 * sz["K"]], axis=1)
        q = jnp.repeat(_l2norm(q.reshape(T, Hk, kd)), H // Hk, 1) / np.sqrt(kd)
        k = jnp.repeat(_l2norm(k.reshape(T, Hk, kd)), H // Hk, 1)
        beta = jax.nn.sigmoid(_mm(xn, p["wb"], quant))
        g = -jnp.exp(p["a_log"]) * jax.nn.softplus(
            _mm(xn, p["wa"], quant) + p["dt_bias"])
        o, state = gated_delta_recurrence(q, k, v.reshape(T, H, dv), g, beta,
                                          state)
        z = _mm(xn, p["wg"], quant).reshape(T, H, dv)
        out = _mm((_norm(o, p["o_norm"], sz["eps"])
                   * 2.0 * jax.nn.sigmoid(z)).reshape(T, H * dv), p["wo"],
                  quant)
        return (state, ext[T:]), xb + _norm(out, p["norm2"], sz["eps"])

    init = (jnp.zeros((H, dv, kd), jnp.float32),
            jnp.zeros((c - 1, taps.shape[1]), jnp.float32))
    _, h = jax.lax.scan(block, init, x.reshape(S // T, T, -1))
    return h.reshape(x.shape)


def _mla_mixer(x, p, sz, inv, scale, quant):
    """h = x + N2(latent attention layer(N1 x)), expanded: each head's keys
    and values from the latent, causal softmax over every earlier
    position, a head at a time and ``ROWS`` query rows at a time."""
    S, H, dn, dr, R = x.shape[0], sz["heads"], sz["dn"], sz["dr"], sz["R"]
    pos = jnp.arange(S)

    def pre(xb):
        xn = _norm(xb, p["norm1"], sz["eps"])
        ckr = _mm(xn, p["wkv_a"], quant)
        return jnp.concatenate(
            [_norm(_mm(xn, p["wq_a"], quant), p["q_a_norm"], sz["eps"]),
             _norm(ckr[:, :R], p["kv_a_norm"], sz["eps"]), ckr[:, R:]], 1)

    qc = _rows(pre, x)
    c_q, c_kv = qc[:, :sz["Q"]], qc[:, sz["Q"]:sz["Q"] + R]
    k_r = _rope(qc[:, sz["Q"] + R:], pos, inv)
    wq = p["wq_b"].reshape(sz["Q"], H, dn + dr)
    wkv = p["wkv_b"].reshape(R, H, dn + sz["dvh"])

    def head(h):
        q = _mm(c_q, wq[:, h], quant)
        q = jnp.concatenate([q[:, :dn], _rope(q[:, dn:], pos, inv)], 1)
        kv = _mm(c_kv, wkv[:, h], quant)
        k = jnp.concatenate([kv[:, :dn], k_r], 1)
        v = kv[:, dn:]
        r = math.gcd(S, ROWS)

        def block(r0):
            s = jnp.einsum("qd,kd->qk", jax.lax.dynamic_slice_in_dim(
                q, r0, r), k, precision=HI) * scale
            s = jnp.where(pos[None] <= (r0 + jnp.arange(r))[:, None], s,
                          -jnp.inf)
            return jnp.matmul(jax.nn.softmax(s, -1), v, precision=HI)

        return jax.lax.map(block, jnp.arange(0, S, r)).reshape(S, -1)

    o = jax.lax.map(head, jnp.arange(H))                   # [H, S, dvh]
    r = math.gcd(S, ROWS)

    def post(r0):
        xb = jax.lax.dynamic_slice_in_dim(x, r0, r)
        ob = jax.lax.dynamic_slice_in_dim(o, r0, r, axis=1)
        xn = _norm(xb, p["norm1"], sz["eps"])
        y = ob.transpose(1, 0, 2).reshape(r, -1) * 2.0 * jax.nn.sigmoid(
            _mm(xn, p["wg"], quant))
        return xb + _norm(_mm(y, p["wo"], quant), p["norm2"], sz["eps"])

    return jax.lax.map(post, jnp.arange(0, S, r)).reshape(x.shape)


def route(hn, p, sz):
    """Sigmoid scores over all the layer's experts, the top k by score plus
    the correction bias, normalised weights times the scale. Returns the
    [S, E_held] weight each held expert gets from each token (0 where the
    token did not choose it)."""
    s = jax.nn.sigmoid(jnp.matmul(hn, p["router"], precision=HI))
    _, top = jax.lax.top_k(s + p["bias"], sz["k"])
    chosen = jnp.take_along_axis(s, top, -1)
    w = chosen / chosen.sum(-1, keepdims=True) * sz["scale"]
    held = sz["first"] + jnp.arange(sz["E"])
    return jnp.sum(jnp.where(top[:, :, None] == held, w[:, :, None], 0.0), 1)


def _dense_ffn(hn, p, sz, quant):
    return _swiglu(hn, p["w_gate"], p["w_up"], p["w_down"], sz["limit"],
                   quant)


def _moe_ffn(hn, p, sz, quant, layer=None):
    """The shared expert over every row and, expert by expert, each held
    expert over the rows that chose it (gathered ``ROWS`` at a time, its
    result added back at its weight). With ``layer``, ``p``'s expert
    leaves are the whole stacks [layers, experts, ...], read an expert at
    a time (no layer's experts are copied out)."""
    S = hn.shape[0]
    r = min(S, ROWS)
    weight = _rows(lambda hb: route(hb, p, sz), hn)        # [S, E_held]
    y = _rows(lambda hb: _swiglu(hb, p["s_gate"], p["s_up"], p["s_down"],
                                 sz["limit"], quant), hn)

    def expert(e, y):
        chose = weight[:, e] > 0
        count = chose.sum()
        order = jnp.pad(jnp.argsort(~chose, stable=True), (0, -S % r))

        def block(i, y):
            rows = jax.lax.dynamic_slice_in_dim(order, i * r, r)
            w = jnp.where(i * r + jnp.arange(r) < count, weight[rows, e], 0.0)
            at = (e,) if layer is None else (layer, e)
            out = _swiglu(hn[rows], p["w_gate"][at], p["w_up"][at],
                          p["w_down"][at], sz["limit"], quant)
            return y.at[rows].add(w[:, None] * out)

        return jax.lax.fori_loop(0, (count + r - 1) // r, block, y)

    return jax.lax.fori_loop(0, sz["E"], expert, y)


def _at(stack, i):
    return {n: a[i] for n, a in stack.items()
            if n not in ("w_gate", "w_up", "w_down") or a.ndim < 4}


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3,))
def _mixer(cfg_items, kind, quant, x, stack, i):
    """One layer's mixer and its residual, ``h = x + N2(mix(N1 x))``; the
    layer's weights are sliced from the stack inside (no copy of them)."""
    cfg = _cfg(cfg_items)
    sz = sizes(cfg)
    p = _at(stack, i)
    with jax.default_matmul_precision("highest"):
        if kind == LINEAR:
            return _linear_mixer(x, p, sz, quant)
        inv = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
        scale = (sz["dn"] + sz["dr"]) ** -0.5 * mscale(cfg) ** 2
        return _mla_mixer(x, p, sz, inv, scale, quant)


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3,))
def _ffn(cfg_items, kind, quant, h, stack, i):
    """``out = h + N4(ffn(N3 h))``, the position-wise work ``ROWS`` rows at
    a time."""
    sz = sizes(_cfg(cfg_items))
    p = _at(stack, i)
    with jax.default_matmul_precision("highest"):
        if kind == "dense":
            return _rows(lambda hb: hb + _norm(_dense_ffn(
                _norm(hb, p["norm3"], sz["eps"]), p, sz, quant), p["norm4"],
                sz["eps"]), h)
        p.update({n: stack[n] for n in ("w_gate", "w_up", "w_down")})
        y = _moe_ffn(_rows(lambda hb: _norm(hb, p["norm3"], sz["eps"]), h),
                     p, sz, quant, layer=i)
        return _rows(lambda hb, yb: hb + _norm(yb, p["norm4"], sz["eps"]),
                     h, y)


def hidden(w: dict, ids, cfg: dict, quant=None):
    """[S] token ids -> [S, d] after the final norm. A layer at a time, its
    weights sliced from the stacks and upcast inside the call."""
    key, sz = cfg_key(cfg), sizes(cfg)
    x = _embed(w["embed"], jnp.asarray(ids))
    at = {LINEAR: 0, FULL: 0}
    for i, kind in enumerate(sz["kinds"]):
        x = _mixer(key, kind, quant, x, w["linear" if kind == LINEAR
                                          else "mla"], at[kind])
        at[kind] += 1
        ffn = "dense" if i < sz["n_dense"] else "moe"
        x = _ffn(key, ffn, quant, x, w[ffn],
                 i if ffn == "dense" else i - sz["n_dense"])
    return _final_norm(x, w["final_norm"], sz["eps"])


@jax.jit
def _embed(embed, ids):
    return embed[ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(2,))
def _final_norm(x, g, eps):
    return _norm(x, g, eps)


def logits(w: dict, ids, cfg: dict, quant=None):
    """[S] token ids -> [S, V]: the tests' whole-sequence forward."""
    return _head(quant, hidden(w, ids, cfg, quant), w["head"])


@functools.partial(jax.jit, static_argnums=(0,))
def _head(quant, h, head):
    with jax.default_matmul_precision("highest"):
        return _mm(h, head, quant)


# ---------------------------------------------------------------------------
# serving: the gap of a served token below the reference's best
# ---------------------------------------------------------------------------


def served_gaps(cfg: dict, w: dict, prompt, served, *, pad_to: int,
                n_out: int, quant=None):
    """One request. The reference runs once over ``prompt + served`` and,
    at each of the positions that produced a served token, gives the gap
    between its best logit and the served token's logit (0 where the served
    token is its first choice). With ``quant`` set, the control's reading:
    the gap of the token that the lower precision puts first at the same
    positions. Shapes are fixed (``pad_to`` tokens, ``n_out`` positions) so
    that every request shares the compiled layers; the padding comes after
    the request and a causal model's earlier positions do not see it."""
    toks = list(prompt) + list(served)
    P, n = len(prompt), len(served)
    if len(toks) > pad_to or n > n_out:
        raise ValueError(f"request of {len(toks)} tokens, {n} served, does "
                         f"not fit the check's shape ({pad_to}, {n_out})")
    ids = np.zeros(pad_to, np.int32)
    ids[:len(toks)] = toks
    pos = np.full(n_out, P - 1, np.int32)
    pos[:n] = np.arange(P - 1, P - 1 + n)
    tok = np.zeros(n_out, np.int32)
    tok[:n] = served
    ref = hidden(w, ids, cfg)[pos]
    low = None if quant is None else hidden(w, ids, cfg, quant)[pos]
    return np.asarray(_gaps(quant, w["head"], ref, low, jnp.asarray(tok)))[:n]


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(quant, head, ref_h, low_h, tok):
    with jax.default_matmul_precision("highest"):
        ref = _mm(ref_h, head, None)
        chosen = tok if quant is None else jnp.argmax(
            _mm(low_h, head, quant), axis=-1)
    return ref.max(-1) - jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]
