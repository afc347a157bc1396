"""Plain Olmo-Hybrid (allenai/Olmo-Hybrid-7B `config.json`; the linear
layers are Gated DeltaNet, arXiv 2412.06464) in float32 `jax.numpy`: no
kernel, no cache, no batching, and nothing imported from the program. It
decides `correct`, so it follows the equations as written down:

Linear-attention layer, token t, head h, input x_t (d wide):
  q~, k~, v~ = W_q x, W_k x, W_v x; every channel through a causal
  depth-wise convolution of `linear_conv_kernel_dim` taps over time, then
  SiLU; q = l2norm(q~_h) / sqrt(dk), k = l2norm(k~_h), v = v~_h;
  beta_t = 2 sigmoid(w_b x_t) (the 2 is `linear_allow_neg_eigval`);
  g_t = -exp(A_log_h) softplus(w_a x_t + dt_bias_h), alpha_t = exp(g_t);
  S_0 = 0 (dv x dk), S_t = alpha_t S_{t-1}
      + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T, o_t = S_t q_t;
  y_t = W_o [RMSNorm_h(o_t) * SiLU(W_g x_t)_h] (one learned scale of dv).
Full layer: q, k, v = W_q x, W_k x, W_v x, RMSNorm over the whole
projection on q and on k, heads of d / H, causal softmax attention scaled
by 1 / sqrt(head width), W_o. No bias, no rotary or learned position.
MLP: W_down(SiLU(W_gate x) * W_up x).
Block (OLMo 2/3): h = x + RMSNorm(mix(x)); out = h + RMSNorm(MLP(h)); a
final RMSNorm, an untied head.

The recurrence is a plain `lax.scan` over tokens. The weights are the
model's own bfloat16 weights (4.1 B of them at the benchmark's cut would
not fit in float32); a layer is upcast when its turn comes, and everything
computed is float32 at `precision=highest`. `quant="int8"` / `"fp8"` is
the control: the operands of every weight matmul rounded to 8 bits (one
scale a tensor), the nearest precision below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LINEAR, FULL = "linear_attention", "full_attention"

#: name -> (shape code, kind); `kind` picks the random law in `make_weights`
LINEAR_LEAVES = {
    "wq": ("dK", "matrix"), "wk": ("dK", "matrix"), "wv": ("dU", "matrix"),
    "wg": ("dU", "matrix"), "wo": ("Ud", "matrix"),
    "wb": ("dH", "matrix"), "wa": ("dH", "matrix"),
    "a_log": ("H", "a_log"), "dt_bias": ("H", "dt_bias"),
    "conv_q": ("cK", "conv"), "conv_k": ("cK", "conv"),
    "conv_v": ("cU", "conv"), "o_norm": ("v", "gain"),
}
FULL_LEAVES = {
    "wq": ("dd", "matrix"), "wk": ("dd", "matrix"), "wv": ("dd", "matrix"),
    "wo": ("dd", "matrix"), "q_norm": ("d", "gain"), "k_norm": ("d", "gain"),
}
#: what every layer of either kind carries besides its mixer
BLOCK_LEAVES = {
    "norm1": ("d", "gain"), "norm2": ("d", "gain"),
    "w_gate": ("df", "matrix"), "w_up": ("df", "matrix"),
    "w_down": ("fd", "matrix"),
}
TOP_LEAVES = {"embed": ("Vd", "matrix"), "head": ("dV", "matrix"),
              "final_norm": ("d", "gain")}

#: the configuration keys the reference reads
CFG_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "vocab_size", "rms_norm_eps",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_allow_neg_eigval",
            "initializer_range")


def sizes(cfg: dict) -> dict:
    H = cfg["linear_num_value_heads"]
    if cfg["linear_num_key_heads"] != H:
        raise ValueError("key and value heads of the linear layers differ")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the reference has no grouped K/V heads")
    if cfg.get("tie_word_embeddings") or cfg.get("attention_bias"):
        raise ValueError("the reference has an untied head and no biases")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference's MLP is SiLU-gated")
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {LINEAR, FULL}:
        raise ValueError("layer_types does not name every layer's kind")
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "H": H, "k": dk, "v": dv, "K": H * dk,
            "U": H * dv, "c": cfg["linear_conv_kernel_dim"],
            "heads": cfg["num_attention_heads"], "kinds": kinds,
            "eps": cfg["rms_norm_eps"],
            "std": cfg.get("initializer_range", 0.02)}


def cfg_key(cfg: dict) -> tuple:
    """The configuration's sizes as a hashable static argument."""
    return (tuple((k, cfg.get(k)) for k in CFG_KEYS)
            + (("layer_types", tuple(cfg["layer_types"])),
               ("num_hidden_layers", cfg["num_hidden_layers"])))


def seed_key(seed: int):
    """A key from any non-negative whole number (the driver's seeds pass
    2**31, which a 32-bit PRNGKey argument cannot hold)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, shape, kind, std):
    """One leaf, float32. Matrices N(0, std^2), norm scales 1 + N(0, std^2),
    convolution taps N(0, 1/taps); the gates as the published layer draws
    them, so that a random model neither forgets everything nor nothing:
    A uniform in (0, 16), dt log-uniform in (1e-3, 1e-1) with `dt_bias` its
    inverse softplus."""
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-4, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "conv":
        return x / np.sqrt(shape[0])
    return 1.0 + std * x if kind == "gain" else std * x


#: leaves kept in float32 on the device (small, and they steer the gates)
F32_KINDS = ("a_log", "dt_bias", "gain", "conv")

#: the last weights made: the program serves the reference's own arrays
#: (`to_program_tree` copies nothing), and the comparison after the window
#: asks for the same seed again; 8 GB are not made, or held, twice
_LAST = {}


def make_weights(cfg: dict, seed: int, sharding=None, stacked: bool = True):
    """Every weight of the model from ``seed``, made on the device in one
    jitted call: matrices bfloat16 (the model's weights), gates, norm
    scales and convolution taps float32. Layers are stacked by kind, in
    layer order: `{"linear": {leaf: [n_linear, ...]}, "full": {leaf:
    [n_full, ...]}, top leaves}`, each leaf of a stack drawn a layer at a
    time. ``stacked`` is accepted for the drivers' sake: the program takes
    this layout as it is."""
    del stacked
    key = (cfg_key(cfg), int(seed), sharding)
    if key in _LAST:
        return _LAST[key]
    _LAST.clear()  # the old weights go before the new ones are made
    sz = sizes(cfg)
    n = {LINEAR: sz["kinds"].count(LINEAR), FULL: sz["kinds"].count(FULL)}

    def shape(code):
        return tuple(sz[c] for c in code)

    def stack(key, leaves, count):
        names = sorted(leaves)
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            code, kind = leaves[name]
            dtype = jnp.float32 if kind in F32_KINDS else jnp.bfloat16
            out[name] = jax.lax.map(
                lambda kk, code=code, kind=kind, dtype=dtype: _leaf(
                    kk, shape(code), kind, sz["std"]).astype(dtype),
                jax.random.split(k, count))
        return out

    def build(key):
        k_lin, k_full, k_top = jax.random.split(key, 3)
        out = {"linear": stack(k_lin, LINEAR_LEAVES | BLOCK_LEAVES,
                               n[LINEAR]),
               "full": stack(k_full, FULL_LEAVES | BLOCK_LEAVES, n[FULL])}
        for name, k in zip(sorted(TOP_LEAVES),
                           jax.random.split(k_top, len(TOP_LEAVES))):
            code, kind = TOP_LEAVES[name]
            dtype = jnp.float32 if kind in F32_KINDS else jnp.bfloat16
            out[name] = _leaf(k, shape(code), kind, sz["std"]).astype(dtype)
        return out

    _LAST[key] = jax.jit(build, out_shardings=sharding)(seed_key(seed))
    return _LAST[key]


def leaf_norms(tree: dict) -> dict:
    """name -> L2 norm; a stack gives a vector over its layers."""
    norm = lambda x, axes: jnp.sqrt(
        (x.astype(jnp.float32) ** 2).sum(axes))
    out = {n: norm(x, None) for n, x in tree.items()
           if n not in ("linear", "full")}
    for kind in ("linear", "full"):
        for n, x in tree[kind].items():
            out[f"blocks.{kind}.{n}"] = norm(x, tuple(range(1, x.ndim)))
    return out


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


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _causal_conv(u, taps):
    """u [S, C], taps [c, C]: y_t = sum_j taps[j] u_{t-(c-1)+j}, the last
    tap on the current token, nothing before the sequence's start."""
    c = taps.shape[0]
    padded = jnp.pad(u, ((c - 1, 0), (0, 0)))
    return sum(taps[j] * padded[j:j + u.shape[0]] for j in range(c))


def gated_delta_recurrence(q, k, v, g, beta, state=None):
    """The recurrence as written, one token at a time. q, k [S, H, dk]
    (already normalised and scaled), v [S, H, dv], g, beta [S, H]; the
    state [H, dv, dk], zero unless given. Returns (o [S, H, dv], state)."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    if state is None:
        state = jnp.zeros((H, dv, dk), jnp.float32)

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        decayed = jnp.exp(g_t)[:, None, None] * S
        err = v_t - jnp.einsum("hvk,hk->hv", decayed, k_t, precision=HI)
        S = decayed + jnp.einsum("hv,hk->hvk", b_t[:, None] * err, k_t,
                                 precision=HI)
        return S, jnp.einsum("hvk,hk->hv", S, q_t, precision=HI)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def _mlp_block(h, p, sz, quant):
    y = _mm(jax.nn.silu(_mm(h, p["w_gate"], quant)) * _mm(h, p["w_up"], quant),
            p["w_down"], quant)
    return h + _rms(y, p["norm2"], sz["eps"])


def _linear_layer(x, p, sz, neg_eigval, quant):
    S, H, dk, dv = x.shape[0], sz["H"], sz["k"], sz["v"]
    q = jax.nn.silu(_causal_conv(_mm(x, p["wq"], quant), p["conv_q"]))
    k = jax.nn.silu(_causal_conv(_mm(x, p["wk"], quant), p["conv_k"]))
    v = jax.nn.silu(_causal_conv(_mm(x, p["wv"], quant), p["conv_v"]))
    q = _l2norm(q.reshape(S, H, dk)) / np.sqrt(dk)
    k = _l2norm(k.reshape(S, H, dk))
    beta = jax.nn.sigmoid(_mm(x, p["wb"], quant))
    if neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(
        _mm(x, p["wa"], quant) + p["dt_bias"])
    o, _ = gated_delta_recurrence(q, k, v.reshape(S, H, dv), g, beta)
    gate = jax.nn.silu(_mm(x, p["wg"], quant)).reshape(S, H, dv)
    y = _mm((_rms(o, p["o_norm"], sz["eps"]) * gate).reshape(S, H * dv),
            p["wo"], quant)
    return _mlp_block(x + _rms(y, p["norm1"], sz["eps"]), p, sz, quant)


def _full_layer(x, p, sz, quant, rows):
    S, d, H = x.shape[0], sz["d"], sz["heads"]
    D = d // H
    heads = lambda t: t.reshape(S, H, D).transpose(1, 0, 2)
    q = heads(_rms(_mm(x, p["wq"], quant), p["q_norm"], sz["eps"]))
    k = heads(_rms(_mm(x, p["wk"], quant), p["k_norm"], sz["eps"]))
    v = heads(_mm(x, p["wv"], quant))
    kpos = jnp.arange(S)

    def block(r0):
        """`rows` queries from r0 on against every key: the score matrix of
        a whole long request would not fit."""
        qb = jax.lax.dynamic_slice_in_dim(q, r0, rows, axis=1)
        s = jnp.einsum("hqd,hkd->hqk", qb, k, precision=HI) / np.sqrt(D)
        s = jnp.where(kpos[None, None] <= (r0 + jnp.arange(rows))[None, :,
                                                                 None],
                      s, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    a = jax.lax.map(block, jnp.arange(0, S, rows))       # [S/rows, H, rows, D]
    a = a.transpose(0, 2, 1, 3).reshape(S, d)
    return _mlp_block(x + _rms(_mm(a, p["wo"], quant), p["norm1"],
                               sz["eps"]), p, sz, quant)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(cfg_items, kind, quant, x, p):
    cfg = dict(cfg_items)
    cfg["layer_types"] = list(cfg["layer_types"])
    sz = sizes(cfg)
    if kind == LINEAR:
        return _linear_layer(x, p, sz, cfg["linear_allow_neg_eigval"], quant)
    S = x.shape[0]
    rows = next(r for r in (256, 128, 64, 32, 16, 8, 4, 2, 1) if S % r == 0)
    return _full_layer(x, p, sz, quant, rows)


def hidden(w: dict, ids, cfg: dict, quant=None):
    """[S] token ids -> [S, d] after the final RMSNorm. One layer at a time:
    its weights are sliced from the stack and upcast inside the call."""
    key = cfg_key(cfg)
    x = w["embed"][ids].astype(jnp.float32)
    at = {LINEAR: 0, FULL: 0}
    for kind in cfg["layer_types"]:
        stack = w["linear" if kind == LINEAR else "full"]
        p = {n: a[at[kind]] for n, a in stack.items()}
        x = _layer(key, kind, quant, x, p)
        at[kind] += 1
    return _final_norm(x, w["final_norm"], cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(2,))
def _final_norm(x, g, eps):
    return _rms(x, g, eps)


def logits_at(w: dict, h, quant=None):
    """Untied-head logits of hidden states [..., d] -> [..., V]."""
    return _mm(h, w["head"], quant)


def logits(w: dict, ids, cfg: dict, quant=None):
    """[S] token ids -> [S, V]: the tests' whole-sequence forward."""
    return _head(quant, hidden(w, jnp.asarray(ids), cfg, quant), w["head"])


@functools.partial(jax.jit, static_argnums=(0,))
def _head(quant, h, head):
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
    ids, pos, tok = jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(tok)
    ref = hidden(w, ids, cfg)[pos]
    low = None if quant is None else hidden(w, ids, cfg, quant)[pos]
    return np.asarray(_gaps(quant, w["head"], ref, low, tok))[:n]


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(quant, head, ref_h, low_h, tok):
    ref = _mm(ref_h, head, None)
    chosen = tok if quant is None else jnp.argmax(
        _mm(low_h, head, quant), axis=-1)
    return ref.max(-1) - jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]
