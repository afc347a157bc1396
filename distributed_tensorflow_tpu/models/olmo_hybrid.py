"""Hybrid decoder: gated-delta-rule (linear-attention) layers beside
full-attention layers, the Olmo-Hybrid layout (``layer_types`` a repeated
period such as three linear layers and one full layer).

A second decoder beside ``models/transformer.py`` rather than more fields
on ``TransformerConfig``: nothing of the block is shared. RMSNorm after
each sub-layer (``h = x + norm(mix(x)); out = h + norm(mlp(h))``), a
SiLU-gated MLP, no biases, an untied head, no positional encoding (the
recurrent layers carry order), QK-norm over the whole projection in the
full layers. The linear layer is the published Gated DeltaNet layer: q, k, v
through a short causal depth-wise convolution and SiLU, q and k of unit
length, a decay gate and a write gate per head, the gated delta rule
(``ops/gated_delta.py``), a gated RMSNorm on its output.

Parameters are one dict, stacked by kind in layer order
(``{"linear": {leaf: [n_linear, ...]}, "full": {leaf: [n_full, ...]},
"embed", "head", "final_norm"}``), and the forward is a ``lax.scan`` over
periods whose body holds one period's layers: trace, lower and compile do
not grow with depth. Serving state rides the scan's carry and is written
in place: the K/V pool of the full layers ``[n_full, blocks, H, block, D]``
through kernels that take the layer's index, the recurrent
state ``[n_linear, slots, H, dk, dv]`` through the kernels' aliased output.

Precision: bfloat16 matmul operands with float32 accumulation; the residual
stream, norms, gates, convolution, softmax, recurrent state and logits in
float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops import attention as attn_ops
from ..ops import gated_delta
from ..parallel import mesh as mesh_lib
from ..parallel import sharding

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int
    d_model: int
    d_ff: int
    num_heads: int
    #: every layer's kind, a whole number of repeats of one period
    layer_types: tuple
    linear_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv_kernel: int = 4
    #: write gate in [0, 2] instead of [0, 1] (negative eigenvalues)
    allow_neg_eigval: bool = True
    rms_eps: float = 1e-6
    max_len: int = 65536
    #: ops.attention.paged_attention / ops.gated_delta ``impl=``
    paged_attention_impl: str = "auto"
    gated_delta_impl: str = "auto"

    #: the serving engine asks every model config
    causal = True

    def __post_init__(self):
        kinds = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", kinds)
        if not kinds or set(kinds) - {LINEAR, FULL}:
            raise ValueError(f"layer_types must name {LINEAR!r} or {FULL!r} "
                             f"for every layer, got {kinds!r}")
        if self.d_model % self.num_heads:
            raise ValueError("d_model must divide into num_heads")
        if self.conv_kernel < 2:
            raise ValueError("conv_kernel must be at least 2")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def period(self) -> tuple:
        """The shortest prefix of ``layer_types`` whose repeats give all of
        it: the scan's body."""
        kinds = self.layer_types
        for n in range(1, len(kinds) + 1):
            if len(kinds) % n == 0 and kinds[:n] * (len(kinds) // n) == kinds:
                return kinds[:n]
        return kinds

    @property
    def num_periods(self) -> int:
        return self.num_layers // len(self.period)

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def decoder(self) -> "OlmoHybrid":
        """The model this config describes (``serve.ServeEngine`` asks)."""
        return OlmoHybrid(self)

    @property
    def linear_key_heads(self) -> int:
        """q and k heads of a linear layer: one for every value head here
        (``models/gigachat3_5.py`` has fewer, each serving a group)."""
        return self.linear_heads

    @property
    def conv_channels(self) -> int:
        """q, k and v of a linear layer side by side: what its convolution
        runs over and its window remembers."""
        return (2 * self.linear_key_heads * self.linear_key_dim
                + self.linear_heads * self.linear_value_dim)


#: leaf -> shape as a function of the config, for each kind of layer
def _leaf_shapes(cfg: OlmoHybridConfig) -> dict:
    d, f, H = cfg.d_model, cfg.d_ff, cfg.linear_heads
    K, U, c = H * cfg.linear_key_dim, H * cfg.linear_value_dim, cfg.conv_kernel
    block = {"norm1": (d,), "norm2": (d,), "w_gate": (d, f), "w_up": (d, f),
             "w_down": (f, d)}
    return {
        "linear": {"wq": (d, K), "wk": (d, K), "wv": (d, U), "wg": (d, U),
                   "wo": (U, d), "wb": (d, H), "wa": (d, H), "a_log": (H,),
                   "dt_bias": (H,), "conv_q": (c, K), "conv_k": (c, K),
                   "conv_v": (c, U), "o_norm": (cfg.linear_value_dim,),
                   **block},
        "full": {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
                 "q_norm": (d,), "k_norm": (d,), **block},
    }


#: leaves held in float32 (norm scales, gates, convolution taps); the
#: matrices are bfloat16
F32_LEAVES = ("norm1", "norm2", "q_norm", "k_norm", "o_norm", "final_norm",
              "a_log", "dt_bias", "conv_q", "conv_k", "conv_v")


def param_shapes(cfg: OlmoHybridConfig) -> dict:
    """The parameter tree as ``jax.ShapeDtypeStruct`` leaves."""
    def leaf(name, shape):
        dtype = jnp.float32 if name in F32_LEAVES else jnp.bfloat16
        return jax.ShapeDtypeStruct(shape, dtype)

    shapes = _leaf_shapes(cfg)
    out = {kind: {n: leaf(n, (cfg.count(tag), *s))
                  for n, s in shapes[kind].items()}
           for kind, tag in (("linear", LINEAR), ("full", FULL))}
    out["embed"] = leaf("embed", (cfg.vocab_size, cfg.d_model))
    out["head"] = leaf("head", (cfg.d_model, cfg.vocab_size))
    out["final_norm"] = leaf("final_norm", (cfg.d_model,))
    return out


def init_params(cfg: OlmoHybridConfig, key, std: float = 0.02) -> dict:
    """Random parameters: matrices N(0, std^2), norm scales 1, the gates as
    the published layer draws them (A uniform in (0, 16), dt log-uniform in
    (1e-3, 1e-1), ``dt_bias`` its inverse softplus)."""
    shapes = param_shapes(cfg)
    leaves, tree = jax.tree.flatten_with_path(shapes)
    out = []
    for (path, s), k in zip(leaves, jax.random.split(key, len(leaves))):
        name = path[-1].key
        if name == "a_log":
            x = jnp.log(jax.random.uniform(k, s.shape, jnp.float32, 1e-4, 16.))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, s.shape, jnp.float32,
                                            np.log(1e-3), np.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif name.endswith("norm") or name in ("norm1", "norm2"):
            x = jnp.ones(s.shape, jnp.float32)
        elif name.startswith("conv_"):
            x = jax.random.normal(k, s.shape) / np.sqrt(cfg.conv_kernel)
        else:
            x = std * jax.random.normal(k, s.shape)
        out.append(x.astype(s.dtype))
    return jax.tree.unflatten(tree, out)


_COVERAGE = tuple(sorted(
    [f"linear/{n}" for n in (
        "wq", "wk", "wv", "wg", "wo", "wb", "wa", "a_log", "dt_bias",
        "conv_q", "conv_k", "conv_v", "o_norm", "norm1", "norm2", "w_gate",
        "w_up", "w_down")]
    + [f"full/{n}" for n in (
        "wq", "wk", "wv", "wo", "q_norm", "k_norm", "norm1", "norm2",
        "w_gate", "w_up", "w_down")]
    + ["embed", "head", "final_norm"]))

#: Partition-rules table of the parameter tree (leading axis of a stack:
#: the layer). Heads shard over ``model`` the way the transformer's do:
#: column-parallel into the mixers and the MLP, row-parallel out of them;
#: the per-head gates and convolution taps follow their heads.
OLMO_HYBRID_RULES = sharding.partition_rules(
    "olmo-hybrid",
    (
        (r"(linear|full)/(wq|wk|wv|wg|wb|wa|w_gate|w_up)$",
         P(None, None, mesh_lib.MODEL)),
        (r"(linear|full)/(wo|w_down)$", P(None, mesh_lib.MODEL, None)),
        (r"linear/(conv_q|conv_k|conv_v)$", P(None, None, mesh_lib.MODEL)),
        (r"linear/(a_log|dt_bias)$", P(None, mesh_lib.MODEL)),
        (r"^embed$", P(mesh_lib.MODEL, None)),     # vocab-sharded
        (r"^head$", P(None, mesh_lib.MODEL)),
        (sharding.CATCH_ALL, sharding.REPLICATED),  # norm scales
    ),
    coverage=_COVERAGE,
)


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------


def _mm(x, w):
    """bfloat16 operands, float32 accumulation."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _mlp(h, p, cfg):
    y = _mm(jax.nn.silu(_mm(h, p["w_gate"])) * _mm(h, p["w_up"]),
            p["w_down"])
    return h + _rms(y, p["norm2"], cfg.rms_eps)


def _conv_taps(p):
    return jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]], axis=1)


def _causal_conv(ext, taps, T):
    """``ext`` [taps - 1 + T, C]: the inputs with the window before them;
    the last tap is on the current token."""
    return sum(taps[j] * ext[j:j + T] for j in range(taps.shape[0]))


def _gates(x, p, cfg):
    beta = jax.nn.sigmoid(_mm(x, p["wb"]))
    if cfg.allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(_mm(x, p["wa"]) + p["dt_bias"])
    return g, beta


def _split_qkv(y, cfg):
    """Convolved and activated channels [..., C] -> q, k (unit length, q
    scaled) and v by value head: where there are fewer q/k heads, q/k head
    ``j`` serves value heads ``j * g .. j * g + g - 1`` and is repeated
    for each of them before the kernels."""
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    Hk = cfg.linear_key_heads
    lead = y.shape[:-1]
    q, k, v = jnp.split(y, [Hk * dk, 2 * Hk * dk], axis=-1)
    q = _l2norm(q.reshape(*lead, Hk, dk)) * dk ** -0.5
    k = _l2norm(k.reshape(*lead, Hk, dk))
    if Hk != H:
        q, k = (jnp.repeat(t, H // Hk, axis=-2) for t in (q, k))
    return q, k, v.reshape(*lead, H, dv)


def _linear_out(x, o, p, cfg):
    """Gated RMSNorm of the rule's output, the output projection, the
    block's norm and residual, the MLP."""
    lead = x.shape[:-1]
    gate = jax.nn.silu(_mm(x, p["wg"])).reshape(o.shape)
    y = _rms(o.astype(jnp.float32), p["o_norm"], cfg.rms_eps) * gate
    y = _mm(y.reshape(*lead, -1), p["wo"])
    return _mlp(x + _rms(y, p["norm1"], cfg.rms_eps), p, cfg)


def _qkv_inputs(x, p):
    return jnp.concatenate(
        [_mm(x, p["wq"]), _mm(x, p["wk"]), _mm(x, p["wv"])], axis=-1)


def _linear_chunk(x, p, cfg, state, conv, layer, slot, length, fresh):
    """``T`` tokens of one request (x [T, d]) through a linear layer's
    mixer, from the slot's carried state and convolution window to the new
    ones. Returns (the rule's output o [T, H, dv], state, conv): what
    follows it is the decoder's."""
    T, c = x.shape[0], cfg.conv_kernel
    u = _qkv_inputs(x, p)                                     # [T, C]
    window = jnp.where(fresh, 0.0, conv[layer, slot])         # [c-1, C]
    ext = jnp.concatenate([window, u], axis=0)
    y = _causal_conv(ext, _conv_taps(p), T)
    # the window after `length` tokens: the inputs just before that position
    conv = conv.at[layer, slot].set(
        jax.lax.dynamic_slice_in_dim(ext, length, c - 1, axis=0))
    q, k, v = _split_qkv(jax.nn.silu(y), cfg)
    g, beta = _gates(x, p, cfg)
    # q, k and v go in as float32: rounded to bfloat16 they cost the toy
    # model's logits several hundredths (the rule subtracts what the state
    # predicts from v, and writes the difference back)
    o, state = gated_delta.gated_delta_chunk(
        q, k, v, g, beta, state, layer=layer, slot=slot,
        length=length, fresh=fresh, impl=cfg.gated_delta_impl)
    return o, state, conv


def _linear_step(x, p, cfg, state, conv, layer, live):
    """One token for every slot (x [B, d]); returns as `_linear_chunk`."""
    u = _qkv_inputs(x, p)                                     # [B, C]
    window = conv[layer]                                      # [B, c-1, C]
    taps = _conv_taps(p)
    y = (jnp.einsum("jc,bjc->bc", taps[:-1], window) + taps[-1] * u)
    moved = jnp.concatenate([window[:, 1:], u[:, None]], axis=1)
    conv = conv.at[layer].set(
        jnp.where(live[:, None, None], moved, window))
    q, k, v = _split_qkv(jax.nn.silu(y), cfg)
    g, beta = _gates(x, p, cfg)
    o, state = gated_delta.gated_delta_step(
        q, k, v, g, beta, state, layer=layer, live=live,
        impl=cfg.gated_delta_impl)
    return o, state, conv


def _linear_full_sequence(x, p, cfg):
    """A whole sequence from a zero state, no cache (x [S, d])."""
    S, c = x.shape[0], cfg.conv_kernel
    ext = jnp.pad(_qkv_inputs(x, p), ((c - 1, 0), (0, 0)))
    y = _causal_conv(ext, _conv_taps(p), S)
    q, k, v = _split_qkv(jax.nn.silu(y), cfg)
    g, beta = _gates(x, p, cfg)
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    o, _ = gated_delta.recurrence(q, k, v, g, beta,
                                  jnp.zeros((H, dk, dv), jnp.float32))
    return _linear_out(x, o, p, cfg)


def _full_qkv(x, p, cfg):
    """x [B, S, d] -> q, k, v [B, H, S, D], q and k RMS-normed over the
    whole projection."""
    B, S, _ = x.shape
    heads = lambda t: t.reshape(B, S, cfg.num_heads, cfg.head_dim).transpose(
        0, 2, 1, 3)
    q = heads(_rms(_mm(x, p["wq"]), p["q_norm"], cfg.rms_eps))
    k = heads(_rms(_mm(x, p["wk"]), p["k_norm"], cfg.rms_eps))
    return q, k, heads(_mm(x, p["wv"]))


def _full_out(x, a, p, cfg):
    B, S, _ = x.shape
    a = a.transpose(0, 2, 1, 3).reshape(B, S, cfg.d_model)
    return _mlp(x + _rms(_mm(a, p["wo"]), p["norm1"], cfg.rms_eps), p, cfg)


def _full_paged(x, p, cfg, k_pool, v_pool, layer, table, pos):
    """x [B, S, d] at absolute positions ``pos`` [B, S] through a full
    layer: K and V written into row ``layer`` of the pool through the
    block table (sentinel positions and entries write nothing), attention
    over the pool in place (``ops.attention.paged_layer_attention``, the
    transformer's too)."""
    q, k, v = _full_qkv(x, p, cfg)
    a, k_pool, v_pool = attn_ops.paged_layer_attention(
        q.astype(k_pool.dtype), k, v, k_pool, v_pool, table, pos,
        layer=layer, impl=cfg.paged_attention_impl)
    return _full_out(x, a.astype(jnp.float32), p, cfg), k_pool, v_pool


def _full_sequence(x, p, cfg):
    q, k, v = _full_qkv(x[None], p, cfg)
    a = attn_ops.attention_reference(q, k, v, causal=True)
    return _full_out(x[None], a, p, cfg)[0]


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _head_logits(h, head):
    """float32 logits of float32 rows ``h`` against the bfloat16 head: the
    rows go in as two bfloat16 halves (value and rounding remainder) so
    that the head is read once and the rows lose nothing."""
    hi = h.astype(jnp.bfloat16)
    lo = (h - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    both = jnp.matmul(jnp.concatenate([hi, lo], axis=0), head,
                      preferred_element_type=jnp.float32)
    return both[:h.shape[0]] + both[h.shape[0]:]


class OlmoHybrid:
    """The decoder as three jittable functions over a parameter dict:
    ``forward`` (a whole sequence, no cache), ``prefill_chunk`` and
    ``decode_step`` (serving, over a ``serve.kv_cache.HybridCache``)."""

    #: recurrent state beside the K/V pool (serve/decode.py "the serving
    #: protocol"): the engine keeps snapshots of it and refuses speculation
    has_state = True

    def __init__(self, cfg: OlmoHybridConfig):
        self.cfg = cfg

    def init_params(self, key) -> dict:
        """Random parameters (``init_params``)."""
        return init_params(self.cfg, key)

    def serving_params(self, params) -> dict:
        """The tree the serving programs take (serve/decode.py "the serving
        protocol"): the params as they are (at the published widths every
        table is a multiple of the lanes already)."""
        return params

    def init_cache(self, num_slots, num_blocks, block_size, num_snapshots,
                   dtype=jnp.bfloat16):
        """The engine's cache for this model: ``serve.kv_cache.HybridCache``."""
        from ..serve import kv_cache

        return kv_cache.init_hybrid_cache(self.cfg, num_slots, num_blocks,
                                          block_size, num_snapshots, dtype)

    def _scan(self, params, carry, layer_fn):
        """``layer_fn(kind, index of the layer among its kind, its
        parameters, carry) -> carry`` over every layer, a period a scan
        step. A layer's parameters are read from the stacks at a computed
        index (a slice of the leading axis, which the consumer reads in
        place); scanning the stacks themselves as ``xs`` makes XLA copy a
        period's weights out, every step."""
        cfg = self.cfg
        per_period = {kind: cfg.period.count(kind) for kind in (LINEAR, FULL)}
        stacks = {LINEAR: params["linear"], FULL: params["full"]}

        def body(carry, period):
            at = {LINEAR: 0, FULL: 0}
            for kind in cfg.period:
                index = period * per_period[kind] + at[kind]
                p = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, index, keepdims=False), stacks[kind])
                carry = layer_fn(kind, index, p, carry)
                at[kind] += 1
            return carry, None

        carry, _ = jax.lax.scan(body, carry, jnp.arange(cfg.num_periods))
        return carry

    def forward(self, params, tokens):
        """[S] token ids -> [S, vocab] float32 logits."""
        cfg = self.cfg

        def layer(kind, index, p, x):
            del index
            return (_linear_full_sequence(x, p, cfg) if kind == LINEAR
                    else _full_sequence(x, p, cfg))

        x = self._scan(params, params["embed"][tokens].astype(jnp.float32),
                       layer)
        return _head_logits(_rms(x, params["final_norm"], cfg.rms_eps),
                            params["head"])

    def prefill_chunk(self, params, cache, table_row, tokens, start, length,
                      slot):
        """One chunk of the request in ``slot``: ``tokens`` [C] (padded
        past ``length``) at positions ``start`` on. The full layers write
        K/V through ``table_row``; the linear layers advance the slot's
        state and window by ``length`` tokens (from nought where ``start``
        is 0). Returns (logits [vocab] of the last real position, cache)."""
        cfg = self.cfg
        C = tokens.shape[0]
        idx = jnp.arange(C, dtype=jnp.int32)
        sentinel = table_row.shape[0] * cache.block_size
        pos = jnp.where(idx < length, start + idx, sentinel)[None]
        fresh = start == 0

        def layer(kind, index, p, carry):
            x, k, v, state, conv = carry
            if kind == LINEAR:
                o, state, conv = _linear_chunk(
                    x, p, cfg, state, conv, index, slot, length, fresh)
                x = _linear_out(x, o, p, cfg)
            else:
                y, k, v = _full_paged(x[None], p, cfg, k, v, index,
                                      table_row[None], pos)
                x = y[0]
            return x, k, v, state, conv

        x, k, v, state, conv = self._scan(
            params, (params["embed"][tokens].astype(jnp.float32), cache.k,
                     cache.v, cache.state, cache.conv), layer)
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=0)
        logits = _head_logits(_rms(last, params["final_norm"], cfg.rms_eps),
                              params["head"])[0]
        return logits, dataclasses.replace(cache, k=k, v=v, state=state,
                                           conv=conv)

    def decode_step(self, params, cache, block_tables, tokens, lengths):
        """One token for every slot. ``lengths`` [slots] is each slot's
        write position; a slot that carries the past-the-table sentinel
        (idle, mid-prefill) writes no K/V and keeps its recurrent state
        and window. Returns (logits [slots, vocab], cache)."""
        cfg = self.cfg
        live = lengths < block_tables.shape[1] * cache.block_size

        def layer(kind, index, p, carry):
            x, k, v, state, conv = carry
            if kind == LINEAR:
                o, state, conv = _linear_step(x, p, cfg, state, conv, index,
                                              live)
                x = _linear_out(x, o, p, cfg)
            else:
                y, k, v = _full_paged(x[:, None], p, cfg, k, v, index,
                                      block_tables, lengths[:, None])
                x = y[:, 0]
            return x, k, v, state, conv

        x, k, v, state, conv = self._scan(
            params, (params["embed"][tokens].astype(jnp.float32), cache.k,
                     cache.v, cache.state, cache.conv), layer)
        logits = _head_logits(_rms(x, params["final_norm"], cfg.rms_eps),
                              params["head"])
        return logits, dataclasses.replace(cache, k=k, v=v, state=state,
                                           conv=conv)
