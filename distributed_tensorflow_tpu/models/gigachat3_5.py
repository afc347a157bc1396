"""GigaChat 3.5's decoder, served as one chip's share of an expert-parallel
deployment: multi-head latent attention (MLA) layers and gated-delta-rule
(linear-attention) layers, a leading dense SwiGLU layer and then sparse
expert layers with a shared expert.

The layer, in the readings written down in the benchmark's configuration
(`benchmark/configs/gigachat3.5-432b-a28b.json`, ``assumed``):

- Every norm is a zero-centred RMSNorm, ``x / rms(x) * (1 + w)``, and every
  sub-layer has one before and one after it: ``h = x + N2(mix(N1(x)))``,
  ``out = h + N4(ffn(N3(h)))``; a final norm before the untied head.
- MLA (DeepSeek-V3's form): ``c_q = N(x W_dq)``, ``q = c_q W_uq`` (heads of
  nope 128 || rope 64); ``[c_kv || k_r] = x W_dkv``, ``c_kv`` normed,
  ``k_r`` (one head for all) rotated; ``[k_nope || v] = c_kv W_ukv``.
  Rotary on interleaved pairs with YaRN frequencies; scores scaled by
  ``qk_head_dim^-1/2 * m^2``, ``m = 0.1 ln(factor) + 1``. The output is
  gated element-wise, ``o * 2 sigmoid(x W_g)``, then ``W_o``. Served in the
  absorbed form: one latent row a token in the cache, 64 query rows of
  ``[q_nope W_uk^T || q_r]`` against it (``ops/latent_attention.py``), the
  result through ``W_uv``.
- Gated DeltaNet as ``models/olmo_hybrid.py`` has it (its convolution,
  chunk and step are shared), with fewer q/k heads than value heads (each
  serves a group, repeated before the kernels), ``beta = sigmoid(x W_b)``,
  and the output ``N(o_h) * 2 sigmoid(z_h)`` per value head.
- SwiGLU everywhere (dense, shared and routed experts) with its inputs
  clamped: ``silu(min(g, L)) * clip(u, -L, L)``.
- The expert layer routes over ALL the published experts (sigmoid scores,
  top-k by score plus a correction bias, weights normalised and scaled)
  and computes the part of the result that the experts held here give
  (``ops/moe.expert_share``), plus the shared expert; the exchange with the
  chips that hold the others is not part of this program.

Parameters are one dict stacked by kind (``linear``, ``mla`` mixers;
``dense``, ``moe`` feed-forwards; ``embed``, ``head``, ``final_norm``); the
leading dense layers run first and the rest is a ``lax.scan`` over periods
of ``layer_types``, whose carry holds the cache, written in place: the
latent pool ``[mla layers, blocks, 1, block, W]`` through a kernel that
takes the layer's index, the recurrent state through the gated-delta
kernels' aliased output. The expert weights ``[moe layers, experts, ...]``
are handed whole to the grouped matmul with the layer's index.

Precision: bfloat16 matmul operands with float32 accumulation; the residual
stream, norms, gates, router, softmax, recurrent state and logits float32.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import latent_attention as la
from ..ops import moe
from . import olmo_hybrid as oh

LINEAR, FULL = oh.LINEAR, oh.FULL


@dataclasses.dataclass(frozen=True)
class GigaChat35Config:
    vocab_size: int
    d_model: int
    #: the leading dense layers' SwiGLU width, and the experts'
    d_ff: int
    moe_d_ff: int
    #: every layer's kind (``linear_attention`` / ``full_attention``)
    layer_types: tuple
    #: leading layers with a dense feed-forward; the rest have experts
    first_dense: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    linear_key_heads: int
    linear_heads: int
    linear_key_dim: int
    linear_value_dim: int
    #: experts of the whole layer (the router's width), those held here
    #: and the first of them
    num_experts: int
    experts_held: int
    first_expert: int = 0
    top_k: int = 8
    routed_scale: float = 2.5
    swiglu_limit: float = 10.0
    conv_kernel: int = 4
    rope_theta: float = 1e5
    rope_factor: float = 8.0
    rope_original_max: int = 32768
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    max_len: int = 262144
    #: ops.latent_attention / ops.gated_delta ``impl=``
    latent_attention_impl: str = "auto"
    gated_delta_impl: str = "auto"

    #: the serving engine asks every model config
    causal = True
    #: the shared gated-delta pieces ask: the write gate is in [0, 1]
    allow_neg_eigval = False

    def __post_init__(self):
        kinds = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", kinds)
        if not kinds or set(kinds) - {LINEAR, FULL}:
            raise ValueError(f"layer_types must name {LINEAR!r} or {FULL!r} "
                             f"for every layer, got {kinds!r}")
        if not 0 <= self.first_dense < len(kinds):
            raise ValueError("first_dense must leave at least one expert "
                             "layer")
        if self.linear_heads % self.linear_key_heads:
            raise ValueError("value heads must be a whole number of groups "
                             "of the q/k heads")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held <= self.num_experts):
            raise ValueError("the held experts must lie inside the layer's")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def decoder(self) -> "GigaChat35":
        """The model this config describes (``serve.ServeEngine`` asks)."""
        return GigaChat35(self)

    @property
    def period(self) -> tuple:
        """The shortest repeating unit of the layers after the dense ones:
        the scan's body."""
        kinds = self.layer_types[self.first_dense:]
        for n in range(1, len(kinds) + 1):
            if len(kinds) % n == 0 and kinds[:n] * (len(kinds) // n) == kinds:
                return kinds[:n]
        return kinds

    @property
    def num_periods(self) -> int:
        return (self.num_layers - self.first_dense) // len(self.period)

    @property
    def latent_width(self) -> int:
        """A cached token's row: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def conv_channels(self) -> int:
        return (2 * self.linear_key_heads * self.linear_key_dim
                + self.linear_heads * self.linear_value_dim)

    @property
    def mscale(self) -> float:
        return 0.1 * self.mscale_all_dim * math.log(self.rope_factor) + 1.0

    @property
    def sm_scale(self) -> float:
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5 * self.mscale ** 2


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies (arXiv 2309.00071; DeepSeek-V3's rotary):
    the low dimensions extrapolated, the high ones interpolated by
    ``factor``, a linear ramp between the dimensions at which ``beta_fast``
    and ``beta_slow`` rotations fit the original context."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    return (1.0 / (factor * pos)) * ramp + (1.0 / pos) * extrapolate


def _leaf_shapes(cfg: GigaChat35Config) -> dict:
    d, H = cfg.d_model, cfg.num_heads
    Hv, Hk = cfg.linear_heads, cfg.linear_key_heads
    K, U = Hk * cfg.linear_key_dim, Hv * cfg.linear_value_dim
    c, E, f = cfg.conv_kernel, cfg.experts_held, cfg.moe_d_ff
    R, dq = cfg.kv_lora_rank, cfg.qk_nope_dim + cfg.qk_rope_dim
    norms = {"norm1": (d,), "norm2": (d,)}
    return {
        "linear": {"wq": (d, K), "wk": (d, K), "wv": (d, U), "wg": (d, U),
                   "wo": (U, d), "wb": (d, Hv), "wa": (d, Hv),
                   "a_log": (Hv,), "dt_bias": (Hv,), "conv_q": (c, K),
                   "conv_k": (c, K), "conv_v": (c, U),
                   "o_norm": (cfg.linear_value_dim,), **norms},
        "mla": {"wq_a": (d, cfg.q_lora_rank), "q_a_norm": (cfg.q_lora_rank,),
                "wq_b": (cfg.q_lora_rank, H * dq),
                "wkv_a": (d, cfg.latent_width), "kv_a_norm": (R,),
                "wkv_b": (R, H * (cfg.qk_nope_dim + cfg.v_head_dim)),
                "wg": (d, H * cfg.v_head_dim), "wo": (H * cfg.v_head_dim, d),
                **norms},
        "dense": {"w_gate": (d, cfg.d_ff), "w_up": (d, cfg.d_ff),
                  "w_down": (cfg.d_ff, d), "norm3": (d,), "norm4": (d,)},
        "moe": {"router": (d, cfg.num_experts), "bias": (cfg.num_experts,),
                "w_gate": (E, d, f), "w_up": (E, d, f), "w_down": (E, f, d),
                "s_gate": (d, f), "s_up": (d, f), "s_down": (f, d),
                "norm3": (d,), "norm4": (d,)},
    }


#: leaves held in float32: norm weights, gates, convolution taps, router
F32_LEAVES = ("norm1", "norm2", "norm3", "norm4", "q_a_norm", "kv_a_norm",
              "o_norm", "final_norm", "a_log", "dt_bias", "conv_q", "conv_k",
              "conv_v", "router", "bias")
#: the expert stacks the grouped matmul reads in place, with the layer
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def param_shapes(cfg: GigaChat35Config) -> dict:
    """The parameter tree as ``jax.ShapeDtypeStruct`` leaves."""
    def leaf(name, shape):
        dtype = jnp.float32 if name in F32_LEAVES else jnp.bfloat16
        return jax.ShapeDtypeStruct(shape, dtype)

    n = {"linear": cfg.count(LINEAR), "mla": cfg.count(FULL),
         "dense": cfg.first_dense, "moe": cfg.num_layers - cfg.first_dense}
    out = {kind: {name: leaf(name, (n[kind], *shape))
                  for name, shape in leaves.items()}
           for kind, leaves in _leaf_shapes(cfg).items()}
    out["embed"] = leaf("embed", (cfg.vocab_size, cfg.d_model))
    out["head"] = leaf("head", (cfg.d_model, cfg.vocab_size))
    out["final_norm"] = leaf("final_norm", (cfg.d_model,))
    return out


def init_params(cfg: GigaChat35Config, key, std: float = 0.02) -> dict:
    """Random parameters: matrices and the router N(0, std^2), norm weights
    and the correction bias nought, convolution taps N(0, 1/taps), the
    decay gates as ``olmo_hybrid.init_params`` draws them."""
    leaves, tree = jax.tree.flatten_with_path(param_shapes(cfg))
    out = []
    for (path, s), k in zip(leaves, jax.random.split(key, len(leaves))):
        name = path[-1].key
        if name == "a_log":
            x = jnp.log(jax.random.uniform(k, s.shape, jnp.float32, 1e-4, 16.))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, s.shape, jnp.float32,
                                            np.log(1e-3), np.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif "norm" in name or name == "bias":
            x = jnp.zeros(s.shape, jnp.float32)
        elif name.startswith("conv_"):
            x = jax.random.normal(k, s.shape) / np.sqrt(cfg.conv_kernel)
        else:
            x = std * jax.random.normal(k, s.shape)
        out.append(x.astype(s.dtype))
    return jax.tree.unflatten(tree, out)


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------


_mm = oh._mm


def _norm(x, w, eps):
    """Zero-centred RMSNorm: the weight is the gain's offset from 1."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def _swiglu(x, w_gate, w_up, w_down, limit):
    g, u = _mm(x, w_gate), _mm(x, w_up)
    return _mm(jax.nn.silu(jnp.minimum(g, limit)) * jnp.clip(u, -limit, limit),
               w_down)


def _rope(x, pos, inv_freq):
    """Rotary embedding on interleaved pairs ``(x[2i], x[2i+1])`` at
    frequency ``inv_freq[i]``; ``pos`` broadcasts against ``x[..., 0]``."""
    ang = pos[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _linear_out(xn, o, p, cfg):
    """The gated output of a linear layer's rule: per value head the
    zero-centred norm of ``o`` times ``2 sigmoid(z)``, then ``W_o``."""
    z = _mm(xn, p["wg"]).reshape(o.shape)
    y = _norm(o.astype(jnp.float32), p["o_norm"], cfg.rms_eps)
    return _mm((y * 2.0 * jax.nn.sigmoid(z)).reshape(*xn.shape[:-1], -1),
               p["wo"])


def _mla(xn, p, cfg, kv, layer, table, pos, q0, nq, inv_freq):
    """Latent attention of B sequences of S tokens (xn [B, S, d], normed)
    at positions ``pos`` [B, S] (past-the-table sentinel for padding and
    idle slots: nothing written): the tokens' latent rows written into row
    ``layer`` of the pool, then attended in the absorbed form."""
    B, S, _ = xn.shape
    H, dn, R = cfg.num_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    valid = pos < table.shape[1] * kv.shape[3]
    rpos = jnp.where(valid, pos, 0)
    q = _mm(_norm(_mm(xn, p["wq_a"]), p["q_a_norm"], cfg.rms_eps),
            p["wq_b"]).reshape(B, S, H, -1)
    ckr = _mm(xn, p["wkv_a"])
    row = jnp.concatenate(
        [_norm(ckr[..., :R], p["kv_a_norm"], cfg.rms_eps),
         _rope(ckr[..., R:], rpos, inv_freq)], axis=-1)      # [B, S, R + dr]
    wkv_b = p["wkv_b"].reshape(R, H, dn + cfg.v_head_dim)
    q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :dn].astype(wkv_b.dtype),
                       wkv_b[..., :dn], preferred_element_type=jnp.float32)
    qa = jnp.concatenate([q_lat, _rope(q[..., dn:], rpos[..., None],
                                       inv_freq)], axis=-1)
    kv = la.write_rows(kv, row, table, pos, layer=layer,
                       impl=cfg.latent_attention_impl)
    W = kv.shape[-1]
    qa = jnp.pad(qa, ((0, 0),) * 3 + ((0, W - qa.shape[-1]),))
    o = la.latent_attention(
        qa.reshape(B, S * H, W), kv, table, q0, nq, layer=layer, heads=H,
        value_width=R, sm_scale=cfg.sm_scale, impl=cfg.latent_attention_impl)
    v = jnp.einsum("bshr,rhv->bshv",
                   o.reshape(B, S, H, R).astype(wkv_b.dtype), wkv_b[..., dn:],
                   preferred_element_type=jnp.float32).reshape(B, S, -1)
    return _mm(v * 2.0 * jax.nn.sigmoid(_mm(xn, p["wg"])), p["wo"]), kv


def _moe(hn, p, experts, layer, cfg, valid):
    """The expert layer's output for tokens hn [N, d] (normed): the shared
    expert and the held experts' part; the tokens that are not ``valid``
    (padding, idle slots) are routed to no one. Returns (y, assignments
    [held experts])."""
    routed, load = moe.expert_share(
        hn, p["router"], p["bias"], *experts,
        layer=layer, first=cfg.first_expert, top_k=cfg.top_k,
        scale=cfg.routed_scale, limit=cfg.swiglu_limit, valid=valid)
    shared = _swiglu(hn, p["s_gate"], p["s_up"], p["s_down"],
                     cfg.swiglu_limit)
    return shared + routed, load


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class GigaChat35:
    """The decoder as two jittable serving functions over a parameter dict,
    ``prefill_chunk`` and ``decode_step``, over a
    ``serve.kv_cache.LatentCache`` that ``init_cache`` builds."""

    #: recurrent state beside the latent pool: snapshots, no speculation
    has_state = True

    def __init__(self, cfg: GigaChat35Config):
        self.cfg = cfg
        self.inv_freq = jnp.asarray(yarn_inv_freq(
            cfg.qk_rope_dim, cfg.rope_theta, cfg.rope_factor,
            cfg.rope_original_max, cfg.beta_fast, cfg.beta_slow), jnp.float32)

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
        """The engine's cache for this model: ``serve.kv_cache.LatentCache``."""
        from ..serve import kv_cache

        return kv_cache.init_latent_cache(self.cfg, num_slots, num_blocks,
                                          block_size, num_snapshots, dtype)

    def _layers(self, params, carry, layer_fn):
        """``layer_fn(kind, mixer index, mixer params, ffn kind, ffn index,
        ffn params, carry) -> carry`` over every layer: the dense ones
        first, then the rest a period a scan step. A layer's parameters are
        read from the stacks at a computed index, the expert stacks are
        handed over whole (the grouped matmul reads the layer in place)."""
        cfg = self.cfg
        kinds = cfg.layer_types
        at = {LINEAR: 0, FULL: 0}
        stack = {LINEAR: params["linear"], FULL: params["mla"]}

        def pick(tree, index):
            return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, index, keepdims=False), tree)

        for i in range(cfg.first_dense):
            kind = kinds[i]
            carry = layer_fn(kind, at[kind], pick(stack[kind], at[kind]),
                             "dense", i, pick(params["dense"], i), carry)
            at[kind] += 1
        per = {kind: cfg.period.count(kind) for kind in (LINEAR, FULL)}
        base = dict(at)
        moe_p = {n: a for n, a in params["moe"].items()
                 if n not in EXPERT_LEAVES}
        experts = tuple(params["moe"][n] for n in EXPERT_LEAVES)

        def body(carry, period):
            seen = {LINEAR: 0, FULL: 0}
            for j, kind in enumerate(cfg.period):
                index = base[kind] + period * per[kind] + seen[kind]
                ffn = period * len(cfg.period) + j
                carry = layer_fn(kind, index, pick(stack[kind], index),
                                 "moe", ffn, (pick(moe_p, ffn), experts),
                                 carry)
                seen[kind] += 1
            return carry, None

        carry, _ = jax.lax.scan(body, carry, jnp.arange(cfg.num_periods))
        return carry

    def _ffn(self, ffn_kind, index, p, h, valid):
        """The feed-forward half of a block: ``h + N4(ffn(N3(h)))`` and the
        expert layer's assignments (none for a dense layer)."""
        cfg = self.cfg
        if ffn_kind == "dense":
            hn = _norm(h, p["norm3"], cfg.rms_eps)
            y = _swiglu(hn, p["w_gate"], p["w_up"], p["w_down"],
                        cfg.swiglu_limit)
            return h + _norm(y, p["norm4"], cfg.rms_eps), None
        p, experts = p
        hn = _norm(h, p["norm3"], cfg.rms_eps)
        y, load = _moe(hn, p, experts, index, cfg, valid)
        return h + _norm(y, p["norm4"], cfg.rms_eps), load

    @staticmethod
    def _count(counts, load):
        if load is None:
            return counts
        return counts + jnp.stack([load, (load > 0).astype(jnp.int32)])

    def prefill_chunk(self, params, cache, table_row, tokens, start, length,
                      slot):
        """One chunk of the request in ``slot``: ``tokens`` [C] (padded
        past ``length``) at positions ``start`` on; the MLA layers write
        their latent rows through ``table_row``, the linear layers advance
        the slot's state and window by ``length`` tokens (from nought where
        ``start`` is 0). Returns (logits [vocab] of the last real position,
        cache)."""
        cfg = self.cfg
        C = tokens.shape[0]
        idx = jnp.arange(C, dtype=jnp.int32)
        valid = idx < length
        sentinel = table_row.shape[0] * cache.block_size
        pos = jnp.where(valid, start + idx, sentinel)[None]
        q0, nq = jnp.reshape(start, (1,)), jnp.reshape(length, (1,))
        fresh = start == 0

        def layer(kind, index, p, ffn_kind, ffn_index, ffn_p, carry):
            x, kv, state, conv, counts = carry
            xn = _norm(x, p["norm1"], cfg.rms_eps)
            if kind == LINEAR:
                o, state, conv = oh._linear_chunk(
                    xn, p, cfg, state, conv, index, slot, length, fresh)
                y = _linear_out(xn, o, p, cfg)
            else:
                y, kv = _mla(xn[None], p, cfg, kv, index, table_row[None],
                             pos, q0, nq, self.inv_freq)
                y = y[0]
            h = x + _norm(y, p["norm2"], cfg.rms_eps)
            x, load = self._ffn(ffn_kind, ffn_index, ffn_p, h, valid)
            return x, kv, state, conv, self._count(counts, load)

        x, kv, state, conv, counts = self._layers(params, (
            params["embed"][tokens].astype(jnp.float32), cache.kv,
            cache.state, cache.conv, cache.moe_counts), layer)
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=0)
        logits = oh._head_logits(_norm(last, params["final_norm"], cfg.rms_eps),
                              params["head"])[0]
        return logits, dataclasses.replace(cache, kv=kv, state=state,
                                           conv=conv, moe_counts=counts)

    def decode_step(self, params, cache, block_tables, tokens, lengths):
        """One token for every slot. ``lengths`` [slots] is each slot's
        write position; a slot that carries the past-the-table sentinel
        (idle, mid-prefill) writes no latent row, keeps its recurrent state
        and window, and is routed to no expert. Returns (logits [slots,
        vocab], cache)."""
        cfg = self.cfg
        live = lengths < block_tables.shape[1] * cache.block_size
        q0 = jnp.where(live, lengths, 0)
        nq = live.astype(jnp.int32)

        def layer(kind, index, p, ffn_kind, ffn_index, ffn_p, carry):
            x, kv, state, conv, counts = carry
            xn = _norm(x, p["norm1"], cfg.rms_eps)
            if kind == LINEAR:
                o, state, conv = oh._linear_step(xn, p, cfg, state, conv,
                                                 index, live)
                y = _linear_out(xn, o, p, cfg)
            else:
                y, kv = _mla(xn[:, None], p, cfg, kv, index, block_tables,
                             lengths[:, None], q0, nq, self.inv_freq)
                y = y[:, 0]
            h = x + _norm(y, p["norm2"], cfg.rms_eps)
            x, load = self._ffn(ffn_kind, ffn_index, ffn_p, h, live)
            return x, kv, state, conv, self._count(counts, load)

        x, kv, state, conv, counts = self._layers(params, (
            params["embed"][tokens].astype(jnp.float32), cache.kv,
            cache.state, cache.conv, cache.moe_counts), layer)
        logits = oh._head_logits(_norm(x, params["final_norm"], cfg.rms_eps),
                              params["head"])
        return logits, dataclasses.replace(cache, kv=kv, state=state,
                                           conv=conv, moe_counts=counts)
