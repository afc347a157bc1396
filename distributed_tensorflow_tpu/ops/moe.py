"""Mixture-of-Experts layer — expert parallelism over the ``expert`` mesh axis.

The reference has no MoE (SURVEY.md §2c marks EP as a new-framework
capability on the same collective substrate as Ulysses: `lax.all_to_all`
token dispatch over an `expert` mesh axis). TPU-first design:

- **Dispatch by einsum, not gather**: tokens are routed with one-hot
  dispatch/combine tensors contracted by einsums (the Mesh-TensorFlow /
  Switch-Transformer pattern). Static shapes — capacity-bounded expert
  buffers — so XLA can tile the expert FFNs on the MXU, and with the expert
  dimension sharded over the ``expert`` axis GSPMD lowers the dispatch
  einsum to exactly the all_to_all exchange of a hand-written EP backend.
- **Capacity + drop**: each expert processes at most
  ``ceil(top_k · T · capacity_factor / E)`` tokens per batch; overflow
  tokens are dropped (residual connection carries them) — lockstep SPMD
  needs shape-static buffers, the TPU analog of the reference's unbounded
  PS queues.
- **Router in f32**: routing logits/softmax stay f32 (bf16 elsewhere), the
  same precision split as attention softmax.
- **Load-balance aux loss** (Switch §2.2): E · Σ_e f_e · p̄_e, sown into
  the ``losses`` collection so loss adapters can pick it up without
  threading it through every return value.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..parallel import mesh as mesh_lib


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    d_model: int = 512
    d_ff: int = 2048
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    dtype: str = "bfloat16"
    # Tokens are routed within fixed-size groups (the Mesh-TF/Switch group
    # dimension): capacity is per-group, so dispatch/combine memory is
    # O(T·group) instead of O(T²). 0 = auto (largest divisor of T ≤ 1024).
    group_size: int = 0
    # "einsum": one-hot dispatch/combine contractions (Mesh-TF/Switch) —
    #   MXU-dense, and GSPMD lowers the sharded-E einsum to the EP
    #   all_to_all; FLOPs O(G²·top_k·cf·D) per group.
    # "scatter": position-indexed scatter/gather into the expert buffers —
    #   FLOPs/memory linear in G (the sorted-dispatch style every
    #   large-scale MoE eventually needs); same routing, same drops.
    dispatch_impl: str = "einsum"


def moe_rules() -> list[tuple[str, P]]:
    """Path rules: expert dim over `expert`, FFN hidden dim over `model`
    (EP × TP compose); router stays replicated.

    Patterns anchor on the parameter *leaf* names (``w_in``/``b_in``/
    ``w_out``/``b_out``), so the rules match wherever the module is
    mounted — bare, or under any parent scope — instead of silently
    returning replicated specs when the parent isn't literally called
    'moe' (round-1 advisor finding). CAVEAT: these leaf names are not
    globally unique — do NOT apply moe_rules to a tree whose dense FFN
    weights use the same leaf names (2-D) — the 3-axis expert spec would
    mis-rank onto them. In-tree models either use flax ``mlp_in/mlp_out``
    names or build their specs directly, so there is no live collision."""
    return [
        (r"(^|/)w_in$", P(mesh_lib.EXPERT, None, mesh_lib.MODEL)),
        (r"(^|/)b_in$", P(mesh_lib.EXPERT, mesh_lib.MODEL)),
        (r"(^|/)w_out$", P(mesh_lib.EXPERT, mesh_lib.MODEL, None)),
        (r"(^|/)b_out$", P(mesh_lib.EXPERT, None)),
    ]


def expert_capacity(num_tokens: int, cfg: MoEConfig) -> int:
    return max(
        1,
        -(-int(cfg.top_k * num_tokens * cfg.capacity_factor) // cfg.num_experts),
    )


def resolve_group_size(num_tokens: int, cfg: MoEConfig) -> int:
    """Routing-group size: must divide T. Auto = largest divisor ≤ 1024."""
    if cfg.group_size > 0:
        if num_tokens % cfg.group_size != 0:
            raise ValueError(
                f"group_size={cfg.group_size} must divide tokens={num_tokens}"
            )
        return cfg.group_size
    g = min(num_tokens, 1024)
    while num_tokens % g != 0:
        g -= 1
    return g


def _greedy_slots(probs: jax.Array, capacity: int, top_k: int):
    """Shared routing decision for both dispatch impls. probs [T, E] →
    per-slot arrays (choice [k,T] int, pos [k,T] int, keep [k,T] bool,
    gate [k,T] f32) and the aux loss. Greedy per-slot: slot j sends each
    token to its j-th choice expert if that expert still has capacity
    (position = running count of tokens already routed there, across
    slots — so (expert, position) pairs are unique across ALL slots)."""
    T, E = probs.shape
    remaining = probs
    fill = jnp.zeros((E,), jnp.int32)  # tokens assigned per expert so far
    choices, positions, keeps, gates = [], [], [], []
    for _ in range(top_k):
        choice = jnp.argmax(remaining, axis=-1)  # [T]
        onehot = jax.nn.one_hot(choice, E, dtype=probs.dtype)  # [T, E]
        # position of each token in its chosen expert's buffer
        pos = fill[None, :] + (jnp.cumsum(onehot, axis=0) - onehot).astype(
            jnp.int32
        )
        my_pos = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # [T]
        keep = my_pos < capacity
        gate = jnp.sum(probs * onehot, axis=-1)  # [T]
        choices.append(choice); positions.append(my_pos)
        keeps.append(keep); gates.append(gate)
        kept_oh = onehot * keep[:, None].astype(probs.dtype)
        fill = fill + jnp.sum(kept_oh, axis=0).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)
    choice = jnp.stack(choices); pos = jnp.stack(positions)
    keep = jnp.stack(keeps); gate = jnp.stack(gates)
    if top_k > 1:
        # renormalize gates over the KEPT choices (top-k gates sum to 1)
        denom = jnp.sum(gate * keep, axis=0, keepdims=True)
        gate = gate / jnp.maximum(denom, 1e-9)
    # top_k == 1 keeps the RAW gate probability (Switch Transformer §2.1):
    # renormalizing would make the gate exactly 1.0 and cut the router off
    # from the main-loss gradient (round-1 advisor finding).
    # Switch load-balance loss on first-choice statistics
    first = jax.nn.one_hot(jnp.argmax(probs, -1), E, dtype=probs.dtype)
    aux = E * jnp.sum(first.mean(axis=0) * probs.mean(axis=0))
    return choice, pos, keep, gate, aux


def top_k_routing(probs: jax.Array, capacity: int, top_k: int):
    """probs [T, E] → (dispatch [T, E, C] 0/1, combine [T, E, C] weights,
    aux_loss scalar) — the one-hot ("einsum") form of :func:`_greedy_slots`."""
    T, E = probs.shape
    choice, pos, keep, gate, aux = _greedy_slots(probs, capacity, top_k)
    dispatch = jnp.zeros((T, E, capacity), probs.dtype)
    combine = jnp.zeros((T, E, capacity), probs.dtype)
    for j in range(top_k):
        d = (
            jax.nn.one_hot(choice[j], E, dtype=probs.dtype)[:, :, None]
            * jax.nn.one_hot(pos[j], capacity, dtype=probs.dtype)[:, None, :]
            * keep[j][:, None, None].astype(probs.dtype)
        )
        dispatch = dispatch + d
        combine = combine + gate[j][:, None, None] * d
    return dispatch, combine, aux


def _scatter_expert_ffn(tokens, probs, capacity, top_k, apply_ffn, dtype):
    """Linear-memory dispatch: scatter tokens into [E*C, D] expert buffers
    at their (expert, position) slot, run the FFN, gather back weighted by
    the gates. (expert, position) uniqueness across slots (see
    _greedy_slots) makes the scatter collision-free; dropped tokens target
    a sentinel row that is sliced off."""
    T, D = tokens.shape
    E = probs.shape[-1]
    choice, pos, keep, gate, aux = _greedy_slots(probs, capacity, top_k)
    flat_idx = jnp.where(keep, choice * capacity + pos, E * capacity)  # [k,T]
    buf = jnp.zeros((E * capacity + 1, D), dtype)
    for j in range(top_k):
        buf = buf.at[flat_idx[j]].add(tokens)
    expert_in = buf[:-1].reshape(E, capacity, D)
    out = apply_ffn(expert_in)  # [E, C, D]
    out_flat = jnp.concatenate(
        [out.reshape(E * capacity, D), jnp.zeros((1, D), out.dtype)], axis=0
    )
    y = jnp.zeros((T, D), dtype)
    for j in range(top_k):
        y = y + out_flat[flat_idx[j]] * (
            gate[j] * keep[j].astype(gate.dtype)
        )[:, None].astype(dtype)
    return y, aux


class MoEMLP(nn.Module):
    """Drop-in replacement for a transformer FFN block: [B, S, D] → [B, S, D].

    Expert weights live as [E, ...] arrays; `moe_rules()` shards the E dim
    over the `expert` mesh axis, so the dispatch/combine einsums become
    all_to_all exchanges under GSPMD."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        assert D == cfg.d_model, (D, cfg.d_model)
        T = B * S
        tokens = x.reshape(T, D)

        logits = nn.Dense(
            cfg.num_experts, dtype=jnp.float32, name="router",
            kernel_init=nn.initializers.normal(0.02),
        )(tokens.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        # group the token axis: capacity (and the [g, E, C] one-hots) are
        # per-group, so memory is linear in T, not quadratic
        G = resolve_group_size(T, cfg)
        n_groups = T // G
        probs_g = probs.reshape(n_groups, G, cfg.num_experts)
        C = expert_capacity(G, cfg)

        w_in = self.param(
            "w_in", nn.initializers.normal(0.02),
            (cfg.num_experts, D, cfg.d_ff), jnp.float32,
        )
        b_in = self.param(
            "b_in", nn.initializers.zeros, (cfg.num_experts, cfg.d_ff),
            jnp.float32,
        )
        w_out = self.param(
            "w_out", nn.initializers.normal(0.02),
            (cfg.num_experts, cfg.d_ff, D), jnp.float32,
        )
        b_out = self.param(
            "b_out", nn.initializers.zeros, (cfg.num_experts, D), jnp.float32,
        )
        tokens_g = tokens.reshape(n_groups, G, D).astype(dtype)

        if cfg.dispatch_impl == "einsum":
            dispatch, combine, aux = jax.vmap(
                lambda p: top_k_routing(p, C, cfg.top_k)
            )(probs_g)  # [n, G, E, C] ×2, aux [n]
            aux = aux.mean()
            # dispatch: [n,G,E,C] × [n,G,D] → expert buffers [n,E,C,D]
            expert_in = jnp.einsum("ngec,ngd->necd", dispatch.astype(dtype),
                                   tokens_g)
            h = jnp.einsum("necd,edf->necf", expert_in, w_in.astype(dtype))
            h = nn.gelu(h + b_in[None, :, None, :].astype(dtype))
            out = jnp.einsum("necf,efd->necd", h, w_out.astype(dtype))
            out = out + b_out[None, :, None, :].astype(dtype)
            # combine: [n,G,E,C] × [n,E,C,D] → [n,G,D]; dropped → zeros
            y = jnp.einsum("ngec,necd->ngd", combine.astype(dtype), out)
        elif cfg.dispatch_impl == "scatter":

            def ffn(expert_in):  # [E, C, D] → [E, C, D]
                h = jnp.einsum("ecd,edf->ecf", expert_in, w_in.astype(dtype))
                h = nn.gelu(h + b_in[:, None, :].astype(dtype))
                out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(dtype))
                return out + b_out[:, None, :].astype(dtype)

            y, aux_g = jax.vmap(
                lambda t, p: _scatter_expert_ffn(
                    t, p, C, cfg.top_k, ffn, dtype
                )
            )(tokens_g, probs_g)
            aux = aux_g.mean()
        else:
            raise ValueError(f"Unknown dispatch_impl {cfg.dispatch_impl!r}")

        self.sow(
            "losses", "moe_aux", cfg.router_aux_weight * aux,
            init_fn=lambda: jnp.zeros((), jnp.float32),
            reduce_fn=lambda a, b: a + b,
        )
        return y.reshape(B, S, D)


def collect_aux_loss(variables: Any) -> jax.Array:
    """Sum every sown `losses` entry (zero if none) — call on the mutated
    collections returned by ``model.apply(..., mutable=['losses'])``."""
    losses = variables.get("losses", {}) if isinstance(variables, dict) else {}
    leaves = jax.tree.leaves(losses)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return sum(jnp.sum(l) for l in leaves)


def flops_per_token(cfg: MoEConfig) -> float:
    """Fwd FLOPs per token: top_k experts' FFN matmuls (router negligible)."""
    return cfg.top_k * 2.0 * 2.0 * cfg.d_model * cfg.d_ff


# ---------------------------------------------------------------------------
# Expert share: dropless, sorted dispatch to the experts this device holds
# (the layer of an expert-parallel deployment, without its exchange)
# ---------------------------------------------------------------------------


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


#: rows of a group tile: a decode step of some tens of slots gives a held
#: expert a few assignments, a prefill chunk some tens
DECODE_TILE_ROWS, PREFILL_TILE_ROWS = 16, 128
GMM_VMEM_LIMIT = 64 * 1024 * 1024  # of the v5e's 128 MiB; the default is 16


def _divisor_tile(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is at most ``most`` and a multiple
    of the 128 lanes, or ``n`` itself where there is none."""
    for t in range(min(n, most) // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n


def _gmm_kernel(te_ref, nr_ref, ly_ref, x_ref, *refs, n_w, limit):
    """One row tile of one held expert against one column tile of its
    weights, accumulated over the contraction's tiles. With two weight
    operands (gate, up) the result is the clamped SwiGLU of the two
    products; with one, the product. A tile past the real ones computes and
    writes nothing (its blocks are the last real tile's, so nothing is
    fetched or written back either)."""
    del te_ref, ly_ref
    w_refs, o_ref, acc_refs = refs[:n_w], refs[n_w], refs[n_w + 1:]
    t, k = pl.program_id(0), pl.program_id(2)

    @pl.when(t < nr_ref[0])
    def _tile():
        @pl.when(k == 0)
        def _init():
            for acc in acc_refs:
                acc[...] = jnp.zeros_like(acc)

        x = x_ref[...]
        for w, acc in zip(w_refs, acc_refs):
            acc[...] += jnp.dot(x, w[0, 0],
                                preferred_element_type=jnp.float32)

        @pl.when(k == pl.num_programs(2) - 1)
        def _out():
            o_ref[...] = _finish([a[...] for a in acc_refs], limit).astype(
                o_ref.dtype)


def _finish(products, limit):
    """Two products (gate, up): the SwiGLU with its inputs clamped at
    ``limit`` (the gate from above, the up product both ways); one: as it
    is."""
    if len(products) == 1:
        return products[0]
    g, u = products
    return jax.nn.silu(jnp.minimum(g, limit)) * jnp.clip(u, -limit, limit)


@functools.partial(jax.jit, static_argnames=(
    "tile_rows", "limit", "out_dtype", "interpret"))
def _gmm_pallas(x, weights, tile_expert, n_real, layer, *, tile_rows, limit,
                out_dtype, interpret):
    M, K = x.shape
    N = weights[0].shape[-1]
    tm, n_w = tile_rows, len(weights)
    tk = _divisor_tile(K, 3584)
    tn = _divisor_tile(N, 512 if n_w == 2 else 1024)
    nk, nn_ = K // tk, N // tn

    def at(live, i, last):
        return jnp.where(live, i, last)

    def x_map(t, n, k, te, nr, ly):
        live = t < nr[0]
        return at(live, t, jnp.maximum(nr[0] - 1, 0)), at(live, k, nk - 1)

    def w_map(t, n, k, te, nr, ly):
        live = t < nr[0]
        return ly[0], te[t], at(live, k, nk - 1), at(live, n, nn_ - 1)

    def o_map(t, n, k, te, nr, ly):
        live = t < nr[0]
        return at(live, t, jnp.maximum(nr[0] - 1, 0)), at(live, n, nn_ - 1)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, n_w=n_w, limit=limit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(M // tm, nn_, nk),
            in_specs=[pl.BlockSpec((tm, tk), x_map)]
            + [pl.BlockSpec((1, 1, tk, tn), w_map)] * n_w,
            out_specs=pl.BlockSpec((tm, tn), o_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * n_w,
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=GMM_VMEM_LIMIT),
        interpret=interpret,
        name="moe_grouped_mm",
    )(tile_expert, n_real.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1),
      x, *weights)


def moe_grouped_mm(x, weights, tile_expert, n_real, *, layer, tile_rows,
                   limit=None, out_dtype=jnp.float32, impl="auto"):
    """Grouped matmul over held experts' row groups. ``x`` [M, K] holds
    ``M / tile_rows`` tiles of rows, tile ``t`` all of expert
    ``tile_expert[t]``'s (a group padded to whole tiles), the first
    ``n_real`` tiles real; ``weights`` is one stack [layers, experts, K, N]
    (the product) or two (gate and up: the clamped SwiGLU of the two
    products, at ``limit``), of which row ``layer`` is read in place.
    Returns [M, N] in ``out_dtype``; rows of tiles past ``n_real`` are not
    written (the caller reads none of them). ``impl``: the Pallas kernel
    ``moe_grouped_mm`` (``"pallas"``; the interpreter off the TPU), the
    same in ``jax.numpy`` (``"plain"``), or by backend (``"auto"``)."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "plain"
    weights = tuple(weights)
    n_real = jnp.asarray(n_real, jnp.int32)
    if impl == "pallas":
        return _gmm_pallas(x, weights, tile_expert.astype(jnp.int32), n_real,
                           layer, tile_rows=tile_rows, limit=limit,
                           out_dtype=jnp.dtype(out_dtype),
                           interpret=not _on_tpu())
    if impl != "plain":
        raise ValueError(f"grouped matmul impl must be 'auto', 'plain' or "
                         f"'pallas', got {impl!r}")
    tiles = x.reshape(-1, tile_rows, x.shape[-1])
    out = _finish([jnp.einsum("tmk,tkn->tmn", tiles, w[layer][tile_expert],
                              preferred_element_type=jnp.float32)
                   for w in weights], limit)
    return out.reshape(x.shape[0], -1).astype(out_dtype)


def sigmoid_route(x, router_w, bias, *, top_k, scale):
    """DeepSeek-V3 routing over every expert of the layer: scores
    ``s = sigmoid(x W_r)`` in float32, the ``top_k`` experts by ``s + b``
    (``b`` the per-expert correction, used for the choice only), weights
    ``s / sum(s of the chosen) * scale``. Returns (experts [T, k], weights
    [T, k] float32)."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), router_w,
                                  precision=jax.lax.Precision.HIGHEST))
    _, top = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.take_along_axis(s, top, axis=-1)
    return top, chosen / chosen.sum(-1, keepdims=True) * scale


def expert_share(x, router_w, bias, w_gate, w_up, w_down, *, layer, first,
                 top_k, scale, limit, valid=None, impl="auto"):
    """The routed part of a sigmoid-routed MoE layer that one device of an
    expert-parallel deployment computes: routing over ALL the layer's
    experts (``router_w`` [d, E_all]), then the assignments to the experts
    this device holds, ``[first, first + E)`` (``w_gate``/``w_up`` [layers,
    E, d, f], ``w_down`` [layers, E, f, d], row ``layer``), sorted by
    expert with no capacity and nothing dropped; assignments to experts
    held elsewhere contribute nothing here.

    The held experts' rows form contiguous groups, each padded to whole
    tiles of ``DECODE_TILE_ROWS`` or ``PREFILL_TILE_ROWS`` rows, and one
    grouped matmul kernel (``moe_grouped_mm``) runs the gate and up
    products with the clamped SwiGLU, another the down product. The tiles
    are counted for the worst case (every assignment held), so the shapes
    are static; tiles past the real ones do nothing.

    x [T, d] float32; ``valid`` [T] (all by default): tokens that are
    not (padding, idle slots) are assigned to no expert. Returns (y [T, d]
    float32, assignments [E] int32: the count of local assignments each
    held expert received)."""
    T, d = x.shape
    E = w_gate.shape[1]
    top, weight = sigmoid_route(x, router_w, bias, top_k=top_k, scale=scale)
    local = top - first
    held = (local >= 0) & (local < E)
    if valid is not None:
        held = held & valid[:, None]
    flat = jnp.where(held, local, E).reshape(-1)                # [T k]
    counts = jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]
    tm = DECODE_TILE_ROWS if T <= 64 else PREFILL_TILE_ROWS
    n_tiles = -(-min(T * top_k, T * E) // tm) + E
    M = n_tiles * tm
    tiles = -(-counts // tm)
    ends = jnp.cumsum(tiles)                                    # in tiles
    n_real = ends[-1]
    # each assignment's row: its group's padded start plus its rank in the
    # group; an assignment held elsewhere goes to row M (dropped)
    order = jnp.argsort(flat, stable=True)
    by_expert = flat[order]
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * top_k) - jnp.append(starts, 0)[by_expert]
    pstart = jnp.append((ends - tiles) * tm, M)
    row_sorted = jnp.where(by_expert < E, pstart[by_expert] + rank, M)
    row = jnp.zeros_like(flat).at[order].set(row_sorted)        # [T k]
    xs = jnp.zeros((M, d), w_gate.dtype).at[row].set(
        jnp.repeat(x.astype(w_gate.dtype), top_k, axis=0), mode="drop")
    # tile -> expert; tiles past the real ones keep the last real one's
    tile_expert = jnp.searchsorted(
        ends, jnp.minimum(jnp.arange(n_tiles), jnp.maximum(n_real - 1, 0)),
        side="right").astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, E - 1)
    h = moe_grouped_mm(xs, (w_gate, w_up), tile_expert, n_real, layer=layer,
                       tile_rows=tm, limit=limit, out_dtype=w_down.dtype,
                       impl=impl)
    ys = moe_grouped_mm(h, (w_down,), tile_expert, n_real, layer=layer,
                        tile_rows=tm, out_dtype=jnp.float32, impl=impl)
    held = held.reshape(-1)
    got = jnp.where(held[:, None], ys[jnp.minimum(row, M - 1)], 0.0)
    y = (got.reshape(T, top_k, d) * weight[..., None]).sum(1)
    return y, counts
