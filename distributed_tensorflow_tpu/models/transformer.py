"""Transformer family: BERT-style encoder (MLM) and causal decoder (LM).

Reference analog: the harness's BERT-base distributed train script
(SURVEY.md §2a 'Model fns' row; BASELINE.json:10) — a raw-TF graph whose
variables `replica_device_setter` scattered over PS tasks. TPU-first
choices here:

- **bf16 compute, f32 LayerNorm/softmax**: matmuls hit the MXU in
  bfloat16; normalization statistics and attention logits stay f32.
- **Tensor parallelism by layout, not code**: parameters are plain flax
  params; `tp_rules()` returns the path-regex → PartitionSpec table
  (megatron column/row pattern) and GSPMD inserts the all-gather /
  reduce-scatter. Swapping TP degree touches zero model code
  (parallel/sharding.py design).
- **Attention dispatch**: dense oracle (ops/attention.py), Pallas flash
  kernel on TPU (ops/flash_attention.py), or sequence-parallel schedules
  (ring/ulysses/allgather, parallel/ring_attention.py) when the mesh has
  a `seq` axis — selected by config, same module code.
- **Tied embeddings**: the MLM/LM head attends the input embedding table
  (one [vocab, d_model] matrix, vocab-shardable over `model`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..data.text import IGNORE_INDEX  # single sentinel shared with the data layer
from ..ops.attention import attention_reference, blockwise_attention
from ..ops.flash_attention import LANES, flash_attention
from ..ops.moe import collect_aux_loss
from ..parallel import mesh as mesh_lib
from ..parallel import sharding
from ..parallel.ring_attention import sequence_parallel_attention
from ..utils import flops as flops_lib


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 30528  # BERT vocab rounded up to a multiple of 128
    max_len: int = 512
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    d_ff: int = 3072
    dropout: float = 0.1
    causal: bool = False         # False = bidirectional encoder (BERT)
    pre_ln: bool = False         # BERT is post-LN; decoders default pre-LN
    dtype: str = "bfloat16"
    # "auto": flash kernel on TPU, dense reference elsewhere.
    # "dense" | "blockwise" | "flash" force an implementation.
    attention_impl: str = "auto"
    # Paged-decode attention dispatch (ops.attention.paged_attention):
    # "auto" = block-table Pallas kernel on TPU, gather-free fused einsum
    # elsewhere; "gather" forces the PR-13 gather-then-attend path (the
    # exact-parity escape hatch); "fused" | "pallas" force those.
    paged_attention_impl: str = "auto"
    # None = no sequence parallelism; "ring"|"ulysses"|"allgather" engage
    # when the model is built with a mesh whose seq axis > 1.
    seq_impl: str | None = None
    # MoE: 0 = dense FFN everywhere; >0 = every `moe_every`-th block swaps
    # its FFN for a MoEMLP with this many experts (ops/moe.py; expert dim
    # shards over the `expert` mesh axis via moe_rules()).
    num_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # Fuse LayerNorm into the following matmul's prologue via the Pallas
    # kernel (ops/fused_ln_matmul.py): the normalized tensor between
    # ln1→q/k/v and ln2→mlp_in never hits HBM. Pre-LN only (post-LN's
    # LayerNorm output IS the residual stream — it must materialize), and
    # incompatible with a model-axis (TP) sharded mesh (the kernel isn't
    # shard_map-wrapped here). Same param tree as the unfused path.
    fused_ln_matmul: bool = False
    # Rematerialize each Block on the backward pass (jax.checkpoint via
    # nn.remat): activation memory drops from O(L) blocks to O(1) at the
    # cost of one extra forward — the TPU-native descendant of TF's
    # recompute_grad, and the standard lever for long-sequence/large-batch
    # HBM pressure (task brief: trade FLOPs for memory).
    remat: bool = False
    # Project q/k/v with ONE [d, 3·d] matmul ("qkv") instead of three
    # [d, d] matmuls — one larger MXU call, one read of the residual
    # stream instead of three (megatron-style fused QKV). GSPMD path
    # only: incompatible with fused_ln_matmul (which owns its own
    # projections) and with manual TP islands (tp_shards > 1); GSPMD TP
    # shards the fused kernel columns via tp_rules and reshards to heads.
    # Param tree differs from the unfused layout (qkv/{kernel,bias}).
    fused_qkv: bool = False
    # >0: causal-LM training loss runs the vocab projection + xent per
    # sequence chunk of this size (chunked_lm_loss_fn) so the [B, S,
    # vocab] logits tensor never materializes — required for large-vocab
    # LMs at real batch sizes (13 GB f32 at B=128, S=512, V=50304).
    # 0 = dense loss. Identical math either way (parity-tested).
    xent_chunk: int = 0
    # Input dtype of the tied-embedding vocab projection. "float32"
    # (default) is the exact path; "bfloat16" runs the head matmul on
    # the fast MXU tier with f32 accumulation — the standard LLM head
    # recipe. At GPT-2 shapes the head is ~25-30% of model FLOPs and an
    # f32 matmul runs at ~1/4 the bf16 MXU rate, so this is a large
    # lever for causal LMs; softmax/xent always run in f32 regardless.
    head_dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.num_heads == 0
        return self.d_model // self.num_heads


def bert_base() -> TransformerConfig:
    """BERT-base/uncased shape (BASELINE.json:10)."""
    return TransformerConfig()


def gpt_small(causal_len: int = 1024) -> TransformerConfig:
    """Decoder-only LM, GPT-2-small shape — pre-LN, causal."""
    return TransformerConfig(
        vocab_size=50304, max_len=causal_len, num_layers=12, d_model=768,
        num_heads=12, d_ff=3072, causal=True, pre_ln=True,
    )


# ---------------------------------------------------------------------------
# Tensor-parallel layout (megatron column/row pattern) — the rules table
# ---------------------------------------------------------------------------

#: Static param-path coverage fixture for TRANSFORMER_RULES: the UNION of
#: the three shipped tree variants at num_layers=2 — BERT encoder
#: (post-LN, split q/k/v, dense MLP), causal pre-LN decoder with
#: fused_qkv, and the MoE interleave (num_experts>0, moe_every=2).
#: tests/test_sharding.py::test_transformer_coverage_fixture_is_live
#: regenerates this union from the real models and pins it; the dtflint
#: shard-rules-coverage rule re-checks totality/liveness against it on
#: every lint run.
#: (fully literal — the dtflint shard-rules-coverage rule reads it
#: statically, so no comprehension/format indirection)
_TRANSFORMER_COVERAGE = (
    "embed_ln/bias", "embed_ln/scale", "final_ln/bias", "final_ln/scale",
    "layer_0/attn/attn_out/bias", "layer_0/attn/attn_out/kernel",
    "layer_0/attn/key/bias", "layer_0/attn/key/kernel",
    "layer_0/attn/qkv/bias", "layer_0/attn/qkv/kernel",
    "layer_0/attn/query/bias", "layer_0/attn/query/kernel",
    "layer_0/attn/value/bias", "layer_0/attn/value/kernel",
    "layer_0/ln1/bias", "layer_0/ln1/scale", "layer_0/ln2/bias",
    "layer_0/ln2/scale", "layer_0/mlp_in/bias", "layer_0/mlp_in/kernel",
    "layer_0/mlp_out/bias", "layer_0/mlp_out/kernel",
    "layer_1/attn/attn_out/bias", "layer_1/attn/attn_out/kernel",
    "layer_1/attn/key/bias", "layer_1/attn/key/kernel",
    "layer_1/attn/qkv/bias", "layer_1/attn/qkv/kernel",
    "layer_1/attn/query/bias", "layer_1/attn/query/kernel",
    "layer_1/attn/value/bias", "layer_1/attn/value/kernel",
    "layer_1/ln1/bias", "layer_1/ln1/scale", "layer_1/ln2/bias",
    "layer_1/ln2/scale", "layer_1/mlp_in/bias", "layer_1/mlp_in/kernel",
    "layer_1/mlp_out/bias", "layer_1/mlp_out/kernel", "layer_1/moe/b_in",
    "layer_1/moe/b_out", "layer_1/moe/router/bias",
    "layer_1/moe/router/kernel", "layer_1/moe/w_in", "layer_1/moe/w_out",
    "mlm_bias", "mlm_ln/bias", "mlm_ln/scale", "mlm_transform/bias",
    "mlm_transform/kernel", "pos_embed", "tok_embed/embedding",
)

#: The Transformer family's partition-rules table (parallel/sharding.py
#: engine; docs/parallelism.md "Authoring partition-rules tables").
#: Column-parallel in (output dim over `model`), row-parallel out (input
#: dim over `model`) — one all-reduce per block half, placed by GSPMD on
#: ICI. Variant-conditional rows carry tags; ``transformer_rules(cfg)``
#: selects the exact table for a config, so a dead row (or a param the
#: table forgot) is a hard PartitionCoverageError, not a silent layout.
#: The four MoE rows mirror ops.moe.moe_rules() (pinned by
#: tests/test_sharding.py::test_transformer_moe_rows_mirror_moe_rules).
TRANSFORMER_RULES = sharding.partition_rules(
    "transformer",
    (
        # MoE experts first: "moe/w_in" must not fall through to the
        # dense "mlp_in" patterns (first-match precedence)
        (r"(^|/)w_in$", P(mesh_lib.EXPERT, None, mesh_lib.MODEL), "moe"),
        (r"(^|/)b_in$", P(mesh_lib.EXPERT, mesh_lib.MODEL), "moe"),
        (r"(^|/)w_out$", P(mesh_lib.EXPERT, mesh_lib.MODEL, None), "moe"),
        (r"(^|/)b_out$", P(mesh_lib.EXPERT, None), "moe"),
        (r"(query|key|value)/kernel", P(None, mesh_lib.MODEL), "split_qkv"),
        (r"(query|key|value)/bias", P(mesh_lib.MODEL), "split_qkv"),
        (r"qkv/kernel", P(None, mesh_lib.MODEL), "fused_qkv"),
        (r"qkv/bias", P(mesh_lib.MODEL), "fused_qkv"),
        (r"attn_out/kernel", P(mesh_lib.MODEL, None)),
        (r"mlp_in/kernel", P(None, mesh_lib.MODEL), "dense_mlp"),
        (r"mlp_in/bias", P(mesh_lib.MODEL), "dense_mlp"),
        (r"mlp_out/kernel", P(mesh_lib.MODEL, None), "dense_mlp"),
        (r"tok_embed/embedding", P(mesh_lib.MODEL, None)),  # vocab-sharded
        (r"mlm_bias", P(mesh_lib.MODEL)),
        # everything else (LayerNorms, pos_embed, biases of row-parallel
        # projections, the MoE router) is DECLARED replicated
        (sharding.CATCH_ALL, sharding.REPLICATED),
    ),
    coverage=_TRANSFORMER_COVERAGE,
)


def transformer_rules(cfg: TransformerConfig) -> sharding.PartitionRules:
    """The exact rules table for ``cfg``'s param tree: variant rows
    (split vs fused QKV, MoE experts, dense MLP) selected so that
    match_partition_rules' dead-rule check holds — a config/table
    mismatch fails loudly with the full attribution listing."""
    tags = ["fused_qkv" if cfg.fused_qkv else "split_qkv"]
    n_moe = sum(
        1 for i in range(cfg.num_layers)
        if cfg.num_experts > 0 and i % cfg.moe_every == cfg.moe_every - 1
    )
    if n_moe:
        tags.append("moe")
    if n_moe < cfg.num_layers:
        tags.append("dense_mlp")
    return TRANSFORMER_RULES.select(*tags)


def tp_rules():
    """Legacy soft form of :data:`TRANSFORMER_RULES` (every variant row,
    replicate-on-miss semantics) — kept for ad-hoc trees and the
    pre-engine call sites; shipped workloads use
    :func:`transformer_rules`."""
    return TRANSFORMER_RULES.as_path_rules()


# ---------------------------------------------------------------------------
# Modules
# ---------------------------------------------------------------------------


class _LNParams(nn.Module):
    """LayerNorm scale/bias params only (flax naming) — the fused
    ln_matmul path owns the math, this scope owns the tree."""

    features: int

    @nn.compact
    def __call__(self):
        return (
            self.param("scale", nn.initializers.ones, (self.features,)),
            self.param("bias", nn.initializers.zeros, (self.features,)),
        )


class _DenseParams(nn.Module):
    """nn.Dense-compatible kernel/bias params (same shapes, inits, tree)."""

    features: int
    in_features: int

    @nn.compact
    def __call__(self):
        return (
            self.param("kernel", nn.initializers.normal(0.02),
                       (self.in_features, self.features)),
            self.param("bias", nn.initializers.zeros, (self.features,)),
        )


def _row_parallel_dense(h, out_features, in_features_local, name, dtype,
                        parent):
    """Megatron row-parallel projection inside a shard_map island: local
    [in_local, out] slice computes a partial sum, psum over `model`
    reduces it, the (replicated) bias is added ONCE after the reduce.
    Shared by attn_out and mlp_out so the two cannot drift."""
    w, b = _DenseParams(out_features, in_features_local, name=name,
                        parent=parent)()
    y = jnp.dot(h, w.astype(dtype), preferred_element_type=jnp.float32)
    y = jax.lax.psum(y, mesh_lib.MODEL)
    return (y + b).astype(dtype)


def _flash_on_mesh(q, k, v, kv_mask, *, causal: bool, mesh):
    """The flash kernel under a GSPMD step. A Mosaic kernel is opaque to
    the SPMD partitioner — jax refuses to lower one inside a multi-device
    jit ("Mosaic kernels cannot be automatically partitioned") — so on a
    mesh the call runs per device under ``shard_map``: batch over
    (data, fsdp), heads over ``model``, the layout ``transformer_rules``
    already gives q/k/v. Where the arrays are already per-device —
    ``mesh=None`` (single device, the pipeline island) or a caller's own
    ``shard_map`` over this mesh (the sharded evaluator) — it is called
    directly; ``shard_map`` does not nest."""
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    if mesh is None or mesh.size == 1 or manual == set(mesh.axis_names):
        return flash_attention(q, k, v, causal=causal, kv_mask=kv_mask)
    heads, shards = q.shape[1], mesh.shape[mesh_lib.MODEL]
    if heads % shards:
        raise ValueError(
            f"heads ({heads}) not divisible by the model axis ({shards})")
    qkv_spec = P(mesh_lib.BATCH_AXES, mesh_lib.MODEL, None, None)
    return jax.shard_map(
        lambda q, k, v, m: flash_attention(
            q, k, v, causal=causal, kv_mask=m),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec,
                  None if kv_mask is None else P(mesh_lib.BATCH_AXES, None)),
        out_specs=qkv_spec,
        check_vma=False,  # the kernel is per-(batch, head): nothing varies
    )(q, k, v, kv_mask)


class SelfAttention(nn.Module):
    cfg: TransformerConfig
    mesh: Any = None  # jax.sharding.Mesh or None; static module metadata
    # >1 = MANUAL megatron tensor parallelism for shard_map islands (the
    # pipelined path): this instance sees LOCAL column slices of q/k/v
    # (H/tp heads) and a LOCAL row slice of attn_out, and reduces the
    # out-projection with an explicit psum over the `model` axis. Mutually
    # exclusive with GSPMD TP (tp_rules), which shards the SAME math from
    # outside jit. Param tree paths/full shapes are identical either way.
    tp_shards: int = 1

    @nn.compact
    def __call__(self, x, mask, *, train: bool, ln_params=None,
                 cache=None, decode_pos=None):
        # ``cache`` = {"k","v"} [B,H,M,D] per-layer KV buffers (serve/
        # kv_cache.py) and ``decode_pos`` [B,S] the absolute positions of
        # the S incoming tokens: the new k/v are written at those offsets
        # and attention runs over the updated buffers via the masked dense
        # path (ops.attention.cached_attention) — returns (out, new_cache).
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        if cache is not None and self.tp_shards > 1:
            raise ValueError(
                "KV-cached decode supports GSPMD TP only; manual TP "
                "islands (tp_shards > 1) hold local head slices the "
                "cache layout does not model"
            )
        if cfg.num_heads % self.tp_shards:
            raise ValueError(
                f"num_heads={cfg.num_heads} not divisible by "
                f"tp_shards={self.tp_shards}"
            )
        if self.tp_shards > 1 and ln_params is not None:
            raise ValueError(
                "fused_ln_matmul is incompatible with manual TP islands"
            )
        if cfg.fused_qkv and ln_params is not None:
            raise ValueError(
                "fused_qkv and fused_ln_matmul are mutually exclusive "
                "(the LN+matmul kernel owns its own per-projection path)"
            )
        H, D = cfg.num_heads // self.tp_shards, cfg.head_dim
        B, S, _ = x.shape
        # [B,S,Hd] -> [B,H,S,D] (ops/ layout convention)
        split = lambda t: t.reshape(B, S, H, D).transpose(0, 2, 1, 3)
        if ln_params is not None:
            # fused path: x is the RAW residual stream; q/k/v matmuls
            # apply the block's LayerNorm in their kernel prologue
            from ..ops.fused_ln_matmul import ln_matmul

            ls, lb = ln_params
            x2 = x.reshape(B * S, cfg.d_model)

            def proj(name):
                w, b = _DenseParams(H * D, cfg.d_model, name=name)()
                return ln_matmul(
                    x2, ls, lb, w.astype(dtype), b, out_dtype=dtype
                ).reshape(B, S, H * D)

            q = split(proj("query"))
            k = split(proj("key"))
            v = split(proj("value"))
        elif cfg.fused_qkv:
            if self.tp_shards > 1:
                raise ValueError(
                    "fused_qkv is incompatible with manual TP islands "
                    "(tp_shards > 1); use the GSPMD tp_rules path")
            # Column order is HEAD-major ([d] -> [H, 3, D]), not
            # projection-major ([3, H, D]): under GSPMD TP the kernel's
            # column axis shards contiguously over `model`, and head-major
            # grouping puts each shard's columns at whole-head boundaries
            # (q_h/k_h/v_h co-located), so the q/k/v extraction below is
            # shard-local — projection-major would straddle the q|k|v
            # boundaries and force a per-layer reshard.
            qkv = nn.Dense(
                3 * H * D, dtype=dtype, name="qkv",
                kernel_init=nn.initializers.normal(0.02),
            )(x).reshape(B, S, H, 3, D)
            q = qkv[..., 0, :].transpose(0, 2, 1, 3)  # [B,H,S,D]
            k = qkv[..., 1, :].transpose(0, 2, 1, 3)
            v = qkv[..., 2, :].transpose(0, 2, 1, 3)
        else:
            dense = lambda name: nn.Dense(
                H * D, dtype=dtype, name=name,
                kernel_init=nn.initializers.normal(0.02),
            )
            q = split(dense("query")(x))
            k = split(dense("key")(x))
            v = split(dense("value")(x))

        new_cache = None
        seq_shards = self.mesh.shape[mesh_lib.SEQ] if self.mesh is not None else 1
        if cache is not None and "bt" in cache:
            from ..ops.attention import paged_layer_attention

            # paged path: the pools of ALL layers [L,NB+1,H,bs,D], this
            # layer's index and the block table [B,MB]. New K/V go into
            # row `layer` through the table at the tokens' absolute
            # positions (sentinel ids and positions write nothing a
            # request owns) and attention reads that row in place: on the
            # TPU the two Pallas kernels, elsewhere a scatter and the
            # impl-selected XLA form. The pools come back whole: the
            # caller carries them from layer to layer.
            out, ck, cv = paged_layer_attention(
                q, k, v, cache["k"], cache["v"], cache["bt"], decode_pos,
                layer=cache["layer"], impl=cfg.paged_attention_impl,
            )
            new_cache = {"k": ck, "v": cv}
        elif cache is not None:
            from ..ops.attention import append_kv, cached_attention

            start = decode_pos[:, 0]
            ck = append_kv(cache["k"], k, start)
            cv = append_kv(cache["v"], v, start)
            new_cache = {"k": ck, "v": cv}
            # masked full attention over the cache: the flash kernel does
            # not apply at Sq=1 / per-sequence offsets (see cached_attention)
            out = cached_attention(q, ck, cv, q_pos=decode_pos)
        elif cfg.seq_impl is not None and seq_shards > 1:
            out = sequence_parallel_attention(
                q, k, v, self.mesh, impl=cfg.seq_impl,
                causal=cfg.causal, kv_mask=mask,
            )
        else:
            impl = cfg.attention_impl
            if impl == "auto":
                impl = "flash" if jax.default_backend() == "tpu" else "dense"
            if impl == "flash":
                # pad S to the kernel's block multiple; padded keys masked out,
                # padded query rows sliced off (flash_attention requires
                # Sq/Sk % block == 0)
                pad = (-S) % 128 if S > 128 else 0
                if pad:
                    pq, pk, pv = (
                        jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
                        for t in (q, k, v)
                    )
                    pmask = (
                        mask
                        if mask is not None
                        else jnp.ones((B, S), bool)
                    )
                    pmask = jnp.pad(pmask, ((0, 0), (0, pad)))
                    out = _flash_on_mesh(
                        pq, pk, pv, pmask, causal=cfg.causal, mesh=self.mesh
                    )[:, :, :S]
                else:
                    out = _flash_on_mesh(
                        q, k, v, mask, causal=cfg.causal, mesh=self.mesh)
            elif impl == "blockwise":
                out = blockwise_attention(q, k, v, causal=cfg.causal, kv_mask=mask)
            else:
                out = attention_reference(q, k, v, causal=cfg.causal, kv_mask=mask)

        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * D)
        if self.tp_shards > 1:
            # _DenseParams keeps the exact nn.Dense param tree
            # ('attn_out/{kernel,bias}')
            out = _row_parallel_dense(out, cfg.d_model, H * D, "attn_out",
                                      dtype, self)
        else:
            out = nn.Dense(cfg.d_model, dtype=dtype, name="attn_out",
                           kernel_init=nn.initializers.normal(0.02))(out)
        out = nn.Dropout(cfg.dropout, deterministic=not train)(out)
        return out if cache is None else (out, new_cache)


class Block(nn.Module):
    cfg: TransformerConfig
    mesh: Any = None
    use_moe: bool = False
    # Manual megatron TP inside a shard_map island (see SelfAttention.
    # tp_shards): column-parallel q/k/v + mlp_in (local out slices),
    # row-parallel attn_out + mlp_out (psum over `model`, bias once).
    # LayerNorms see the full d_model (never sharded). The pipelined path
    # sets this from the mesh; the GSPMD path must leave it at 1.
    tp_shards: int = 1

    @nn.compact
    def __call__(self, x, mask, train: bool, cache=None, decode_pos=None):
        # ``train`` is positional (not kw-only) so nn.remat can mark it
        # static (static_argnums counts the module itself as arg 0) —
        # but deliberately has no default: every call site must decide.
        # ``cache``/``decode_pos``: KV-cached decode (see SelfAttention);
        # the return becomes (x, new_cache). Never combined with remat —
        # decode is forward-only.
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        tp = self.tp_shards
        if tp > 1 and self.use_moe:
            raise ValueError("manual TP islands don't support MoE blocks")
        if tp > 1 and cfg.d_ff % tp:
            raise ValueError(f"d_ff={cfg.d_ff} not divisible by tp={tp}")
        ln = lambda name: nn.LayerNorm(dtype=jnp.float32, name=name)
        base_attn = SelfAttention(cfg, self.mesh, tp_shards=tp, name="attn")

        new_cache = [None]  # box: closed over by the three attn call sites

        def attn(h, **kw):
            if cache is None:
                return base_attn(h, mask, train=train, **kw)
            y, new_cache[0] = base_attn(
                h, mask, train=train, cache=cache, decode_pos=decode_pos,
                **kw,
            )
            return y

        if self.use_moe:
            from ..ops.moe import MoEConfig, MoEMLP

            moe = MoEMLP(
                MoEConfig(
                    num_experts=cfg.num_experts, d_model=cfg.d_model,
                    d_ff=cfg.d_ff, top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor, dtype=cfg.dtype,
                ),
                name="moe",
            )

            def mlp(h):
                h = moe(h, train=train)
                return nn.Dropout(cfg.dropout, deterministic=not train)(h)

            mlp_tail = None
        else:

            def mlp_tail(h):
                # everything after the mlp_in matmul — shared by the
                # plain and fused-LN paths so they cannot drift
                h = nn.gelu(h)
                if tp > 1:
                    h = _row_parallel_dense(h, cfg.d_model, cfg.d_ff // tp,
                                            "mlp_out", dtype, self)
                else:
                    h = nn.Dense(cfg.d_model, dtype=dtype, name="mlp_out",
                                 kernel_init=nn.initializers.normal(0.02))(h)
                return nn.Dropout(cfg.dropout, deterministic=not train)(h)

            def mlp(h):
                # column-parallel under tp: local d_ff/tp out slice
                h = nn.Dense(cfg.d_ff // tp, dtype=dtype, name="mlp_in",
                             kernel_init=nn.initializers.normal(0.02))(h)
                return mlp_tail(h)

        use_fused_ln = cfg.fused_ln_matmul and not self.use_moe
        if use_fused_ln:
            if tp > 1:
                raise ValueError(
                    "fused_ln_matmul is incompatible with manual TP islands"
                )
            if not cfg.pre_ln:
                raise ValueError(
                    "fused_ln_matmul requires pre_ln=True (a post-LN "
                    "LayerNorm output is the residual stream itself and "
                    "must materialize)"
                )
            if self.mesh is not None and self.mesh.shape.get(
                    mesh_lib.MODEL, 1) > 1:
                raise ValueError(
                    "fused_ln_matmul is incompatible with a model-axis "
                    "(TP) sharded mesh; disable one of the two"
                )
            from ..ops.fused_ln_matmul import ln_matmul

            B, S, d = x.shape
            ln1 = _LNParams(d, name="ln1")()
            x = x + attn(x, ln_params=ln1)
            ls2, lb2 = _LNParams(d, name="ln2")()
            wi, bi = _DenseParams(cfg.d_ff, d, name="mlp_in")()
            h = ln_matmul(
                x.reshape(B * S, d), ls2, lb2, wi.astype(dtype), bi,
                out_dtype=dtype,
            ).reshape(B, S, cfg.d_ff)
            x = x + mlp_tail(h)
        elif cfg.pre_ln:
            x = x + attn(ln("ln1")(x).astype(dtype))
            x = x + mlp(ln("ln2")(x).astype(dtype))
        else:  # post-LN (BERT)
            x = ln("ln1")(x + attn(x)).astype(dtype)
            x = ln("ln2")(x + mlp(x)).astype(dtype)
        return x if cache is None else (x, new_cache[0])


class Transformer(nn.Module):
    """Token-in, logits-out transformer. ``input_ids`` [B,S] int32;
    ``attention_mask`` [B,S] (1 = real token) or None. Returns [B,S,vocab]
    logits (f32) from the tied embedding head.

    ``positions`` [B,K] (MLM only): gather the K prediction positions
    AFTER the block stack and run the MLM head + vocab projection on
    [B,K,d] instead of [B,S,d] — the standard BERT masked-position
    optimization (the reference fed `masked_lm_positions` the same way).
    At seq 512 / K=76 this cuts the head+logits term ~6.7x; the [B,S,V]
    logits tensor (16 GiB f32 at batch 256, vocab 30K) was the dominant
    memory term in the pipelined BERT step (tools/pipeline_memory_
    analysis.py), not the schedule. Returns [B,K,vocab] logits.
    """

    cfg: TransformerConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, *,
                 train: bool = False, positions=None,
                 return_hidden: bool = False,
                 kv_cache=None, decode_pos=None, block_table=None):
        # ``kv_cache`` (serve.kv_cache.KVCache: k/v [L,B,H,M,D]) with
        # ``decode_pos`` [B,S] switches on the serving path: the S incoming
        # tokens sit at those ABSOLUTE positions (prefill: arange(P);
        # decode: the per-sequence write index, S=1), attention runs over
        # the per-layer cache buffers, and the return is (logits, new
        # kv_cache). Causal models only; ``attention_mask`` is rejected —
        # validity is the contiguous-fill predicate (ops.cached_attention).
        # With ``block_table`` [B, max_blocks] the cache is instead a
        # paged block POOL (serve.kv_cache.PagedKVCache: k/v
        # [L, num_blocks + 1, H, block_size, D]): K/V are written through
        # the table into the pool, which is carried whole through the
        # layers, and attention reads it in place
        # (ops.attention.paged_layer_attention).
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        B, S = input_ids.shape
        if block_table is not None and kv_cache is None:
            raise ValueError("block_table requires kv_cache (a block pool)")
        if kv_cache is not None:
            if not cfg.causal:
                raise ValueError("KV-cached decode requires causal=True")
            if decode_pos is None:
                raise ValueError("kv_cache requires decode_pos [B,S]")
            if attention_mask is not None:
                raise ValueError(
                    "attention_mask cannot be honored on the kv_cache "
                    "path: validity is the contiguous-fill predicate "
                    "(ops.cached_attention), which assumes real tokens "
                    "start at slot position 0 — left-padded prompts "
                    "would silently attend to garbage"
                )
            if train:
                raise ValueError("KV-cached decode is inference-only")
            if return_hidden:
                raise ValueError(
                    "kv_cache with return_hidden would drop the updated "
                    "cache (the hidden-state early return predates the "
                    "(logits, new_cache) contract)"
                )
        tok = nn.Embed(cfg.vocab_size, cfg.d_model, name="tok_embed",
                       embedding_init=nn.initializers.normal(0.02))
        # a tree that serving_params prepared carries both tables padded to
        # the lanes in its "serving" collection: token rows, and on the
        # cached path position rows, are gathered from those, which the
        # device holds row-major (the head below reads tok_embed as it is)
        laid_out = self.has_variable("serving", "tok_rows")
        if laid_out:
            x = _rows(self.get_variable("serving", "tok_rows"), input_ids,
                      cfg.d_model)
        else:
            x = tok(input_ids)
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (cfg.max_len, cfg.d_model), jnp.float32,
        )
        if kv_cache is not None:
            # per-sequence absolute positions (clip guards the padded tail
            # of a bucketed prefill, whose rows are discarded anyway)
            at = jnp.clip(decode_pos, 0, cfg.max_len - 1)
            pos = (_rows(self.get_variable("serving", "pos_rows"), at,
                         cfg.d_model) if laid_out else pos[at])
        else:
            pos = pos[None, :S]
        x = (x + pos).astype(dtype)
        if not cfg.pre_ln:
            x = nn.LayerNorm(dtype=jnp.float32, name="embed_ln")(x).astype(dtype)
        x = nn.Dropout(cfg.dropout, deterministic=not train)(x)

        mask = attention_mask.astype(bool) if attention_mask is not None else None
        # nn.remat-ed blocks recompute their forward during backward:
        # O(1)-block activation memory (cfg.remat docstring). argnums:
        # 0 = module, 1 = x, 2 = mask, 3 = train (static python bool).
        block_cls = (
            nn.remat(Block, static_argnums=(3,))
            if cfg.remat and kv_cache is None else Block
        )
        paged = block_table is not None
        # the block pool rides the layers whole and is written in place: a
        # layer takes both stacked pools and its index, and hands them on
        pools = {"k": kv_cache.k, "v": kv_cache.v} if paged else None
        new_k, new_v = [], []
        for i in range(cfg.num_layers):
            use_moe = (
                cfg.num_experts > 0 and i % cfg.moe_every == cfg.moe_every - 1
            )
            block = block_cls(cfg, self.mesh, use_moe, name=f"layer_{i}")
            if paged:
                x, pools = block(
                    x, mask, train,
                    cache={**pools, "bt": block_table, "layer": i},
                    decode_pos=decode_pos,
                )
            elif kv_cache is not None:
                layer_cache = {"k": kv_cache.k[i], "v": kv_cache.v[i]}
                x, lc = block(
                    x, mask, train,
                    cache=layer_cache,
                    decode_pos=decode_pos,
                )
                new_k.append(lc["k"])
                new_v.append(lc["v"])
            else:
                x = block(x, mask, train)
        if cfg.pre_ln:
            x = nn.LayerNorm(dtype=jnp.float32, name="final_ln")(x).astype(dtype)

        if return_hidden:
            # skip the vocab head: chunked losses (chunked_lm_loss_fn)
            # apply the SAME tied-embedding projection per sequence chunk
            # so the [B, S, vocab] logits tensor never materializes
            return x

        if positions is not None:
            if cfg.causal:
                raise ValueError(
                    "positions gather is the MLM head path; causal LMs "
                    "predict every position"
                )
            x = jnp.take_along_axis(
                x, positions[..., None].astype(jnp.int32), axis=1
            )  # [B, K, d]
        if not cfg.causal:
            # BERT MLM transform head before the tied projection
            x = nn.Dense(cfg.d_model, dtype=dtype, name="mlm_transform",
                         kernel_init=nn.initializers.normal(0.02))(x)
            x = nn.gelu(x)
            x = nn.LayerNorm(dtype=jnp.float32, name="mlm_ln")(x).astype(dtype)
        logits = _head_projection(x, tok.embedding, cfg.head_dtype)
        bias = self.param("mlm_bias", nn.initializers.zeros,
                          (cfg.vocab_size,), jnp.float32)
        if kv_cache is not None:
            # same dataclass type in, same out — no serve/ import here,
            # so models/ stays independent of the serving subsystem
            if paged:
                new_cache = dataclasses.replace(kv_cache, **pools)
            else:
                new_cache = dataclasses.replace(
                    kv_cache, k=jnp.stack(new_k), v=jnp.stack(new_v)
                )
            return logits + bias, new_cache
        return logits + bias

    # -- what the paged engine asks of a model (serve/decode.py "the
    #    serving protocol"): two steps over a block pool, and whether there
    #    is recurrent state beside it ------------------------------------

    #: no recurrent state: everything a request has lives in the K/V pool
    has_state = False

    @nn.nowrap
    def serving_params(self, params):
        """The tree the engine's programs take (serve/decode.py "the
        serving protocol"). The device lays out a table whose width is not
        a multiple of the 128 lanes with its long axis minor (GPT-2-XL's
        ``[50304, 1600]`` embedding and ``[1024, 1600]`` positions), and a
        row gather then copies the whole table into row-major order first,
        in every program run. So such tables are padded once, here, on the
        device, to the next multiple of the lanes, and ride beside
        ``params`` in the "serving" collection (``__call__`` reads their
        first ``d_model`` columns; the zeros are never read). At a
        lane-multiple width ``params`` come back as they are."""
        if self.cfg.d_model % LANES == 0:
            return params
        return {"params": params, "serving": _lane_padded({
            "tok_rows": params["tok_embed"]["embedding"],
            "pos_rows": params["pos_embed"]})}

    @nn.nowrap
    def prefill_chunk(self, params, cache, table_row, tokens, start, length,
                      slot=None):
        """One prefill chunk of one request through the block pool
        (serve.decode.paged_prefill_chunk has the contract); ``slot`` is
        for models with per-slot state and unused here."""
        del slot
        sentinel = table_row.shape[0] * cache.block_size
        idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        pos = jnp.where(idx < length, start + idx, sentinel)
        logits, cache = self.apply(
            serving_variables(params), tokens[None], kv_cache=cache,
            decode_pos=pos[None], block_table=table_row[None],
        )
        return logits[0, length - 1], cache

    @nn.nowrap
    def decode_step(self, params, cache, block_tables, tokens, lengths):
        """One token for every slot over the block pool
        (serve.decode.paged_decode_step has the contract)."""
        logits, cache = self.apply(
            serving_variables(params), tokens[:, None], kv_cache=cache,
            decode_pos=lengths[:, None], block_table=block_tables,
        )
        return logits[:, 0], cache


def _rows(table, ids, width):
    """Rows ``ids`` of a lane-padded table, its first ``width`` columns, as
    ONE gather of ``width`` columns (``jnp.take``'s semantics: an index out
    of range reads NaN). A gather of whole rows and then a slice reads the
    same numbers, but on the TPU the slice moves the compiler's layout
    choices through the whole program: every layer's q/k/v projection then
    transposed its weights, 72 copies a decode step at 24 layers."""
    dims = jax.lax.GatherDimensionNumbers(
        offset_dims=(ids.ndim,), collapsed_slice_dims=(0,),
        start_index_map=(0,))
    return jax.lax.gather(
        table, ids[..., None], dims, slice_sizes=(1, width),
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP, fill_value=jnp.nan)


@jax.jit
def _lane_padded(tables):
    """Each ``[rows, width]`` table zero-padded to a multiple of the lanes."""
    return jax.tree.map(
        lambda t: jnp.pad(t, ((0, 0), (0, -t.shape[-1] % LANES))), tables)


def serving_variables(params):
    """What ``Transformer.apply`` takes for a serving tree: one that
    ``Transformer.serving_params`` prepared is already the variables
    (``params`` beside the ``serving`` tables); a param tree is wrapped."""
    return params if "serving" in params else {"params": params}


# ---------------------------------------------------------------------------
# Pipeline-parallel path (parallel/pipeline.py): same family, pipe layout
# ---------------------------------------------------------------------------
#
# The flax param tree keeps one subtree per layer (layer_0..layer_{L-1});
# the SPMD pipeline schedule instead wants every block leaf stacked with a
# leading [n_stages, layers_per_stage] dim sharded P('pipe'). The two
# layouts are pure transposes of each other (to/from_pipeline_params — an
# exact round trip, so dense checkpoints load into the pipelined layout and
# back). The stage function applies the SAME ``Block`` module that the
# dense ``Transformer.__call__`` uses, so the math is shared by
# construction — no twin implementation. Constraints: homogeneous blocks
# only (no MoE interleave — MoE layers break the stacked layout). Dropout
# works through the schedule (pipelined_apply train=True + rng: per-
# (microbatch, global-layer) keys threaded through the tick, schedule-
# independent by construction).


def _layer_keys(cfg: TransformerConfig) -> list[str]:
    return [f"layer_{i}" for i in range(cfg.num_layers)]


def _check_pipelineable(cfg: TransformerConfig, n_stages: int,
                        n_virtual: int = 1) -> None:
    if cfg.num_experts > 0:
        raise ValueError(
            "pipelined Transformer requires homogeneous blocks; "
            "num_experts > 0 interleaves MoE layers (stack would be ragged)"
        )
    if cfg.num_layers % (n_stages * n_virtual):
        raise ValueError(
            f"num_layers={cfg.num_layers} not divisible by "
            f"n_stages*n_virtual={n_stages}*{n_virtual}"
        )


def to_pipeline_params(params: Any, cfg: TransformerConfig, n_stages: int,
                       n_virtual: int = 1):
    """Dense flax tree -> {"ends": non-block params, "blocks": every leaf
    [n_stages, layers_per_stage, ...]}. With ``n_virtual`` > 1 the layout
    is [n_stages, n_virtual, layers_per_chunk, ...]: device d's v-th
    chunk is the contiguous layer range of global chunk v·S+d (the
    interleaved schedule of parallel/pipeline.py)."""
    _check_pipelineable(cfg, n_stages, n_virtual)
    layers = [params[k] for k in _layer_keys(cfg)]
    blocks = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    S, V = n_stages, n_virtual
    lc = cfg.num_layers // (S * V)
    if V == 1:
        blocks = jax.tree.map(
            lambda x: x.reshape(S, lc, *x.shape[1:]), blocks
        )
    else:
        # [L, ...] -> chunks [V, S, lc, ...] (chunk c = v*S + d) -> [S, V, lc]
        blocks = jax.tree.map(
            lambda x: x.reshape(V, S, lc, *x.shape[1:]).swapaxes(0, 1),
            blocks,
        )
    ends = {k: v for k, v in params.items() if not k.startswith("layer_")}
    return {"ends": ends, "blocks": blocks}


def from_pipeline_params(pparams: Any, cfg: TransformerConfig,
                         n_virtual: int = 1):
    """Inverse of :func:`to_pipeline_params` (for eval/checkpoint interop
    with the dense family)."""
    if n_virtual == 1:
        blocks = jax.tree.map(
            lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]),
            pparams["blocks"],
        )
    else:
        blocks = jax.tree.map(
            lambda x: x.swapaxes(0, 1).reshape(
                x.shape[0] * x.shape[1] * x.shape[2], *x.shape[3:]
            ),
            pparams["blocks"],
        )
    out = dict(pparams["ends"])
    for i, k in enumerate(_layer_keys(cfg)):
        out[k] = jax.tree.map(lambda x: x[i], blocks)
    return out


def pipeline_param_specs(pparams: Any, *, tp: bool = False) -> Any:
    """blocks → P('pipe', ...); ends pipe-replicated (FSDP on the ends is
    out of scope for the PP path).

    ``tp=True`` additionally places the `model` axis on each stacked block
    leaf — the megatron layout of TRANSFORMER_RULES shifted past the
    leading [n_stages(, n_virtual), layers_per_stage] stacking dims:
    column-parallel kernels/biases (query/key/value/mlp_in) shard their
    LAST dim, row-parallel kernels (attn_out/mlp_out) their
    second-to-last, and row-parallel biases + LayerNorms stay
    replicated. Must match ``Block(tp_shards=...)``'s local-slice
    expectations exactly. Spec construction itself lives at the seam
    (sharding.stacked_stage_specs)."""
    blocks = sharding.stacked_stage_specs(
        pparams["blocks"],
        col=r"(query|key|value|mlp_in)/(kernel|bias)$" if tp else None,
        row=r"(attn_out|mlp_out)/kernel$" if tp else None,
    )
    return {
        "ends": sharding.replicated_specs(pparams["ends"]),
        "blocks": blocks,
    }


def pipelined_apply(
    pparams: Any,
    input_ids: jax.Array,
    attention_mask: jax.Array | None,
    cfg: TransformerConfig,
    mesh: Any,
    n_microbatches: int,
    n_virtual: int = 1,
    train: bool = False,
    rng: jax.Array | None = None,
    positions: jax.Array | None = None,
) -> jax.Array:
    """input_ids [B,S] -> logits [B,S,vocab] (f32, pipe-replicated), same
    math as ``Transformer.apply(...)`` with blocks run through the
    parallel/pipeline.py microbatch schedule. With ``positions`` [B,K]
    (MLM gathered head, see ``Transformer.__call__``), the head runs on
    the gathered positions OUTSIDE the pipeline island and the return is
    [B,K,vocab].

    ``train=True`` with ``rng`` enables dropout (training-semantics parity
    with the dense path): each layer's mask key is
    ``fold_in(fold_in(rng, microbatch), global_layer_index)`` plus, inside
    a pipe>1 island, the (data, fsdp) shard index — flax draws masks at
    the LOCAL shape there, so the shard fold keeps dropout decorrelated
    across batch shards. Keys derive from schedule-independent identities,
    so any S>1 (S, V) decomposition at a fixed batch sharding draws the
    SAME masks (asserted in tests/test_pipeline.py::
    test_pipelined_dropout_schedule_independent). The pipe=1 degenerate
    path draws global-shape masks (a different but equally deterministic
    stream), and the dense path's flax-internal derivation differs again —
    exact dense-vs-pipelined parity holds at ``train=False`` only.
    """
    from ..parallel.pipeline import microbatch, pipeline_apply, unmicrobatch

    use_dropout = train and cfg.dropout > 0.0
    if use_dropout and rng is None:
        raise ValueError("train=True with cfg.dropout > 0 requires rng")
    dtype = jnp.dtype(cfg.dtype)
    ends = pparams["ends"]
    B, S = input_ids.shape
    embed_tbl = ends["tok_embed"]["embedding"]
    x = embed_tbl[input_ids] + ends["pos_embed"][None, :S]
    x = x.astype(dtype)
    if not cfg.pre_ln:
        x = nn.LayerNorm(dtype=jnp.float32).apply(
            {"params": ends["embed_ln"]}, x
        ).astype(dtype)
    if use_dropout:
        # the dense path's embedding dropout (Transformer.__call__), done
        # outside the pipeline island; num_layers offsets it past every
        # global layer index used below
        keep = 1.0 - cfg.dropout
        ekey = jax.random.fold_in(rng, cfg.num_layers)
        x = x * jax.random.bernoulli(
            ekey, keep, x.shape).astype(x.dtype) / keep

    stage_cfg = dataclasses.replace(
        cfg, dropout=cfg.dropout if use_dropout else 0.0, seq_impl=None)
    # PP×TP: a model axis on the mesh turns on manual megatron TP inside
    # the island — each device holds [pipe-slice × model-slice] of every
    # block leaf and the Block psums its row-parallel projections.
    tp = mesh.shape.get(mesh_lib.MODEL, 1) if mesh is not None else 1
    if tp > 1 and mesh.shape.get(mesh_lib.PIPE, 1) == 1:
        raise ValueError(
            "model axis without a pipe axis: use the dense Transformer "
            "with tp_rules (GSPMD TP) instead of the pipelined path"
        )
    block = Block(stage_cfg, None, False, tp_shards=tp)

    x_mb = microbatch(x, n_microbatches)

    n_stages = mesh.shape.get(mesh_lib.PIPE, 1) if mesh is not None else 1
    layers_per_chunk = cfg.num_layers // (n_stages * n_virtual)

    def run_layers(stage_params, x, mask, mb_key, chunk):
        if mb_key is None:
            def layer(x, p):
                return block.apply({"params": p}, x, mask, train=False), None

            y, _ = jax.lax.scan(layer, x, stage_params)
        else:
            if n_stages > 1:
                # inside the shard_map island each device holds a
                # (data, fsdp) slice of the microbatch and flax draws
                # masks at the LOCAL shape — without this fold every
                # shard would reuse the same mask for different rows
                # (correlated dropout across the batch)
                shard = (jax.lax.axis_index(mesh_lib.DATA)
                         * mesh.shape.get(mesh_lib.FSDP, 1)
                         + jax.lax.axis_index(mesh_lib.FSDP))
                mb_key = jax.random.fold_in(mb_key, shard)

            def layer(x, pl):
                p, li = pl
                lkey = jax.random.fold_in(
                    mb_key, chunk * layers_per_chunk + li)
                return block.apply(
                    {"params": p}, x, mask, train=True,
                    rngs={"dropout": lkey},
                ), None

            y, _ = jax.lax.scan(
                layer, x, (stage_params, jnp.arange(layers_per_chunk)))
        return y

    mask_mb = (
        microbatch(attention_mask.astype(bool), n_microbatches)
        if attention_mask is not None else None
    )
    # positional adapters: pipeline_apply appends (mb_key, chunk) only
    # when rng is given, and aux only when mask_mb is given
    if use_dropout:
        if mask_mb is not None:
            stage_fn = run_layers
        else:
            stage_fn = lambda p, x, k, c: run_layers(p, x, None, k, c)
    else:
        if mask_mb is not None:
            stage_fn = lambda p, x, a: run_layers(p, x, a, None, None)
        else:
            stage_fn = lambda p, x: run_layers(p, x, None, None, None)
    y = pipeline_apply(
        stage_fn, pparams["blocks"], x_mb, mesh, aux_mb=mask_mb,
        n_virtual=n_virtual,
        param_specs=(
            pipeline_param_specs(pparams, tp=True)["blocks"]
            if tp > 1 else None
        ),
        rng=rng if use_dropout else None,
    )
    y = unmicrobatch(y)

    if cfg.pre_ln:
        y = nn.LayerNorm(dtype=jnp.float32).apply(
            {"params": ends["final_ln"]}, y
        ).astype(dtype)
    if positions is not None:
        if cfg.causal:
            raise ValueError(
                "positions gather is the MLM head path; causal LMs "
                "predict every position"
            )
        # MLM gathered head (see Transformer.__call__): head + vocab
        # projection on [B,K,d]; runs outside the pipeline island, so the
        # pipelined path gets the same memory/FLOPs win
        y = jnp.take_along_axis(
            y, positions[..., None].astype(jnp.int32), axis=1
        )
    if not cfg.causal:
        y = nn.Dense(cfg.d_model, dtype=dtype).apply(
            {"params": ends["mlm_transform"]}, y
        )
        y = nn.gelu(y)
        y = nn.LayerNorm(dtype=jnp.float32).apply(
            {"params": ends["mlm_ln"]}, y
        ).astype(dtype)
    logits = _head_projection(y, embed_tbl, cfg.head_dtype)
    return logits + ends["mlm_bias"]


def make_pipelined_init_fn(cfg: TransformerConfig, n_stages: int,
                           seq_len: int, n_virtual: int = 1):
    """init_fn(rng) -> (pipeline-layout params, {}): init the dense family,
    transpose into the pipe layout."""
    _check_pipelineable(cfg, n_stages, n_virtual)
    base = make_init_fn(
        Transformer(dataclasses.replace(cfg, seq_impl=None)), seq_len
    )

    def init_fn(rng):
        params, _ = base(rng)
        return to_pipeline_params(params, cfg, n_stages, n_virtual), {}

    return init_fn


def pipelined_lm_loss_fn(cfg: TransformerConfig, mesh: Any,
                         n_microbatches: int, n_virtual: int = 1):
    """Engine LossFn: next-token loss through the pipelined forward.
    Dropout active per cfg.dropout — same training semantics as the
    dense lm_loss_fn (per-step engine rng threaded through the tick)."""

    def loss_fn(params, model_state, batch, rng):
        ids = batch["input_ids"]
        logits = pipelined_apply(
            params, ids, batch.get("attention_mask"), cfg, mesh,
            n_microbatches, n_virtual, train=True, rng=rng,
        )
        labels = _shifted_lm_labels(ids, batch.get("attention_mask"))
        loss, acc = _masked_xent(logits, labels)
        return loss, (model_state, {"accuracy": acc})

    return loss_fn


def pipelined_mlm_loss_fn(cfg: TransformerConfig, mesh: Any,
                          n_microbatches: int, n_virtual: int = 1):
    """Engine LossFn: masked-LM loss through the pipelined forward.
    Dropout active per cfg.dropout (see pipelined_lm_loss_fn)."""

    def loss_fn(params, model_state, batch, rng):
        positions, labels = _mlm_targets(batch)
        logits = pipelined_apply(
            params, batch["input_ids"], batch.get("attention_mask"), cfg,
            mesh, n_microbatches, n_virtual, train=True, rng=rng,
            positions=positions,
        )
        loss, acc = _masked_xent(logits, labels)
        return loss, (model_state, {"accuracy": acc})

    return loss_fn


# ---------------------------------------------------------------------------
# Loss adapters (train-engine LossFn contract, cf. models/common.py)
# ---------------------------------------------------------------------------



def _xent_eval_stats(logits, labels):
    """SUMMED per-token eval statistics over valid (non-IGNORE) positions
    — summed, not averaged, so sharded eval batches aggregate exactly
    (models/common.classification_eval_fn contract; the runner derives
    loss/accuracy ratios)."""
    valid = labels != IGNORE_INDEX
    safe = jnp.where(valid, labels, 0)
    xent = -jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    per_tok = jnp.take_along_axis(xent, safe[..., None], axis=-1)[..., 0]
    return {
        "loss_sum": jnp.where(valid, per_tok, 0.0).sum(),
        "correct": jnp.where(
            valid, jnp.argmax(logits, -1) == safe, False
        ).sum().astype(jnp.float32),
        "count": valid.sum().astype(jnp.float32),
    }


def _shifted_lm_labels(ids, attention_mask=None):
    """Next-token labels: position t predicts ids[t+1]; the final
    position (and positions whose TARGET is padding) are IGNOREd. Shared
    by lm_loss_fn and lm_eval_fn."""
    labels = jnp.concatenate(
        [ids[:, 1:], jnp.full_like(ids[:, :1], IGNORE_INDEX)], axis=1
    )
    if attention_mask is not None:
        label_valid = jnp.concatenate(
            [attention_mask[:, 1:] > 0,
             jnp.zeros_like(attention_mask[:, :1], bool)], axis=1
        )
        labels = jnp.where(label_valid, labels, IGNORE_INDEX)
    return labels


def _mlm_targets(batch):
    """(positions, labels) for the MLM head: the gathered-head batch
    format {"masked_positions" [B,K], "masked_labels" [B,K]} when the
    pipeline provides it (TextDataConfig.max_predictions > 0 — the
    reference's masked_lm_positions format), else the dense [B,S]
    labels with IGNORE_INDEX on unmasked positions."""
    if "masked_positions" in batch:
        return batch["masked_positions"], batch["masked_labels"]
    return None, batch["labels"]


def transformer_eval_fn(model: Transformer, *, mlm: bool):
    """Summed-stats eval, MLM or next-token (reference analog: the eval
    loop over latest_checkpoint, SURVEY.md §3.5). Same ``mlm`` switch as
    :func:`pipelined_eval_fn`."""

    def eval_fn(params, model_state, batch):
        ids = batch["input_ids"]
        positions, labels = (
            _mlm_targets(batch) if mlm
            else (None, _shifted_lm_labels(ids, batch.get("attention_mask")))
        )
        logits, _ = model.apply(
            {"params": params}, ids, batch.get("attention_mask"),
            train=False, mutable=["losses"], positions=positions,
        )
        return _xent_eval_stats(logits, labels)

    return eval_fn


def mlm_eval_fn(model: Transformer):
    return transformer_eval_fn(model, mlm=True)


def lm_eval_fn(model: Transformer, xent_chunk: int = 0):
    """``xent_chunk > 0``: summed stats computed per sequence chunk from
    hidden states (same chunking as :func:`chunked_lm_loss_fn`) — a
    large-vocab training run must not OOM at the final eval it was
    configured to avoid OOMing in."""
    if xent_chunk <= 0:
        return transformer_eval_fn(model, mlm=False)

    def eval_fn(params, model_state, batch):
        ids = batch["input_ids"]
        labels = _shifted_lm_labels(ids, batch.get("attention_mask"))
        h, _ = model.apply(
            {"params": params}, ids, batch.get("attention_mask"),
            train=False, mutable=["losses"], return_hidden=True,
        )
        return _chunked_xent_stats(h, labels, params, xent_chunk,
                                   model.cfg.head_dtype)

    return eval_fn


def _head_projection(x, embedding, head_dtype: str):
    """The tied-embedding vocab projection, f32 logits out — ONE
    definition shared by the model head and the chunked loss/eval so
    the two cannot drift. head_dtype="float32" reproduces
    ``Embed.attend`` exactly (f32 dot); "bfloat16" runs the matmul on
    the fast MXU tier with f32 accumulation."""
    hd = jnp.dtype(head_dtype)
    return jnp.dot(x.astype(hd), embedding.astype(hd).T,
                   preferred_element_type=jnp.float32)


def _chunked_xent_stats(h, labels, params, chunk_size: int,
                        head_dtype: str = "float32"):
    """Summed xent stats from hidden states, vocab head applied per
    sequence chunk (shared by chunked_lm_loss_fn and the chunked eval;
    projection via the same :func:`_head_projection` as the model head)."""
    emb = params["tok_embed"]["embedding"]
    bias = params["mlm_bias"]
    B, S, d = h.shape
    C = min(chunk_size, S)
    if S % C:
        raise ValueError(
            f"seq len {S} not divisible by xent chunk size {C} — set "
            f"model.xent_chunk (BENCH_XENT_CHUNK in the bench) to a "
            f"divisor of the sequence length, or 0 for the dense loss")
    N = S // C
    hs = h.reshape(B, N, C, d).swapaxes(0, 1)      # [N, B, C, d]
    ls = labels.reshape(B, N, C).swapaxes(0, 1)    # [N, B, C]

    @jax.checkpoint
    def body(carry, inp):
        hc, lc = inp
        logits = _head_projection(hc, emb, head_dtype) + bias
        s = _xent_eval_stats(logits, lc)
        return (carry[0] + s["loss_sum"], carry[1] + s["correct"],
                carry[2] + s["count"]), None

    zero = jnp.zeros((), jnp.float32)
    (loss_sum, correct, count), _ = jax.lax.scan(
        body, (zero, zero, zero), (hs, ls))
    return {"loss_sum": loss_sum, "correct": correct, "count": count}


def causal_lm_loss(model: Transformer, xent_chunk: int = 0):
    """THE causal-LM loss selector (one home for the chunk>0 ladder so
    the workload builder and the bench cannot drift): chunked when
    ``xent_chunk > 0``, dense otherwise."""
    return (chunked_lm_loss_fn(model, xent_chunk) if xent_chunk > 0
            else lm_loss_fn(model))


def pipelined_eval_fn(cfg: TransformerConfig, mesh: Any,
                      n_microbatches: int, n_virtual: int = 1,
                      *, mlm: bool):
    """Summed-stats eval through the pipelined forward (pipe-layout
    params), MLM or next-token."""

    def eval_fn(params, model_state, batch):
        ids = batch["input_ids"]
        positions, labels = (
            _mlm_targets(batch) if mlm
            else (None, _shifted_lm_labels(ids, batch.get("attention_mask")))
        )
        logits = pipelined_apply(
            params, ids, batch.get("attention_mask"), cfg, mesh,
            n_microbatches, n_virtual, positions=positions,
        )
        return _xent_eval_stats(logits, labels)

    return eval_fn


def _masked_xent(logits, labels):
    """Mean cross-entropy over positions where labels != IGNORE_INDEX —
    the ratio form of _xent_eval_stats (one implementation of the masked
    gather/argmax math)."""
    s = _xent_eval_stats(logits, labels)
    count = jnp.maximum(s["count"], 1)
    return s["loss_sum"] / count, s["correct"] / count


def mlm_loss_fn(model: Transformer):
    """Masked-LM loss. Batch: {"input_ids" [B,S], "labels" [B,S] with
    IGNORE_INDEX on unmasked positions, optional "attention_mask" [B,S]}."""

    def loss_fn(params, model_state, batch, rng):
        positions, labels = _mlm_targets(batch)
        logits, mut = model.apply(
            {"params": params}, batch["input_ids"],
            batch.get("attention_mask"), train=True, rngs={"dropout": rng},
            mutable=["losses"], positions=positions,
        )
        loss, acc = _masked_xent(logits, labels)
        loss = loss + collect_aux_loss(mut)  # MoE router load-balance
        return loss, (model_state, {"accuracy": acc})

    return loss_fn


def lm_loss_fn(model: Transformer):
    """Next-token loss for causal models. Batch: {"input_ids" [B,S]};
    position t predicts token t+1."""

    def loss_fn(params, model_state, batch, rng):
        ids = batch["input_ids"]
        logits, mut = model.apply(
            {"params": params}, ids, batch.get("attention_mask"),
            train=True, rngs={"dropout": rng}, mutable=["losses"],
        )
        labels = _shifted_lm_labels(ids, batch.get("attention_mask"))
        loss, acc = _masked_xent(logits, labels)
        loss = loss + collect_aux_loss(mut)  # MoE router load-balance
        return loss, (model_state, {"accuracy": acc})

    return loss_fn


def chunked_lm_loss_fn(model: Transformer, chunk_size: int):
    """Next-token loss that never materializes the full ``[B, S, vocab]``
    logits tensor — the memory bomb of large-vocab causal LMs (GPT-2
    vocab 50304 at B=128, S=512 is 13 GB in f32 before the backward,
    over a v5e's entire HBM; cf. the gathered MLM head, which solves the
    same problem for BERT by gathering K positions — a causal LM predicts
    EVERY position, so the fix is chunking instead of gathering).

    The block stack runs once (``return_hidden=True``); the tied-embedding
    projection + masked cross-entropy then run per sequence chunk inside a
    rematerialized ``lax.scan``: peak logits memory drops from
    ``[B, S, V]`` to ``[B, chunk, V]`` (the backward recomputes each
    chunk's logits from the saved ``[B, chunk, d]`` hiddens).
    Numerically identical to :func:`lm_loss_fn` — same f32 projection
    math as the model head, exact-parity-tested."""

    def loss_fn(params, model_state, batch, rng):
        ids = batch["input_ids"]
        h, mut = model.apply(
            {"params": params}, ids, batch.get("attention_mask"),
            train=True, rngs={"dropout": rng}, mutable=["losses"],
            return_hidden=True,
        )
        labels = _shifted_lm_labels(ids, batch.get("attention_mask"))
        s = _chunked_xent_stats(h, labels, params, chunk_size,
                                model.cfg.head_dtype)
        count = jnp.maximum(s["count"], 1)
        loss = s["loss_sum"] / count + collect_aux_loss(mut)
        return loss, (model_state, {"accuracy": s["correct"] / count})

    return loss_fn


def make_init_fn(model: Transformer, seq_len: int):
    """init_fn(rng) -> (params, {}) for init_train_state.

    Initializes through a dense twin (seq_impl=None, no mesh): attention
    has no impl-dependent parameters, and the twin avoids tracing shard_map
    islands with a batch-1 dummy that a data axis couldn't divide."""
    cfg = model.cfg
    init_model = (
        Transformer(dataclasses.replace(cfg, seq_impl=None))
        if (model.mesh is not None or cfg.seq_impl is not None)
        else model
    )

    def init_fn(rng):
        dummy = jnp.zeros((1, seq_len), jnp.int32)
        variables = init_model.init({"params": rng, "dropout": rng}, dummy,
                                    train=False)
        return variables["params"], {}

    return init_fn


def _block_counts(cfg: TransformerConfig) -> tuple[int, int]:
    """(number of dense-FFN blocks, number of MoE blocks)."""
    if cfg.num_experts <= 0:
        return cfg.num_layers, 0
    n_moe = sum(
        1 for i in range(cfg.num_layers)
        if i % cfg.moe_every == cfg.moe_every - 1
    )
    return cfg.num_layers - n_moe, n_moe


def _ffn_params(cfg: TransformerConfig, experts: int) -> int:
    """FFN params per block with ``experts`` expert copies (1 = dense)."""
    d, f = cfg.d_model, cfg.d_ff
    ffn = experts * (2 * d * f + f + d)
    if experts > 1:
        ffn += d * cfg.num_experts + cfg.num_experts  # router
    return ffn


def param_count(cfg: TransformerConfig) -> int:
    """Analytic parameter count (embeddings + blocks + heads + experts)."""
    d, L = cfg.d_model, cfg.num_layers
    embed = cfg.vocab_size * d + cfg.max_len * d
    embed += 2 * d  # embed_ln (post-LN) or final_ln (pre-LN)
    attn = 4 * d * d + 4 * d  # qkv+out kernels + biases
    ln = 4 * d  # 2 LayerNorms
    head = 0 if cfg.causal else d * d + 3 * d
    n_dense, n_moe = _block_counts(cfg)
    blocks = (
        L * (attn + ln)
        + n_dense * _ffn_params(cfg, 1)
        + n_moe * _ffn_params(cfg, cfg.num_experts)
    )
    return embed + blocks + head + cfg.vocab_size


def active_param_count(cfg: TransformerConfig) -> int:
    """Params touched per token: MoE blocks engage only top_k experts —
    this is the N that enters the 2N FLOPs/token estimate."""
    if cfg.num_experts <= 0:
        return param_count(cfg)
    _, n_moe = _block_counts(cfg)
    d, f = cfg.d_model, cfg.d_ff
    idle_experts = cfg.num_experts - cfg.moe_top_k
    return param_count(cfg) - n_moe * idle_experts * (2 * d * f + f + d)


def flops_per_example(cfg: TransformerConfig, seq_len: int,
                      n_predictions: int | None = None) -> float:
    """Forward FLOPs per example at ``seq_len`` (×3 for training in the
    engine's MFU accounting, utils/flops.py train_flops_multiplier).
    Uses *active* params so MoE MFU accounting stays honest (SURVEY.md §7
    'MFU accounting honesty').

    ``n_predictions``: gathered MLM head (Transformer positions arg) —
    the head (mlm_transform d×d + tied d×vocab projection) runs on K
    positions instead of all seq_len; subtract the skipped positions'
    share so declared FLOPs stay honest (tests/test_flops_contract.py).
    """
    base = seq_len * flops_lib.transformer_flops_per_token(
        active_param_count(cfg), seq_len, cfg.num_layers, cfg.d_model
    )
    if n_predictions is not None and not cfg.causal:
        per_pos_head = 2.0 * (cfg.vocab_size * cfg.d_model
                              + cfg.d_model * cfg.d_model)
        base -= (seq_len - n_predictions) * per_pos_head
    return base
