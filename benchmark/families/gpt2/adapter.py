"""GPT-2 on the program's side: `models/transformer.py`'s pre-LN decoder
block is GPT-2's, so a GPT-2 configuration file maps onto a
`TransformerConfig`, the weights of `reference/gpt2.py` onto its flax tree,
a training job onto `workloads/gpt_lm` and a `serving` block onto
`ServeEngine`'s arguments."""

from __future__ import annotations

import jax.numpy as jnp

#: the workload of the program that a `train_job` of this family runs
TRAIN_WORKLOAD = "gpt_lm"

#: reference leaf -> path in the program's flax tree (under `layer_<i>` for
#: block leaves)
BLOCK_PATHS = {
    "ln1_g": ("ln1", "scale"), "ln1_b": ("ln1", "bias"),
    "wq": ("attn", "query", "kernel"), "bq": ("attn", "query", "bias"),
    "wk": ("attn", "key", "kernel"), "bk": ("attn", "key", "bias"),
    "wv": ("attn", "value", "kernel"), "bv": ("attn", "value", "bias"),
    "wo": ("attn", "attn_out", "kernel"), "bo": ("attn", "attn_out", "bias"),
    "ln2_g": ("ln2", "scale"), "ln2_b": ("ln2", "bias"),
    "w1": ("mlp_in", "kernel"), "b1": ("mlp_in", "bias"),
    "w2": ("mlp_out", "kernel"), "b2": ("mlp_out", "bias"),
}
TOP_PATHS = {
    "wte": ("tok_embed", "embedding"), "wpe": ("pos_embed",),
    "lnf_g": ("final_ln", "scale"), "lnf_b": ("final_ln", "bias"),
    "head_b": ("mlm_bias",),
}


def _put(tree: dict, path: tuple, value) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = value


def _get(tree: dict, path: tuple):
    for key in path:
        tree = tree[key]
    return tree


def to_program_tree(w: dict) -> dict:
    """The benchmark's weights (the per-layer layout of
    `reference.gpt2.make_weights(stacked=False)`) as the program's
    parameter tree: the same arrays, no copy."""
    tree: dict = {}
    for name, path in TOP_PATHS.items():
        _put(tree, path, w[name])
    for i, layer in enumerate(w["layers"]):
        for name, path in BLOCK_PATHS.items():
            _put(tree, (f"layer_{i}", *path), layer[name])
    return tree


def from_program_tree(tree: dict) -> dict:
    """The inverse: the program's tree in the benchmark's stacked layout
    (what `reference.gpt2.leaf_norms` names leaves by)."""
    n_layers = sum(1 for k in tree if k.startswith("layer_"))
    out = {name: _get(tree, path) for name, path in TOP_PATHS.items()}
    out["blocks"] = {
        name: jnp.stack([_get(tree[f"layer_{i}"], path)
                         for i in range(n_layers)])
        for name, path in BLOCK_PATHS.items()}
    return out


def model_config(cfg: dict):
    """The program's `TransformerConfig` for a GPT-2 configuration file."""
    from distributed_tensorflow_tpu.models import transformer as tfm

    if cfg["activation_function"] != "gelu_new" or cfg.get(
            "layer_norm_epsilon") != 1e-6:
        raise ValueError("the program's block is gelu_new with LayerNorm "
                         "epsilon 1e-6; the configuration must say so")
    if cfg["resid_pdrop"] or cfg["embd_pdrop"] or cfg["attn_pdrop"]:
        raise ValueError("the comparison with the reference needs dropout 0")
    return tfm.TransformerConfig(
        vocab_size=cfg["vocab_size"], max_len=cfg["n_positions"],
        num_layers=cfg["n_layer"], d_model=cfg["n_embd"],
        num_heads=cfg["n_head"], d_ff=cfg.get("n_inner") or 4 * cfg["n_embd"],
        dropout=0.0, causal=True, pre_ln=True, dtype=cfg["compute_dtype"])


def train_overrides(cfg: dict, job: dict) -> list:
    """The model's part of the `--section.key=value` overrides that turn
    `workloads/gpt_lm`'s default run into a cell's job."""
    m = model_config(cfg)
    return [
        f"--model.vocab_size={m.vocab_size}", f"--model.max_len={m.max_len}",
        f"--model.num_layers={m.num_layers}", f"--model.d_model={m.d_model}",
        f"--model.num_heads={m.num_heads}", f"--model.d_ff={m.d_ff}",
        "--model.dropout=0.0", f"--model.dtype={m.dtype}",
        f"--model.xent_chunk={job['xent_chunk']}",
        f"--data.vocab_size={m.vocab_size}",
    ]


def engine_args(cfg: dict) -> dict:
    """`ServeEngine`'s keyword arguments for the file's `serving` block."""
    deploy = cfg["serving"]
    return {
        "num_slots": deploy["num_slots"], "block_size": deploy["block_size"],
        "num_blocks": deploy["num_blocks"],
        "prefill_chunk": deploy["prefill_chunk"],
        "prefix_reuse": deploy["prefix_reuse"], "spec_k": deploy["spec_k"],
        "temperature": deploy["temperature"],
        "cache_dtype": jnp.dtype(deploy["cache_dtype"])}
