"""Everything the benchmark knows about the program under test, in one
place: how a configuration file becomes the program's own configuration,
where each weight sits in its parameter tree, and which of its entry points
a driver calls. Nothing else under `benchmark/` imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import reference

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


def train_overrides(cfg: dict, job: dict, seed: int, n_chips: int) -> list:
    """`--section.key=value` overrides that turn `workloads/gpt_lm`'s default
    run into this cell's job."""
    m = model_config(cfg)
    seed31 = int(seed) & 0x7FFFFFFF
    out = [
        f"--model.vocab_size={m.vocab_size}", f"--model.max_len={m.max_len}",
        f"--model.num_layers={m.num_layers}", f"--model.d_model={m.d_model}",
        f"--model.num_heads={m.num_heads}", f"--model.d_ff={m.d_ff}",
        "--model.dropout=0.0", f"--model.dtype={m.dtype}",
        f"--model.xent_chunk={job['xent_chunk']}",
        f"--data.dataset={job['dataset']}",
        f"--data.seq_len={job['seq_len']}",
        f"--data.vocab_size={m.vocab_size}",
        f"--data.global_batch_size={job['sequences_per_chip'] * n_chips}",
        f"--data.seed={seed31}", f"--train.seed={seed31}",
        f"--mesh.data={n_chips}",
        # the window ends the run; nothing logs, evaluates or saves in it
        "--train.num_steps=1000000000", "--train.log_every=1000000000",
    ]
    out += [f"--optimizer.{k}={v}" for k, v in job["optimizer"].items()]
    out += list(job.get("overrides", []))
    return out


def run_training(overrides: list, callback):
    """The program's own training entry with one more callback."""
    from distributed_tensorflow_tpu import workloads

    return workloads.run_workload("gpt_lm", overrides,
                                  extra_callbacks=[callback])


def callback_base():
    from distributed_tensorflow_tpu.train import callbacks as cb

    return cb.Callback


def adam_mu(opt_state):
    """Adam's first moment inside the program's optax state."""
    for part in jax.tree.leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")):
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("no Adam state (mu) in the optimizer state")


def configure_compile_cache() -> str:
    from distributed_tensorflow_tpu.parallel import cluster

    return cluster.configure_compile_cache()


def make_engine(cfg: dict, deploy: dict, seed: int):
    """The program's serving engine on the benchmark's weights (made from
    ``seed``), with the deployment's settings from the configuration file."""
    from distributed_tensorflow_tpu import serve

    return serve.ServeEngine(
        model_config(cfg), program_weights(cfg, seed),
        num_slots=deploy["num_slots"], block_size=deploy["block_size"],
        num_blocks=deploy["num_blocks"],
        prefill_chunk=deploy["prefill_chunk"],
        prefix_reuse=deploy["prefix_reuse"], spec_k=deploy["spec_k"],
        temperature=deploy["temperature"],
        cache_dtype=jnp.dtype(deploy["cache_dtype"]))


def program_weights(cfg: dict, seed: int, shardings=None) -> dict:
    """The seed's weights in the program's tree, made on the device in one
    jitted call; ``shardings`` is a tree like the program's parameters."""
    tree = to_program_tree(reference.for_config(cfg).make_weights(
        cfg, seed, stacked=False))
    return tree if shardings is None else jax.device_put(tree, shardings)
