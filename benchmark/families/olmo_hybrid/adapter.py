"""Olmo-Hybrid on the program's side: a configuration file maps onto
`models/olmo_hybrid.OlmoHybridConfig`, the weights of
`reference/olmo_hybrid.py` are the program's parameter tree as they are
(stacked by kind, the same arrays, no copy), and a `serving` block becomes
`ServeEngine`'s arguments, the recurrent-state settings among them. The
family has no training cell: the cut that trains does not fit a chip
(PERF.md section 4)."""

from __future__ import annotations

import jax.numpy as jnp

#: no `train_job` of this family
TRAIN_WORKLOAD = None


def to_program_tree(w: dict) -> dict:
    """The reference's layout is the program's."""
    return w


def from_program_tree(tree: dict) -> dict:
    return tree


def model_config(cfg: dict):
    """The program's `OlmoHybridConfig` for a configuration file."""
    from distributed_tensorflow_tpu.models import olmo_hybrid

    if (cfg["num_key_value_heads"] != cfg["num_attention_heads"]
            or cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]):
        raise ValueError("the program's hybrid decoder has no grouped heads")
    if (cfg["hidden_act"] != "silu" or cfg["attention_bias"]
            or cfg["tie_word_embeddings"]
            or cfg["rope_parameters"]["rope_theta"] is not None):
        raise ValueError("the program's hybrid decoder is SiLU-gated, has no "
                         "bias, an untied head and no rotary embedding; the "
                         "configuration must say so")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name every layer")
    return olmo_hybrid.OlmoHybridConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], num_heads=cfg["num_attention_heads"],
        layer_types=tuple(cfg["layer_types"]),
        linear_heads=cfg["linear_num_value_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        rms_eps=cfg["rms_norm_eps"],
        max_len=cfg["max_position_embeddings"])


def train_overrides(cfg: dict, job: dict) -> list:
    raise ValueError("the olmo_hybrid family has no training workload")


def engine_args(cfg: dict) -> dict:
    """`ServeEngine`'s keyword arguments for the file's `serving` block."""
    deploy = cfg["serving"]
    return {
        "num_slots": deploy["num_slots"], "block_size": deploy["block_size"],
        "num_blocks": deploy["num_blocks"],
        "prefill_chunk": deploy["prefill_chunk"],
        "max_len": deploy["max_len"],
        "num_state_snapshots": deploy["num_state_snapshots"],
        "prefix_reuse": deploy["prefix_reuse"], "spec_k": deploy["spec_k"],
        "temperature": deploy["temperature"],
        "cache_dtype": jnp.dtype(deploy["cache_dtype"])}
