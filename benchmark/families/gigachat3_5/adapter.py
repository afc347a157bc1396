"""GigaChat 3.5 on the program's side: a configuration file maps onto
`models/gigachat3_5.GigaChat35Config` (the router at the published count of
experts, `expert_share`; the experts held here, `n_routed_experts`), the
weights of `reference/gigachat3_5.py` are the program's parameter tree as
they are (stacked by kind, the same arrays, no copy), and a `serving` block
becomes `ServeEngine`'s arguments. The family has no training cell: the cut
that trains does not fit a chip (PERF.md section 4)."""

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
    """The program's `GigaChat35Config` for a configuration file."""
    from distributed_tensorflow_tpu.models import gigachat3_5

    if (cfg["hidden_act"] != "silu" or cfg["attention_bias"]
            or cfg["tie_word_embeddings"] or cfg["n_shared_experts"] != 1
            or cfg["n_group"] != 1 or not cfg["norm_topk_prob"]
            or cfg["use_shared_expert_sigmoid"]):
        raise ValueError("the program's decoder is SiLU-gated, has no bias, "
                         "an untied head, one ungated shared expert and "
                         "normalised, ungrouped top-k; the configuration "
                         "must say so")
    n = cfg["num_hidden_layers"]
    full = set(cfg["full_attention_layers"])
    rope = cfg["rope_scaling"]
    share = cfg["expert_share"]
    return gigachat3_5.GigaChat35Config(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], moe_d_ff=cfg["moe_intermediate_size"],
        layer_types=tuple("full_attention" if i in full
                          else "linear_attention" for i in range(n)),
        first_dense=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        linear_key_heads=cfg["linear_num_key_heads"],
        linear_heads=cfg["linear_num_value_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        num_experts=share["router_experts"],
        experts_held=cfg["n_routed_experts"], first_expert=share["first_held"],
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"],
        swiglu_limit=cfg["swiglu_limit"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        rope_theta=cfg["rope_theta"], rope_factor=rope["factor"],
        rope_original_max=rope["original_max_position_embeddings"],
        beta_fast=rope["beta_fast"], beta_slow=rope["beta_slow"],
        mscale_all_dim=rope["mscale_all_dim"], rms_eps=cfg["rms_norm_eps"],
        max_len=cfg["max_position_embeddings"])


def train_overrides(cfg: dict, job: dict) -> list:
    raise ValueError("the gigachat3_5 family has no training workload")


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
