"""Family ``joyai``: binds the program's ``models/joyai.py`` to the
benchmark's seeded weights, batches, FLOP count and plain reference. The
family's loss has two heads (the main model's and the prediction
module's over one head matrix): the reference's ``nll_sum`` returns one
sum a block of rows that the harness divides by the block's main
positions, the module's part weighed for its own count there."""

from __future__ import annotations

import jax.numpy as jnp

from ..reference import joyai as reference  # noqa: F401  (the harness reads it)


def program_config(cfg: dict):
    """The program's own configuration of the cell: the layers, the
    experts and the vocabulary slice held, from the configuration's
    file (the program's tile sizes are the model layer's own)."""
    from byteps_tpu.models import joyai

    return joyai.JoyAIConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_dense_layers=cfg["first_k_dense_replace"],
        n_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], dense_hidden=cfg["intermediate_size"],
        n_experts=cfg["n_routed_experts"],
        n_experts_held=cfg["num_experts_held"],
        first_expert=cfg.get("first_expert_held", 0),
        top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"], n_group=cfg["n_group"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        n_mtp=cfg["num_nextn_predict_layers"],
        mtp_weight=float(cfg["mtp_loss_weight"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        router_dtype=jnp.dtype(cfg["router_dtype"]), remat=cfg["remat"])


def program_loss(cfg: dict):
    """``loss_fn(params, batch) -> (loss, stats)`` through the program's
    own model code. The expert bias is the buffer the reference makes
    from the configuration's file: an argument of the model beside the
    parameters, so it is in no gradient, no optimizer state and no
    push."""
    from byteps_tpu.models import joyai

    pc = program_config(cfg)
    if not cfg["norm_topk_prob"] or cfg["scoring_func"] != "sigmoid" \
            or cfg["topk_method"] != "noaux_tc" or cfg["topk_group"] != 1 \
            or cfg["rope_scaling"] is not None or not cfg["rope_interleave"] \
            or cfg["n_shared_experts"] != 1 or cfg["moe_layer_freq"] != 1 \
            or cfg["tie_word_embeddings"] or cfg["attention_bias"]:
        raise ValueError(
            "the program normalises the top-k sigmoid weights under a "
            "selection bias in one group, rotates interleaved pairs "
            "unscaled, holds one shared expert and a sparse FFN in every "
            "layer after the dense ones, and an untied head without bias")
    bias = reference.expert_bias(cfg)
    return lambda params, batch: joyai.loss_fn(params, batch, pc, bias)
