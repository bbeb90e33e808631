"""Family ``kimi``: binds the program's ``models/kimi.py`` to the
benchmark's seeded weights, batches, FLOP count and plain reference
(``reference/kimi.py``: the delta rule a position at a time, no chunk
algebra)."""

from __future__ import annotations

import jax.numpy as jnp

from ..reference import kimi as reference  # noqa: F401  (the harness reads it)


def program_config(cfg: dict):
    """The program's own configuration of the cell: the layers held and
    their operators, the experts and the vocabulary slice held, from
    the configuration's file (the chunk and the tile sizes are the
    program's own)."""
    from byteps_tpu.models import kimi

    lin = cfg["linear_attn_config"]
    return kimi.KimiConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_ops=tuple(reference.layer_ops(cfg)),
        n_dense_layers=cfg["first_k_dense_replace"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"],
        gate_rank=cfg["kda_gate_rank"], n_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        dense_hidden=cfg["intermediate_size"], n_experts=cfg["num_experts"],
        n_experts_held=cfg["num_experts_held"],
        first_expert=cfg.get("first_expert_held", 0),
        top_k=cfg["num_experts_per_token"],
        expert_hidden=cfg["moe_intermediate_size"],
        n_group=cfg["num_expert_group"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_eps=cfg["rms_norm_eps"], dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        router_dtype=jnp.dtype(cfg["router_dtype"]), remat=cfg["remat"])


def program_loss(cfg: dict):
    """``loss_fn(params, batch) -> (loss, stats)`` through the program's
    own model code. The expert bias is the buffer the reference makes
    from the configuration's file: an argument of the model beside the
    parameters, so it is in no gradient, no optimizer state and no
    push."""
    from byteps_tpu.models import kimi

    pc = program_config(cfg)
    if not cfg["moe_renormalize"] \
            or cfg["moe_router_activation_func"] != "sigmoid" \
            or cfg["topk_group"] != 1 or cfg["q_lora_rank"] is not None \
            or not cfg["mla_use_nope"] or cfg["num_shared_experts"] != 1 \
            or cfg["moe_layer_freq"] != 1 or cfg["tie_word_embeddings"] \
            or cfg["num_nextn_predict_layers"]:
        raise ValueError(
            "the program normalises the top-k sigmoid weights under a "
            "selection bias in one group, projects the queries of latent "
            "attention directly and rotates nothing, holds one shared "
            "expert and a sparse FFN in every layer after the dense ones, "
            "an untied head and no prediction module")
    bias = reference.expert_bias(cfg)
    return lambda params, batch: kimi.loss_fn(params, batch, pc, bias)
