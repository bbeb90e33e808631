"""Family ``sdar``: binds the program's ``models/sdar.py`` to the
benchmark's seeded weights, batches, FLOP count and plain reference."""

from __future__ import annotations

import jax.numpy as jnp

from ..reference import sdar as reference  # noqa: F401  (the harness reads it)


def program_config(cfg: dict):
    """The program's own configuration of the cell: the layers, the
    experts and the vocabulary slice held, the diffusion block and the
    mask token, from the configuration's file (the program's tile sizes
    are the model layer's own)."""
    from byteps_tpu.models import sdar

    return sdar.SDARConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_experts=cfg["num_experts"],
        n_experts_held=cfg["num_experts_held"],
        first_expert=cfg.get("first_expert_held", 0),
        top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        block_length=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        router_dtype=jnp.dtype(cfg["router_dtype"]), remat=cfg["remat"])


def program_loss(cfg: dict):
    """``loss_fn(params, batch) -> (loss, stats)`` through the program's
    own model code."""
    from byteps_tpu.models import sdar

    pc = program_config(cfg)
    if not cfg["norm_topk_prob"] or cfg["rope_scaling"] is not None \
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1:
        raise ValueError("the program normalises the top-k weights, scales "
                         "no rotary and holds a sparse FFN in every layer")
    return lambda params, batch: sdar.loss_fn(params, batch, pc)
