"""Family ``mellum``: binds the program's ``models/mellum.py`` to the
benchmark's seeded weights, batches, FLOP count and plain reference."""

from __future__ import annotations

import jax.numpy as jnp

from ..reference import mellum as reference  # noqa: F401  (the harness reads it)


def program_loss(cfg: dict):
    """``loss_fn(params, batch) -> (loss, stats)`` through the program's
    own model code: the experts held, the vocabulary slice and both
    rotary sections from the configuration's file (the program's tile
    sizes are the model layer's own)."""
    from byteps_tpu.models import mellum

    full = cfg["rope_parameters"][mellum.FULL]
    sliding = cfg["rope_parameters"][mellum.SLIDING]
    if full["rope_theta"] != sliding["rope_theta"]:
        raise ValueError("the program keeps one rope_theta for both kinds")
    pc = mellum.MellumConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_experts=cfg["num_experts"],
        n_experts_held=cfg["num_experts_held"],
        first_expert=cfg.get("first_expert_held", 0),
        top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        sliding_window=cfg["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original_len=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=full["attention_factor"],
        norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        router_dtype=jnp.dtype(cfg["router_dtype"]), remat=cfg["remat"])
    return lambda params, batch: mellum.loss_fn(params, batch, pc)
