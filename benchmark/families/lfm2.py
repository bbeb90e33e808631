"""Family ``lfm2``: binds the program's ``models/lfm2.py`` to the
benchmark's seeded weights, batches, FLOP count and plain reference."""

from __future__ import annotations

import jax.numpy as jnp

from ..reference import lfm2 as reference  # noqa: F401  (the harness reads it)


def program_config(cfg: dict):
    """The program's own configuration of the cell: the layers, the
    experts and the vocabulary slice held, from the configuration's
    file (the program's tile sizes are the model layer's own)."""
    from byteps_tpu.models import lfm2

    first = cfg.get("first_layer_held", 0)
    return lfm2.LFM2Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=tuple(
            cfg["layer_types"][first:first + cfg["num_hidden_layers"]]),
        n_dense_layers=cfg["num_dense_layers"],
        dense_hidden=cfg["intermediate_size"],
        n_experts=cfg["num_experts"],
        n_experts_held=cfg["num_experts_held"],
        first_expert=cfg.get("first_expert_held", 0),
        top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        conv_kernel=cfg["conv_L_cache"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["norm_eps"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        router_dtype=jnp.dtype(cfg["router_dtype"]), remat=cfg["remat"])


def program_loss(cfg: dict):
    """``loss_fn(params, batch) -> (loss, stats)`` through the program's
    own model code. The expert bias is the buffer the reference makes
    from the configuration's file: an argument of the model beside the
    parameters, so it is in no gradient, no optimizer state and no
    push."""
    from byteps_tpu.models import lfm2

    pc = program_config(cfg)
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program normalises the top-k weights")
    bias = reference.expert_bias(cfg)
    return lambda params, batch: lfm2.loss_fn(params, batch, pc, bias)
