"""Family ``afmoe``: binds the program's ``models/afmoe.py`` to the
benchmark's seeded weights, batches, FLOP count and plain reference
(``reference/afmoe.py``: dense ``[S, S]`` scores under masks written
out, the gate, the four norms, the held experts as a plain loop)."""

from __future__ import annotations

import jax.numpy as jnp

from ..reference import afmoe as reference  # noqa: F401  (the harness reads it)


def program_config(cfg: dict):
    """The program's own configuration of the cell: the layers held and
    their kinds, the experts and the vocabulary slice held, from the
    configuration's file (the tile sizes are the program's own). The
    program's configuration refuses more than one group, another score
    function and a tied head."""
    from byteps_tpu.models import afmoe

    return afmoe.AfmoeConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        n_dense_layers=cfg["num_dense_layers"],
        dense_hidden=cfg["intermediate_size"], n_experts=cfg["num_experts"],
        n_experts_held=cfg["num_experts_held"],
        first_expert=cfg.get("first_expert_held", 0),
        top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        route_scale=float(cfg["route_scale"]), score_func=cfg["score_func"],
        groups=(cfg["n_group"], cfg["topk_group"], cfg["num_expert_groups"],
                cfg["num_limited_groups"]),
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        router_dtype=jnp.dtype(cfg["router_dtype"]), remat=cfg["remat"])


def program_loss(cfg: dict):
    """``loss_fn(params, batch) -> (loss, stats)`` through the program's
    own model code. The expert bias is the buffer the reference makes
    from the configuration's file: an argument of the model beside the
    parameters, so it is in no gradient, no optimizer state and no
    push."""
    from byteps_tpu.models import afmoe

    pc = program_config(cfg)
    if not cfg["route_norm"] or not cfg["mup_enabled"] \
            or cfg["num_shared_experts"] != 1 \
            or cfg["rope_scaling"] is not None \
            or cfg["hidden_act"] != "silu":
        raise ValueError(
            "the program normalises the top-k sigmoid weights, scales the "
            "embedding by sqrt(hidden_size), holds one shared expert, "
            "rotates the sliding layers by the plain table and gates its "
            "FFNs with SiLU")
    bias = reference.expert_bias(cfg)
    return lambda params, batch: afmoe.loss_fn(params, batch, pc, bias)
