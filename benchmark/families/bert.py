"""Family ``bert``: binds the program's ``models/bert.py`` to the
benchmark's seeded weights, batches, FLOP count and plain reference."""

from __future__ import annotations

import jax.numpy as jnp

from ..reference import bert as reference  # noqa: F401  (the harness reads it)


def program_loss(cfg: dict):
    """``loss_fn(params, batch)`` through the program's own model code."""
    from byteps_tpu.models import bert

    pc = bert.BertConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        ffn_dim=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        type_vocab=cfg["type_vocab_size"], norm_eps=cfg["layer_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]), remat=cfg["remat"])
    return lambda params, batch: bert.loss_fn(params, batch, pc)
