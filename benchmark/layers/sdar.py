"""What the ``sdar`` family adds (``models/sdar.py``: block-diffusion
training through ``ops/flash_attention.py``'s third mask). Device: the
Pallas forward, dK/dV and dQ kernels under ``bps.attn.blockdiff``, one
operation family in the reduced trace; their share of the roofline is
what a training step needs of the layers held (``reference/sdar.py
attention_step_cost``: two products forward and five backward over the
``L^2 + L B`` pairs a row the mask lets through, every tensor of the ``2
L`` positions across HBM once) over the device time of all the kernels'
calls: the forward run again under remat and the scores computed in both
backward kernels show as a lower share, so it cannot pass 100. Counter:
``diffusion/masked_tokens``, the positions a step's loss was taken at (a
statistic the step program returns beside its loss). Every reader
returns None where the trace has no such kernel or the program no such
counter."""

from . import _cell

SCOPE = "bps.attn.blockdiff"


def _device_ms(ctx):
    s = _cell.family_seconds_per_step(ctx, SCOPE)
    return None if s is None else s * 1e3


def _roofline(ctx):
    seconds = _cell.family_seconds_per_step(ctx, SCOPE)
    if not seconds:
        return None
    from ..reference.sdar import attention_step_cost

    cfg = _cell.running_config()
    layers = cfg["num_hidden_layers"]
    flops, nbytes = attention_step_cost(ctx.global_batch // ctx.chips, cfg)
    return _cell.roofline_pct(ctx, layers * flops, layers * nbytes, seconds)


def _masked_tokens(ctx):
    masked = ctx.counter_delta("diffusion/masked_tokens")
    return masked / ctx.steps if masked is not None and ctx.steps else None


METRICS = {
    "attention.blockdiff_device_ms": _device_ms,
    "attention.blockdiff_roofline_pct": _roofline,
    "diffusion.masked_tokens_per_step": _masked_tokens,
}
