"""What the ``kimi`` family adds (``models/kimi.py``: Kimi Delta
Attention through ``ops/delta_rule.py``). Device: the Pallas forward and
backward kernels under ``bps.attn.kda``, one operation family in the
reduced trace; their share of the roofline is what a training step
NEEDS of the KDA layers held whatever implements them
(``reference/kimi.py kda_step_cost``: the recurrence's own arithmetic,
nothing recomputed and no chunk algebra, and every operand and every
gradient across HBM once) over the device time of all the kernels'
calls: the forward run again under remat, the chunk's forward computed
again inside the backward kernel and the chunk algebra's extra products
show as a lower share, so it cannot pass 100. Counter:
``kda/chunk_steps``, the sequential chunk steps of a step's KDA layers
(rows x heads x chunks a layer: a statistic the step program returns
beside its loss), the number the family's time scales with. Every
reader returns None where the trace has no such kernel or the program
no such counter."""

from . import _cell

SCOPE = "bps.attn.kda"


def _device_ms(ctx):
    s = _cell.family_seconds_per_step(ctx, SCOPE)
    return None if s is None else s * 1e3


def _roofline(ctx):
    seconds = _cell.family_seconds_per_step(ctx, SCOPE)
    if not seconds:
        return None
    from ..reference.kimi import kda_layers, kda_step_cost

    cfg = _cell.running_config()
    layers = kda_layers(cfg)
    flops, nbytes = kda_step_cost(ctx.global_batch // ctx.chips, cfg)
    return _cell.roofline_pct(ctx, layers * flops, layers * nbytes, seconds)


def _chunk_steps(ctx):
    steps = ctx.counter_delta("kda/chunk_steps")
    return steps / ctx.steps if steps is not None and ctx.steps else None


METRICS = {
    "attention.kda_device_ms": _device_ms,
    "attention.kda_roofline_pct": _roofline,
    "attention.kda_chunk_steps_per_step": _chunk_steps,
}
