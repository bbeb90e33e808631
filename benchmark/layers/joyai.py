"""What the ``joyai`` family adds (``models/joyai.py``: latent attention
through ``ops/flash_attention.py latent_attention``, a prediction module
that uses the head a second time). Device: the Pallas forward, dK/dV
and dQ kernels under ``bps.attn.mla``, one operation family in the
reduced trace; their share of the roofline is what a training step
needs of the blocks that hold such attention, the layers held and the
module's (``reference/joyai.py attention_step_cost``: two products
forward and five backward over the causal pairs, four of them over the
score's 192 columns and three over the value's 128, every tensor across
HBM once, the one rotary key once a row and not once a head) over the
device time of all the kernels' calls: the forward run again under
remat and the scores computed in both backward kernels show as a lower
share, so it cannot pass 100. Counter: ``mtp/predicted_tokens``, the
positions a step's second loss was taken at (a statistic the step
program returns beside its loss). Every reader returns None where the
trace has no such kernel or the program no such counter."""

from . import _cell

SCOPE = "bps.attn.mla"


def _device_ms(ctx):
    s = _cell.family_seconds_per_step(ctx, SCOPE)
    return None if s is None else s * 1e3


def _roofline(ctx):
    seconds = _cell.family_seconds_per_step(ctx, SCOPE)
    if not seconds:
        return None
    from ..reference.joyai import attention_blocks, attention_step_cost

    cfg = _cell.running_config()
    blocks = attention_blocks(cfg)
    flops, nbytes = attention_step_cost(ctx.global_batch // ctx.chips, cfg)
    return _cell.roofline_pct(ctx, blocks * flops, blocks * nbytes, seconds)


def _predicted_tokens(ctx):
    tokens = ctx.counter_delta("mtp/predicted_tokens")
    return tokens / ctx.steps if tokens is not None and ctx.steps else None


METRICS = {
    "attention.mla_device_ms": _device_ms,
    "attention.mla_roofline_pct": _roofline,
    "mtp.predicted_tokens_per_step": _predicted_tokens,
}
