"""Kernels (the XLA step programs; no Pallas kernel is on these cells'
path): model FLOPs per step over the seconds the device was busy in a
step and the chip's peak. Says how well the programs use the chip while
they run, whatever the host does in between."""


def _busy_mfu(ctx):
    if ctx.trace is None or not ctx.traced_steps:
        return None
    busy_per_step = ctx.trace.busy_mean_s / ctx.traced_steps
    return 100.0 * ctx.flops_per_step / ctx.chips / (
        busy_per_step * ctx.peak_flops_per_chip)


METRICS = {"kernels.busy_mfu_pct": _busy_mfu}
