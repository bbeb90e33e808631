"""Worker step (``jax/train.py make_ps_train_step``, and the fused
``make_train_step`` in the control's cell): the program's own StepReport
spans, median over the window's steps; and the mean of the middle half
of the window's step walls, a steadier statistic to stand beside the
end-to-end ``step_ms``, which is the whole window over its steps and so
counts every stall (single steps of 1.2 to 6 s on the chip machines'
shared host; PERF.md, PR 23). The two apart say how much of a run's
``step_ms`` was stalls."""

import statistics


def _centre_step_ms(ctx):
    s = sorted(ctx.walls_ms)
    q = len(s) // 4
    return statistics.fmean(s[q:len(s) - q]) if s else None


METRICS = {
    "worker.compute_ms": lambda ctx: ctx.report_median("compute_ms"),
    "worker.ttfp_ms": lambda ctx: ctx.report_median("ttfp_ms"),
    "worker.centre_step_ms": _centre_step_ms,
}
