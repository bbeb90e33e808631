"""What the claim and the drain waited for (``jax/train.py``'s
``bps.step.backward_wait`` span and marks, reduced by the program into
its StepReport: ``core/metrics.py step_path_fields``): the wait for the
backward program, the host behind it, the drain split into the wait for
the wire and the train thread's imports, the wire's tail after the last
submission, and the CPU the process used over a whole step. Medians over
the timed window's steps, profiler off; the train thread's own CPU is
the window's MEAN, because a kernel may count a thread's CPU in ticks of
10 ms and one step's reading is then 0, 10 or 20. A program without
these StepReport fields gives nothing to read."""

import statistics


def _field(key):
    return lambda ctx: ctx.report_median(key)


def _mean(key):
    def read(ctx):
        vals = [r[key] for r in ctx.reports if r.get(key) is not None]
        return statistics.fmean(vals) if vals else None
    return read


METRICS = {
    "worker.backward_wait_ms": _field("backward_wait_ms"),
    "export.behind_backward_ms": _field("export_behind_backward_ms"),
    "export.train_thread_cpu_ms": _mean("claim_thread_cpu_ms"),
    "host.step_cpu_ms": _field("step_cpu_ms"),
    "apply.pull_wait_ms": _field("pull_wait_ms"),
    "apply.land_ms": _field("drain_land_ms"),
    "wire.tail_after_claim_ms": _field("wire_tail_after_claim_ms"),
}
