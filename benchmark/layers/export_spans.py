"""Export, from inside (``jax/train.py``'s spans on the export path,
reduced by the program into its StepReport): where ``worker.compute_ms``
goes between the backward's dispatch and the last leaf's submission.
Medians over the timed window's steps, profiler off. A program without
these StepReport fields gives nothing to read."""


def _field(key):
    return lambda ctx: ctx.report_median(key)


METRICS = {
    "export.dispatch_ms": _field("dispatch_ms"),
    "export.tap_span_ms": _field("export_tap_span_ms"),
    "export.router_busy_ms": _field("export_router_busy_ms"),
    "export.materialize_ms": _field("export_materialize_ms"),
    "export.submit_ms": _field("export_submit_ms"),
    "export.router_wait_max_ms": _field("export_router_wait_max_ms"),
}
