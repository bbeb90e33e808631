"""Server (``native/ps.cc``): stage walls summed over a step's requests,
from the program's StepReport (STATS_PULL deltas)."""

METRICS = {
    "server.fold_ms": lambda ctx: ctx.report_median("server_fold_ms"),
    "server.queue_ms": lambda ctx: ctx.report_median("server_queue_ms"),
}
