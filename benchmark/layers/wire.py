"""Scheduler and wire (``core/scheduler.py``, ``server/client.py``)."""


def _requests(ctx):
    n = ctx.counter_delta("wire/pushpull_requests")
    return None if n is None or not ctx.steps else n / ctx.steps


METRICS = {
    "wire.requests_per_step": _requests,
    "wire.pull_p95_ms": lambda ctx: ctx.report_median("pull_p95_ms"),
}
