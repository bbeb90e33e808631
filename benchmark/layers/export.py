"""Export, device to host (io_callback taps and the export pools): bytes
from the registry's counters, rate over the step's export span."""


def _bytes_per_step(ctx):
    total = ctx.counter_delta("export/whole_bytes", "export/shard_bytes")
    return None if total is None or not ctx.steps else total / ctx.steps


def _mb(ctx):
    b = _bytes_per_step(ctx)
    return None if b is None else b / 1e6


def _gbps(ctx):
    b, ms = _bytes_per_step(ctx), ctx.report_median("compute_ms")
    return None if b is None or not ms else b / 1e9 / (ms / 1e3)


METRICS = {"export.mb_per_step": _mb, "export.gbps": _gbps}
