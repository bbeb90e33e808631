"""Device: the share of the traced window in which no operation ran,
averaged over the cell's chips."""

METRICS = {
    "device.idle_pct": lambda ctx: (
        None if ctx.trace is None else 100.0 * ctx.trace.idle_share),
}
