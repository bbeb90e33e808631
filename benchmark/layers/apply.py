"""Import and apply (``land``, ``jax/optim.py``): the completion-ordered
PULL, H2D and update loop after the export."""

METRICS = {"apply.drain_ms": lambda ctx: ctx.report_median("drain_ms")}
