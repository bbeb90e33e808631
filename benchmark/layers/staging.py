"""Staging arena (``core/arena.py``): slots allocated inside the window.
After warm-up every checkout should be served from a slot that exists."""

METRICS = {
    "staging.slot_allocs": lambda ctx: ctx.counter_delta(
        "arena/slot_allocs", "arena/fresh_allocs"),
}
