"""Expert layer (``models/moe.py moe_layer``: the router and the held
experts' grouped products). Counters: the statistics the step program
returns beside its loss, in the registry after each step:
``moe/expert_load/<layer>/<expert>``, the pairs each held expert
computed, and ``moe/dropped_pairs``. The pairs routed here are the
loads' sum; the imbalance is the busiest (layer, expert)'s load over
the mean's, both over the whole window. Device: the three grouped
products are XLA's ``ragged-dot`` kernels (forward, the recomputations
of the remat, and both transposes of the backward), the only operations
under ``bps.moe.experts`` the reduced trace can name; their share of
the roofline counts what forward and backward need at the pairs the
counters give, nothing recomputed."""

from . import _cell

KERNEL = "ragged-dot"
LOAD = "moe/expert_load/"


def _loads(ctx):
    """The window's pairs of each held (layer, expert)."""
    return [after - ctx.counters_before.get(key, 0)
            for key, after in ctx.counters_after.items()
            if key.startswith(LOAD)]


def _routed_pairs_per_step(ctx):
    loads = _loads(ctx)
    return sum(loads) / ctx.steps if loads and ctx.steps else None


def _load_ratio(ctx):
    loads = _loads(ctx)
    return max(loads) * len(loads) / sum(loads) \
        if loads and sum(loads) else None


def _device_ms(ctx):
    s = _cell.family_seconds_per_step(ctx, KERNEL)
    return None if s is None else s * 1e3


def _roofline(ctx):
    pairs = _routed_pairs_per_step(ctx)
    seconds = _cell.family_seconds_per_step(ctx, KERNEL)
    if not pairs or not seconds:
        return None
    from ..reference.mellum import expert_products_cost

    flops, nbytes = expert_products_cost(pairs / ctx.chips,
                                         _cell.running_config())
    return _cell.roofline_pct(ctx, flops, nbytes, seconds)


METRICS = {
    "experts.routed_pairs_per_step": _routed_pairs_per_step,
    "experts.load_max_over_mean": _load_ratio,
    "experts.dropped_pairs": lambda ctx: ctx.counter_delta("moe/dropped_pairs"),
    "experts.device_ms": _device_ms,
    "experts.grouped_mm_roofline_pct": _roofline,
}
