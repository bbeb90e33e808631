"""What the ``lfm2`` family adds to the expert layer's metrics
(``models/lfm2.py`` through ``models/moe.py moe_layer``). Counter:
``moe/bias_moved_pairs``, the (token, slot) pairs a step whose selected
expert is not among the token's ``top_k`` by score alone: the selection
bias at work. Device: the grouped products' share of their roofline as
``layers/moe.py`` reads it (XLA's ``ragged-dot`` kernels, at the pairs
the load counters give), with the held experts' weights counted in the
layers that have experts (``reference/lfm2.py expert_products_cost``):
in this family the leading layers have none. Every reader returns None
where the program has no such counter or the trace no such kernel."""

from . import _cell
from .moe import KERNEL, _routed_pairs_per_step


def _bias_moved(ctx):
    moved = ctx.counter_delta("moe/bias_moved_pairs")
    return moved / ctx.steps if moved is not None and ctx.steps else None


def _roofline(ctx):
    pairs = _routed_pairs_per_step(ctx)
    seconds = _cell.family_seconds_per_step(ctx, KERNEL)
    if not pairs or not seconds:
        return None
    from ..reference.lfm2 import expert_products_cost

    flops, nbytes = expert_products_cost(pairs / ctx.chips,
                                         _cell.running_config())
    return _cell.roofline_pct(ctx, flops, nbytes, seconds)


METRICS = {
    "experts.bias_moved_pairs_per_step": _bias_moved,
    "experts.sparse_mm_roofline_pct": _roofline,
}
