"""What the ``afmoe`` family adds (``models/afmoe.py``: window and full
attention three to one, both behind a cut backward). Device: the Pallas
kernels under ``bps.attn.window`` and ``bps.attn.full`` each on its own
(``layers/attention.py`` has only their sum), and the grouped products'
share of their roofline as ``layers/moe.py`` reads it (the
``ragged-dot`` family, at the pairs the load counters give), with the
held experts' weights counted in the layers that have experts
(``reference/afmoe.py expert_products_cost``): in this family the
leading layer has none. Counters: ``attn/window_pairs`` and
``attn/full_pairs``, the (query, key, head) triples inside the masks of
a step's sliding and full layers (statistics the step program returns
beside its loss), the numbers the two kernels' time scales with. Every
reader returns None where the trace has no such kernel or the program no
such counter."""

from . import _cell
from .attention import SCOPES
from .moe import KERNEL, _routed_pairs_per_step


def _device_ms(kind):
    def reader(ctx):
        s = _cell.family_seconds_per_step(ctx, SCOPES[kind])
        return None if s is None else s * 1e3
    return reader


def _band_pairs(ctx):
    pairs = ctx.counter_delta("attn/window_pairs", "attn/full_pairs")
    return pairs / ctx.steps if pairs is not None and ctx.steps else None


def _roofline(ctx):
    pairs = _routed_pairs_per_step(ctx)
    seconds = _cell.family_seconds_per_step(ctx, KERNEL)
    if not pairs or not seconds:
        return None
    from ..reference.afmoe import expert_products_cost

    flops, nbytes = expert_products_cost(pairs / ctx.chips,
                                         _cell.running_config())
    return _cell.roofline_pct(ctx, flops, nbytes, seconds)


METRICS = {
    "attention.window_device_ms": _device_ms("sliding_attention"),
    "attention.full_device_ms": _device_ms("full_attention"),
    "attention.band_pairs_per_step": _band_pairs,
    "experts.held_mm_roofline_pct": _roofline,
}
