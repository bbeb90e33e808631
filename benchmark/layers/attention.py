"""Attention kernels (``ops/flash_attention.py``): the Pallas forward,
dK/dV and dQ kernels under ``bps.attn.window`` (sliding layers; only the
band's tiles are visited) and ``bps.attn.full`` (causal). Mosaic names a
kernel's instruction by the scope around its call, so the reduced trace
holds one operation family a scope. The share of the roofline is what a
training step needs of a layer (``reference/mellum.py
attention_step_cost``: two products forward and five backward over the
mask's band, every tensor across HBM once) over the device time of all
the kernels' calls: a forward run again under remat, or scores computed
in both backward kernels, shows as a lower share."""

from . import _cell

SCOPES = {"sliding_attention": "bps.attn.window",
          "full_attention": "bps.attn.full"}


def _device_ms(ctx):
    parts = [_cell.family_seconds_per_step(ctx, scope)
             for scope in SCOPES.values()]
    parts = [p for p in parts if p is not None]
    return sum(parts) * 1e3 if parts else None


def _roofline(kind):
    def reader(ctx):
        seconds = _cell.family_seconds_per_step(ctx, SCOPES[kind])
        if not seconds:
            return None
        from ..reference.mellum import attention_step_cost

        cfg = _cell.running_config()
        layers = cfg["layer_types"][:cfg["num_hidden_layers"]].count(kind)
        flops, nbytes = attention_step_cost(
            ctx.global_batch // ctx.chips, cfg, kind)
        return _cell.roofline_pct(ctx, layers * flops, layers * nbytes,
                                  seconds)
    return reader


METRICS = {
    "attention.device_ms": _device_ms,
    "attention.window_roofline_pct": _roofline("sliding_attention"),
    "attention.full_roofline_pct": _roofline("full_attention"),
}
