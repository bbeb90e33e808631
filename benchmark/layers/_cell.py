"""What ``layer_api.LayerContext`` does not carry and the readers of a
kernel's roofline share need: the configuration of the cell this process
runs (its widths and lengths), found the way ``run.py`` finds it, from
the process's own ``--workload``, ``--manifest`` and ``--rehearse``; the
chip's peak bytes a second; and the device seconds of an operation
family in the reduced trace. A reader asks for the first two only once
it has found its kernel in the trace, and then they must be there: a
cell whose configuration or chip cannot be found raises, it does not
lose its metric in silence. A leading ``_`` keeps this file out of
``load_readers``."""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def _overlay(base: dict, tiny: dict) -> dict:
    out = dict(base)
    for k, v in tiny.items():
        out[k] = _overlay(base[k], v) if isinstance(v, dict) \
            and isinstance(base.get(k), dict) else v
    return out


def running_config(argv: Optional[Sequence[str]] = None) -> dict:
    """The configuration (with its ``rehearse`` overlay in a rehearsal)
    of the cell named on the command line."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--manifest", default=os.path.join(REPO, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    args, _ = ap.parse_known_args(sys.argv[1:] if argv is None else argv)
    with open(args.manifest) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise LookupError(
            f"this process's command line names no cell of {args.manifest} "
            f"(--workload {args.workload!r}): the reader cannot size its "
            f"kernel's work")
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    with open(os.path.join(os.path.dirname(os.path.abspath(args.manifest)),
                           files[cells[args.workload]["config"]])) as f:
        config = json.load(f)
    return _overlay(config, config.get("rehearse", {})) \
        if args.rehearse else config


def peak_bytes_per_s() -> float:
    """The memory bandwidth of the chip this process runs on, from
    ``peaks.json`` by the device's kind, as ``run.py`` finds its peak
    FLOPs."""
    import jax

    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    return peaks[jax.devices()[0].device_kind]["hbm_bytes_per_s"]


def family_seconds_per_step(ctx, prefix: str) -> Optional[float]:
    """Device seconds a step and chip of the operations whose family
    name (``trace_reduce.op_family``: the HLO instruction's name less
    its number) starts with ``prefix``. A Pallas kernel's instruction is
    named by the ``jax.named_scope`` around its call, XLA's grouped
    matrix product ``ragged-dot``; a fusion carries no scope, so only
    such kernels can be told apart in the reduced trace. None where the
    trace holds no such operation (the parent of the PR that brought
    the kernel, or a run without a trace)."""
    if ctx.trace is None or not ctx.traced_steps:
        return None
    total = sum(t for name, t in ctx.trace.op_seconds
                if name.startswith(prefix))
    return total / ctx.traced_steps / ctx.chips if total > 0 else None


def roofline_pct(ctx, flops: float, nbytes: float, seconds: float) -> float:
    """The least time the chip could take (the larger of the FLOP and
    the byte bound) over the time it took."""
    return 100.0 * max(flops / ctx.peak_flops_per_chip,
                       nbytes / peak_bytes_per_s()) / seconds
