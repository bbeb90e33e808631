"""What a per-layer metric's reader is given, and how the harness finds
the readers: every ``layers/<layer>.py`` has a ``METRICS`` dict of
``name -> reader(ctx)``; a reader that finds nothing to read returns
``None`` and the metric is left out of the line.
"""

from __future__ import annotations

import importlib
import os
import statistics
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class LayerContext:
    steps: int                        # whole steps in the window
    window_s: float                   # first step's start to last step's end
    step_ms: float                    # the window over its whole steps
    walls_ms: List[float]             # each step's wall, in order
    global_batch: int
    chips: int
    reports: List[dict]               # the program's StepReports of the window
    counters_before: Dict[str, float]
    counters_after: Dict[str, float]
    flops_per_step: float
    peak_flops_per_chip: float
    trace: Optional[object] = None    # trace_reduce.Reduced of the traced steps
    traced_steps: int = 0
    control_step_ms: Optional[float] = None

    def report_median(self, key: str) -> Optional[float]:
        vals = [r[key] for r in self.reports if r.get(key) is not None]
        return statistics.median(vals) if vals else None

    def counter_delta(self, *keys: str) -> Optional[float]:
        if not all(k in self.counters_after for k in keys):
            return None
        return sum(self.counters_after[k] - self.counters_before.get(k, 0)
                   for k in keys)


def load_readers(layers_dir: str = os.path.join(HERE, "layers")
                 ) -> Dict[str, Callable[[LayerContext], Optional[float]]]:
    readers: Dict[str, Callable] = {}
    for fname in sorted(os.listdir(layers_dir)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        mod = importlib.import_module(f"benchmark.layers.{fname[:-3]}")
        for name, reader in mod.METRICS.items():
            if name in readers:
                raise ValueError(f"per-layer metric {name!r} has two readers")
            readers[name] = reader
    return readers
