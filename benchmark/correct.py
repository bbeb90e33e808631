"""The comparison that decides ``correct``. Every number compared is
printed beside its limit; the limits live in each configuration's file
(``limits``), with the readings they were set from in PERF.md.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

import numpy as np

# one wire partition (4 MB of f32): a slot or partition that carried
# another's bytes shows up whole in one block
BLOCK = 1_024_000


def worst_leaf_gap(got: List[float], want: List[float]) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, as a share of the reference's norm of that leaf or of
    the median leaf, whichever is larger (some gradients are all but
    zero)."""
    floor = statistics.median(want)
    return max(abs(g - w) / max(w, floor, 1e-30)
               for g, w in zip(got, want))


def compare_training(program: dict, reference: dict,
                     limits: Dict[str, float]) -> Dict[str, Tuple[float, float]]:
    """name -> (value, limit) for the first steps' losses, the first
    gradient's norms and the parameters' change, each against the
    reference's. A non-finite reading compares as infinite."""
    rows = {
        "loss_rel_gap": max(
            abs(p - r) / abs(r) for p, r in
            zip(program["losses"], reference["losses"])),
        "grad_norm_gap": worst_leaf_gap(program["grad_norms"],
                                        reference["grad_norms"]),
        "delta_norm_gap": worst_leaf_gap(program["delta_norms"],
                                         reference["delta_norms"]),
    }
    return {k: (v if math.isfinite(v) else math.inf, limits[k])
            for k, v in rows.items()}


def compare_wire(pushed: int, folded: int, steps: int, wire_bytes: int,
                 limits: Dict[str, float]) -> Dict[str, Tuple[float, float]]:
    """name -> (value, limit) for the timed path's own wire, from the
    program's counters over the window: the bytes a step pushed against
    ``wire_bytes``, every gradient element once in the wire type the
    configuration states (a lower-precision or compressed push sends
    fewer), and the bytes the server folded against those pushed."""
    return {
        "wire_bytes_per_step_gap": (abs(pushed / steps - wire_bytes),
                                    limits["wire_bytes_per_step_gap"]),
        "server_fold_bytes_gap": (float(abs(folded - pushed)),
                                  limits["server_fold_bytes_gap"]),
    }


def transport_mismatch(sent: np.ndarray, back: np.ndarray) -> int:
    """How many ``BLOCK``-element blocks of one leaf came back from the
    parameter server differing in any bit from what one worker sent (its
    sum over one worker)."""
    a = np.ascontiguousarray(sent).reshape(-1).view(np.uint32)
    b = np.ascontiguousarray(back).reshape(-1)
    if b.dtype != np.float32 or b.size != a.size:
        return -(-a.size // BLOCK)
    differs = a != b.view(np.uint32)
    edges = np.arange(0, a.size, BLOCK)
    return int(np.count_nonzero(np.add.reduceat(differs, edges)))


def verdict(rows: Dict[str, Tuple[float, float]],
            log=print) -> bool:
    ok = True
    for name, (value, limit) in rows.items():
        passed = value <= limit
        ok = ok and passed
        log(f"correct: {name} = {value:.6g} (limit {limit:.6g}) "
            f"{'ok' if passed else 'FAILED'}")
    return ok
