"""The comparison that decides ``correct``.

Every number compared has a limit of its own, kept in the cell's file
(``benchmarks/cells/<cell>.json``, ``limits``) with the readings it was
set from. A number over its limit, a number that is not finite, or a
number the cell's file has no limit for, makes the run not correct.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List

ZERO_GRADIENT_SHARE = 1e-3


def training_numbers(program: dict, reference: dict) -> Dict[str, float]:
    """``program`` and ``reference`` each hold ``loss`` (one per step),
    ``grad_norm`` (per leaf, of the first gradient as the optimizer got
    it) and ``change_norm`` (per leaf, of the parameters' change over the
    steps).

    - ``loss_gap_step<i>``: ``|program - reference| / |reference|``.
    - ``grad_norm_gap``, ``change_norm_gap``: by the worst leaf, the gap
      between the program's norm and the reference's (not the norm of
      their difference), against the reference's norm of that leaf or of
      the median leaf, whichever is larger; ``*_median_leaf`` is the same
      gap of the median leaf, which is steady from seed to seed where the
      worst leaf carries one small leaf's noise.
    - leaves whose reference gradient is under a thousandth of the median
      leaf's move under Adam by round-off alone and are left out of the
      change (by this rule on the gradient, not by name).
    - ``bn_state_gap``: see :func:`difference_gaps`; only where both sides
      hold ``bn_state``.
    """
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], reference["loss"])):
        out[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    if len(program["loss"]) != len(reference["loss"]):
        out["loss_gap_step1"] = math.inf
    g_ref = reference["grad_norm"]
    g_median = statistics.median(g_ref.values())
    gaps = leaf_gaps(program["grad_norm"], g_ref)
    out["grad_norm_gap"] = max(gaps.values())
    out["grad_norm_gap_median_leaf"] = statistics.median(gaps.values())
    moved = {k for k, g in g_ref.items()
             if g >= ZERO_GRADIENT_SHARE * g_median}
    c_ref = {k: v for k, v in reference["change_norm"].items() if k in moved}
    gaps = leaf_gaps(program["change_norm"], c_ref)
    out["change_norm_gap"] = max(gaps.values())
    out["change_norm_gap_median_leaf"] = statistics.median(gaps.values())
    if "bn_state" in program and "bn_state" in reference:
        gaps = difference_gaps(program["bn_state"], reference["bn_state"])
        out["bn_state_gap"] = max(gaps.values())
        out["bn_state_gap_median_leaf"] = statistics.median(gaps.values())
    return out


def difference_gaps(program: dict, reference: dict) -> Dict[str, float]:
    """Per leaf (a vector: BatchNorm's running mean or variance after the
    checked steps) the norm of the difference against the reference's
    norm of that leaf or of the median leaf, whichever is larger. Unlike a
    gap of norms this is of first order in the forward pass's rounding,
    which is what tells the configuration's precision from the one
    below it."""
    import numpy as np

    norms = {k: float(np.linalg.norm(v)) for k, v in reference.items()}
    median = statistics.median(norms.values())
    out = {}
    for leaf, ref in reference.items():
        if leaf not in program or program[leaf].shape != ref.shape:
            out[leaf] = math.inf
            continue
        gap = float(np.linalg.norm(program[leaf] - ref)) / max(
            norms[leaf], median)
        out[leaf] = gap if math.isfinite(gap) else math.inf
    return out


def leaf_gaps(program: dict, reference: dict) -> Dict[str, float]:
    """Per leaf ``|program - reference| / max(reference, median
    reference)``; a leaf the program lacks, or a norm that is not finite,
    reads infinity."""
    median = statistics.median(reference.values())
    out = {}
    for leaf, ref in reference.items():
        gap = (abs(program[leaf] - ref) / max(ref, median)
               if leaf in program else math.inf)
        out[leaf] = gap if math.isfinite(gap) else math.inf
    return out


def worst_leaves(program: dict, reference: dict, n: int = 6):
    """The look behind a norm's gap: the ``n`` leaves that read most, as
    ``[leaf, gap, program norm, reference norm]``."""
    gaps = leaf_gaps(program, reference)
    top = sorted(gaps, key=lambda k: -gaps[k])[:n]
    return [[k, gaps[k], program.get(k), reference[k]] for k in top]


def logit_gaps(ref_logits, start: int, served: List[int]) -> List[float]:
    """For each served token the gap by which its logit lies below the
    reference's best at that position, in units of the standard deviation
    of the reference's logits there. ``ref_logits[start + j]`` predicts
    ``served[j]``."""
    import numpy as np

    rows = np.asarray(ref_logits[start:start + len(served)], np.float64)
    best = rows.max(axis=-1)
    got = rows[np.arange(len(served)), np.asarray(served)]
    return ((best - got) / rows.std(axis=-1)).tolist()


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """``(correct, compared)``. The cell's file decides which numbers are
    compared: each name in ``limits``, in that order, against its limit.
    ``compared`` maps each to its value and its limit. A number the run
    did not produce, or one that is not finite, is over its limit."""
    compared = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name, math.inf)
        compared[name] = {"value": _plain(value), "limit": float(limit)}
        if not (math.isfinite(value) and value <= limit):
            ok = False
    return ok and bool(limits), compared


def _plain(x: float) -> float:
    return float(x) if math.isfinite(x) else 1e30
