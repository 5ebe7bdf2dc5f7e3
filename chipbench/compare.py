"""The numbers that decide ``correct``: the program's compared rounds
against the plain reference's (``reference.py``).

* ``loss1``: the first compared round's mean client loss, as a gap over
  the reference's; ``loss``: the largest such gap over the compared rounds;
* ``update1``: the server update of round 1 (params after it minus the
  params before it), as the optimizer gets it;
* ``change_n``: the params' change over all compared rounds;
* ``residual_n``: every client's error-feedback residual after them.

The last three are taken leaf by leaf: the gap between the program's norm
and the reference's, over the reference's norm of that leaf or of the median
leaf, whichever is larger. The plain name is the worst leaf; ``_median``
is the median leaf, for a configuration whose small leaves amplify rounding
(VGG16's BatchNorm vectors, see PERF.md). Leaves whose round-1 update in the
reference is under a thousandth of the median leaf's (conv biases ahead of a
BatchNorm, whose gradient is nought up to rounding) are left out of all of
them by that rule, not by name. A cell's ``limits/<cell>.json`` names the
numbers it compares.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss1", "loss", "update1", "change_n", "residual_n",
           "update1_median", "change_n_median", "residual_n_median")
NEGLIGIBLE = 1e-3


def _norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64).reshape(-1)))


def _worst(gaps) -> float:
    """The largest gap; NaN when any gap is NaN, so that it fails."""
    gaps = list(gaps)
    return math.nan if any(math.isnan(g) for g in gaps) else max(gaps)


def _leaf_gaps(prog: dict, ref: dict, keep: list) -> list:
    scale = float(np.median([ref[n] for n in keep]))
    return [abs(prog[n] - ref[n]) / max(ref[n], scale, 1e-30) for n in keep]


def _median(gaps) -> float:
    return math.nan if any(math.isnan(g) for g in gaps) else float(
        np.median(gaps))


def numbers(prog, ref) -> dict:
    """Both sides are ``reference.Outputs`` (the program's built from what
    its run produced)."""
    upd = {side: {n: _norm(o.params1[n] - o.params0[n]) for n in o.params0}
           for side, o in (("prog", prog), ("ref", ref))}
    median = float(np.median(list(upd["ref"].values())))
    keep = [n for n, v in upd["ref"].items() if v >= NEGLIGIBLE * median]
    chg = {side: {n: _norm(o.params_n[n] - o.params0[n]) for n in o.params0}
           for side, o in (("prog", prog), ("ref", ref))}
    res = {side: {n: _norm(o.residuals[n]) for n in o.residuals}
           for side, o in (("prog", prog), ("ref", ref))}
    loss_gaps = ([abs(a - b) / abs(b) for a, b in zip(prog.losses,
                                                      ref.losses)]
                 if len(prog.losses) == len(ref.losses) else [math.inf])
    out = {"loss1": loss_gaps[0], "loss": _worst(loss_gaps)}
    for name, per_side in (("update1", upd), ("change_n", chg),
                           ("residual_n", res)):
        gaps = _leaf_gaps(per_side["prog"], per_side["ref"], keep)
        out[name] = _worst(gaps)
        out[name + "_median"] = _median(gaps)
    return {k: out[k] for k in NUMBERS}


def compared(limits: dict) -> list:
    """The numbers a cell compares: those its limits file gives a limit."""
    return [k for k in NUMBERS if k in limits]


def verdict(nums: dict, limits: dict) -> bool:
    """True when the cell compares a number and every compared number is
    finite and at most its limit."""
    names = compared(limits)
    return bool(names) and all(math.isfinite(nums[k]) and nums[k] <= limits[k]
                               for k in names)
