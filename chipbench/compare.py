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

The teacher-forced numbers hold each compared round of the program against
``reference.forced``, which repeats that round from the program's own state
at its start (its params, and for the aggregation its deltas and
residuals), so that they read each stage before rounding grows:

* ``agg_update``, ``agg_update_median``: each round's server update (the
  params after it minus those before it) against the reference's
  aggregation of the program's deltas, leaf by leaf as above, worst round;
* ``agg_residual_median``: the cohort's residuals after each round, median
  leaf, worst round;
* ``sgd_delta_median``: the cohort's local-SGD deltas, median leaf, worst
  round.

The probe number reads local SGD's first step, which the harness takes in
the compared rounds through the program's own compiled local SGD, run again
on the round's params with ``lr`` 0 and each step's batch the first one
(``harness.probe_sgd``):

* ``sgd_step1``: each cohort client's step-1 loss (a forward pass at the
  round-start params), ``|prog - ref| / |ref|``, worst client and round.

Each round's negligible leaves follow the same rule on that round's
reference update (for the deltas: on the reference's deltas).

Which numbers a kind of cell compares, and why (readings in PERF.md):

* an MLP cell (``mlp_t2_secagg_drop``), whose local SGD keeps float32
  rounding small over a round, compares the free-running ``loss1`` and
  medians: the bfloat16 control fails ``loss1``, each fault a median;
* VGG16 with BatchNorm (``vgg16_t2_secagg``) grows float32 rounding to
  about 1e-2 within a round's local steps, so no number read after local
  SGD tells the control from a sound run. The teacher-forced
  ``agg_update_median`` and ``agg_update`` do for the aggregation: a sound
  one agrees to rounding, the control and a lost upload read orders of
  magnitude higher. ``sgd_delta_median`` catches a half batch. Local SGD
  run in bfloat16 alone, or at the default matmul precision, is caught by
  the probe's ``sgd_step1``, read before BatchNorm amplifies rounding.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss1", "loss", "update1", "change_n", "residual_n",
           "update1_median", "change_n_median", "residual_n_median",
           "agg_update", "agg_update_median", "agg_residual_median",
           "sgd_delta_median", "sgd_step1")
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


def _kept(ref_norms: dict) -> list:
    """The leaves whose reference norm is not under ``NEGLIGIBLE`` of the
    median leaf's."""
    median = float(np.median(list(ref_norms.values())))
    return [n for n, v in ref_norms.items() if v >= NEGLIGIBLE * median]


def _loss_gaps(prog, ref) -> list:
    return [float(abs(a - b) / abs(b)) for a, b in zip(prog, ref)]


def _forced(prog, forced: list) -> dict:
    """The teacher-forced numbers (module docstring); ``forced`` holds
    ``reference.forced``'s record of each of ``prog.rounds``."""
    starts = [prog.params0] + [rd["params"] for rd in prog.rounds[:-1]]
    upd, upd_med, res_med, delta_med, step1 = [], [], [], [], []
    for start, mine, theirs in zip(starts, prog.rounds, forced):
        sides = (("prog", mine), ("ref", theirs))
        u = {side: {n: _norm(o["params"][n] - start[n]) for n in start}
             for side, o in sides}
        keep = _kept(u["ref"])
        gaps = _leaf_gaps(u["prog"], u["ref"], keep)
        upd.append(_worst(gaps))
        upd_med.append(_median(gaps))
        r = {side: {n: _norm(o["residuals"][n]) for n in start}
             for side, o in sides}
        res_med.append(_median(_leaf_gaps(r["prog"], r["ref"], keep)))
        d = {side: {n: _norm(o["deltas"][n]) for n in start}
             for side, o in sides}
        delta_med.append(_median(_leaf_gaps(d["prog"], d["ref"],
                                            _kept(d["ref"]))))
        step1.append(_worst(_loss_gaps(mine["step1"], theirs["step1"])))
    return {"agg_update": _worst(upd), "agg_update_median": _worst(upd_med),
            "agg_residual_median": _worst(res_med),
            "sgd_delta_median": _worst(delta_med),
            "sgd_step1": _worst(step1)}


def numbers(prog, ref, forced: list) -> dict:
    """``prog`` and ``ref`` are ``reference.Outputs`` (the program's built
    from what its run produced), ``forced`` is ``reference.forced`` on
    ``prog``."""
    upd = {side: {n: _norm(o.rounds[0]["params"][n] - o.params0[n])
                  for n in o.params0}
           for side, o in (("prog", prog), ("ref", ref))}
    keep = _kept(upd["ref"])
    chg = {side: {n: _norm(o.rounds[-1]["params"][n] - o.params0[n])
                  for n in o.params0}
           for side, o in (("prog", prog), ("ref", ref))}
    res = {side: {n: _norm(o.residuals[n]) for n in o.residuals}
           for side, o in (("prog", prog), ("ref", ref))}
    loss_gaps = (_loss_gaps(prog.losses, ref.losses)
                 if len(prog.losses) == len(ref.losses) else [math.inf])
    out = {"loss1": loss_gaps[0], "loss": _worst(loss_gaps)}
    for name, per_side in (("update1", upd), ("change_n", chg),
                           ("residual_n", res)):
        gaps = _leaf_gaps(per_side["prog"], per_side["ref"], keep)
        out[name] = _worst(gaps)
        out[name + "_median"] = _median(gaps)
    out.update(_forced(prog, forced))
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
