"""The comparison that decides `correct`.

Set-up drives the program from the seed through its first
`check_rounds` rounds, one `run_campaign` call per round (the window's
own call, chunk and compiled program), and keeps its model after each
round: the global model, or for handover the RSU models. The plain
reference (reference.py) follows the same rounds. Numbers:

loss1_gap     relative gap of round 1's mean client loss: the blur, the
              augmentations, the backbone's passes and the DT loss;
grad_gap      the first gradient as the optimizer gets it, worked out
              from the model after round 1 (its change over round 1 is
              -lr times the aggregated step): by the worst parameter
              leaf, the gap between the program's and the reference's
              norm of that change, over the larger of the reference's
              norm for the leaf and the median leaf's. Parameter leaves
              whose round-1 gradient in the reference is under a
              thousandth of the median leaf's are left out;
change_gap    the same for the change over all `check_rounds` rounds,
              over every leaf (state leaves too, where the backbone has any);
schedule_gap  the largest difference between the velocities and
              learning rates the program recorded and those the
              reference drew: the two sides must train on one schedule.

Each number is held to its limit in `limits/<workload>.json`; a number
with no limit there is printed and not compared (PERF.md says why).
"""
from __future__ import annotations

import sys

import jax
import numpy as np

NAMES = ("loss1_gap", "grad_gap", "change_gap", "schedule_gap")


def _paths(tree):
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _norm_gap(m0, mp, mr, keep):
    """Worst leaf (among `keep`) of |‖mp - m0‖ - ‖mr - m0‖| over the
    larger of ‖mr - m0‖ and the median leaf's; also the median gap."""
    dp = {k: float(np.linalg.norm(mp[k] - m0[k])) for k in keep}
    dr = {k: float(np.linalg.norm(mr[k] - m0[k])) for k in keep}
    med = float(np.median(list(dr.values())))
    gaps = {k: abs(dp[k] - dr[k]) / max(dr[k], med, 1e-30) for k in keep}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst, float(np.median(list(gaps.values())))


def numbers(prog: dict, ref: dict, model0) -> dict:
    lp = np.asarray(prog["losses"], np.float64)
    lr_ = np.asarray(ref["losses"], np.float64)
    gaps = np.abs(lp - lr_) / np.abs(lr_)

    m0 = _paths(model0)
    p1, r1 = _paths(prog["models"][0]), _paths(ref["models"][0])
    pn, rn = _paths(prog["models"][-1]), _paths(ref["models"][-1])
    grads = {jax.tree_util.keystr(p): float(v) for p, v in
             jax.tree_util.tree_flatten_with_path(ref["grad_norms"])[0]}
    gmed = float(np.median(list(grads.values())))
    quiet = {k for k, g in grads.items() if g < 1e-3 * gmed}
    params = [k for k in m0 if "['params']" in k
              and not any(k.endswith("['params']" + q) for q in quiet)]
    grad_gap, grad_worst, grad_med = _norm_gap(m0, p1, r1, params)
    change_gap, change_worst, change_med = _norm_gap(m0, pn, rn, list(m0))

    vp = np.asarray(prog["velocities"], np.float64)
    vr = np.asarray(ref["velocities"], np.float64)
    sched = float(max(np.max(np.abs(vp - vr)),
                      np.max(np.abs(np.asarray(prog["lrs"])
                                    - np.asarray(ref["lrs"])))))
    return {"loss1_gap": float(gaps[0]), "grad_gap": grad_gap,
            "change_gap": change_gap, "schedule_gap": sched,
            "_loss_gaps": gaps.tolist(), "_grad_worst": grad_worst,
            "_grad_median": grad_med, "_change_worst": change_worst,
            "_change_median": change_med, "_left_out": sorted(quiet),
            "_losses": [lp.tolist(), lr_.tolist()]}


def compare(got: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for the numbers with a limit; prints
    every number, and last those compared, on stderr."""
    print(f"check: per-round loss gaps {got['_loss_gaps']}; worst leaves "
          f"{got['_grad_worst']} (round 1), {got['_change_worst']} "
          f"(all rounds); median leaf gaps {got['_grad_median']}, "
          f"{got['_change_median']}; left out {got['_left_out']}",
          file=sys.stderr)
    for k in NAMES:
        if k not in limits:
            print(f"check {k}: {got[k]!r} (not compared)", file=sys.stderr)
    out = {k: {"value": got[k], "limit": limits[k]}
           for k in NAMES if k in limits}
    for k, v in out.items():
        ok = "ok" if v["value"] <= v["limit"] else "FAIL"
        print(f"check {k}: {v['value']!r} limit {v['limit']!r} {ok}",
              file=sys.stderr)
    return out
