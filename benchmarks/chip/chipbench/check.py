"""The comparison that decides ``correct``: what the timed path produced
against the plain float32 reference, each number beside its limit.

Serving: for a sample of finished requests (``serve_cell._check_sample``),
``max_logit_gap`` is the widest gap by which a served token's logit lies
below the reference's best at that position. The control ranks tokens with
the reference computed one precision lower and reads their gaps.

Training: over the first three steps, ``loss_gap`` is the largest absolute
difference of a step's loss; ``grad_gap`` the worst leaf of the gap between
the norms of the first clipped gradient; ``change_gap`` the worst leaf of
the gap between the norms of the parameters' change after step three.
Both leaf gaps are relative to the reference's norm of that leaf or of the
median leaf, whichever is larger. Leaves whose reference gradient is under
a thousandth of the median leaf's are left out of ``change_gap``: Adam
moves them by round-off alone. ``loss_gap`` is read but a cell's limits
need not judge it: a batch row that repeats one token sums the rounding of
default-precision matmuls over the row instead of averaging it away.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference
from chipbench.serve_cell import logit_gap_readings
from chipbench.train_cell import change_norms, leaf_norms
from chipbench.weights import make_params

STILL = 1e-3      # reference gradient under STILL x median: leaf left out


def serve_numbers(run, arch, control: str | None = None):
    gaps, ctrl = logit_gap_readings(run.params, run.check_inputs, arch, control)
    return _gap_numbers(gaps), (_gap_numbers(ctrl) if ctrl.size else None)


def _gap_numbers(gaps) -> dict:
    """The widest gap, the mean gap, and the share of tokens that are the
    reference's own best."""
    if not gaps.size:
        return {"max_logit_gap": None, "served_tokens": 0}
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "top1_share": float(np.mean(gaps <= 0.0)),
            "served_tokens": int(gaps.size)}


def _leaf_gap(prog: dict, ref: dict, keys) -> float:
    keys = list(keys)
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def train_numbers_from(ci: dict, ref_losses, ref_grad, ref_change) -> dict:
    """The three numbers of one set of readings ``ci`` (losses, first
    gradient norms, change norms by leaf) against the reference's."""
    med = float(np.median(list(ref_grad.values())))
    moved = [k for k, v in ref_grad.items() if v >= STILL * med]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(ci["losses"], ref_losses)),
        "grad_gap": _leaf_gap(ci["first_grad"], ref_grad, ref_grad),
        "change_gap": _leaf_gap(ci["change"], ref_change, moved),
    }


def reference_readings(ci: dict, seed: int, arch, opt: dict,
                       precision: str = "f32") -> dict:
    """Losses, first clipped gradient norms and change norms of the plain
    reference over the same weights and batches."""
    p0 = make_params(ci["shapes"], seed)
    losses, g1, p3 = reference.train_steps(p0, ci["batches"], arch, opt,
                                           precision)
    return {"losses": losses, "first_grad": leaf_norms(g1),
            "change": change_norms(p3, p0)}


def train_numbers(run, arch, opt: dict, control: str | None = None):
    ci = run.check_inputs
    ref = reference_readings(ci, run.seed, arch, opt)
    prog = train_numbers_from(ci, ref["losses"], ref["first_grad"],
                              ref["change"])
    low = None
    if control is not None:
        c = reference_readings(ci, run.seed, arch, opt, control)
        low = train_numbers_from(c, ref["losses"], ref["first_grad"],
                                 ref["change"])
    return prog, low


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the ``{name: {"value", "limit"}}`` table. Every
    number with a limit must be present and at or under it."""
    table, ok = {}, True
    for name, lim in limits.items():
        v = numbers.get(name)
        table[name] = {"value": v, "limit": lim["limit"]}
        ok = ok and v is not None and np.isfinite(v) and v <= lim["limit"]
    return ok, table
