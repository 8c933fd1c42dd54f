"""The numbers that decide ``correct``: what the timed path produced against
the plain reference, each beside its limit.

Serving: ``logit_err``, over a seeded sample of the requests answered,
the worst request's max |served logit - reference logit| over the
reference's max |logit|.

Training, over the first ``check_steps`` steps that set-up drove through
the window's own step: ``loss_err``, step 1's |loss - reference| /
|reference|; ``grad_gap``, the worst leaf's gap between the norms of the
first gradient as SGD got it ((p0 - p1) / lr) on the two sides;
``change_gap``, the median leaf's gap between the norms of the
parameters' change over the checked steps.  A gap is over the larger of
the reference leaf's norm and the median leaf's.  Leaves whose reference
gradient is under a thousandth of the median leaf's (the batch-norm
running statistics, which no gradient reaches) are left out of both gaps.
The later steps' loss and the worst leaf's change are printed, not
compared: from random initial weights, batch norm and ReLU amplify any
rounding step by step, so they spread as widely on sound runs as on the
control (PERF.md).
"""
from __future__ import annotations

import numpy as np

IGNORE_BELOW = 1e-3


def logit_err(got: np.ndarray, want: np.ndarray) -> float:
    """Worst row of max|got - want| / max|want| (rows are requests)."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    num = np.max(np.abs(got - want), axis=-1)
    den = np.maximum(np.max(np.abs(want), axis=-1), 1e-30)
    err = num / den
    return float(np.max(err)) if err.size else float("inf")


def _norms(tree) -> dict:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(v, np.float64))) for p, v in flat}


def _diff(a, b, scale=1.0):
    import jax
    return jax.tree.map(lambda x, y: (np.asarray(x, np.float64)
                                      - np.asarray(y, np.float64)) * scale,
                        a, b)


def gaps(got: dict, want: dict, keep) -> dict:
    """Per leaf |got - want| / max(want, median leaf of want)."""
    med = float(np.median([want[k] for k in keep])) if keep else 1.0
    return {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30)
            for k in keep}


def worst_gap(got: dict, want: dict, keep) -> tuple[float, str]:
    """The worst leaf's gap and its name."""
    g = gaps(got, want, keep)
    return max(((v, k) for k, v in g.items()), default=(0.0, ""))


def train_numbers(p0, prog: dict, ref: dict, lr: float) -> dict:
    """``prog`` and ``ref`` each hold ``losses`` (one per checked step),
    ``p1`` (params after step 1) and ``pn`` (after the last checked
    step), as host trees; ``ref`` also ``g1``, its first gradient, which
    picks the leaves that count."""
    lp, lr_ = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if lp.shape != lr_.shape or not np.all(np.isfinite(lp)):
        return {"loss_err": float("inf"), "grad_gap": float("inf"),
                "change_gap": float("inf")}
    steps = np.abs(lp - lr_) / np.abs(lr_)
    g_prog = _norms(_diff(p0, prog["p1"], 1.0 / lr))
    g_ref = _norms(_diff(p0, ref["p1"], 1.0 / lr))
    g_true = _norms(ref["g1"])
    med = float(np.median(list(g_true.values())))
    keep = [k for k, v in g_true.items() if v >= IGNORE_BELOW * med]
    grad = gaps(g_prog, g_ref, keep)
    change = gaps(_norms(_diff(prog["pn"], p0)), _norms(_diff(ref["pn"], p0)),
                  keep)
    worst = max(((v, k) for k, v in grad.items()), default=(0.0, ""))
    worst_ch = max(((v, k) for k, v in change.items()), default=(0.0, ""))
    return {"loss_err": float(steps[0]), "grad_gap": worst[0],
            "change_gap": float(np.median(list(change.values())))
            if change else float("inf"),
            "_leaves": len(keep), "_grad_leaf": worst[1],
            "_loss_err_steps": [float(v) for v in steps],
            "_change_gap_worst": [worst_ch[0], worst_ch[1]]}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the {name: {value, limit}} record of every number
    that has a limit.  A number missing or not finite fails."""
    record, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name, float("nan"))
        good = bool(np.isfinite(v) and v <= limit)
        ok = ok and good
        record[name] = {"value": v if np.isfinite(v) else str(v),
                        "limit": limit}
    return ok, record
