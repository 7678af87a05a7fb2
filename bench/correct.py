"""The comparisons that decide ``correct``, and their limits.

Training: the loss of each checked step; the first gradient as the
optimizer holds it (per-leaf norm of the first moment after step 1); and
the change of the parameters after the checked steps (per-leaf norm). A
leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and of the median leaf; the number
compared is the worst leaf's. Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone under Adam and
are left out of the change.

Serving: the widest gap by which a served token's reference logit lies
below the reference's best at that position.
"""
from __future__ import annotations

import math
import os

import numpy as np
import jax

from bench import harness

TINY_GRAD = 1e-3


def limits_for(workload: str):
    path = os.path.join(harness.BENCH, "limits", workload + ".json")
    if not os.path.exists(path):
        return None
    return harness.load_json(path)["limits"]


def _flat(tree):
    return {jax.tree_util.keystr(k): float(v)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _finite(x):
    return x if math.isfinite(x) else float("inf")


def leaf_gap(prog, ref, keep=None):
    """(worst gap, its leaf) over the leaves in ``keep`` (all if None)."""
    p, r = _flat(prog), _flat(ref)
    med = float(np.median(list(r.values())))
    worst, at = 0.0, None
    for k, rv in r.items():
        if keep is not None and k not in keep:
            continue
        g = _finite(abs(p[k] - rv) / max(rv, med, 1e-30))
        if not g <= worst:
            worst, at = g, k
    return worst, at


def train_readings(got, want):
    (lp, mp, dp), (lr, mr, dr) = got, want
    loss = max(_finite(abs(a - b) / abs(b)) for a, b in zip(lp, lr))
    grad, grad_at = leaf_gap(mp, mr)
    r = _flat(mr)
    med = float(np.median(list(r.values())))
    keep = {k for k, v in r.items() if v >= TINY_GRAD * med}
    upd, upd_at = leaf_gap(dp, dr, keep)
    return {"loss_gap": loss, "grad_norm_gap": grad,
            "update_norm_gap": upd}, {"grad_norm_gap": grad_at,
                                      "update_norm_gap": upd_at,
                                      "left_out": sorted(set(r) - keep)}


def train_checks(got, want, limits):
    vals, where = train_readings(got, want)
    harness.log(correctness_detail=where, program_losses=got[0],
                reference_losses=want[0])
    return {k: {"value": v, "limit": limits[k]} for k, v in vals.items()}


def all_within(checks) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
