"""The comparison that decides ``correct`` for a training cell.

Both sides give a record: each checked step's loss, the first step's
gradient per leaf as the optimizer got it (clipped), and each leaf's change
over the checked steps. A leaf is one parameter array, or one layer's slice
of a stacked array. Numbers compared:

* ``loss<k>``: |program - reference| / reference, for each checked step.
* ``gnorm<k>``: the same of the gradient's global norm before clipping.
* ``grad1``, ``update``: the worst leaf's |program norm - reference norm|
  over the larger of the reference's norm of that leaf and of the median
  leaf. Leaves whose reference gradient is under a thousandth of the median
  leaf's (nought to rounding) are left out of both.
* ``grad1_median``, ``update_median``: the same gap of the median leaf, a
  number steadier from seed to seed than the worst leaf's.
* ``update_all``: the gap of the change's norm over all those leaves
  together, relative to the reference's: steadier still, since rounding
  moves the leaves' norms up and down at random and a fault moves them
  all one way.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

NOUGHT = 1e-3


def _flat(norms: Dict) -> Dict[str, float]:
    out = {}
    for name, v in norms.items():
        v = np.atleast_1d(np.asarray(v, np.float64))
        for i, x in enumerate(v):
            out[f"{name}[{i}]" if v.size > 1 or name.startswith("layers/")
                else name] = float(x)
    return out


def _gaps(prog: Dict[str, float], ref: Dict[str, float], keep):
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def _kept(ref: Dict):
    g_ref = _flat(ref["grad1"])
    med = float(np.median(list(g_ref.values())))
    return g_ref, [k for k, v in g_ref.items() if v >= NOUGHT * med]


def worst_leaves(prog: Dict, ref: Dict) -> Dict[str, str]:
    """Which leaf gives ``grad1`` and ``update`` their readings."""
    g_ref, keep = _kept(ref)
    out = {}
    for name, p, r in (("grad1", _flat(prog["grad1"]), g_ref),
                       ("update", _flat(prog["change"]), _flat(ref["change"]))):
        gaps = _gaps(p, r, keep)
        out[name] = max(gaps, key=gaps.get)
    return out


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Numbers compared, each a relative gap (0 = identical)."""
    out = {f"loss{i + 1}": abs(p - r) / abs(r)
           for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]))}
    out.update({f"gnorm{i + 1}": abs(p - r) / abs(r) for i, (p, r) in
                enumerate(zip(prog["grad_norms"], ref["grad_norms"]))})
    g_ref, keep = _kept(ref)
    for name, p, r in (("grad1", _flat(prog["grad1"]), g_ref),
                       ("update", _flat(prog["change"]), _flat(ref["change"]))):
        gaps = list(_gaps(p, r, keep).values())
        out[name] = max(gaps)
        out[name + "_median"] = float(np.median(gaps))
    p, r = _flat(prog["change"]), _flat(ref["change"])
    whole = [math.sqrt(sum(t[k] ** 2 for k in keep)) for t in (p, r)]
    out["update_all"] = abs(whole[0] - whole[1]) / whole[1]
    return {k: (v if math.isfinite(v) else float("inf"))
            for k, v in out.items()}


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Every number at or under its limit (a non-finite one fails)."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
