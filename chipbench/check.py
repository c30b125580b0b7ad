"""The numbers that decide ``correct``: what the timed path produced
against the plain reference (``reference.py``).

The reference does each step's arithmetic as the program's path does it
(one client's local SGD as a scan, the fleet-wide round vmapped over the
clients, eq. (3) and the weighted sum as a product per term, added in
client order), so a sound run on the chip matches it bit for bit over a
whole run; how far the control lands depends on the model family
(``families/<name>.py`` says).  The numbers:

* ``order_mismatch`` (CSMAAFL) — uploads whose uploader differs from the
  reference scheduler's (a count).
* ``beta_gap`` (CSMAAFL) — the widest gap between the program's eq. (11)
  beta and the reference's.
* ``model_gap`` — the whole run, w0 to the final global, by ``gap``.
* ``head_gap`` (CSMAAFL) — the global at the run's first eval point
  (``eval_every`` uploads in, each blended through the kernel by eq. (3)
  and retrained from) against the reference's there, by ``diff_gap``:
  by the difference itself, so that an error orthogonal to the change,
  such as a blend rounded to bfloat16, shows as well as a wrong
  coefficient.
* ``retrain_gap`` (CSMAAFL) — the last upload's retrain: the row the
  program wrote back against the reference's local training from the
  program's final global, by ``gap`` from that global.

A model is compared leaf by leaf, each leaf named by its path in the
pytree.  ``gap`` takes the worst leaf: the gap between the norm of the
program's change (end minus start) and the reference's, over the
reference's norm of that leaf or of the median leaf, whichever is
larger; ``diff_gap`` the norm of the difference of the two ends over the
same.  Leaves whose reference change is under a thousandth of the median
leaf's are left out.  A model that did not move reads 1; a non-finite
one reads inf.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np


def host(tree) -> Dict[str, np.ndarray]:
    """Each leaf of a pytree as a host array, keyed by its path."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(v) for p, v in leaves}


def leaf_gaps(end: Dict[str, np.ndarray], ref_end: Dict[str, np.ndarray],
              start: Dict[str, np.ndarray], diff: bool = False
              ) -> Optional[Dict[str, float]]:
    """``gap`` of each leaf (``diff``: ``diff_gap``); None where a leaf
    is missing, misshapen or not finite."""
    keys = sorted(ref_end)
    if sorted(end) != keys:
        return None
    a, b = {}, {}
    for k in keys:
        p = np.asarray(end[k], np.float64)
        if p.shape != np.shape(ref_end[k]) or not np.isfinite(p).all():
            return None
        w = np.asarray(start[k], np.float64)
        r = np.asarray(ref_end[k], np.float64)
        a[k] = float(np.linalg.norm(p - (r if diff else w)))
        b[k] = float(np.linalg.norm(r - w))
    med = float(np.median(list(b.values())))
    if med <= 0.0:
        return None
    return {k: (a[k] if diff else abs(a[k] - b[k])) / max(b[k], med)
            for k in keys if b[k] >= 1e-3 * med}


def gap(end, ref_end, start, diff: bool = False) -> float:
    """The worst leaf's gap (``diff``: ``diff_gap``)."""
    per = leaf_gaps(end, ref_end, start, diff)
    if per is None:
        return float("inf")
    return float(max(per.values()))


def diff_gap(end, ref_end, start) -> float:
    return gap(end, ref_end, start, diff=True)


def replay(ref, a, precision: str, fault: Optional[str] = None
           ) -> Dict[str, Any]:
    """What the reference gives for answer ``a``: the whole run from w0
    and, for CSMAAFL, the last retrain from the program's final global."""
    part = dict(a.partition)
    shards = ref.shards(part.pop("kind"), part.pop("seed"), **part)
    kw = dict(seed=a.seed, shards=shards, fleet=a.fleet,
              precision=precision, fault=fault)
    if a.kind == "afl":
        run = ref.afl(w0=a.w0, marks=tuple(a.marks), **kw, **a.run)
        marks = {j: host(m) for j, m in run.marks.items()}
        return {"params": host(run.params), "cids": run.cids,
                "betas": run.betas, "marks": marks, "head": marks[head(a)],
                "row": host(ref.retrain(w=a.params, cid=a.last_cid,
                                        j=a.run["iterations"], **kw))}
    run = ref.fedavg(w0=a.w0, rounds=a.run["rounds"], **kw)
    return {"params": host(run.params)}


def head(a) -> int:
    """The upload count of the first eval point inside run ``a``."""
    return min(a.marks)


def program(a) -> Dict[str, Any]:
    """What the program gave for answer ``a``, in ``replay``'s form."""
    if a.kind == "afl":
        return {"params": host(a.params), "cids": a.cids, "betas": a.betas,
                "head": host(a.marks[head(a)]), "row": host(a.last_row)}
    return {"params": host(a.params)}


def numbers(out: Dict[str, Any], ref: Dict[str, Any], a) -> Dict[str, float]:
    """The numbers of ``out`` (the program's, or a substitute's) against
    the reference's ``ref`` for answer ``a``."""
    got = {"model_gap": gap(out["params"], ref["params"], host(a.w0))}
    if a.kind == "afl":
        c = np.asarray(out["cids"])
        if c.shape != ref["cids"].shape:
            got["order_mismatch"] = float(max(len(c), len(ref["cids"])))
            got["beta_gap"] = float("inf")
        else:
            got["order_mismatch"] = float(np.sum(c != ref["cids"]))
            got["beta_gap"] = float(np.max(np.abs(
                np.asarray(out["betas"], np.float64) - ref["betas"])))
        got["head_gap"] = diff_gap(out["head"], ref["head"], host(a.w0))
        got["retrain_gap"] = gap(out["row"], ref["row"], host(a.params))
    return got


def merge(readings) -> Dict[str, float]:
    """Worst over runs: mismatches add up, gaps take the max."""
    out: Dict[str, float] = {}
    for r in readings:
        for k, v in r.items():
            if k == "order_mismatch":
                out[k] = out.get(k, 0.0) + v
            else:
                out[k] = max(out.get(k, 0.0), v)
    return out


def verdict(got: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number at or under its limit; a number without a limit, or
    a limit without its number, fails."""
    if set(got) != set(limits):
        return False
    return all(np.isfinite(got[k]) and got[k] <= limits[k] for k in got)
