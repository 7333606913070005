"""The comparison that decides ``correct``.

Every result served in the window is compared with the plain reference
(``bench/reference.py``) once the window has closed and the server is
shut:

* ``kkt_rel`` -- the largest full-width KKT residual of a served
  coefficient vector, over all p columns, divided by its lam. It covers
  the screen (a column the screen left out shows as a violation), the CM
  burst (the coefficients on the support) and the server (each rider is
  checked against its own response and lam). The configuration states
  its limit: the serving guarantee ``kkt_rtol``.
* ``coef_err`` -- the largest ``max_j |beta_j - beta*_j| / max_j
  |beta*_j|`` against the reference solution of the same response and
  lam.
* ``ref_kkt_rel`` -- the reference's own full-width KKT residual over its
  lam: a reference that did not converge judges nothing.
* ``bad_verdicts`` -- served results whose certificate is not ``ok``, is
  degraded or needed a retry (exact: limit 0).
* ``failed`` -- requests that raised or never came back (exact: limit 0).

The reference is solved once per distinct (response, lam) pair, and the
KKT residual once per distinct coefficient vector.
"""
from __future__ import annotations

import hashlib
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from bench import reference

NUMBERS = ("kkt_rel", "coef_err", "ref_kkt_rel", "bad_verdicts", "failed")


def verdict_bad(v) -> bool:
    """A certificate that is not ``ok``, is degraded or needed a retry."""
    return (not v.ok) or bool(v.degraded) or int(v.retries) != 0


Answer = Tuple[int, float, object, bool]   # (response, lam, beta, bad)


def judge(X, Y, loss: str, answers: Sequence[Answer], failed: int,
          limits: Dict[str, float],
          refs: Optional[Dict[Tuple[int, float], reference.Solution]] = None
          ) -> Tuple[bool, Dict[str, dict], dict]:
    """``answers``: (response index, lam, coefficients, whether the
    verdict is bad) per answer served. ``refs`` are references already
    solved, by (response index, lam); the missing ones are solved here.
    Returns (correct, {number: {"value": v, "limit": l}}, the references
    by (response index, lam))."""
    p = X.shape[1]
    refs = dict(refs or {})
    kkt_memo: Dict[Tuple[int, float, bytes], float] = {}
    kkt_rel = coef = ref_kkt = 0.0
    bad = 0
    for r, lam, beta, verdict_is_bad in answers:
        key = (r, lam)
        if key not in refs:
            refs[key] = reference.solve(X, Y[r], lam, loss)
        ref_kkt = max(ref_kkt, refs[key].kkt / lam)
        beta = np.asarray(beta, np.float64).reshape(p)
        h = (r, lam, hashlib.sha1(beta.tobytes()).digest())
        if h not in kkt_memo:
            kkt_memo[h] = reference.kkt_residual(X, Y[r], beta, lam, loss)
        kkt_rel = max(kkt_rel, kkt_memo[h] / lam)
        coef = max(coef, reference.coef_error(beta, refs[key], p))
        bad += bool(verdict_is_bad)
    values = {"kkt_rel": kkt_rel, "coef_err": coef, "ref_kkt_rel": ref_kkt,
              "bad_verdicts": float(bad), "failed": float(failed)}
    checks = {k: {"value": values[k], "limit": float(limits[k])}
              for k in NUMBERS}
    ok = bool(answers) and all(c["value"] <= c["limit"]
                               for c in checks.values())
    return ok, checks, refs
