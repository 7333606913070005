"""Auto-lambda model selection: 1-SE CV + stability selection
(DESIGN.md §14).

``Session.select(Select(lams))`` answers the question clients actually
have — "which features?" — without asking them to pick a lambda:

  1. the existing K-fold CV fleet scores the grid (ONE fleet
     compilation, ``core/cv.py``);
  2. the **1-SE rule** picks the largest lambda within one standard
     error of the CV minimum (``rule="min"`` keeps the raw argmin);
  3. optional **stability selection** (Meinshausen–Bühlmann): B
     random half-subsamples solved as ONE weighted ``fleet_solve``
     (binary row masks are exact row subsampling — the CV sample-weight
     trick, DESIGN.md §8 — so the B solves share one compilation and
     compose with ``parity="fast"``), yielding per-feature selection
     frequencies and the stable support ``freq >= pi_threshold``;
  4. a full-data refit at the chosen lambda (the serial engine).

Everything returns in one :class:`SelectionReport`; the serving layer
KKT-certifies the refit and carries the report through Verdict
provenance. Module scope stays numpy+stdlib only (import-light
contract); jax loads inside the solve functions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import numpy as np

__all__ = ["Select", "SelectionReport", "subsample_weights",
           "select_solve"]


@dataclasses.dataclass(frozen=True)
class Select:
    """Model-selection request: CV over ``lams``, 1-SE choice, optional
    stability selection, full-data refit."""
    lams: Any
    n_folds: int = 5
    rule: str = "1se"                 # "1se" | "min"
    stability: bool = True
    n_subsamples: int = 16
    subsample_frac: float = 0.5
    pi_threshold: float = 0.6
    seed: int = 0
    refit: bool = True
    keep_fold_betas: bool = False
    deadline_s: Optional[float] = None
    priority: int = 0

    def __post_init__(self):
        from repro.core.serving import validate_request
        validate_request(self)


class SelectionReport(NamedTuple):
    """What :func:`select_solve` hands back (and serving certifies)."""
    lams: np.ndarray                   # (L,) descending CV grid
    cv_mean: np.ndarray                # (L,) mean held-out loss
    cv_se: np.ndarray                  # (L,) standard error across folds
    lam_min: float                     # argmin of cv_mean
    lam_1se: float                     # 1-SE rule choice
    lam: float                         # the chosen lambda (per rule)
    rule: str                          # "1se" | "min"
    frequencies: Optional[np.ndarray]  # (p,) selection frequencies
    stable_support: Optional[np.ndarray]   # indices with freq >= pi
    pi_threshold: float
    beta: Optional[Any]                # (p,) full-data refit at lam
    best_result: Optional[Any]         # the refit's SaifResult
    fold_betas: Optional[Any]          # per-lambda (K, p), if kept
    n_compilations: Optional[int]      # engine compiles this call added


def subsample_weights(n: int, n_subsamples: int, frac: float,
                      seed: int = 0, dtype=None):
    """(B, n) binary row masks, each keeping ``floor(frac * n)`` rows
    drawn without replacement (host RNG, reproducible) — the stability-
    selection analogue of :func:`repro.core.cv.kfold_weights`."""
    import jax.numpy as jnp

    m = int(frac * n)
    if not 1 <= m < n:
        raise ValueError(
            f"subsample_frac={frac} keeps {m} of {n} rows; need 1 <= "
            f"rows < n")
    rng = np.random.default_rng(seed)
    W = np.zeros((n_subsamples, n))
    for b in range(n_subsamples):
        W[b, rng.choice(n, size=m, replace=False)] = 1.0
    return jnp.asarray(W, dtype if dtype is not None else None)


def stability_frequencies(X, y, lam: float, config, n_subsamples: int,
                          frac: float, seed: int = 0
                          ) -> Tuple[np.ndarray, Any]:
    """Selection frequency per feature over B subsample solves, run as
    ONE weighted fleet (one compilation). Returns ``(freq (p,), fleet
    SaifResult)``."""
    import jax.numpy as jnp

    from repro.core.batch import fleet_solve

    X = jnp.asarray(X)
    y = jnp.asarray(y, X.dtype)
    n = X.shape[0]
    W = subsample_weights(n, n_subsamples, frac, seed=seed,
                          dtype=X.dtype)
    Y = jnp.broadcast_to(y, (int(n_subsamples), n))
    fr = fleet_solve(X, Y, float(lam), config, weights=W)
    freq = np.asarray(
        jnp.mean((jnp.abs(fr.beta) > 0).astype(X.dtype), axis=0))
    return freq, fr


def select_solve(X, y, req: Select,
                 config=None) -> SelectionReport:
    """Run the full selection protocol (module docstring) on (X, y)."""
    from repro.core.batch import saif_batch_compile_count
    from repro.core.cv import cv_solve, one_se_lambda
    from repro.core.saif import SaifConfig, saif, saif_jit_compile_count

    config = config or SaifConfig()
    lams = tuple(float(l) for l in np.asarray(req.lams).ravel())
    c0 = saif_batch_compile_count() + saif_jit_compile_count()
    cv = cv_solve(X, y, lams, n_folds=int(req.n_folds), config=config,
                  seed=int(req.seed),
                  keep_fold_betas=bool(req.keep_fold_betas), refit=False)
    lam_min = float(cv.best_lam)
    lam_1se = one_se_lambda(cv.lams, cv.cv_mean, cv.cv_se)
    lam = lam_1se if req.rule == "1se" else lam_min

    freq = stable = None
    if req.stability:
        freq, _ = stability_frequencies(
            X, y, lam, config, int(req.n_subsamples),
            float(req.subsample_frac), seed=int(req.seed) + 1)
        stable = np.flatnonzero(freq >= float(req.pi_threshold))

    beta = best = None
    if req.refit:
        best = saif(X, y, lam, config)
        beta = best.beta

    c1 = saif_batch_compile_count() + saif_jit_compile_count()
    n_comp = max(c1 - c0, 0)
    return SelectionReport(
        lams=cv.lams, cv_mean=cv.cv_mean, cv_se=cv.cv_se,
        lam_min=lam_min, lam_1se=lam_1se, lam=lam, rule=str(req.rule),
        frequencies=freq, stable_support=stable,
        pi_threshold=float(req.pi_threshold), beta=beta,
        best_result=best, fold_betas=cv.fold_betas,
        n_compilations=n_comp)
