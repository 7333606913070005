"""K-fold cross-validated lambda paths on the batch-polymorphic engine.

The standard glmnet-style protocol — K folds x L lambdas — is a fleet
workload: all K fold problems share the design X and differ only in which
rows count. :func:`cv_path` runs the fold fleet through
``core/batch.py::_saif_batch_jit`` one lambda at a time (descending,
warm-started), so the whole K x L grid costs ONE compilation, the O(p)
screen scan is amortized across folds at every outer step, and the
Gram/screen state of the fleet survives every lambda handoff verbatim
(the slot-preserving warm extraction, exactly like the serial path
engine).

Fold masking is the *sample-weight trick* (DESIGN.md §8): fold k's
training problem is the LASSO on diag(w_k) rows with binary w_k, which
equals the row-subsampled problem exactly — gradients, primal values and
conjugate sums are weighted elementwise while X (and therefore the
screening matmul, the gathered active blocks and the Pallas tiles) stays
shared across the fleet. Per-fold column norms/c0/lambda_max ride along
as fleet (K, p) matrices. The Thm-2 sequential ball assumes the
unweighted null dual, so weighted fleets run on the (precision-floored)
gap ball alone — same deviation discipline as the fused subsystem (§7).
"""
from __future__ import annotations

import math
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch import (_saif_batch_jit, initial_support_batch,
                              prepare_fleet, resolve_batch_inner,
                              saif_batch_compile_count)
from repro.core.inner_backend import cold_inner_carry_batch
from repro.core.losses import get_loss
from repro.core.saif import (SaifConfig, SaifResult, add_batch_size_static,
                             default_capacity, saif)
from repro.core.screen_backend import resolve_batch_screen


class CVPathResult(NamedTuple):
    lams: np.ndarray            # (L,) descending grid
    cv_mean: np.ndarray         # (L,) mean held-out loss per lambda
    cv_se: np.ndarray           # (L,) standard error across folds
    best_lam: float             # argmin of cv_mean
    beta: Optional[jnp.ndarray]      # (p,) full-data refit at best_lam
    best_result: Optional[SaifResult]
    fold_betas: Optional[List[jnp.ndarray]]  # per-lambda (K, p) if kept
    n_compilations: Optional[int]   # batch-engine compiles this path added


def kfold_weights(n: int, n_folds: int, seed: int = 0,
                  dtype=jnp.float64) -> jnp.ndarray:
    """(K, n) binary TRAIN-row masks: row k is 1 off fold k, 0 on it.
    Folds are a balanced random partition (host RNG, reproducible)."""
    if not 2 <= n_folds <= n:
        raise ValueError(f"need 2 <= n_folds <= n, got {n_folds} for n={n}")
    rng = np.random.default_rng(seed)
    assign = rng.permutation(np.arange(n) % n_folds)
    W = np.ones((n_folds, n))
    W[assign, np.arange(n)] = 0.0
    return jnp.asarray(W, dtype)


def cv_solve(X, y, lams: Sequence[float], n_folds: int = 5,
             config: SaifConfig = SaifConfig(), seed: int = 0,
             keep_fold_betas: bool = False,
             refit: bool = True) -> CVPathResult:
    """K-fold cross-validation over a lambda grid, one fleet compilation.

    Solves the K fold problems in lockstep at every lambda (descending,
    fleet-warm-started), scores each lambda by the mean held-out loss
    (``loss.value`` averaged over each fold's validation rows), and
    refits the winner on the full data with the serial solver.
    """
    if config.unpen_idx is not None:
        raise NotImplementedError("cv_path cross-validates plain-LASSO "
                                  "problems (DESIGN.md §8)")
    if len(lams) == 0:
        raise ValueError("cv_path needs a non-empty lambda grid")
    loss = get_loss(config.loss)
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    n, p = X.shape
    K = n_folds
    W = kfold_weights(n, K, seed=seed, dtype=X.dtype)
    Y = jnp.broadcast_to(y, (K, n))
    lams_np = np.asarray(sorted([float(l) for l in lams], reverse=True))
    n_compile0 = saif_batch_compile_count()

    prep = prepare_fleet(X, Y, config, weights=W)
    backend = resolve_batch_screen(config.screen_backend, dtype=X.dtype)
    # grid-max static h over the whole K x L fleet family; per-(fold,
    # lambda) batch sizes and tolerances stay traced — the path-engine
    # trick (§4), fleet edition
    hs_grid = [[add_batch_size_static(config.c, lam, mx, md, p)
                for mx, md in zip(prep.c0_max, prep.c0_median)]
               for lam in lams_np]
    h = max(max(hs_l) for hs_l in hs_grid)
    k_max = config.k_max or default_capacity(h, p)
    eps_vec = jnp.full((K,), config.eps, X.dtype)

    def delta0_vec(lam: float) -> jnp.ndarray:
        if config.delta0 is not None:
            return jnp.full((K,), config.delta0, X.dtype)
        return jnp.asarray([min(max(lam / mx, 1e-3), 1.0)
                            for mx in prep.c0_max], X.dtype)

    # cold start at the grid's first lambda, computed once (elastic growth
    # pads it, mirroring the serial driver's overflow recovery)
    cold_idx, cold_beta, cold_mask = initial_support_batch(
        prep.c0, hs_grid[0], k_max, p, X.dtype)
    while True:
        pad = k_max - cold_idx.shape[1]
        if pad > 0:
            cold_idx = jnp.pad(cold_idx, ((0, 0), (0, pad)))
            cold_beta = jnp.pad(cold_beta, ((0, 0), (0, pad)))
            cold_mask = jnp.pad(cold_mask, ((0, 0), (0, pad)))
        inner = resolve_batch_inner(config, n, k_max, K, X.dtype)
        warm = None
        results: List[SaifResult] = []
        for li, lam in enumerate(lams_np):
            hs_l = hs_grid[li]
            if warm is None:
                init_idx, init_beta, init_mask = cold_idx, cold_beta, \
                    cold_mask
                carry = cold_inner_carry_batch(K, k_max, X.dtype,
                                               backend=inner)
            else:
                init_idx, init_beta, init_mask, carry = warm
            res = _saif_batch_jit(
                X, Y, W, prep.col_norm, prep.c0,
                jnp.full((K,), lam, X.dtype), eps_vec, delta0_vec(lam),
                init_idx, init_beta, init_mask,
                carry.G, carry.rho, carry.gidx,
                jnp.asarray([max(int(math.ceil(config.zeta * h_b)), 1)
                             for h_b in hs_l], jnp.int32),
                jnp.asarray(hs_l, jnp.int32),
                loss_name=config.loss, h=h, k_max=k_max,
                inner_epochs=config.inner_epochs,
                polish_factor=config.polish_factor,
                max_outer=config.max_outer, use_seq_ball=False,
                screen_backend=backend, inner_backend=inner,
                has_weights=True)
            results.append(res)
            # slot-preserving fleet warm handoff (path.py::_warm_state,
            # batched): Gram buffers stay valid verbatim across lambdas
            vals = jnp.where(res.active_mask,
                             jnp.take_along_axis(res.beta, res.active_idx,
                                                 axis=1), 0.0)
            live = res.active_mask & (vals != 0)
            warm = (res.active_idx, jnp.where(live, vals, 0.0), live,
                    res.inner)
        # ONE host sync for the whole grid's overflow flags
        flags = jnp.stack([r.overflowed for r in results])
        if not bool(jnp.any(flags)) or k_max >= p:
            break
        k_max = min(2 * k_max, p)   # elastic growth, full-path re-entry

    # --- held-out scoring: mean validation loss per (fold, lambda) --------
    W_test = 1.0 - W                                        # (K, n)
    n_test = jnp.sum(W_test, axis=1)                        # (K,)
    errs = []
    for res in results:
        Z = res.beta @ X.T                                  # (K, n)
        errs.append(jnp.sum(W_test * loss.value(Z, Y), axis=1) / n_test)
    err_kl = np.asarray(jax.device_get(jnp.stack(errs)))    # (L, K)
    cv_mean = err_kl.mean(axis=1)
    cv_se = err_kl.std(axis=1, ddof=1) / np.sqrt(K)
    best_i = int(np.argmin(cv_mean))
    best_lam = float(lams_np[best_i])

    beta_best = best_result = None
    if refit:
        best_result = saif(X, y, best_lam, config)
        beta_best = best_result.beta

    n_compile1 = saif_batch_compile_count()
    n_comp = max(n_compile1 - n_compile0, 0)
    return CVPathResult(
        lams=lams_np, cv_mean=cv_mean, cv_se=cv_se, best_lam=best_lam,
        beta=beta_best, best_result=best_result,
        fold_betas=[r.beta for r in results] if keep_fold_betas else None,
        n_compilations=n_comp)


def one_se_lambda(lams: np.ndarray, cv_mean: np.ndarray,
                  cv_se: np.ndarray) -> float:
    """The glmnet 1-SE rule (DESIGN.md §14): the *largest* lambda whose
    CV error is within one standard error of the minimum — the sparsest
    model statistically indistinguishable from the best scorer. Expects
    the descending grid / per-lambda scores of a :class:`CVPathResult`.
    """
    lams = np.asarray(lams, np.float64)
    cv_mean = np.asarray(cv_mean, np.float64)
    cv_se = np.asarray(cv_se, np.float64)
    i_min = int(np.argmin(cv_mean))
    thresh = cv_mean[i_min] + cv_se[i_min]
    # descending grid: the first index within the threshold is the
    # largest eligible lambda (i_min itself qualifies, so one exists)
    return float(lams[int(np.argmax(cv_mean <= thresh))])


def cv_path(X, y, lams: Sequence[float], n_folds: int = 5,
            config: SaifConfig = SaifConfig(), seed: int = 0,
            keep_fold_betas: bool = False,
            refit: bool = True) -> CVPathResult:
    """DEPRECATED legacy frontend — one-shot session over
    :func:`cv_solve`.

    Use ``repro.open_session(Problem(X, y), config).solve(CV(n_folds,
    lams))``; a held-open session keeps the fold-fleet compilation alive
    for the next grid (DESIGN.md §9).
    """
    from repro.core._compat import warn_deprecated
    warn_deprecated("repro.core.cv_path",
                    "session.solve(CV(n_folds, lams))")
    from repro.core.api import CV, Problem, open_session

    sess = open_session(Problem(X=X, y=y, loss=config.loss), config)
    return sess.solve(CV(n_folds=n_folds,
                         lams=tuple(float(l) for l in lams), seed=seed,
                         keep_fold_betas=keep_fold_betas, refit=refit))
