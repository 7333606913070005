"""Plain reference for the LASSO served by the system under test.

Independent of ``src/repro``: it imports nothing of the program and takes
nothing it has made. The objective is the one the configurations state,

    P(beta) = sum_i f(x_i . beta, y_i) + lam * ||beta||_1,

with ``f(z, y) = 0.5 (z - y)^2`` (least squares) or
``f(z, y) = log(1 + exp(-y z))`` (logistic, labels in {-1, +1}); no
intercept.

Two parts, both plain:

* full-width passes over every column of the design (``X^T f'(X beta)``),
  in ``jax.numpy`` on the device at ``highest`` matmul precision;
* a working-set solve: a restricted LASSO over a set W of columns, in
  float64 ``numpy`` on the host (FISTA with adaptive restart, then a
  Newton polish on the support with the signs fixed), grown by the
  full-width violators until there are none.

The same solve runs in a lower precision for the control (``q``):
every input and every intermediate is rounded to bfloat16 after it is
formed, products accumulating in float32, as a bfloat16 matrix unit does.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import numpy as np

LOSSES = ("least_squares", "logistic")


def _f64(a) -> np.ndarray:
    return np.asarray(a, np.float64)


def grad_z(loss: str, z, y):
    """f'(z, y) elementwise."""
    if loss == "least_squares":
        return z - y
    return -y / (1.0 + np.exp(y * z))


def hess_z(loss: str, z, y):
    if loss == "least_squares":
        return np.ones_like(z)
    s = 1.0 / (1.0 + np.exp(y * z))
    return s * (1.0 - s)


# ---------------------------------------------------------------------------
# full-width passes (device, highest precision)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _xt_g(dtype):
    """One jitted ``g @ X`` over the row-major design, at ``highest``
    precision: X is read once and not copied. ``dtype`` casts both
    operands first (the control's lower precision)."""
    import jax
    import jax.numpy as jnp

    def f(X, g):
        if dtype is not None:
            X, g = X.astype(dtype), g.astype(dtype)
        return jnp.dot(g, X, precision=jax.lax.Precision.HIGHEST
                       ).astype(jnp.float32)
    return jax.jit(f)


def full_correlation(X, g, dtype=None) -> np.ndarray:
    """``X^T g`` over every column of the device design, as float64."""
    return _f64(_xt_g(dtype)(X, np.asarray(g, np.float32)))


@functools.lru_cache(maxsize=None)
def _take():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda X, idx: jnp.take(X, idx, axis=1))


def columns(X, idx) -> np.ndarray:
    """Columns ``idx`` of the device design, as float64 on the host. The
    index list is padded to a power of two, so few gathers compile."""
    idx = np.asarray(idx, np.int64)
    if idx.size == 0:
        return np.zeros((X.shape[0], 0))
    size = 1 << (int(idx.size) - 1).bit_length()
    pad = np.zeros(size, np.int32)
    pad[:idx.size] = idx
    return _f64(_take()(X, pad))[:, :idx.size]


def lam_max(X, y, loss: str) -> float:
    """Smallest lam at which beta* = 0: max_j |x_j^T f'(0)|."""
    return float(np.max(np.abs(full_correlation(
        X, grad_z(loss, np.zeros_like(_f64(y)), _f64(y))))))


def kkt_residual(X, y, beta, lam: float, loss: str) -> float:
    """Max KKT violation of a dense ``beta`` over all p columns:
    ``|c_j + lam sign(beta_j)|`` on the support, ``(|c_j| - lam)_+`` off
    it, with ``c = X^T f'(X beta)``. Float32 highest on the device, so a
    violation is resolved to about 1e-6 of ``|c_j|``."""
    beta = _f64(beta)
    sup = np.flatnonzero(beta)
    z = columns(X, sup) @ beta[sup]
    c = full_correlation(X, grad_z(loss, z, _f64(y)))
    viol = np.maximum(np.abs(c) - lam, 0.0)
    viol[sup] = np.abs(c[sup] + lam * np.sign(beta[sup]))
    return float(np.max(viol))


# ---------------------------------------------------------------------------
# restricted solve (host)
# ---------------------------------------------------------------------------

def identity(a):
    return a


def to_bfloat16(a):
    """Round to the nearest bfloat16, kept as float32 for the next op."""
    import ml_dtypes
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


@dataclasses.dataclass
class Restricted:
    """A LASSO over the columns ``A`` (n x k) in one precision: every
    product and elementwise result passes through ``q``."""
    A: np.ndarray
    y: np.ndarray
    lam: float
    loss: str
    q: Callable = identity

    def __post_init__(self):
        q = self.q
        self.A = q(self.A)
        self.y = q(self.y)
        if self.loss == "least_squares":
            self.G = q(self.A.T @ self.A)
            self.b = q(self.A.T @ self.y)
        # Lipschitz constant of the smooth part's gradient
        s = np.linalg.norm(self.A, 2) ** 2 if self.A.size else 1.0
        self.L = float(s if self.loss == "least_squares" else 0.25 * s)

    def grad(self, beta):
        q = self.q
        if self.loss == "least_squares":
            return q(q(self.G @ beta) - self.b)
        z = q(self.A @ beta)
        return q(self.A.T @ q(grad_z(self.loss, z, self.y)))

    def kkt(self, beta) -> float:
        g = self.grad(beta)
        viol = np.maximum(np.abs(g) - self.lam, 0.0)
        on = beta != 0
        viol[on] = np.abs(g[on] + self.lam * np.sign(beta[on]))
        return float(np.max(viol)) if viol.size else 0.0

    def fista(self, beta, iters: int, tol: float):
        """Proximal gradient with momentum and adaptive restart
        (O'Donoghue & Candes 2015)."""
        q = self.q
        step = 1.0 / self.L
        x, v, t = beta.copy(), beta.copy(), 1.0
        for it in range(iters):
            u = q(v - q(step * self.grad(v)))
            x_new = q(np.sign(u) * np.maximum(np.abs(u) - step * self.lam,
                                              0.0))
            if np.dot(v - x_new, x_new - x) > 0:     # restart
                t, v = 1.0, x_new
            else:
                t_new = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * t * t))
                v = q(x_new + ((t - 1.0) / t_new) * (x_new - x))
                t = t_new
            x = x_new
            if it % 50 == 49 and self.kkt(x) <= tol:
                break
        return x

    def polish(self, beta, iters: int = 8):
        """Newton steps on the support with its signs held; kept only
        where the signs survive and the KKT residual does not grow."""
        q = self.q
        on = np.flatnonzero(beta)
        if on.size == 0:
            return beta
        s = np.sign(beta[on])
        A = self.A[:, on]
        x = beta.copy()
        for _ in range(iters):
            g = self.grad(x)[on] + self.lam * s
            if self.loss == "least_squares":
                H = self.G[np.ix_(on, on)]
            else:
                z = q(self.A @ x)
                H = q(A.T @ (hess_z(self.loss, z, self.y)[:, None] * A))
            try:
                step = np.linalg.solve(H, g)
            except np.linalg.LinAlgError:
                return beta
            cand = x.copy()
            cand[on] = q(x[on] - q(step))
            if np.any(np.sign(cand[on]) != s):
                break
            x = cand
            if self.loss == "least_squares":
                break
        return x if self.kkt(x) <= self.kkt(beta) else beta


@dataclasses.dataclass
class Solution:
    """A reference solution: coefficients on the working set ``idx``."""
    idx: np.ndarray
    beta: np.ndarray
    kkt: float               # full-width KKT residual (``kkt_residual``)
    rounds: int

    def dense(self, p: int) -> np.ndarray:
        out = np.zeros(p)
        out[self.idx] = self.beta
        return out


def solve(X, y, lam: float, loss: str, *, q: Callable = identity,
          wide_dtype=None, k0: int = 128, max_rounds: int = 24,
          fista_iters: int = 20000,
          rel_tol: float = 1e-12) -> Solution:
    """Working-set LASSO: restricted solves (``Restricted``) over a set W
    grown by the full-width violators (``full_correlation``) until none is
    left. Starts from the ``k0`` columns with the largest ``|x_j^T f'(0)|``.

    ``q`` and ``wide_dtype`` give the precision of the restricted and of
    the full-width arithmetic; float64 and float32-highest by default."""
    if loss not in LOSSES:
        raise ValueError(f"unknown loss {loss!r}")
    y = _f64(y)
    p = X.shape[1]
    c = full_correlation(X, grad_z(loss, np.zeros_like(y), y), wide_dtype)
    W = np.sort(np.argsort(-np.abs(c))[:k0])
    beta = np.zeros(W.size)
    tol = rel_tol * lam
    rounds = 0
    for rounds in range(1, max_rounds + 1):
        sub = Restricted(columns(X, W), y, lam, loss, q)
        # FISTA finds the support, the Newton polish the coefficients
        beta = sub.polish(sub.fista(beta, fista_iters, max(tol, 1e-7 * lam)))
        if sub.kkt(beta) > tol:
            beta = sub.polish(sub.fista(beta, fista_iters, tol))
        z = q(sub.A @ beta)
        c = full_correlation(X, q(grad_z(loss, z, sub.y)), wide_dtype)
        out = np.ones(p, bool)
        out[W] = False
        # a column outside W violates when |c_j| > lam beyond the pass's
        # own rounding (about 1e-6 of |c_j| at float32 highest)
        viol = np.flatnonzero(out & (np.abs(c) > lam * (1.0 + 1e-6)))
        if viol.size == 0:
            break
        add = viol[np.argsort(-np.abs(c[viol]))[:max(64, W.size // 2)]]
        # the grown set starts from the coefficients found so far
        keep = beta != 0
        W_new = np.union1d(W[keep], add)
        beta_new = np.zeros(W_new.size)
        beta_new[np.searchsorted(W_new, W[keep])] = beta[keep]
        W, beta = W_new, beta_new
    full = np.zeros(p)
    full[W] = beta
    return Solution(idx=W, beta=beta,
                    kkt=kkt_residual(X, y, full, lam, loss), rounds=rounds)


def coef_error(beta, ref: Solution, p: Optional[int] = None) -> float:
    """``max_j |beta_j - beta*_j| / max_j |beta*_j|`` over all p columns."""
    beta = _f64(beta)
    ref_d = ref.dense(beta.shape[0] if p is None else p)
    scale = float(np.max(np.abs(ref_d)))
    if scale == 0.0:
        return float(np.max(np.abs(beta)))
    return float(np.max(np.abs(beta - ref_d)) / scale)
