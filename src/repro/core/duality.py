"""Dual-variable machinery: feasibility projection, duality gap, ball regions.

Implements, in order of appearance in the paper:
  * the primal->dual map and scaled feasibility projection (Lemma 2's theta_k)
  * the gap-safe ball   B(theta, r),  r^2 = 2*alpha*gap/lam^2        (Eq. 6/11)
  * the sequential-style ball from lambda_max(t)                     (Thm 2)
  * the covering ball of the intersection of two balls               (Eq. 12)

All functions operate on a *sub-problem* defined by an explicit design matrix
``Xa`` (n x k, the gathered active columns) so the same code serves SAIF
sub-problems, dynamic screening (Xa = X), and fused LASSO (transformed X).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.losses import Loss


class Ball(NamedTuple):
    center: jax.Array  # (n,)
    radius: jax.Array  # scalar


def dual_point(loss: Loss, Xa: jax.Array, y: jax.Array, beta: jax.Array,
               lam: jax.Array) -> jax.Array:
    """hat_theta = -f'(Xa beta) / lam  (the unscaled dual candidate)."""
    z = Xa @ beta
    return -loss.grad(z, y) / lam


def feasible_dual(loss: Loss, X_for_constraints: jax.Array, y: jax.Array,
                  hat_theta: jax.Array, lam: jax.Array,
                  mask: jax.Array | None = None,
                  pen: jax.Array | None = None,
                  x_unpen: jax.Array | None = None) -> jax.Array:
    """Scale hat_theta into Omega = {theta : |x_i^T theta| <= 1 for i in set}.

    Lemma 2: theta = tau * hat_theta with tau = 1 / max_i |x_i^T hat_theta|
    (only when that max exceeds 1 — otherwise already feasible). For least
    squares we additionally use the DPP-style optimal scaling
    tau* = y^T hat_theta / (lam ||hat_theta||^2) clipped into the feasible
    range, which is the projection of theta* direction (paper Thm 7 logic).

    ``mask`` marks valid columns of ``X_for_constraints`` (padded actives).

    Unpenalized coordinate (fused LASSO's ``b``, Thm 7): its dual constraint
    is the *equality* ``x_b^T theta = 0``. Pass its column as ``x_unpen`` and
    a per-column weight vector ``pen`` (0 on the unpenalized column, 1
    elsewhere): ``hat_theta`` is first projected onto the hyperplane, the
    |corr|-scaling then only sees penalized columns, and (scaling through 0)
    the equality survives the rescale. For general losses the final
    dom-f* clamp can leave an O(clip) residual on the equality — same
    approximation grade as the existing general-loss rescale (DESIGN.md §7).
    """
    if x_unpen is not None:
        sq_b = jnp.sum(x_unpen * x_unpen)
        hat_theta = hat_theta - x_unpen * (
            jnp.dot(x_unpen, hat_theta) / jnp.maximum(sq_b, 1e-30))
    corr = X_for_constraints.T @ hat_theta  # (k,)
    if mask is not None:
        corr = jnp.where(mask, corr, 0.0)
    if pen is not None:
        corr = corr * pen
    max_corr = jnp.max(jnp.abs(corr))
    denom = jnp.maximum(max_corr, 1.0)
    bound = 1.0 / jnp.maximum(max_corr, 1e-30)

    if loss.name == "least_squares":
        sq = jnp.sum(hat_theta * hat_theta)
        tau_star = jnp.dot(y, hat_theta) / (lam * jnp.maximum(sq, 1e-30))
        tau = jnp.clip(tau_star, -bound, bound)
        # Fall back to simple scaling if tau* degenerate (e.g. hat_theta ~ 0).
        tau = jnp.where(jnp.isfinite(tau), tau, 1.0 / denom)
        return tau * hat_theta
    # General smooth loss: plain rescale, then clamp into dom f*.
    theta = hat_theta / denom
    return -loss.dual_clip(-lam * theta, y) / lam


def duality_gap(loss: Loss, Xa: jax.Array, y: jax.Array, beta: jax.Array,
                theta: jax.Array, lam: jax.Array,
                mask: jax.Array | None = None,
                pen: jax.Array | None = None) -> jax.Array:
    """P_t(beta) - D_t(theta) for the sub-problem restricted to ``Xa``.

    ``pen`` (optional, (k,)) weights the l1 term per column — 0 on an
    unpenalized coordinate (fused LASSO's ``b``), 1 elsewhere.
    """
    if mask is not None:
        beta = jnp.where(mask, beta, 0.0)
    p_val = loss.primal_objective(Xa, y, beta, lam, weights=pen)
    d_val = loss.dual_objective(y, theta, lam)
    return p_val - d_val


def gap_ball(loss: Loss, theta: jax.Array, gap: jax.Array,
             lam: jax.Array, floor: jax.Array | float = 0.0) -> Ball:
    """Gap-safe ball (Eq. 6 generalized): r^2 = 2*alpha*gap / lam^2.

    f is alpha-smooth => f* is (1/alpha)-strongly convex => the dual objective
    is (lam^2/alpha)-strongly concave, giving the radius below. For least
    squares alpha=1 recovers Eq. (6) exactly.

    ``floor`` (optional) lower-bounds the gap before the radius is derived.
    The computed gap is a *difference* P - D of two near-equal objective
    values, so it is only accurate to ~eps_machine * |D|; once the
    sub-problem is solved to machine precision the raw gap underflows to 0
    (or goes negative) and the radius collapses to exactly 0 — at which
    point the strict <1 DEL rule and the <1 ADD-stop operate with zero
    margin and evict/ignore boundary features (|x^T theta*| = 1) on
    floating-point noise. Passing the gap's own arithmetic-precision scale
    (see :func:`gap_precision_floor`) restores the honest uncertainty
    radius. Default 0.0 preserves the textbook formula.
    """
    gap = jnp.maximum(gap, floor)
    r = jnp.sqrt(2.0 * loss.smoothness * gap) / lam
    return Ball(center=theta, radius=r)


def gap_precision_floor(theta: jax.Array, lam: jax.Array) -> jax.Array:
    """Arithmetic-precision scale of a duality-gap estimate at ``theta``.

    P - D cancels against objective values of magnitude ~|D(theta)|; the
    0.5 lam^2 ||theta||^2 term bounds that magnitude for least squares (and
    its order for the bounded-conjugate losses), so the gap cannot be
    trusted below ~eps_dtype times it. The factor 8 covers the O(n)-term
    accumulation of the two objective sums. Discovered root cause of the
    near-lambda_max support misses on gaussian designs (ROADMAP open item;
    the Thm-2 ball and the h formula were innocent): with the raw gap
    flooring at exactly 0, a truly-active boundary feature sits at
    |x^T theta| = 1 - O(eps) and the full-radius DEL rule deletes it.
    """
    eps_m = jnp.finfo(theta.dtype).eps
    scale = jnp.maximum(
        0.5 * lam * lam * jnp.sum(theta * theta, axis=-1), 1.0)
    return 8.0 * eps_m * scale


def sequential_ball(loss: Loss, y: jax.Array, theta0: jax.Array,
                    lam0: jax.Array, lam: jax.Array) -> Ball:
    """Theorem 2 ball around (lam0/lam) * theta0, for lam < lam0.

    r^2 = (2 alpha / lam^2) [ f*(-(lam^2/lam0) theta0) - f*(-lam0 theta0)
                              + (lam - lam0) <f*'(-lam0 theta0), theta0> ].

    For least squares with theta0 = theta*(lam_max) = -f'(0)/lam_max = y/lam_max
    this reproduces the DPP-style initial ball.
    """
    alpha = loss.smoothness
    u0 = -lam0 * theta0
    # f*'(u) for least squares is u + y; for logistic we use autodiff-free form.
    if loss.name == "least_squares":
        fstar_grad = u0 + y
    else:
        fstar_grad = jax.grad(lambda u: jnp.sum(loss.conj(u, y)))(u0)
    term = (jnp.sum(loss.conj(-(lam * lam / lam0) * theta0, y))
            - jnp.sum(loss.conj(u0, y))
            + (lam - lam0) * jnp.dot(fstar_grad, theta0))
    r2 = jnp.maximum(2.0 * alpha / (lam * lam) * term, 0.0)
    return Ball(center=(lam0 / lam) * theta0, radius=jnp.sqrt(r2))


def intersect_balls(b1: Ball, b2: Ball) -> Ball:
    """Smallest ball covering B1 ∩ B2 (paper Eq. 12), robustly.

    Degenerate cases (disjoint, containment, identical centers) fall back to
    the smaller input ball, which is always a valid (if looser) cover given
    both balls are valid containers of theta*.
    """
    d = jnp.linalg.norm(b1.center - b2.center)
    r1, r2 = b1.radius, b2.radius
    safe_d = jnp.maximum(d, 1e-30)
    # Signed distance from b1.center to the radical plane. The paper's Eq. 12
    # writes d1 = sqrt(r1^2 - rt^2), which drops the sign — when one center
    # lies beyond the chord plane that formula places the cover on the wrong
    # side and the "cover" no longer contains the lens (observed as unsafe
    # DELs). We use the signed radical-plane form instead.
    d1 = (d * d + r1 * r1 - r2 * r2) / (2.0 * safe_d)
    rt = jnp.sqrt(jnp.maximum(r1 * r1 - d1 * d1, 0.0))  # half-chord radius
    center_t = (1.0 - d1 / safe_d) * b1.center + (d1 / safe_d) * b2.center

    # Ball(center_t, rt) covers B1 ∩ B2 iff the spheres genuinely intersect
    # AND the radical center lies between the two centers (0 <= d1 <= d);
    # otherwise one lens cap bulges past the chord disk. Require improvement
    # too, else fall back to the smaller input ball (always a valid cover).
    intersects = (d <= r1 + r2) & (d >= jnp.abs(r1 - r2))
    between = (d1 >= 0.0) & (d1 <= d)
    use_lens = intersects & between & (rt < jnp.minimum(r1, r2))

    small_is_1 = r1 <= r2
    fallback_c = jnp.where(small_is_1, b1.center, b2.center)
    fallback_r = jnp.minimum(r1, r2)
    center = jnp.where(use_lens, center_t, fallback_c)
    radius = jnp.where(use_lens, rt, fallback_r)
    return Ball(center=center, radius=radius)


def kkt_residual(loss: Loss, X: jax.Array, y: jax.Array, beta: jax.Array,
                 lam: jax.Array, pen: jax.Array | None = None,
                 sample_w: jax.Array | None = None,
                 active_tol: float = 0.0) -> jax.Array:
    """Post-hoc KKT residual of a candidate LASSO solution (0 at the
    exact optimum) — the serving runtime's machine-checkable certificate
    (DESIGN.md §10).

    With ``g = X^T f'(X beta)``, the stationarity conditions of Eq. 1 are

      * ``|g_i| <= lam``                for ``beta_i = 0``,
      * ``g_i = -lam * sign(beta_i)``   for ``beta_i != 0``,
      * ``g_i = 0``                     for an unpenalized coordinate
        (``pen_i = 0``, the fused slot).

    Returns the max violation over all p coordinates — El Ghaoui's SAFE
    framework's observation that the post-solve check is one O(np)
    matvec, independent of how the support was produced (screened solve,
    degraded rung, oracle), is exactly why the degradation ladder can be
    *certificate-driven* rather than trust-based. ``pen`` weights the l1
    term per column (0 = unpenalized); ``sample_w`` carries per-sample
    weights (the weighted-fleet gradient); ``active_tol`` is the
    magnitude below which a coefficient is treated as zero.
    """
    g = loss.grad(X @ beta, y)
    if sample_w is not None:
        g = g * sample_w
    c = X.T @ g
    lam_i = lam * (pen if pen is not None else 1.0)
    active = jnp.abs(beta) > active_tol
    inactive_viol = jnp.maximum(jnp.abs(c) - lam_i, 0.0)
    active_viol = jnp.abs(c + lam_i * jnp.sign(beta))
    return jnp.max(jnp.where(active, active_viol, inactive_viol))


# ---------------------------------------------------------------------------
# certified mixed-precision screening: rigorous rounding-error bounds
# (ISSUE 7 / DESIGN.md §11). A gap-safe ball whose radius is widened by a
# bound on the float error of the screening correlations is still safe —
# low precision can then only screen *conservatively*, never unsafely.
# ---------------------------------------------------------------------------

def unit_roundoff(dtype) -> float:
    """u = eps/2 for the dtype: |fl(x op y) - (x op y)| <= u |x op y|."""
    return float(jnp.finfo(jnp.dtype(dtype)).eps) / 2.0


def dot_error_gamma(n: int, u: float) -> float:
    """Classical gamma_n = n*u / (1 - n*u)  (Higham, ASNA Lemma 3.1).

    A length-``n`` inner product evaluated in precision with unit
    roundoff ``u`` — in ANY summation order, including pairwise/blocked
    re-association — satisfies |fl(x.y) - x.y| <= gamma_n * |x|.|y|
    <= gamma_n * ||x||_2 ||y||_2. (Sequential summation needs only
    gamma_n; tree orders need gamma_{ceil(log2 n)+1} <= gamma_n, so the
    bound is order-oblivious — exactly what a re-associating batched
    contraction requires.) Returns +inf when n*u >= 1 (bound vacuous).
    """
    nu = float(n) * u
    if nu >= 1.0:
        return float("inf")
    return nu / (1.0 - nu)


def mixed_precision_gamma(n: int, in_dtype, acc_dtype) -> float:
    """Forward-error factor of a dot with inputs *cast* to ``in_dtype``
    and accumulated in ``acc_dtype``.

    Casting x_i -> fl_in(x_i) = x_i(1+d_i), |d_i| <= u_in, on both
    operands multiplies each product by at most (1+u_in)^2; the
    accumulation then contributes (1 + gamma_n(u_acc)). Composed:

        |fl(x.y) - x.y| <= gamma_total * ||x||_2 ||y||_2,
        gamma_total = (1+u_in)^2 (1 + gamma_n(u_acc)) - 1.

    This is the bound for an MXU/gemm-style bf16-input f32-accumulator
    screen pass (and, with in_dtype == acc_dtype, for a plain
    re-associated working-precision contraction). Monotone increasing
    in ``n`` and in both unit roundoffs.
    """
    u_in = unit_roundoff(in_dtype)
    u_acc = unit_roundoff(acc_dtype)
    return (1.0 + u_in) ** 2 * (1.0 + dot_error_gamma(n, u_acc)) - 1.0


def widened_radius(r: jax.Array, theta: jax.Array,
                   gamma: float) -> jax.Array:
    """Safe-ball radius widened to absorb screening-dot rounding error.

    With unit columns (||x_i|| <= 1) the error of each low-precision
    correlation fl(x_i . theta) is <= gamma * ||theta||_2 by
    Cauchy-Schwarz, so the exact screening rule evaluated on the
    low-precision score is implied by the same rule with radius

        r' = r + gamma * ||theta||_2.

    Column norms > 1 are covered because every screening rule already
    multiplies the radius by the column norm (ub = score + cn_i * r).
    The *computed* ||theta||_2 is itself inexact; it is inflated by
    1 + 2*gamma_{n+2}(u_work) so r' upper-bounds the true widening.
    ``theta`` is the ball center, shape (..., n); r broadcasts.
    """
    n = theta.shape[-1]
    u_w = unit_roundoff(theta.dtype)
    slack = 1.0 + 2.0 * dot_error_gamma(n + 2, u_w)
    norm = jnp.sqrt(jnp.sum(theta * theta, axis=-1))
    return r + gamma * slack * norm


def lambda_max(loss: Loss, X: jax.Array, y: jax.Array) -> jax.Array:
    """Smallest lam with beta* = 0:  max_i |x_i^T f'(0)|   (paper Sec 2.2)."""
    g0 = loss.grad(jnp.zeros_like(y), y)
    return jnp.max(jnp.abs(X.T @ g0))


def polish_unpen(loss: Loss, x: jax.Array, y: jax.Array, z: jax.Array,
                 b: jax.Array, iters: int = 4):
    """Newton-polish the unpenalized coordinate to stationarity.

    ``iters`` exact 1-D Newton steps on ``b`` along column ``x`` from the
    point ``z`` (the full model vector, which already includes ``x b``).
    Returns the updated ``(b, z)`` with ``x^T f'(z) ~ 0``.

    Why this exists (DESIGN.md §7): the CM burst's prox step on ``b`` uses
    the *majorized* curvature ``alpha ||x||^2``, so ``x^T f'(z)`` is small
    but not ~0 after a burst. For general losses the dual point must
    satisfy the equality constraint ``x^T theta = 0`` WITHOUT a geometric
    projection — projecting ``-f'(z)/lam`` can flip the sign structure
    (for logistic: theta_j y_j > 0) and the subsequent dom-f* clamp then
    moves theta far enough that D(theta) is no longer a lower bound
    (observed as *negative* duality gaps => bogus instant convergence).
    Driving ``b`` to stationarity makes the gradient itself satisfy the
    equality, so the projection inside :func:`feasible_dual` is a benign
    ~0 correction and the clamp stays epsilon-grade. The Hessian is
    floored and the step clipped so separable logistic data cannot send
    the iterate to infinity.
    """
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    lim = 1e3 / scale

    def step(_, carry):
        b, z = carry
        g = jnp.sum(x * loss.grad(z, y))
        H = jnp.sum(x * x * loss.hess(z, y))
        d = jnp.clip(g / jnp.maximum(H, 1e-30), -lim, lim)
        return b - d, z - d * x

    return jax.lax.fori_loop(0, iters, step, (b, z))


def fit_unpenalized(loss: Loss, x: jax.Array, y: jax.Array,
                    iters: int = 30) -> jax.Array:
    """1-D Newton for ``min_b sum_j f(x_j b, y_j)`` (the unpenalized slot).

    The penalized-null model of a problem with one unpenalized coordinate
    ``b`` (fused LASSO, Thm 7) is beta_tilde = 0 with b at its partial
    optimum — NOT beta = 0.
    """
    b0 = jnp.asarray(0.0, x.dtype)
    b, _ = polish_unpen(loss, x, y, jnp.zeros_like(y), b0, iters=iters)
    return b


def null_gradient(loss: Loss, X: jax.Array, y: jax.Array,
                  unpen_idx: int | None = None):
    """(g0, c0, b0) of the penalized-null model.

    Plain LASSO (unpen_idx None): g0 = f'(0), c0 = |X^T g0|, b0 = 0 — the
    quantities every SAIF driver derives lambda_max / h / the initial
    active set from. With an unpenalized coordinate the null model is the
    partial optimum over that coordinate alone: g0 = f'(x_b b0), and
    c0[unpen] is forced to 0 (the slot is always resident, never a
    screening candidate, and must not distort lambda_max).
    """
    if unpen_idx is None:
        g0 = loss.grad(jnp.zeros_like(y), y)
        return g0, jnp.abs(X.T @ g0), jnp.asarray(0.0, X.dtype)
    xb = X[:, unpen_idx]
    b0 = fit_unpenalized(loss, xb, y)
    g0 = loss.grad(xb * b0, y)
    c0 = jnp.abs(X.T @ g0).at[unpen_idx].set(0.0)
    return g0, c0, b0
