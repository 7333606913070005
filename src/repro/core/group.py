"""Group-LASSO SAIF — the extension the paper's conclusion proposes.

Problem:  min_beta  sum_j f(x_j. beta, y_j) + lam * sum_g ||beta_g||_2
with disjoint equal-size groups (p = n_groups * gsize, static).

Dual feasible set:  Omega = { theta : ||X_g^T theta||_2 <= 1  for all g }.
Everything from the LASSO machinery carries over group-wise:

* gap-safe ball: identical (Eq. 11 depends only on f*, not the penalty);
* screening rule:  ||X_g^T theta|| + ||X_g||_F * r < 1  =>  group inactive
  (|| . ||_F upper-bounds the operator norm, so the rule stays SAFE);
* ADD: recruit the argmax_g ||X_g^T theta|| groups from the remaining set;
* inner solver: cyclic block-proximal minimization with the group
  soft-threshold  S_t(v) = v * max(0, 1 - t/||v||)  and block Lipschitz
  L_g = ||X_g||_F^2 * alpha (majorization — exact for orthonormal groups).

Implementation mirrors core/saif.py at group granularity with a
fixed-capacity *group* active set.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.losses import Loss, get_loss


@dataclasses.dataclass(frozen=True)
class GroupSaifConfig:
    eps: float = 1e-8
    inner_epochs: int = 5
    polish_factor: int = 8
    k_max: Optional[int] = None    # active-set capacity in GROUPS
    max_outer: int = 2000
    h: Optional[int] = None        # groups recruited per ADD
    loss: str = "least_squares"


class GroupSaifResult(NamedTuple):
    beta: jax.Array
    gap: jax.Array
    n_outer: jax.Array
    n_active_groups: jax.Array
    # final slot state — the warm handoff a session threads between group
    # requests (mirrors SaifResult.active_idx/active_mask, DESIGN.md §9)
    gidx: jax.Array = None          # (k_max,) slot -> group id
    gmask: jax.Array = None         # (k_max,) slot validity
    beta_slots: jax.Array = None    # (k_max, gsize) slot coefficients


def _group_norms(v: jax.Array, gsize: int) -> jax.Array:
    """(p,) -> (n_groups,) euclidean norms of consecutive blocks."""
    return jnp.linalg.norm(v.reshape(-1, gsize), axis=1)


def group_soft_threshold(v: jax.Array, t: jax.Array) -> jax.Array:
    nrm = jnp.linalg.norm(v)
    scale = jnp.maximum(1.0 - t / jnp.maximum(nrm, 1e-30), 0.0)
    return v * scale


def solve_group_lasso_bcd(loss: Loss, X, y, lam, gsize: int,
                          tol=1e-10, max_epochs=50_000):
    """Unscreened block-CD oracle (ground truth for tests/benches)."""
    n, p = X.shape
    ng = p // gsize
    Xg = X.reshape(n, ng, gsize)
    Lg = jnp.maximum(loss.smoothness
                     * jnp.sum(Xg * Xg, axis=(0, 2)), 1e-30)   # (ng,)

    def epoch(carry):
        beta, z, _, t = carry

        def block(g, bz):
            beta, z = bz
            bg = jax.lax.dynamic_slice(beta, (g * gsize,), (gsize,))
            grad = jnp.einsum("nk,n->k", jax.lax.dynamic_slice(
                Xg, (0, g, 0), (n, 1, gsize))[:, 0], loss.grad(z, y))
            v = bg - grad / Lg[g]
            bg_new = group_soft_threshold(v, lam / Lg[g])
            z = z + jax.lax.dynamic_slice(Xg, (0, g, 0),
                                          (n, 1, gsize))[:, 0] @ (bg_new - bg)
            beta = jax.lax.dynamic_update_slice(beta, bg_new, (g * gsize,))
            return beta, z

        beta, z = jax.lax.fori_loop(0, ng, block, (beta, z))
        # duality gap with the group-feasible scaled dual point
        hat = -loss.grad(z, y) / lam
        gmax = jnp.max(_group_norms(X.T @ hat, gsize))
        theta = hat / jnp.maximum(gmax, 1.0)
        p_val = (jnp.sum(loss.value(z, y))
                 + lam * jnp.sum(_group_norms(beta, gsize)))
        gap = p_val - loss.dual_objective(y, theta, lam)
        return beta, z, gap, t + 1

    def cond(c):
        return (c[2] > tol) & (c[3] < max_epochs)

    beta0 = jnp.zeros((p,), X.dtype)
    out = jax.lax.while_loop(cond, epoch,
                             (beta0, jnp.zeros_like(y),
                              jnp.asarray(jnp.inf, X.dtype), jnp.asarray(0)))
    return out[0]


@partial(jax.jit, static_argnames=("loss_name", "gsize", "h", "k_max",
                                   "inner_epochs", "polish_factor",
                                   "max_outer"))
def _gsaif_jit(X, y, gfro, lam, eps, init_gidx, init_beta, init_gmask, *,
               loss_name, gsize, h, k_max, inner_epochs, polish_factor,
               max_outer):
    # (init_gidx, init_beta, init_gmask) are traced (k_max,)-shaped slot
    # buffers — zeros/top-h for a cold start, the previous solve's final
    # slot state for a warm one — so every lambda served at a given
    # (gsize, h, k_max) signature shares ONE compilation (the group
    # engine's edition of the path-engine trick, DESIGN.md §9).
    loss = get_loss(loss_name)
    n, p = X.shape
    ng = p // gsize
    Xg = X.reshape(n, ng, gsize)
    Lg_all = jnp.maximum(loss.smoothness * gfro ** 2, 1e-30)

    class S(NamedTuple):
        gidx: jax.Array     # (k_max,) group ids
        gmask: jax.Array    # (k_max,)
        beta: jax.Array     # (k_max, gsize)
        in_active: jax.Array  # (ng,)
        gap: jax.Array
        is_add: jax.Array
        stop: jax.Array
        t: jax.Array

    s0 = S(gidx=init_gidx.astype(jnp.int32),
           gmask=init_gmask,
           beta=init_beta.astype(X.dtype),
           in_active=jnp.zeros((ng,), bool).at[
               jnp.where(init_gmask, init_gidx, ng)].set(True, mode="drop"),
           gap=jnp.asarray(jnp.inf, X.dtype),
           is_add=jnp.asarray(True), stop=jnp.asarray(False),
           t=jnp.asarray(0))

    def cond(s):
        return (~s.stop) & (s.t < max_outer)

    def body(s: S) -> S:
        Xa = jnp.where(s.gmask[None, :, None],
                       jnp.take(Xg, s.gidx, axis=1), 0.0)  # (n, k_max, gs)
        Lg = jnp.where(s.gmask, jnp.take(Lg_all, s.gidx), 1.0)

        def bcd_epoch(_, bz):
            def block(j, bz):
                beta, z = bz
                xj = Xa[:, j]                          # (n, gsize)
                grad = xj.T @ loss.grad(z, y)
                v = beta[j] - grad / Lg[j]
                bnew = group_soft_threshold(v, lam / Lg[j])
                bnew = jnp.where(s.gmask[j], bnew, 0.0)
                z = z + xj @ (bnew - beta[j])
                return beta.at[j].set(bnew), z
            return jax.lax.fori_loop(0, k_max, block, bz)

        n_ep = jnp.where(s.is_add, inner_epochs,
                         inner_epochs * polish_factor)
        beta, z = jax.lax.fori_loop(
            0, n_ep, bcd_epoch,
            (s.beta, jnp.einsum("nkg,kg->n", Xa, s.beta)))

        # dual point, gap, ball
        hat = -loss.grad(z, y) / lam
        gnorm_hat = jnp.linalg.norm(
            jnp.einsum("nkg,n->kg", Xa, hat), axis=1)
        tau = 1.0 / jnp.maximum(jnp.max(jnp.where(s.gmask, gnorm_hat, 0.0)),
                                1.0)
        theta = tau * hat
        p_val = (jnp.sum(loss.value(z, y))
                 + lam * jnp.sum(jnp.where(s.gmask,
                                           jnp.linalg.norm(beta, axis=1),
                                           0.0)))
        gap = p_val - loss.dual_objective(y, theta, lam)
        r = jnp.sqrt(2.0 * loss.smoothness * jnp.maximum(gap, 0.0)) / lam

        stop_now = (~s.is_add) & (gap <= eps)

        # DEL groups
        corr_act = jnp.linalg.norm(jnp.einsum("nkg,n->kg", Xa, theta),
                                   axis=1)
        fro_act = jnp.where(s.gmask, jnp.take(gfro, s.gidx), 0.0)
        drop = s.gmask & (corr_act + fro_act * r < 1.0) & ~stop_now
        gmask = s.gmask & ~drop
        beta = jnp.where(drop[:, None], 0.0, beta)
        in_active = s.in_active.at[jnp.where(drop, s.gidx, ng)].set(
            False, mode="drop")

        # ADD groups
        scores = jnp.linalg.norm(jnp.einsum("njg,n->jg", Xg, theta), axis=1)
        scores = jnp.where(in_active, -jnp.inf, scores)
        ub = scores + gfro * r
        add_done = jnp.max(ub) < 1.0

        def on_add(args):
            gidx, gmask, in_active, is_add = args
            top_s, top_i = jax.lax.top_k(scores, h)
            keep = jnp.isfinite(top_s)
            free = ~gmask
            free_rank = jnp.cumsum(free.astype(jnp.int32)) - free
            order_key = jnp.where(free, free_rank, k_max + 1)
            slot_of_rank = jnp.argsort(order_key)
            cand_rank = jnp.cumsum(keep.astype(jnp.int32)) - keep
            placed = keep & (cand_rank < jnp.sum(free))
            tgt = jnp.where(placed,
                            slot_of_rank[jnp.clip(cand_rank, 0, k_max - 1)],
                            k_max)
            gidx = gidx.at[tgt].set(top_i.astype(jnp.int32), mode="drop")
            gmask = gmask.at[tgt].set(True, mode="drop")
            in_active = in_active.at[jnp.where(placed, top_i, ng)].set(
                True, mode="drop")
            return gidx, gmask, in_active, is_add

        def on_done(args):
            gidx, gmask, in_active, _ = args
            return gidx, gmask, in_active, jnp.asarray(False)

        gidx, gmask, in_active, is_add = jax.lax.cond(
            s.is_add & ~stop_now,
            lambda a: jax.lax.cond(add_done, on_done, on_add, a),
            lambda a: a, (s.gidx, gmask, in_active, s.is_add))

        return S(gidx=gidx, gmask=gmask, beta=beta, in_active=in_active,
                 gap=gap, is_add=is_add, stop=stop_now, t=s.t + 1)

    f = jax.lax.while_loop(cond, body, s0)
    beta_full = jnp.zeros((ng, gsize), X.dtype).at[
        jnp.where(f.gmask, f.gidx, ng)].add(
        jnp.where(f.gmask[:, None], f.beta, 0.0), mode="drop")
    return GroupSaifResult(beta=beta_full.reshape(-1), gap=f.gap,
                           n_outer=f.t,
                           n_active_groups=jnp.sum(f.gmask),
                           gidx=f.gidx, gmask=f.gmask, beta_slots=f.beta)


def group_compile_count() -> int:
    """Distinct ``_gsaif_jit`` compilations alive in this process (the
    group-engine leg of :func:`repro.core.api.unified_compile_count`;
    mirrors ``saif_jit_compile_count``). The group static signature
    (gsize, h, k_max) is lambda-independent, so a session serving many
    group requests must move this counter exactly once — asserted in
    tests/test_api.py."""
    return int(_gsaif_jit._cache_size())


class GroupPrep(NamedTuple):
    """One-time group-problem preparation: null-gradient group norms, the
    per-group Frobenius norms, and the (lambda-independent) static sizes.
    Computed once per session (``repro.core.api``)."""
    X: jax.Array
    y: jax.Array
    c0: jax.Array      # (ng,) group norms of X^T f'(0)
    gfro: jax.Array    # (ng,) per-group Frobenius norms
    gsize: int
    h: int
    k_max: int


def prepare_group(X, y, gsize: int,
                  config: GroupSaifConfig = GroupSaifConfig()) -> GroupPrep:
    loss = get_loss(config.loss)
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    n, p = X.shape
    assert p % gsize == 0, "p must be a multiple of the group size"
    ng = p // gsize
    g0 = loss.grad(jnp.zeros_like(y), y)
    c0 = _group_norms(X.T @ g0, gsize)
    gfro = jnp.sqrt(jnp.sum((X * X).reshape(n, ng, gsize), axis=(0, 2)))
    h = config.h or max(1, 1 << (math.ceil(math.log2(max(ng, 2))) // 2))
    k_max = config.k_max or min(ng, max(8 * h, 32))
    return GroupPrep(X=X, y=y, c0=c0, gfro=gfro, gsize=gsize, h=h,
                     k_max=k_max)


def group_solve(prep: GroupPrep, lam: float,
                config: GroupSaifConfig = GroupSaifConfig(),
                warm=None) -> GroupSaifResult:
    """One group solve from an existing preparation. ``warm`` is the
    previous solve's ``(gidx, gmask, beta_slots)`` (e.g. the fields of a
    :class:`GroupSaifResult` at the neighbouring lambda); ``None`` is the
    cold top-h start — bitwise the legacy ``group_saif`` behavior."""
    X, gsize, h, k_max = prep.X, prep.gsize, prep.h, prep.k_max
    if warm is None:
        m = min(h, k_max)
        top = jax.lax.top_k(prep.c0, m)[1]
        gidx = jnp.zeros((k_max,), jnp.int32).at[:m].set(
            top.astype(jnp.int32))
        gmask = jnp.zeros((k_max,), bool).at[:m].set(True)
        beta = jnp.zeros((k_max, gsize), X.dtype)
    else:
        gidx, gmask, beta = warm
    return _gsaif_jit(X, prep.y, prep.gfro, jnp.asarray(lam, X.dtype),
                      jnp.asarray(config.eps, X.dtype), gidx, beta, gmask,
                      loss_name=config.loss, gsize=gsize, h=h, k_max=k_max,
                      inner_epochs=config.inner_epochs,
                      polish_factor=config.polish_factor,
                      max_outer=config.max_outer)


def group_saif(X, y, lam: float, gsize: int,
               config: GroupSaifConfig = GroupSaifConfig()
               ) -> GroupSaifResult:
    """DEPRECATED legacy frontend — one-shot session over
    :func:`group_solve`. Use ``repro.open_session(Problem(X, y,
    penalty=group(gsize)), config).solve(Scalar(lam))``; the session
    reuses the preparation, the single group compilation and the warm
    slot buffers across requests (DESIGN.md §9)."""
    from repro.core._compat import warn_deprecated
    warn_deprecated("repro.core.group_saif",
                    "session.solve(Scalar(lam)) with penalty=group(gsize)")
    from repro.core.api import Problem, Scalar, group, open_session

    sess = open_session(Problem(X=X, y=y, loss=config.loss,
                                penalty=group(gsize)), config)
    return sess.solve(Scalar(lam=float(lam)))


def group_lambda_max(loss: Loss, X, y, gsize: int) -> float:
    g0 = loss.grad(jnp.zeros_like(jnp.asarray(y)), jnp.asarray(y))
    return float(jnp.max(_group_norms(jnp.asarray(X).T @ g0, gsize)))
