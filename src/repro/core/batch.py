"""Batch-polymorphic SAIF: one compilation solving a fleet of B problems.

Real traffic arrives as *fleets* of related solves — many responses over a
shared design, K-fold cross-validation over a lambda grid (the glmnet-style
workload; see Fercoq et al.'s CV protocol). The serial engine
(``core/saif.py``) prices Theorem 5's economics — a tiny active block plus
one O(p) scan — per problem; this module re-prices them per *fleet*:

  * **one compilation** — ``_saif_batch_jit`` is a single hand-batched
    ``lax.while_loop`` whose every state leaf carries a leading problem
    axis B. One XLA program drives B lockstep solves; the compile counter
    (``saif_jit_compile_count``) must move by exactly 1 per fleet.
  * **amortized fixed costs + shared scans** — the fleet pays ONE host
    driver, ONE preprocessing pass, ONE dispatch and ONE set of device
    syncs where B serial calls pay B of each (the dominant term for
    serving-sized solves), and the screening stage is pluggable per fleet:
    the default keeps per-problem serial scans (bitwise, and skipped per
    problem outside its ADD phase), while the opt-in ``matmul`` shared-X
    path and the problem-gridded Pallas kernels read the O(n p) design
    once per outer step for the entire fleet.
  * **per-problem masks, not a barrier** — ``lam``/``eps``/``h_cap``/
    ``h~``/``delta`` are traced (B,) vectors; convergence, the ADD ramp
    and capacity overflow are all per-problem. A finished problem is
    *frozen*: its state is select-masked, its inner burst runs zero
    epochs, and it never forces extra work on stragglers. This is why the
    loop is hand-batched — ``vmap`` over the serial while_loop would
    re-run every problem's full body until the whole fleet converges and
    could not give per-problem burst budgets.

The batching discipline (DESIGN.md §8): every float path of the default
configuration — bursts, dual points, gaps, balls, DEL certificates, the
screening scans, even the c0 preprocessing — runs as a ``lax.map`` of the
*literal serial code* over the fleet, under per-problem liveness conds.
Batch-dim float contractions provably re-associate on XLA:CPU (a batched
dot is not bitwise the serial dot, and near an ADD-stop boundary an ulp
flips a decision), so mapping the serial bodies is what makes fleet
supports, coefficients, gaps and traces byte-for-byte those of B serial
solves — asserted across every screen x inner backend combination in
``tests/test_batch_parity.py``. The explicitly opt-in deviations are the
``matmul`` screen and the sharded collective, which trade ulp-grade score
equality for fleet-shared memory traffic.

Frontends: :func:`saif_batch` (B responses, one X, per-problem lambdas)
here; :func:`repro.core.cv.cv_path` (K-fold CV fleets via the
sample-weight trick); ``repro.distributed.saif_sharded.
saif_batch_distributed`` (the §5 collective serving all B problems per
wire round). DESIGN.md §8 documents the layer.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import active_set as aset_lib
from repro.core.cm import soft_threshold
from repro.core.duality import (gap_ball, gap_precision_floor,
                                intersect_balls, mixed_precision_gamma,
                                sequential_ball, widened_radius)
from repro.core.inner_backend import (InnerCarry, _dual_and_gap,
                                      cold_inner_carry_batch,
                                      make_batch_inner)
from repro.core.losses import get_loss
from repro.core.saif import (SaifConfig, SaifResult, add_batch_size_static,
                             default_capacity)
from repro.core.screen_backend import (SCREEN_RULES, BatchScreenFn,
                                       ScreenOut, ScreenRule,
                                       fleet_col_norms, make_batch_screen,
                                       make_batch_screen_fast,
                                       resolve_batch_screen,
                                       resolve_screen_rule)
from repro.runtime.inject import seam as _fault_seam
from repro.runtime.spans import span


class _BatchState(NamedTuple):
    aset: aset_lib.ActiveSet   # every field with leading problem axis B
    z: jax.Array        # (B, n)
    gap: jax.Array      # (B,)
    delta: jax.Array    # (B,)
    is_add: jax.Array   # (B,) bool
    stop: jax.Array     # (B,) bool
    t: jax.Array        # (B,) int32 per-problem outer counters
    inner: InnerCarry   # batched inner carry
    trace_n_active: jax.Array   # (B, max_outer)
    trace_gap: jax.Array
    trace_screened: jax.Array   # (B, max_outer) int32 observability (ISSUE 9)
    trace_survivors: jax.Array
    trace_post_viol: jax.Array
    # (B, n, k_max) active blocks of the pallas fleet step, carried across
    # outer steps: live slots hold their feature's column of X, dead slots
    # are stale and zeroed on read. None on the map-fused paths.
    block: Optional[jax.Array] = None


def _freeze_select(live: jax.Array, old, new):
    """Per-problem state freeze: keep ``old`` wherever ``live`` is False."""
    def sel(o, n):
        m = live.reshape(live.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, old, new)


def _n_surv32_batch(out: ScreenOut, b: int) -> jax.Array:
    """(B,) int32 survivor counts; ``None`` (legacy custom BatchScreenFns)
    reads as 0, matching the serial engine's normalization."""
    ns = out.n_surv
    if ns is None:
        return jnp.zeros((b,), jnp.int32)
    return jnp.broadcast_to(ns.astype(jnp.int32), (b,))


def _fetches_columns(screen_backend: str,
                     screen_fn: Optional[BatchScreenFn]) -> bool:
    """Does the engine fetch recruited columns with the fetch kernel?

    Yes when the screen is the compiled Pallas kernel, which streams
    row-major tiles of X: a minor-axis gather (``jnp.take(X, ids,
    axis=1)``) would make XLA hold X column-major and lay the whole design
    out again for the screen at every ADD phase. Elsewhere (the CPU, the
    interpreter, the XLA screens, a custom ``screen_fn``) ``jnp.take``
    costs no such copy. Either way each value is a copy of the column."""
    from repro.kernels.screen import screen as screen_kernels
    return (screen_fn is None and screen_backend == "pallas"
            and not screen_kernels.default_interpret())


def _read_block(block: jax.Array, aset: aset_lib.ActiveSet) -> jax.Array:
    """The carried (B, n, k_max) active blocks as
    ``active_set.gather_columns_batch`` gives them: dead slots zeroed."""
    return jnp.where(aset.mask[:, None, :], block, 0.0)


@partial(jax.jit, static_argnames=("loss_name", "h", "k_max",
                                   "inner_epochs", "polish_factor",
                                   "max_outer", "use_seq_ball",
                                   "screen_backend", "inner_backend",
                                   "has_weights", "screen_fn",
                                   "screen_rule"))
def _saif_batch_jit(X, Y, W, col_norm, c0, lam, eps, delta0, init_idx,
                    init_beta, init_mask, init_G, init_rho, init_gidx,
                    h_tilde, h_cap, pad_mask=None,
                    *, loss_name: str, h: int, k_max: int,
                    inner_epochs: int, polish_factor: int, max_outer: int,
                    use_seq_ball: bool, screen_backend: str = "jnp",
                    inner_backend: str = "jnp", has_weights: bool = False,
                    screen_fn: Optional[BatchScreenFn] = None,
                    screen_rule: ScreenRule = SCREEN_RULES["saif"]
                    ) -> SaifResult:
    """The fleet while_loop. Mirrors ``_saif_jit`` body-for-body with a
    leading problem axis; see the module docstring for the batching rules.
    ``lam``/``eps``/``delta0``/``h_tilde``/``h_cap`` are (B,) traced
    vectors, ``col_norm``/``c0`` fleet (B, p) matrices, ``W`` the sample
    weights ((B, n); a (1, 1) placeholder when ``has_weights`` is False).
    Returns a :class:`SaifResult` whose every field has a leading B.
    """
    loss = get_loss(loss_name)
    n, p = X.shape
    b = Y.shape[0]
    barange = jnp.arange(b)
    lam = jnp.asarray(lam, X.dtype)
    weights = W if has_weights else None
    if screen_fn is not None:
        screen = screen_fn
    else:
        screen = make_batch_screen(screen_backend, X, col_norm, h)
    inner = make_batch_inner(inner_backend, loss, X, Y, col_norm, h,
                             weights=weights)
    # The pallas fleet step carries the active blocks (``_BatchState.
    # block``): filled once here, DEL only changes the mask, ADD writes
    # the slots it fills.
    carried = inner.fleet_step is not None
    fetch = _fetches_columns(screen_backend, screen_fn)

    def take_rows(ids, placed):
        """(..., n) rows: X's columns ``ids`` where ``placed``."""
        if fetch:
            from repro.kernels.screen.fetch import fetch_columns_pallas
            rows = fetch_columns_pallas(X, ids.reshape(-1),
                                        placed.reshape(-1))
            return rows.reshape(ids.shape + (n,))
        return jnp.moveaxis(jnp.take(X, ids, axis=1), 0, -1)

    def place(block, ids, slot):
        """Write X's columns ``ids`` into the (B, n, k_max) blocks at
        ``slot`` ((B, h); k_max = not placed)."""
        with jax.named_scope("add_delete"):
            rows = take_rows(ids, slot < k_max)
            return block.at[barange[:, None], :, slot].set(rows,
                                                           mode="drop")

    aset0 = aset_lib.init_active_set_batch(p, k_max, init_idx, X.dtype,
                                           init_beta, live_mask=init_mask)
    if pad_mask is not None:
        # bucket-pad columns are born "already active" in every problem
        # (traced, shared across the compile bucket) — never recruited,
        # never scored; see the serial engine's identical guard
        aset0 = aset0._replace(in_active=aset0.in_active | pad_mask[None, :])
    carry_in = InnerCarry(G=init_G, rho=init_rho, gidx=init_gidx)
    if carried:
        block0 = jnp.swapaxes(take_rows(aset0.idx, aset0.mask), 1, 2)
        Xa0 = _read_block(block0, aset0)
    else:
        block0 = None
        Xa0 = aset_lib.gather_columns_batch(X, aset0)
    inner0 = inner.init(aset0, carry_in, Xa0)
    trace0 = jnp.full((b, max_outer), -1.0, X.dtype)
    itrace0 = jnp.full((b, max_outer), -1, jnp.int32)
    state0 = _BatchState(
        aset=aset0, z=jnp.zeros_like(Y),
        gap=jnp.full((b,), jnp.inf, X.dtype),
        delta=jnp.asarray(delta0, X.dtype),
        is_add=jnp.ones((b,), bool), stop=jnp.zeros((b,), bool),
        t=jnp.zeros((b,), jnp.int32), inner=inner0,
        trace_n_active=trace0, trace_gap=trace0,
        trace_screened=itrace0, trace_survivors=itrace0,
        trace_post_viol=itrace0, block=block0)
    # per-problem serial Newton polish (hybrid rule): rides inside the
    # map-fused live branch so each problem's arithmetic is the literal
    # serial newton_step — the parity contract extends to the hybrid rule
    newton = (screen_rule.newton_polish and inner_backend == "gram"
              and loss_name == "least_squares")

    def cond(s: _BatchState):
        return jnp.any(~s.stop & (s.t < max_outer))

    def _newton_one(carry_b, mask_b, Xa_b, y_b, w_b, lam_b, args):
        """The serial engine's working-set Newton step for one problem
        (core/saif.py body, DESIGN.md §13): solve on the CM iterate's
        support, accept only if the official gap certifies improvement."""
        beta_c, z_c, theta_c_, gap_c = args
        G, rho = carry_b.G, carry_b.rho
        m = mask_b & (beta_c != 0.0)
        sgn = jnp.sign(beta_c)
        mf = m.astype(X.dtype)
        Gm = G * (mf[:, None] * mf[None, :]) + jnp.diag(1.0 - mf)
        rhs = (rho - lam_b * sgn) * mf
        b_n = jnp.where(m, jnp.linalg.solve(Gm, rhs), 0.0)
        z_n = Xa_b @ b_n
        if w_b is None:
            th_n, gap_n = _dual_and_gap(loss, Xa_b, y_b, b_n, z_n, m,
                                        lam_b)
        else:
            th_n, gap_n = _dual_and_gap(loss, Xa_b, y_b, b_n, z_n, m,
                                        lam_b, sample_w=w_b)
        gap_n = jnp.asarray(gap_n, X.dtype)
        better = gap_n < gap_c          # NaN/garbage reads False
        return (jnp.where(better, b_n, beta_c),
                jnp.where(better, z_n, z_c),
                jnp.where(better, th_n, theta_c_),
                jnp.where(better, gap_n, gap_c))

    def _certify(y_b, w_b, theta_b, gap_b, lam_b, eps_b, delta_b,
                 is_add_b, Xa_b, idx_b, mask_b, cn_b, c0_b):
        """Serial ball / stop / DEL certificates for one problem — the
        exact serial body arithmetic (module docstring: batch-dim
        reductions re-associate, serial maps don't)."""
        ball = gap_ball(loss, theta_b, gap_b, lam_b,
                        floor=gap_precision_floor(theta_b, lam_b))
        if use_seq_ball:
            c0_active = jnp.where(mask_b, jnp.take(c0_b, idx_b), -jnp.inf)
            lam0t = jnp.maximum(jnp.max(c0_active), lam_b * (1 + 1e-12))
            g0_b = loss.grad(jnp.zeros_like(y_b), y_b)
            theta0t = -g0_b / lam0t
            b_seq = sequential_ball(loss, y_b, theta0t, lam0t, lam_b)
            ball = intersect_balls(b_seq, ball)
        stop_now_b = (~is_add_b) & (gap_b <= eps_b)
        corr_act = jnp.abs(Xa_b.T @ ball.center)
        norm_act = jnp.where(mask_b, jnp.take(cn_b, idx_b), 0.0)
        del_row = mask_b & (corr_act + norm_act * ball.radius < 1.0)
        if screen_rule.add_bound == "point":
            # strong-rule ADD geometry (DESIGN.md §13): radius 0
            r_eff_b = jnp.zeros_like(ball.radius)
        else:
            r_eff_b = delta_b * ball.radius
        return (ball.center, r_eff_b, stop_now_b, del_row, ball.radius)

    def body(s: _BatchState) -> _BatchState:
        live = ~s.stop & (s.t < max_outer)       # (B,) frozen problems coast
        aset = s.aset
        n_ep = jnp.where(s.is_add, inner_epochs,
                         inner_epochs * polish_factor)
        n_ep = jnp.where(live, n_ep, 0).astype(jnp.int32)

        if inner.make_one is not None:
            # --- map-fused path: ONE lax.map owns gather + refresh +
            # burst + certificates per problem, and a per-problem liveness
            # cond skips the whole body — a frozen problem costs nothing.
            def solve_one(args):
                if has_weights:
                    (live_b, y_b, w_b, lam_b, eps_b, nep_b, delta_b,
                     is_add_b, z_b, gap_b, carry_b, aset_b, cn_b,
                     c0_b) = args
                else:
                    (live_b, y_b, lam_b, eps_b, nep_b, delta_b,
                     is_add_b, z_b, gap_b, carry_b, aset_b, cn_b,
                     c0_b) = args
                    w_b = None

                def live_branch(_):
                    with jax.named_scope("cm"):
                        Xa_b = aset_lib.gather_columns(X, aset_b)
                        be = inner.make_one(y_b, w_b)
                        carry2 = be.refresh(carry_b, aset_b, Xa_b)
                        out = be.run(carry2, aset_b, Xa_b, lam_b, nep_b)
                    beta_b = out.beta
                    zo_b = out.z
                    theta_b = out.theta
                    gapo_b = jnp.asarray(out.gap, X.dtype)
                    if newton:
                        with jax.named_scope("cm"):
                            beta_b, zo_b, theta_b, gapo_b = jax.lax.cond(
                                ~is_add_b,
                                lambda a: _newton_one(carry2, aset_b.mask,
                                                      Xa_b, y_b, w_b,
                                                      lam_b, a),
                                lambda a: a,
                                (beta_b, zo_b, theta_b, gapo_b))
                    with jax.named_scope("gap"):
                        cert = _certify(y_b, w_b, theta_b, gapo_b, lam_b,
                                        eps_b, delta_b, is_add_b, Xa_b,
                                        aset_b.idx, aset_b.mask, cn_b,
                                        c0_b)
                    return (beta_b, zo_b, gapo_b, carry2) + cert

                def frozen_branch(_):
                    k = aset_b.beta.shape[0]
                    return (aset_b.beta, z_b, gap_b, carry_b,
                            jnp.zeros_like(z_b),
                            jnp.zeros((), X.dtype),
                            jnp.asarray(True),
                            jnp.zeros((k,), bool),
                            jnp.zeros((), X.dtype))

                return jax.lax.cond(live_b, live_branch, frozen_branch,
                                    None)

            xs = (live, Y, lam, eps, n_ep, s.delta, s.is_add, s.z, s.gap,
                  s.inner, aset, col_norm, c0)
            if has_weights:
                xs = (live, Y, weights) + xs[2:]
            (beta, z, gap, inner_carry, theta_c, r_eff, stop_now, del_row,
             r_del) = jax.lax.map(solve_one, xs)
        else:
            # --- fleet-step path (the pallas problem-gridded kernel): the
            # backend owns the whole fleet's bursts in one launch on the
            # carried blocks, then the per-problem certificate map runs
            # on the same blocks (liveness-gated, like the serial body).
            Xa = _read_block(s.block, aset)
            with jax.named_scope("cm"):
                out, inner_carry = inner.fleet_step(s.inner, aset, Xa, lam,
                                                    n_ep)
            beta = jnp.where(live[:, None], out.beta, aset.beta)
            z = jnp.where(live[:, None], out.z, s.z)
            gap = jnp.where(live, jnp.asarray(out.gap, X.dtype), s.gap)
            theta = out.theta

            def certify_one(args):
                if has_weights:
                    (live_b, y_b, w_b, theta_b, gap_b, lam_b, eps_b,
                     delta_b, is_add_b, aset_b, Xa_b, cn_b, c0_b) = args
                else:
                    (live_b, y_b, theta_b, gap_b, lam_b, eps_b, delta_b,
                     is_add_b, aset_b, Xa_b, cn_b, c0_b) = args
                    w_b = None

                def live_branch(_):
                    return _certify(y_b, w_b, theta_b, gap_b, lam_b,
                                    eps_b, delta_b, is_add_b, Xa_b,
                                    aset_b.idx, aset_b.mask, cn_b, c0_b)

                def frozen_branch(_):
                    k = aset_b.mask.shape[0]
                    return (jnp.zeros_like(theta_b),
                            jnp.zeros((), X.dtype), jnp.asarray(True),
                            jnp.zeros((k,), bool), jnp.zeros((), X.dtype))

                return jax.lax.cond(live_b, live_branch, frozen_branch,
                                    None)

            xs = (live, Y, theta, gap, lam, eps, s.delta, s.is_add,
                  aset, Xa, col_norm, c0)
            if has_weights:
                xs = (live, Y, weights) + xs[2:]
            with jax.named_scope("gap"):
                (theta_c, r_eff, stop_now, del_row,
                 r_del) = jax.lax.map(certify_one, xs)

        aset = aset._replace(beta=beta)

        # --- DEL (per-problem gap-safe rule) ------------------------------
        deleting = live & ~stop_now
        del_mask = del_row & deleting[:, None]
        with jax.named_scope("add_delete"):
            aset = aset_lib.delete_features_batch(aset, del_mask)

        # --- ADD phase (skipped fleet-wide once every problem is done) ----
        if screen_rule.add_bound == "point":
            # point screens run on EVERY non-stopping step (see the serial
            # engine: a straggler recruited mid-convergence saves a full
            # re-convergence after the post-check)
            do_add = live & ~stop_now
        else:
            do_add = live & s.is_add & ~stop_now

        def do_add_phase(args):
            aset, block, delta, is_add = args
            with jax.named_scope("screen"):
                out: ScreenOut = screen(theta_c, r_eff, aset.in_active,
                                        do_add)
            add_done = out.max_ub < 1.0                       # (B,)
            n_sur_scr = _n_surv32_batch(out, b)
            n_scr_scr = (jnp.sum(~aset.in_active, axis=1).astype(jnp.int32)
                         - n_sur_scr)
            ranks = jnp.arange(h)
            v_count = jnp.maximum(out.cand_ge - 1 - ranks[None, :], 0)
            keep = ((v_count < h_tilde[:, None]) &
                    (ranks[None, :] < h_cap[:, None]) &
                    jnp.isfinite(out.cand_score))
            if screen_rule.add_bound == "point":
                # strong-rule recruiting: only actual KKT violators
                keep = keep & (out.cand_score >= 1.0)
            keep = jnp.cumprod(keep.astype(jnp.int32), axis=1).astype(bool)
            # progress guarantee, per problem (the serial engine's rule,
            # DESIGN.md §2): every candidate the ball cannot rule out, and
            # the top-scoring one
            keep = keep | _stuck_recruits(out, col_norm, r_eff, gap, eps)
            adding = do_add & ~add_done
            with jax.named_scope("add_delete"):
                aset, slot = aset_lib.add_features_to_slots_batch(
                    aset, out.cand_idx, keep & adding[:, None])
            if carried:
                block = place(block, out.cand_idx, slot)
            done = do_add & add_done
            if screen_rule.delta_ramp:
                grown = jnp.minimum(10.0 * delta, 1.0)
                new_delta = jnp.where(done & (delta < 1.0), grown, delta)
                new_is_add = jnp.where(done & (delta >= 1.0), False,
                                       is_add)
            else:
                new_delta = delta
                new_is_add = jnp.where(done, False, is_add)
            return (aset, block, new_delta, new_is_add,
                    jnp.where(do_add, n_scr_scr, -1),
                    jnp.where(do_add, n_sur_scr, -1))

        neg1 = jnp.full((b,), -1, jnp.int32)
        aset, block, delta, is_add, n_scr, n_sur = jax.lax.cond(
            jnp.any(do_add), do_add_phase,
            lambda a: a + (neg1, neg1),
            (aset, s.block, s.delta, s.is_add))

        # --- safe post-check (hybrid rule, DESIGN.md §13) -----------------
        # one full screen at the unshrunk safe radius gates every stop;
        # violators deny the stop and are recruited (the safe fallback) —
        # the serial engine's check, batched per problem
        if screen_rule.post_check:
            do_check = live & stop_now

            def check(args):
                a, block = args
                with jax.named_scope("screen"):
                    chk: ScreenOut = screen(theta_c, r_del, a.in_active,
                                            do_check)
                viol = do_check & (chk.max_ub >= 1.0)         # (B,)
                ub_c = (chk.cand_score +
                        jnp.take_along_axis(col_norm, chk.cand_idx, axis=1)
                        * r_del[:, None])
                keep = (viol[:, None] & jnp.isfinite(chk.cand_score) &
                        (ub_c >= 1.0))
                keep = keep.at[:, 0].set(
                    viol & jnp.isfinite(chk.cand_score[:, 0]))
                with jax.named_scope("add_delete"):
                    a, slot = aset_lib.add_features_to_slots_batch(
                        a, chk.cand_idx, keep)
                if carried:
                    block = place(block, chk.cand_idx, slot)
                return (a, block,
                        jnp.where(do_check, viol.astype(jnp.int32), -1))

            def no_check(args):
                return args + (neg1,)

            aset, block, post_viol = jax.lax.cond(
                jnp.any(do_check), check, no_check, (aset, block))
            stop_final = stop_now & (post_viol != 1)
        else:
            post_viol = neg1
            stop_final = stop_now

        n_act = aset.count.astype(X.dtype)
        new = _BatchState(
            aset=aset, z=z, gap=gap, delta=delta, is_add=is_add,
            stop=stop_final, t=s.t + 1, inner=inner_carry, block=block,
            trace_n_active=s.trace_n_active.at[barange, s.t].set(
                n_act, mode="drop"),
            trace_gap=s.trace_gap.at[barange, s.t].set(gap, mode="drop"),
            trace_screened=s.trace_screened.at[barange, s.t].set(
                n_scr, mode="drop"),
            trace_survivors=s.trace_survivors.at[barange, s.t].set(
                n_sur, mode="drop"),
            trace_post_viol=s.trace_post_viol.at[barange, s.t].set(
                post_viol, mode="drop"))
        return _freeze_select(live, s, new)

    final = jax.lax.while_loop(cond, body, state0)
    beta_full = aset_lib.scatter_beta_batch(final.aset, p)
    return SaifResult(beta=beta_full, gap=final.gap, n_outer=final.t,
                      n_active=final.aset.count,
                      overflowed=final.aset.overflowed,
                      trace_n_active=final.trace_n_active,
                      trace_gap=final.trace_gap,
                      active_idx=final.aset.idx,
                      active_mask=final.aset.mask,
                      inner=final.inner,
                      trace_screened=final.trace_screened,
                      trace_survivors=final.trace_survivors,
                      trace_post_viol=final.trace_post_viol)


# ---------------------------------------------------------------------------
# fast-parity fleet engine (parity="fast", DESIGN.md §11)
# ---------------------------------------------------------------------------
# The bitwise engine above buys byte-for-byte serial equality by running
# every per-problem float path as a lax.map of the literal serial code —
# which is a scan, so the fleet's per-problem work is SEQUENTIAL and the
# speedup ceiling is the amortized fixed costs (~2.6x measured). The fast
# engine is the opt-in other half of the trade: batch-axis einsums for
# bursts/certificates, a lockstep CM sweep over a STATIC slot order
# (dynamic_slice on batch-leading arrays — no per-problem gathers in the
# inner loop, the measured ~30x XLA:CPU gather trap that killed the PR 4
# lockstep attempt), and the one-gemm-per-step screen, optionally in
# reduced precision with a certified rounding-error widening of the safe
# radius (screen_backend.make_batch_screen_fast). What it may re-associate
# and what it may never skip is the §11 parity contract; acceptance is
# supports + gap <= eps + a passing working-precision KKT residual, not
# bitwise trajectories. Least-squares fleets only — other losses fall
# back to the bitwise engine (fleet_solve dispatch).


def _delete_features_fast(aset, drop):
    """Batched DEL without ``order`` maintenance.

    The fast engine's sweep visits a static slot range (``hi`` in
    :func:`_gram_sweep_fast`) instead of the serial engine's compacted
    ``order[:count]``, so the order permutation is dead weight here —
    skipping its cumsum/scatter upkeep trims the while_loop body, which
    on XLA:CPU is billed per op. Slot placement is unaffected:
    :func:`repro.core.active_set.add_features` ranks free slots by slot
    id, never through ``order``."""
    p = aset.in_active.shape[1]
    drop = drop & aset.mask
    new_mask = aset.mask & ~drop
    new_beta = jnp.where(drop, 0.0, aset.beta)
    write_idx = jnp.where(drop, aset.idx, p)
    bar = jnp.arange(aset.idx.shape[0])[:, None]
    new_in_active = aset.in_active.at[bar, write_idx].set(
        False, mode="drop")
    return aset._replace(mask=new_mask, beta=new_beta,
                         in_active=new_in_active,
                         count=aset.count -
                         jnp.sum(drop, axis=1).astype(jnp.int32))


def _add_features_fast(aset, cand_idx, cand_keep):
    """Batched ADD without ``order`` maintenance (see
    :func:`_delete_features_fast`). Same slot arithmetic as the serial
    :func:`repro.core.active_set.add_features` — kept candidates fill
    the lowest free slots — minus the compact_order call."""
    b, k_max = aset.mask.shape
    p = aset.in_active.shape[1]
    free = ~aset.mask
    free_i = free.astype(jnp.int32)
    free_rank = jnp.cumsum(free_i, axis=1) - free_i
    n_free = jnp.sum(free_i, axis=1)
    keep_i = cand_keep.astype(jnp.int32)
    cand_rank = jnp.cumsum(keep_i, axis=1) - keep_i
    n_want = jnp.sum(keep_i, axis=1)
    placed = cand_keep & (cand_rank < n_free[:, None])
    big = jnp.asarray(k_max + 1, jnp.int32)
    order_key = jnp.where(free, free_rank, big)
    slot_of_rank = jnp.argsort(order_key, axis=1)
    target_slot = jnp.take_along_axis(
        slot_of_rank, jnp.clip(cand_rank, 0, k_max - 1), axis=1)
    target_slot = jnp.where(placed, target_slot, k_max)
    bar = jnp.arange(b)[:, None]
    new_idx = aset.idx.at[bar, target_slot].set(cand_idx, mode="drop")
    new_mask = aset.mask.at[bar, target_slot].set(True, mode="drop")
    new_beta = aset.beta.at[bar, target_slot].set(0.0, mode="drop")
    new_in_active = aset.in_active.at[
        bar, jnp.where(placed, cand_idx, p)].set(True, mode="drop")
    return aset._replace(idx=new_idx, mask=new_mask, beta=new_beta,
                         in_active=new_in_active,
                         overflowed=aset.overflowed | (n_want > n_free),
                         count=aset.count +
                         jnp.sum(placed, axis=1).astype(jnp.int32))


def _gram_rebuild_fast(X, Y, weights, aset):
    """Full batched Gram build at fleet start: G = Xa^T diag(w) Xa,
    rho = Xa^T diag(w) y, per problem via batch-axis einsums."""
    Xa = aset_lib.gather_columns_batch(X, aset)          # (B, n, k)
    Xw = Xa if weights is None else Xa * weights[:, :, None]
    G = jnp.einsum("bnk,bnl->bkl", Xw, Xa)
    rho = jnp.einsum("bnk,bn->bk", Xw, Y)
    gidx = jnp.where(aset.mask, aset.idx, -1)
    return InnerCarry(G=G, rho=rho, gidx=gidx), Xa


def _gram_refresh_fast(X, Y, weights, carry, aset, Xa, h):
    """Per-step batched Gram reconcile: at most ``h`` slots per problem
    changed feature since the last step (the ADD batch); their rows /
    columns / rho entries are recomputed from ``h`` gathered columns.
    Branchless (a problem with nothing dirty scatters into the dropped
    fill slot); dead slots keep stale entries — their beta is masked to
    zero so the sweep never reads them through a live term."""
    kc = aset.idx.shape[1]
    hs = min(h, kc)

    # Xa is already gathered this step — each problem's block rides along
    def one_with_xa(G, rho, gidx, idx_b, mask_b, y_b, Xa_b, w_b):
        gidx = jnp.where(mask_b, gidx, -1)
        dirty = mask_b & (gidx != idx_b)
        slots = jnp.nonzero(dirty, size=hs, fill_value=kc)[0]
        ids = jnp.take(idx_b, jnp.minimum(slots, kc - 1))
        cols = jnp.take(X, ids, axis=1)                  # (n, hs)
        cols_w = cols if w_b is None else cols * w_b[:, None]
        Gblk = Xa_b.T @ cols_w                           # (k, hs)
        G = G.at[:, slots].set(Gblk, mode="drop")
        G = G.at[slots, :].set(Gblk.T, mode="drop")
        rho = rho.at[slots].set(cols_w.T @ y_b, mode="drop")
        return G, rho, jnp.where(mask_b, idx_b, -1)

    if weights is None:
        G, rho, gidx = jax.vmap(
            lambda G, rho, gidx, idx_b, mask_b, y_b, Xa_b:
            one_with_xa(G, rho, gidx, idx_b, mask_b, y_b, Xa_b, None))(
            carry.G, carry.rho, carry.gidx, aset.idx, aset.mask, Y, Xa)
    else:
        G, rho, gidx = jax.vmap(one_with_xa)(
            carry.G, carry.rho, carry.gidx, aset.idx, aset.mask, Y, Xa,
            weights)
    return InnerCarry(G=G, rho=rho, gidx=gidx)


def _gram_sweep_fast(G, rho, beta, mask, lam, n_ep, smoothness=1.0):
    """Lockstep batched CM sweep (least squares, Gram form).

    Every problem steps the SAME static slot j each inner iteration, so
    the per-iteration work is dynamic_slice / dynamic_update_slice on
    batch-leading (B, k) arrays — no batched-index gathers. Dead slots
    are masked to a zero coefficient; problems whose per-problem epoch
    budget ``n_ep[b]`` is exhausted (or that are frozen, budget 0) are
    gated to a no-op so their (beta, qr) carry is exactly preserved.
    Sweeping all k slots instead of the serial engine's compacted
    ``order[:count]`` visits dead slots too — a fast-parity re-ordering
    the §11 contract explicitly allows (a dead slot's step is the
    identity; extra passes only tighten the sub-problem solve).
    """
    k = beta.shape[1]
    diag = jnp.diagonal(G, axis1=1, axis2=2)
    inv_l = 1.0 / jnp.maximum(smoothness * diag, 1e-30)
    thr = lam[:, None] * inv_l
    qr = jnp.einsum("bkl,bl->bk", G, beta) - rho
    max_ep = jnp.max(n_ep)
    # the sweep visits slots [0, hi): everything above the fleet's highest
    # live slot is dead everywhere (adds fill the lowest free slots), so
    # the loop trip count tracks the actual active-set size, not k_max
    hi = jnp.max(jnp.where(mask, jnp.arange(k)[None, :] + 1, 0))

    def slot_step(j, carry, gate):
        beta, qr = carry
        col = lambda a: jax.lax.dynamic_slice_in_dim(a, j, 1, axis=1)[:, 0]
        bj, qrj, ilj, tj, mj = col(beta), col(qr), col(inv_l), col(thr), \
            col(mask)
        val = jnp.where(mj, soft_threshold(bj - qrj * ilj, tj), 0.0)
        b_new = jnp.where(gate, val, bj)
        Gj = jax.lax.dynamic_slice_in_dim(G, j, 1, axis=2)[:, :, 0]
        qr = qr + (b_new - bj)[:, None] * Gj
        beta = jax.lax.dynamic_update_slice_in_dim(
            beta, b_new[:, None], j, axis=1)
        return beta, qr

    # one flat loop (i -> epoch i//hi, slot i%hi) instead of nested
    # fori_loops: the scalar divmod is cheaper than per-epoch loop setup
    def flat_step(i, carry):
        return slot_step(i % hi, carry, (i // hi) < n_ep)

    beta, _ = jax.lax.fori_loop(0, max_ep * hi, flat_step, (beta, qr))
    return beta


@partial(jax.jit, static_argnames=("loss_name", "h", "k_max",
                                   "inner_epochs", "polish_factor",
                                   "max_outer", "use_seq_ball",
                                   "screen_dtype", "has_weights",
                                   "screen_rule"))
def _saif_batch_fast_jit(X, Y, W, col_norm, c0, lam, eps, delta0, init_idx,
                         init_beta, init_mask, h_tilde, h_cap,
                         pad_mask=None, *,
                         loss_name: str, h: int, k_max: int,
                         inner_epochs: int, polish_factor: int,
                         max_outer: int, use_seq_ball: bool,
                         screen_dtype: str = "working",
                         has_weights: bool = False,
                         screen_rule: ScreenRule = SCREEN_RULES["saif"]
                         ) -> SaifResult:
    """The fast-parity fleet while_loop (see the section comment above).

    Same decision structure as ``_saif_batch_jit`` — the same per-problem
    liveness masks, DEL / ADD-stop / delta-ramp / stuck-recruit rules,
    traces and overflow flags — but every stage is genuinely batched, and
    both screening radii (the one-gemm ADD screen and the vmapped DEL
    certificate) are widened by the certified rounding bound of their
    respective compute precisions before any decision is taken.
    """
    loss = get_loss(loss_name)
    n, p = X.shape
    b = Y.shape[0]
    barange = jnp.arange(b)
    lam = jnp.asarray(lam, X.dtype)
    weights = W if has_weights else None
    screen = make_batch_screen_fast(X, col_norm, h,
                                    screen_dtype=screen_dtype)
    # working-precision batched contractions re-associate: the DEL rule's
    # correlations carry the working-dtype gamma widening (tiny — ~3e-6
    # relative at n=50/f32 — but what makes the re-association *certified*
    # rather than hoped-harmless)
    gamma_work = mixed_precision_gamma(n, X.dtype, X.dtype)

    aset0 = aset_lib.init_active_set_batch(p, k_max, init_idx, X.dtype,
                                           init_beta, live_mask=init_mask)
    if pad_mask is not None:
        aset0 = aset0._replace(in_active=aset0.in_active | pad_mask[None, :])
    carry0, _ = _gram_rebuild_fast(X, Y, weights, aset0)
    trace0 = jnp.full((b, max_outer), -1.0, X.dtype)
    itrace0 = jnp.full((b, max_outer), -1, jnp.int32)
    state0 = _BatchState(
        aset=aset0, z=jnp.zeros_like(Y),
        gap=jnp.full((b,), jnp.inf, X.dtype),
        delta=jnp.asarray(delta0, X.dtype),
        is_add=jnp.ones((b,), bool), stop=jnp.zeros((b,), bool),
        t=jnp.zeros((b,), jnp.int32), inner=carry0,
        trace_n_active=trace0, trace_gap=trace0,
        trace_screened=itrace0, trace_survivors=itrace0,
        trace_post_viol=itrace0)

    def cond(s: _BatchState):
        return jnp.any(~s.stop & (s.t < max_outer))

    def _certify_one(y_b, w_b, theta_b, gap_b, lam_b, eps_b, delta_b,
                     is_add_b, Xa_b, idx_b, mask_b, cn_b, c0_b):
        """Serial certificate arithmetic, vmapped (re-associated) — with
        the DEL radius widened by the working-precision dot bound."""
        ball = gap_ball(loss, theta_b, gap_b, lam_b,
                        floor=gap_precision_floor(theta_b, lam_b))
        if use_seq_ball:
            c0_active = jnp.where(mask_b, jnp.take(c0_b, idx_b), -jnp.inf)
            lam0t = jnp.maximum(jnp.max(c0_active), lam_b * (1 + 1e-12))
            g0_b = loss.grad(jnp.zeros_like(y_b), y_b)
            theta0t = -g0_b / lam0t
            b_seq = sequential_ball(loss, y_b, theta0t, lam0t, lam_b)
            ball = intersect_balls(b_seq, ball)
        stop_now_b = (~is_add_b) & (gap_b <= eps_b)
        corr_act = jnp.abs(Xa_b.T @ ball.center)
        norm_act = jnp.where(mask_b, jnp.take(cn_b, idx_b), 0.0)
        r_del = widened_radius(ball.radius, ball.center, gamma_work)
        del_row = mask_b & (corr_act + norm_act * r_del < 1.0)
        if screen_rule.add_bound == "point":
            # strong-rule ADD at radius 0: the mixed-precision screen
            # widens whatever radius it is handed by its own certified
            # rounding bound, so the "point" screen under a reduced dtype
            # is really a gamma*||theta||-ball — still aggressive, still
            # covered by the post-check below
            r_eff_b = jnp.zeros_like(ball.radius)
        else:
            r_eff_b = delta_b * ball.radius
        # the raw safe radius rides along for the post-check screen, which
        # re-applies the dtype-appropriate widening internally
        return (ball.center, r_eff_b, stop_now_b, del_row, ball.radius)

    if has_weights:
        certify = jax.vmap(_certify_one)
        dual_gap = jax.vmap(
            lambda Xa_b, y_b, beta_b, z_b, mask_b, lam_b, w_b:
            _dual_and_gap(loss, Xa_b, y_b, beta_b, z_b, mask_b, lam_b,
                          sample_w=w_b))
    else:
        certify = jax.vmap(
            lambda *a: _certify_one(a[0], None, *a[1:]))
        dual_gap = jax.vmap(
            lambda Xa_b, y_b, beta_b, z_b, mask_b, lam_b:
            _dual_and_gap(loss, Xa_b, y_b, beta_b, z_b, mask_b, lam_b))

    def body(s: _BatchState) -> _BatchState:
        live = ~s.stop & (s.t < max_outer)
        aset = s.aset
        n_ep = jnp.where(s.is_add, inner_epochs,
                         inner_epochs * polish_factor)
        n_ep = jnp.where(live, n_ep, 0).astype(jnp.int32)

        # --- lockstep inner burst (Gram form; LS-only by dispatch) -------
        with jax.named_scope("cm"):
            Xa = aset_lib.gather_columns_batch(X, aset)  # (B, n, k)
        # polish bodies (post-ADD) mutate nothing but masks, so the
        # h-column Gram reconcile is skipped fleet-wide when no slot is
        # dirty; dead slots still drop their feature id (gidx=-1) so a
        # later re-add of the same feature forces a refresh — its Gram
        # row was zeroed by neighbours' refreshes while the slot was dead
        gidx2 = jnp.where(aset.mask, s.inner.gidx, -1)
        any_dirty = jnp.any(aset.mask & (gidx2 != aset.idx))
        with jax.named_scope("cm"):
            carry2 = jax.lax.cond(
                any_dirty,
                lambda c: _gram_refresh_fast(X, Y, weights, c, aset, Xa, h),
                lambda c: c._replace(gidx=gidx2),
                s.inner)
            beta = _gram_sweep_fast(carry2.G, carry2.rho, aset.beta,
                                    aset.mask, lam, n_ep,
                                    smoothness=loss.smoothness)
            z = jnp.einsum("bnk,bk->bn", Xa, beta)
        with jax.named_scope("gap"):
            if has_weights:
                theta, gap = dual_gap(Xa, Y, beta, z, aset.mask, lam,
                                      weights)
            else:
                theta, gap = dual_gap(Xa, Y, beta, z, aset.mask, lam)
        gap = jnp.asarray(gap, X.dtype)

        # --- fleet Newton polish (hybrid rule, DESIGN.md §13) -------------
        # The lockstep engine already holds the batched working-set normal
        # equations, so the serial engine's Newton step batches as ONE
        # (B, k, k) masked solve. Acceptance stays per problem and is
        # certified by the same (vmapped) official dual/gap the §11
        # contract already trusts — a rejected proposal leaves that
        # problem's CM iterate untouched.
        if screen_rule.newton_polish:
            polishing = live & ~s.is_add

            def newton_fleet(args):
                beta_c, z_c, theta_cc, gap_c = args
                m = aset.mask & (beta_c != 0.0)
                mf = m.astype(X.dtype)
                k = beta_c.shape[1]
                Gm = (carry2.G * (mf[:, :, None] * mf[:, None, :]) +
                      jnp.eye(k, dtype=X.dtype) * (1.0 - mf)[:, :, None])
                rhs = (carry2.rho - lam[:, None] * jnp.sign(beta_c)) * mf
                b_n = jnp.where(
                    m, jnp.linalg.solve(Gm, rhs[..., None])[..., 0], 0.0)
                z_n = jnp.einsum("bnk,bk->bn", Xa, b_n)
                if has_weights:
                    th_n, gap_n = dual_gap(Xa, Y, b_n, z_n, m, lam,
                                           weights)
                else:
                    th_n, gap_n = dual_gap(Xa, Y, b_n, z_n, m, lam)
                gap_n = jnp.asarray(gap_n, X.dtype)
                better = polishing & (gap_n < gap_c)
                return (jnp.where(better[:, None], b_n, beta_c),
                        jnp.where(better[:, None], z_n, z_c),
                        jnp.where(better[:, None], th_n, theta_cc),
                        jnp.where(better, gap_n, gap_c))

            with jax.named_scope("cm"):
                beta, z, theta, gap = jax.lax.cond(
                    jnp.any(polishing), newton_fleet, lambda a: a,
                    (beta, z, theta, gap))

        with jax.named_scope("gap"):
            if has_weights:
                (theta_c, r_eff, stop_now, del_row,
                 r_del_raw) = certify(
                    Y, weights, theta, gap, lam, eps, s.delta, s.is_add,
                    Xa, aset.idx, aset.mask, col_norm, c0)
            else:
                (theta_c, r_eff, stop_now, del_row,
                 r_del_raw) = certify(
                    Y, theta, gap, lam, eps, s.delta, s.is_add, Xa,
                    aset.idx, aset.mask, col_norm, c0)

        aset = aset._replace(beta=beta)

        # --- DEL (per-problem widened gap-safe rule) ----------------------
        deleting = live & ~stop_now
        del_mask = del_row & deleting[:, None]
        with jax.named_scope("add_delete"):
            aset = _delete_features_fast(aset, del_mask)

        # --- ADD phase (skipped fleet-wide once every problem is done) ----
        if screen_rule.add_bound == "point":
            do_add = live & ~stop_now
        else:
            do_add = live & s.is_add & ~stop_now

        def do_add_phase(args):
            aset, delta, is_add = args
            with jax.named_scope("screen"):
                out: ScreenOut = screen(theta_c, r_eff, aset.in_active,
                                        do_add)
            add_done = out.max_ub < 1.0                  # (B,)
            n_sur_scr = _n_surv32_batch(out, b)
            n_scr_scr = (jnp.sum(~aset.in_active, axis=1).astype(jnp.int32)
                         - n_sur_scr)
            ranks = jnp.arange(h)
            v_count = jnp.maximum(out.cand_ge - 1 - ranks[None, :], 0)
            keep = ((v_count < h_tilde[:, None]) &
                    (ranks[None, :] < h_cap[:, None]) &
                    jnp.isfinite(out.cand_score))
            if screen_rule.add_bound == "point":
                keep = keep & (out.cand_score >= 1.0)
            keep = jnp.cumprod(keep.astype(jnp.int32), axis=1).astype(bool)
            keep = keep | _stuck_recruits(out, col_norm, r_eff, gap, eps)
            adding = do_add & ~add_done
            with jax.named_scope("add_delete"):
                aset = _add_features_fast(aset, out.cand_idx,
                                          keep & adding[:, None])
            done = do_add & add_done
            if screen_rule.delta_ramp:
                grown = jnp.minimum(10.0 * delta, 1.0)
                new_delta = jnp.where(done & (delta < 1.0), grown, delta)
                new_is_add = jnp.where(done & (delta >= 1.0), False,
                                       is_add)
            else:
                new_delta = delta
                new_is_add = jnp.where(done, False, is_add)
            return (aset, new_delta, new_is_add,
                    jnp.where(do_add, n_scr_scr, -1),
                    jnp.where(do_add, n_sur_scr, -1))

        neg1 = jnp.full((b,), -1, jnp.int32)
        aset, delta, is_add, n_scr, n_sur = jax.lax.cond(
            jnp.any(do_add), do_add_phase,
            lambda a: a + (neg1, neg1),
            (aset, s.delta, s.is_add))

        # --- safe post-check (hybrid rule) --------------------------------
        # the mixed-precision screen re-widens the raw safe radius for its
        # own dtype, so a passing check certifies the exact screen passes
        if screen_rule.post_check:
            do_check = live & stop_now

            def check(a):
                with jax.named_scope("screen"):
                    chk: ScreenOut = screen(theta_c, r_del_raw,
                                            a.in_active, do_check)
                viol = do_check & (chk.max_ub >= 1.0)
                ub_c = (chk.cand_score +
                        jnp.take_along_axis(col_norm, chk.cand_idx, axis=1)
                        * r_del_raw[:, None])
                keep = (viol[:, None] & jnp.isfinite(chk.cand_score) &
                        (ub_c >= 1.0))
                keep = keep.at[:, 0].set(
                    viol & jnp.isfinite(chk.cand_score[:, 0]))
                with jax.named_scope("add_delete"):
                    a = _add_features_fast(a, chk.cand_idx, keep)
                return a, jnp.where(do_check, viol.astype(jnp.int32), -1)

            def no_check(a):
                return a, neg1

            aset, post_viol = jax.lax.cond(jnp.any(do_check), check,
                                           no_check, aset)
            stop_final = stop_now & (post_viol != 1)
        else:
            post_viol = neg1
            stop_final = stop_now

        n_act = aset.count.astype(X.dtype)
        new = _BatchState(
            aset=aset, z=z, gap=gap, delta=delta, is_add=is_add,
            stop=stop_final, t=s.t + 1, inner=carry2,
            trace_n_active=s.trace_n_active.at[barange, s.t].set(
                n_act, mode="drop"),
            trace_gap=s.trace_gap.at[barange, s.t].set(gap, mode="drop"),
            trace_screened=s.trace_screened.at[barange, s.t].set(
                n_scr, mode="drop"),
            trace_survivors=s.trace_survivors.at[barange, s.t].set(
                n_sur, mode="drop"),
            trace_post_viol=s.trace_post_viol.at[barange, s.t].set(
                post_viol, mode="drop"))
        return _freeze_select(live, s, new)

    final = jax.lax.while_loop(cond, body, state0)
    beta_full = aset_lib.scatter_beta_batch(final.aset, p)
    return SaifResult(beta=beta_full, gap=final.gap, n_outer=final.t,
                      n_active=final.aset.count,
                      overflowed=final.aset.overflowed,
                      trace_n_active=final.trace_n_active,
                      trace_gap=final.trace_gap,
                      active_idx=final.aset.idx,
                      active_mask=final.aset.mask,
                      inner=final.inner,
                      trace_screened=final.trace_screened,
                      trace_survivors=final.trace_survivors,
                      trace_post_viol=final.trace_post_viol)


def saif_batch_compile_count() -> int:
    """Distinct fleet-engine compilations alive in this process (the
    bitwise ``_saif_batch_jit`` cache plus the fast-parity
    ``_saif_batch_fast_jit`` cache)."""
    return (int(_saif_batch_jit._cache_size()) +
            int(_saif_batch_fast_jit._cache_size()))


class FleetPrep(NamedTuple):
    """One-time per-fleet preprocessing (one host sync for the h formula).
    ``c0_max`` doubles as the per-problem lambda_max: for the penalized-
    null model, lambda_max = max_i |x_i^T f'(null)| = max(c0) exactly."""
    X: jax.Array            # (n, p) shared design
    Y: jax.Array            # (B, n)
    W: Optional[jax.Array]  # (B, n) sample weights or None
    c0: jax.Array           # (B, p) per-problem |X^T f'(null)|
    col_norm: jax.Array     # (B, p) per-problem column norms
    c0_max: list            # B host floats (= per-problem lambda_max)
    c0_median: list
    # bucket-padded fleets (DESIGN.md §12): X/Y carry trailing zero
    # rows/columns up to a compile-bucket shape while every policy
    # quantity is computed on the real dims. 0 means "use X.shape".
    n_true: int = 0
    p_true: int = 0


@partial(jax.jit, static_argnames=("loss_name", "has_w"))
def _prepare_fleet_fast_jit(X, Y, W, *, loss_name: str, has_w: bool):
    """Device side of fast-parity fleet prep, fused under ONE dispatch:
    c0 as one gemm (the §11 re-association contract), col norms and the
    c0 statistics the host h formula syncs."""
    loss = get_loss(loss_name)
    G0 = loss.grad(jnp.zeros_like(Y), Y)
    if has_w:
        G0 = W * G0
    c0 = jnp.abs(G0 @ X)
    if has_w:
        col_norm = jnp.sqrt(W @ (X * X))
    else:
        col_norm = jnp.broadcast_to(jnp.linalg.norm(X, axis=0), c0.shape)
    # the median only buckets the pow2 h formula (heuristic-grade): its
    # f64 sort is the most expensive op in prep under x64, so fast parity
    # computes it on f32-cast scores. c0 itself, its max (lambda_max /
    # delta0 / seq-ball inputs) and col_norm stay working precision —
    # those feed certificates.
    med = jnp.median(c0.astype(jnp.float32), axis=1).astype(X.dtype)
    return c0, col_norm, jnp.max(c0, axis=1), med


def prepare_fleet(X, Y, config: SaifConfig, weights=None) -> FleetPrep:
    """Per-problem null gradients, c0, column norms + ONE host sync of the
    c0 statistics the (host-side) h formula needs."""
    loss = get_loss(config.loss)
    X = jnp.asarray(X)
    Y = jnp.asarray(Y)
    if Y.ndim == 1:
        Y = Y[None, :]
    W = None if weights is None else jnp.asarray(weights, X.dtype)
    if config.parity == "fast":
        # fast parity re-associates by contract (DESIGN.md §11): the whole
        # fleet's c0 scans are ONE gemm inside one jitted dispatch. c0
        # feeds the pow2-bucketed h formula, the cold-start top-h and the
        # seq-ball lam0t — all ulp-insensitive consumers (a re-associated
        # c0 only matters on an exact score tie or a bucket boundary).
        W_arg = W if W is not None else jnp.zeros((1, 1), X.dtype)
        c0, col_norm, c0_max, c0_med = _prepare_fleet_fast_jit(
            X, Y, W_arg, loss_name=config.loss, has_w=W is not None)
        with span("repro.sync.fleet_stats"):
            c0_max, c0_med = jax.device_get((c0_max, c0_med))
        return FleetPrep(X=X, Y=Y, W=W, c0=c0, col_norm=col_norm,
                         c0_max=[float(v) for v in c0_max],
                         c0_median=[float(v) for v in c0_med])
    G0 = loss.grad(jnp.zeros_like(Y), Y)
    if W is not None:
        G0 = W * G0
    # per-problem c0 scans as B EAGER serial matvecs — the literal op
    # the serial driver's null_gradient dispatches, so lambda_max,
    # delta0, the cold-start top-h and the seq-ball lam0t are bitwise
    # per problem (a (B, n) x (n, p) matmul — or even a lax.map'd
    # matvec, which compiles under scan instead of dispatching the
    # eager dot executable — re-associates the reduction at the ulp
    # level; same rule as the §8 screen paths). One-time prep cost,
    # off the hot path.
    c0 = jnp.stack([jnp.abs(X.T @ G0[i]) for i in range(Y.shape[0])])
    if W is None:
        col_norm = jnp.broadcast_to(jnp.linalg.norm(X, axis=0),
                                    c0.shape)
    else:
        col_norm = jnp.sqrt(W @ (X * X))                   # (B, p)
    stats = (jnp.max(c0, axis=1), jnp.median(c0, axis=1))
    with span("repro.sync.fleet_stats"):
        c0_max, c0_med = jax.device_get(stats)
    return FleetPrep(X=X, Y=Y, W=W, c0=c0, col_norm=col_norm,
                     c0_max=[float(v) for v in c0_max],
                     c0_median=[float(v) for v in c0_med])


def pad_fleet_prep(prep: FleetPrep, n_bucket: int,
                   p_bucket: int) -> FleetPrep:
    """Zero-pad a real fleet preparation up to a compile-bucket shape —
    the fleet edition of :func:`repro.core.saif.pad_path_state`
    (DESIGN.md §12): the per-problem stats stay those of the real
    problems (c0 pads at -inf, col-norm pads at 1.0, zero pad rows with
    zero weights), and ``n_true``/``p_true`` feed every policy formula.
    """
    n, p = prep.X.shape
    if n_bucket < n or p_bucket < p:
        raise ValueError(
            f"bucket ({n_bucket}, {p_bucket}) must dominate the fleet "
            f"design shape ({n}, {p})")
    if (n_bucket, p_bucket) == (n, p):
        return prep
    dn, dp = n_bucket - n, p_bucket - p
    return prep._replace(
        X=jnp.pad(prep.X, ((0, dn), (0, dp))),
        Y=jnp.pad(prep.Y, ((0, 0), (0, dn))),
        W=None if prep.W is None else jnp.pad(prep.W, ((0, 0), (0, dn))),
        c0=jnp.pad(prep.c0, ((0, 0), (0, dp)), constant_values=-jnp.inf),
        col_norm=jnp.pad(prep.col_norm, ((0, 0), (0, dp)),
                         constant_values=1.0),
        n_true=n, p_true=p)


def fleet_batch_sizes(prep: FleetPrep, lams, config: SaifConfig):
    """Per-problem h values + the fleet-static maximum (pow2-bucketed by
    ``add_batch_size_static`` already)."""
    p = prep.p_true or prep.X.shape[1]
    hs = [add_batch_size_static(config.c, float(lam), mx, md, p)
          for lam, mx, md in zip(lams, prep.c0_max, prep.c0_median)]
    return hs, (max(hs) if hs else 1)


def initial_support_batch(c0: jax.Array, hs, k_max: int, p: int,
                          dtype=jnp.float32):
    """Batched cold start: per-problem top-h_b features by c0.

    Per-problem counts ride on the static fleet maximum via top_k's prefix
    property (top_k(x, m)[: j] == top_k(x, j) for j <= m, ties to the
    lowest id), so every problem's initial slots are bitwise the serial
    :func:`repro.core.saif.initial_support` layout.
    """
    b = c0.shape[0]
    n_cap = min(max(hs), k_max, p)
    top = jax.lax.top_k(c0, n_cap)[1].astype(jnp.int32)    # (B, n_cap)
    n_init = jnp.asarray([min(h_b, k_max, p) for h_b in hs], jnp.int32)
    ranks = jnp.arange(k_max)
    init_idx = jnp.zeros((b, k_max), jnp.int32).at[:, :n_cap].set(top)
    mask = ranks[None, :] < n_init[:, None]
    init_idx = jnp.where(mask, init_idx, 0)
    return init_idx, jnp.zeros((b, k_max), dtype), mask


@partial(jax.jit, static_argnames=("hs", "k_max", "p", "dtype",
                                   "sel_dtype"))
def _initial_support_batch_jit(c0, *, hs, k_max: int, p: int, dtype,
                               sel_dtype=None):
    """Jitted :func:`initial_support_batch` (fast-parity dispatch): the
    eager top_k + scatters are ~2.6 ms of host dispatch at the CI fleet
    shape — a third of the whole fast solve. ``hs`` rides as a static
    tuple; results are identical (top_k and the mask arithmetic are
    deterministic, jit or eager).

    ``sel_dtype`` (mixed-precision screens only) runs the cold-start
    top-h *selection* on down-cast scores: under x64 the f64 top_k sort
    is ~60x the f32 one on XLA:CPU, and which features seed the active
    set is heuristic-grade (any seed set is safe; the certificates that
    consume c0 itself — seq-ball lam0t, delta0 — keep the working-
    precision array)."""
    c0_sel = c0 if sel_dtype is None else c0.astype(sel_dtype)
    return initial_support_batch(c0_sel, list(hs), k_max, p, dtype)


def _delta0s(prep: FleetPrep, lams, config: SaifConfig):
    if config.delta0 is not None:
        return [float(config.delta0)] * len(lams)
    return [min(max(float(lam) / mx, 1e-3), 1.0)
            for lam, mx in zip(lams, prep.c0_max)]


def _stuck_recruits(out: ScreenOut, col_norm, r_eff, gap, eps):
    """(B, h) forced recruits of the fleet's progress guarantee: for each
    problem whose sub-problem is solved to near-target accuracy, every
    candidate its ball cannot rule out plus its top-scoring candidate
    (the serial engine's rule, ``saif._saif_jit``)."""
    stuck = (gap <= 100.0 * eps)[:, None]
    fin = jnp.isfinite(out.cand_score)
    cn = jnp.take_along_axis(fleet_col_norms(col_norm, fin.shape[0]),
                             out.cand_idx, axis=1)
    ub_c = out.cand_score + cn * jnp.reshape(r_eff, (-1, 1))
    top = jnp.arange(fin.shape[1])[None, :] == 0
    return stuck & fin & ((ub_c >= 1.0) | top)


def resolve_batch_inner(config: SaifConfig, n: int, k_max: int,
                        b: int, dtype=None) -> str:
    """Fleet inner-backend policy: the serial policy with the
    double-buffered fleet VMEM budget gating the pallas kernel (and no
    float64 fleet or x64 mode on the TPU kernel, as in the serial
    policy)."""
    from repro.core.screen_backend import mosaic_refuses
    from repro.kernels.cm.cm import cm_vmem_ok

    name, loss_name = config.inner_backend, config.loss
    from repro.core.inner_backend import GRAM_CROSSOVER
    if name == "auto":
        if (jax.default_backend() == "tpu" and cm_vmem_ok(n, k_max, batch=b)
                and not mosaic_refuses(dtype)):
            return "pallas"
        if loss_name == "least_squares" and GRAM_CROSSOVER * n >= k_max:
            return "gram"
        return "jnp"
    if name not in ("jnp", "gram", "pallas"):
        raise ValueError(f"unknown inner backend {name!r}")
    if name == "gram" and loss_name != "least_squares":
        raise ValueError("inner_backend='gram' requires "
                         "loss='least_squares'")
    if name == "pallas" and mosaic_refuses(dtype):
        raise ValueError("inner_backend='pallas' on TPU needs a float32 "
                         "fleet with jax_enable_x64 off (Mosaic has no f64)")
    if name == "pallas" and not cm_vmem_ok(n, k_max, batch=b):
        raise ValueError(
            f"inner_backend='pallas': a fleet of {b} {n}x{k_max} active "
            f"blocks exceeds the double-buffered VMEM budget (DESIGN.md "
            f"§8); shrink k_max or use 'gram'/'jnp'")
    return name


def fleet_solve(X, Y, lam, config: SaifConfig = SaifConfig(),
                weights=None,
                screen_fn: Optional[BatchScreenFn] = None,
                prep: Optional[FleetPrep] = None) -> SaifResult:
    """Solve a fleet of B LASSO problems over a shared design in lockstep.

    Args:
      X:       (n, p) shared design.
      Y:       (B, n) per-problem responses (a (n,) vector is a fleet of 1).
      lam:     scalar or (B,) per-problem regularization.
      weights: optional (B, n) per-problem sample weights (binary row
               masks = the K-fold CV trick, DESIGN.md §8; disables the
               Thm-2 sequential ball exactly like the fused subsystem).
      screen_fn: custom batched screening backend (e.g. the sharded
               collective from ``repro.distributed.saif_sharded``).
      prep:    optional prebuilt :class:`FleetPrep` — the serving layer
               passes a bucket-padded preparation whose c0/col_norm were
               computed on the real design and zero/-inf-padded, with
               ``n_true``/``p_true`` recording the real dims (DESIGN.md
               §12). ``X``/``Y``/``weights`` are ignored when given.

    Returns a :class:`~repro.core.saif.SaifResult` whose every field has a
    leading problem axis. The whole fleet runs in ONE ``_saif_batch_jit``
    compilation (plus the rare elastic-capacity recompile, exactly like
    the serial driver); supports and coefficients are bitwise those of B
    serial :func:`~repro.core.saif.saif` calls.
    """
    if config.unpen_idx is not None:
        raise NotImplementedError(
            "saif_batch solves plain-LASSO fleets; the fused unpenalized "
            "slot is serial-only for now (DESIGN.md §8)")
    # per-request preparation: statistics, h, capacity, cold start
    with span("repro.session.prepare"):
        if prep is None:
            prep = prepare_fleet(X, Y, config, weights=weights)
        X, Y, W = prep.X, prep.Y, prep.W
        n, p = X.shape
        n_eff = prep.n_true or n
        p_eff = prep.p_true or p
        pad_mask = (jnp.arange(p) >= p_eff) if p_eff < p else None
        b = Y.shape[0]
        lam_arr = jnp.broadcast_to(
            jnp.asarray(lam, X.dtype).reshape(-1), (b,))
        with span("repro.sync.lams"):
            lams = [float(v) for v in jax.device_get(lam_arr)]
        rule = resolve_screen_rule(config.screen_rule)
        use_seq = config.use_seq_ball and W is None and rule.use_seq_ball
        backend = resolve_batch_screen(config.screen_backend, b=b, p=p_eff,
                                       dtype=X.dtype)
        # parity="fast" dispatch (DESIGN.md §11): the lockstep engine is
        # least-squares only (its inner burst is the batched Gram sweep)
        # and a custom screen_fn owns its own scores — both fall back to
        # the bitwise engine, which is always a valid (slower)
        # implementation of the same contract.
        use_fast = (config.parity == "fast"
                    and config.loss == "least_squares"
                    and screen_fn is None)

        hs, h = fleet_batch_sizes(prep, lams, config)
        h_tilde = jnp.asarray(
            [max(int(math.ceil(config.zeta * h_b)), 1) for h_b in hs],
            jnp.int32)
        h_cap = jnp.asarray(hs, jnp.int32)
        k_max = config.k_max or default_capacity(h, p_eff)
        delta0 = jnp.asarray(_delta0s(prep, lams, config), X.dtype)
        W_arg = W if W is not None else jnp.zeros((1, 1), X.dtype)

        # cold start computed ONCE at the original capacity: like the
        # serial driver, elastic growth pads the buffers but keeps the
        # original (possibly capacity-truncated) initial support, so a
        # re-entered fleet reproduces the serial overflow-recovery
        # trajectories bitwise
        if use_fast:
            sel_dt = (None if config.screen_dtype == "working"
                      else jnp.dtype(jnp.float32))
            init_idx, init_beta, init_mask = _initial_support_batch_jit(
                prep.c0, hs=tuple(hs), k_max=k_max, p=p_eff, dtype=X.dtype,
                sel_dtype=sel_dt)
        else:
            init_idx, init_beta, init_mask = initial_support_batch(
                prep.c0, hs, k_max, p_eff, X.dtype)
    while True:
        # one engine dispatch through the read that waits for it
        with span("repro.engine.run", b=b, h=h, k_max=k_max):
            pad = k_max - init_idx.shape[1]
            if pad > 0:
                init_idx = jnp.pad(init_idx, ((0, 0), (0, pad)))
                init_beta = jnp.pad(init_beta, ((0, 0), (0, pad)))
                init_mask = jnp.pad(init_mask, ((0, 0), (0, pad)))
            # the fleet dispatch routes through the fault-injection seam
            # (repro.runtime.inject) — a single None-check when disarmed
            if use_fast:
                km = k_max
                res = _fault_seam("fleet", lambda: _saif_batch_fast_jit(
                    X, Y, W_arg, prep.col_norm, prep.c0, lam_arr,
                    jnp.full((b,), config.eps, X.dtype), delta0,
                    init_idx, init_beta, init_mask, h_tilde, h_cap,
                    pad_mask,
                    loss_name=config.loss, h=h, k_max=km,
                    inner_epochs=config.inner_epochs,
                    polish_factor=config.polish_factor,
                    max_outer=config.max_outer, use_seq_ball=use_seq,
                    screen_dtype=config.screen_dtype,
                    has_weights=W is not None, screen_rule=rule))
            else:
                inner = resolve_batch_inner(config, n_eff, k_max, b,
                                            X.dtype)
                carry = cold_inner_carry_batch(b, k_max, X.dtype,
                                               backend=inner)
                res = _fault_seam("fleet", lambda: _saif_batch_jit(
                    X, Y, W_arg, prep.col_norm, prep.c0, lam_arr,
                    jnp.full((b,), config.eps, X.dtype), delta0,
                    init_idx, init_beta, init_mask,
                    carry.G, carry.rho, carry.gidx, h_tilde, h_cap,
                    pad_mask,
                    loss_name=config.loss, h=h, k_max=k_max,
                    inner_epochs=config.inner_epochs,
                    polish_factor=config.polish_factor,
                    max_outer=config.max_outer, use_seq_ball=use_seq,
                    screen_backend=backend, inner_backend=inner,
                    has_weights=W is not None, screen_fn=screen_fn,
                    screen_rule=rule))
            # ONE host sync for the whole fleet's overflow flags; elastic
            # growth re-enters cold at doubled capacity (per-problem
            # results are capacity-invariant, so non-overflowing problems
            # reproduce their previous answers bitwise)
            with span("repro.sync.overflow"):
                overflowed = bool(jnp.any(res.overflowed))
        if not overflowed or k_max >= p_eff:
            return res
        k_max = min(2 * k_max, p_eff)


def saif_batch(X, Y, lam, config: SaifConfig = SaifConfig(),
               weights=None,
               screen_fn: Optional[BatchScreenFn] = None) -> SaifResult:
    """DEPRECATED legacy frontend — one-shot session over
    :func:`fleet_solve`.

    Use ``repro.open_session(Problem(X), config).solve(Fleet(Y, lams))``;
    a held-open session keeps the fleet compilation alive across request
    streams (DESIGN.md §9).
    """
    from repro.core._compat import warn_deprecated
    warn_deprecated("repro.core.saif_batch",
                    "session.solve(Fleet(Y, lams))")
    from repro.core.api import Fleet, Problem, open_session

    sess = open_session(Problem(X=X, loss=config.loss), config)
    return sess.solve(Fleet(Y=Y, lams=lam, weights=weights,
                            screen_fn=screen_fn))
