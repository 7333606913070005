"""Pluggable screening backends for the SAIF ADD phase.

The ADD decision of Algorithm 2 needs, per outer iteration, exactly four
things from the full feature set R_t:

  * ``max_ub``                — the ADD-stop reduction  max_{R_t} ub_i,
  * the top-h candidates      — (score, feature id) pairs,
  * their lower bounds        — lb_l = |score_l - ||x_l|| r|,
  * their violation counts    — |V_l| = #{i in R_t : ub_i >= lb_l}.

A :data:`ScreenFn` produces all four as one :class:`ScreenOut`; the jitted
solver in :mod:`repro.core.saif` is backend-agnostic and touches nothing
(p,)-shaped in the ADD phase. Three implementations ship:

  * ``jnp``     — XLA matvec + ``top_k`` + searchsorted/bincount counts.
  * ``pallas``  — the fused TPU kernel pair from ``repro.kernels.screen``:
                  one pass emits masked (score, ub, lb) + tile-local top-h +
                  tile max-ub; a second streaming pass histograms ub against
                  the merged candidates' lower bounds.
  * sharded     — ``repro.distributed.saif_sharded.make_sharded_screen``,
                  same math under ``shard_map``.

All three produce *identical integers* for the violation counts and the same
candidate sets (ties break to the lowest feature id everywhere), which is
what makes the backends interchangeable mid-path.

Fused problems (DESIGN.md §7) screen through this same interface: the
Theorem-6 transform materializes the edge columns + the b column once, and
every backend — the sharded one included (``saif_fused_distributed``) —
scans the transformed design like any other; the always-resident
unpenalized slot is excluded the same way any active feature is (it is in
``in_active`` from step 0 and never DELed), so no backend needs a fused
special case.

Violation counts without the O(p log p) sort
--------------------------------------------
The legacy implementation sorted the (p,) ub vector and binary-searched each
candidate bound in it. Equivalent, cheaper (O(p log h + h log h)):

  1. sort only the h candidate bounds: ``lb_sorted``;
  2. for every feature, c_i = #{l : lb_sorted[l] <= ub_i}   (searchsorted);
  3. histogram the c_i values into bins 0..h;
  4. suffix sums:  #{i : ub_i >= lb_sorted[j]} = sum_{m > j} hist[m].

Step 2+3 stream over ub once; the (p,)-sized sort is gone. For a candidate
with bound lb_l sitting at position j = searchsorted(lb_sorted, lb_l, 'left')
the suffix sum at j+1 is exactly #{i : ub_i >= lb_l} — including ties, since
both sides count with the same <= comparison.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

# the rule/backend seam (DESIGN.md §13): the ScreenRule picks the
# certificate geometry, the backends here only compute its bounds fast —
# re-exported so rule consumers import one module
from repro.core.screen_rule import (SCREEN_RULES, ScreenRule,  # noqa: F401
                                    resolve_screen_rule)


class ScreenOut(NamedTuple):
    max_ub: jax.Array      # scalar: max over R_t of ub (−inf if R_t empty)
    cand_score: jax.Array  # (h,) top-h scores over R_t (−inf padded)
    cand_idx: jax.Array    # (h,) int32 global feature ids
    cand_lb: jax.Array     # (h,) |score − ||x|| r| per candidate
    cand_ge: jax.Array     # (h,) int32 #{i in R_t : ub_i >= cand_lb}
    # observability (ISSUE 9): #{i in R_t : ub_i >= 1} — the features this
    # screen could NOT rule out ("survivors"; |R_t| - n_surv were screened).
    # Mixed-precision screens count against the widened bounds, so the
    # count is conservative exactly like the decisions themselves. None is
    # tolerated from legacy/custom ScreenFns; engines treat it as 0.
    n_surv: Optional[jax.Array] = None


# signature: (theta (n,), r scalar, in_active (p,) bool) -> ScreenOut
ScreenFn = Callable[[jax.Array, jax.Array, jax.Array], ScreenOut]


def ge_counts_from_hist(hist: jax.Array, lb_sorted: jax.Array,
                        lb_cand: jax.Array) -> jax.Array:
    """Per-candidate #{i : ub_i >= lb} from the c-histogram (exact)."""
    suffix = jnp.cumsum(hist[::-1])[::-1]            # suffix[m] = Σ_{t>=m}
    pos = jnp.searchsorted(lb_sorted, lb_cand, side="left")
    return suffix[pos + 1].astype(jnp.int32)


def violation_ge_counts(ub: jax.Array, lb_cand: jax.Array) -> jax.Array:
    """Pure-jnp counts #{i : ub_i >= lb_l} per candidate, sort-free in p."""
    h = lb_cand.shape[0]
    lb_sorted = jnp.sort(lb_cand)
    c = jnp.searchsorted(lb_sorted, ub, side="right")
    hist = jnp.zeros((h + 1,), jnp.int32).at[c].add(1)
    return ge_counts_from_hist(hist, lb_sorted, lb_cand)


def survivor_count(ub: jax.Array, axis=None) -> jax.Array:
    """#{i : ub_i >= 1} over the trailing feature axis — the screen's
    survivor count. -inf entries (active/skipped) never count."""
    return jnp.sum((ub >= 1.0), axis=axis, dtype=jnp.int32)


def _candidate_out(scores_masked, ub, col_norm, r, h) -> ScreenOut:
    """Shared tail: top-h + bounds + counts from masked scores and ub."""
    cand_score, cand_idx = jax.lax.top_k(scores_masked, h)
    cand_idx = cand_idx.astype(jnp.int32)
    cand_lb = jnp.abs(cand_score - jnp.take(col_norm, cand_idx) * r)
    cand_ge = violation_ge_counts(ub, cand_lb)
    return ScreenOut(max_ub=jnp.max(ub), cand_score=cand_score,
                     cand_idx=cand_idx, cand_lb=cand_lb, cand_ge=cand_ge,
                     n_surv=survivor_count(ub))


def make_screen_jnp(X: jax.Array, col_norm: jax.Array, h: int) -> ScreenFn:
    """Reference backend: one XLA matvec + cheap reductions.

    The scan is written ``theta @ X`` (not ``X.T @ theta``): with the
    row-vector orientation XLA:CPU computes each column's dot product with
    a bracketing that does not depend on how many columns sit to its
    right, so appending zero columns (the serving layer's p-bucket
    padding, DESIGN.md §12) leaves every real column's score bitwise
    unchanged. The transposed orientation re-tiles with the output width
    and is measurably not padding-stable.
    """
    def screen(theta, r, in_active):
        score = jnp.abs(theta @ X)
        masked = jnp.where(in_active, -jnp.inf, score)
        ub = masked + col_norm * r
        return _candidate_out(masked, ub, col_norm, r, h)
    return screen


def make_screen_from_scan(scan_fn, col_norm: jax.Array, h: int) -> ScreenFn:
    """Adapt a bare ``theta -> |X^T theta|`` scan (e.g. the shard_map one)
    to the full backend interface; everything past the scan is O(p) jnp."""
    def screen(theta, r, in_active):
        score = scan_fn(theta)
        masked = jnp.where(in_active, -jnp.inf, score)
        ub = masked + col_norm * r
        return _candidate_out(masked, ub, col_norm, r, h)
    return screen


def make_screen_pallas(X: jax.Array, col_norm: jax.Array, h: int,
                       bn: Optional[int] = None, bp: Optional[int] = None,
                       interpret: Optional[bool] = None) -> ScreenFn:
    """Fused-kernel backend; see repro/kernels/screen/screen.py."""
    from repro.kernels.screen.screen import (screen_fused_pallas,
                                             ub_histogram_pallas)

    def screen(theta, r, in_active):
        _, ub, _, tops, topi, tmax = screen_fused_pallas(
            X, theta, col_norm, in_active, r, h=h, bn=bn, bp=bp,
            interpret=interpret)
        # merge tile winners: O((p/bp) h) candidates, not O(p)
        cand_score, pos = jax.lax.top_k(tops.reshape(-1), h)
        cand_idx = topi.reshape(-1)[pos]
        cand_lb = jnp.abs(cand_score -
                          jnp.take(col_norm, cand_idx).astype(cand_score.dtype)
                          * jnp.asarray(r, cand_score.dtype))
        lb_sorted = jnp.sort(cand_lb)
        hist = ub_histogram_pallas(ub, lb_sorted, interpret=interpret)
        cand_ge = ge_counts_from_hist(hist, lb_sorted, cand_lb)
        return ScreenOut(max_ub=jnp.max(tmax), cand_score=cand_score,
                         cand_idx=cand_idx, cand_lb=cand_lb, cand_ge=cand_ge,
                         n_surv=survivor_count(ub))
    return screen


# --------------------------------------------------------------------------
# batched (problem-axis) screens — the fleet engine (core/batch.py, §8)
# --------------------------------------------------------------------------
# A batched ScreenFn maps (Theta (B, n), r (B,), in_active (B, p),
# do (B,)) to a ScreenOut whose every field carries a leading problem
# axis; ``do`` flags the problems whose ADD phase is actually running this
# outer step (the serial solver's per-solve screen gate, per problem).
#
# The default ``jnp`` fleet screen is a liveness-gated lax.map of the
# SERIAL screen: each problem's scan is the literal serial matvec — the
# bitwise-parity contract — and polish-phase/frozen problems skip their
# scan entirely, exactly like the serial solver's lax.cond. The shared-X
# ``matmul`` fast path turns the fleet's scans into ONE (B, n) x (n, p)
# matmul (the design is read once per outer step for the whole fleet);
# its re-tiled reduction can differ from a serial matvec by an ulp, which
# near an ADD-stop boundary (max_ub == 1 exactly) can flip one decision —
# opt in for scan-bound fleets where that trade is right (DESIGN.md §8).
# The distinct-X fallback (per-problem designs, (B, n, p)) keeps the
# problem axis a batch dim of the contraction and stays bitwise.

# signature: (Theta (B,n), r (B,), in_active (B,p), do (B,)) -> ScreenOut
BatchScreenFn = Callable[[jax.Array, jax.Array, jax.Array, jax.Array],
                         ScreenOut]


def _candidate_out_batch(masked, ub, col_norm, r, h,
                         sel_dtype=None) -> ScreenOut:
    """Batched :func:`_candidate_out`: per-problem top-h + bounds + counts.
    ``col_norm`` is the fleet (B, p) matrix.

    For small h the violation counts are ONE (B, p, h) comparison-reduce
    instead of a vmapped sort+searchsorted — integer-identical (a count
    of exact float comparisons has no accumulation order), and materially
    fewer ops inside the fleet while_loop. Large h keeps the sort form
    (the dense compare would be B*p*h).

    ``sel_dtype`` runs the top-h *selection* sort on down-cast scores
    (the f64 top_k is ~60x the f32 one on XLA:CPU) while the returned
    scores/bounds are gathered from the full-precision ``masked`` — used
    by the mixed-precision escalation tier, where selection order is
    heuristic-grade but the bounds must stay working precision.
    """
    if sel_dtype is None:
        cand_score, cand_idx = jax.lax.top_k(masked, h)      # (B, h)
    else:
        _, cand_idx = jax.lax.top_k(masked.astype(sel_dtype), h)
        cand_score = jnp.take_along_axis(masked, cand_idx, axis=1)
    cand_idx = cand_idx.astype(jnp.int32)
    cand_lb = jnp.abs(cand_score -
                      jnp.take_along_axis(col_norm, cand_idx, axis=1)
                      * r[:, None])
    if h <= 32:
        cand_ge = jnp.sum(
            (ub[:, :, None] >= cand_lb[:, None, :]).astype(jnp.int32),
            axis=1)
    else:
        cand_ge = jax.vmap(violation_ge_counts)(ub, cand_lb)
    return ScreenOut(max_ub=jnp.max(ub, axis=1), cand_score=cand_score,
                     cand_idx=cand_idx, cand_lb=cand_lb, cand_ge=cand_ge,
                     n_surv=survivor_count(ub, axis=1))


def fleet_col_norms(col_norm: jax.Array, b: int) -> jax.Array:
    """(B, p) fleet column norms from a shared (p,) vector or pass-through."""
    cn = jnp.asarray(col_norm)
    return jnp.broadcast_to(cn, (b,) + cn.shape) if cn.ndim == 1 else cn


def _skip_screen_out(h: int, dtype) -> ScreenOut:
    """Neutral per-problem ScreenOut for a skipped scan: max_ub = -inf
    (reads as add_done, but the engine's do-mask already gates every
    consumer), no finite candidates."""
    return ScreenOut(max_ub=jnp.asarray(-jnp.inf, dtype),
                     cand_score=jnp.full((h,), -jnp.inf, dtype),
                     cand_idx=jnp.zeros((h,), jnp.int32),
                     cand_lb=jnp.full((h,), jnp.inf, dtype),
                     cand_ge=jnp.zeros((h,), jnp.int32),
                     n_surv=jnp.zeros((), jnp.int32))


def make_batch_screen_jnp(X: jax.Array, col_norm: jax.Array,
                          h: int) -> BatchScreenFn:
    """Default fleet screen: per-problem serial scans, lax.mapped, with a
    per-problem skip for problems whose ADD phase is off this step."""
    def screen(Theta, r, in_active, do):
        cn = fleet_col_norms(col_norm, Theta.shape[0])

        def one(args):
            do_b, theta_b, r_b, act_b, cn_b = args
            return jax.lax.cond(
                do_b,
                lambda _: make_screen_jnp(X, cn_b, h)(theta_b, r_b, act_b),
                lambda _: _skip_screen_out(h, Theta.dtype), None)

        return jax.lax.map(one, (do, Theta, r, in_active, cn))
    return screen


def make_batch_screen_matmul(X: jax.Array, col_norm: jax.Array,
                             h: int) -> BatchScreenFn:
    """Shared-X fast path: one (B, n) x (n, p) matmul scans the fleet
    (ulp-grade vs serial scans — see the section comment)."""
    def screen(Theta, r, in_active, do):
        cn = fleet_col_norms(col_norm, Theta.shape[0])
        score = jnp.abs(Theta @ X)                           # (B, p)
        masked = jnp.where(in_active, -jnp.inf, score)
        ub = masked + cn * r[:, None]
        return _candidate_out_batch(masked, ub, cn, r, h)
    return screen


def make_batch_screen_distinct(Xs: jax.Array, col_norm: jax.Array,
                               h: int) -> BatchScreenFn:
    """Distinct-X fallback: per-problem designs Xs (B, n, p). The problem
    axis stays a batch dim of the contraction, so every problem's scan is
    bitwise its serial matvec (no shared-operand re-tiling)."""
    def screen(Theta, r, in_active, do):
        cn = fleet_col_norms(col_norm, Theta.shape[0])
        score = jnp.abs(jnp.einsum("bnp,bn->bp", Xs, Theta))
        masked = jnp.where(in_active, -jnp.inf, score)
        ub = masked + cn * r[:, None]
        return _candidate_out_batch(masked, ub, cn, r, h)
    return screen


def make_batch_screen_pallas(X: jax.Array, col_norm: jax.Array, h: int,
                             bn: Optional[int] = None,
                             bp: Optional[int] = None,
                             interpret: Optional[bool] = None
                             ) -> BatchScreenFn:
    """Problem-gridded fused kernels: grid axis over the fleet, shared X
    tiles revisited across problems (kernels/screen/screen.py). Each grid
    step runs the serial kernel body on one problem's blocks, so the
    per-problem scores match the serial pallas screen bitwise."""
    from repro.kernels.screen.screen import (screen_fused_batch_pallas,
                                             ub_histogram_batch_pallas)

    def screen(Theta, r, in_active, do):
        b = Theta.shape[0]
        cn = fleet_col_norms(col_norm, b)
        _, ub, _, tops, topi, tmax = screen_fused_batch_pallas(
            X, Theta, cn, in_active, r, h=h, bn=bn, bp=bp,
            interpret=interpret)
        cand_score, pos = jax.lax.top_k(tops.reshape(b, -1), h)
        cand_idx = jnp.take_along_axis(topi.reshape(b, -1), pos, axis=1)
        cand_lb = jnp.abs(
            cand_score - jnp.take_along_axis(cn, cand_idx, axis=1)
            .astype(cand_score.dtype) * r[:, None].astype(cand_score.dtype))
        lb_sorted = jnp.sort(cand_lb, axis=1)
        hist = ub_histogram_batch_pallas(ub, lb_sorted, interpret=interpret)
        cand_ge = jax.vmap(ge_counts_from_hist)(hist, lb_sorted, cand_lb)
        return ScreenOut(max_ub=jnp.max(tmax, axis=1),
                         cand_score=cand_score, cand_idx=cand_idx,
                         cand_lb=cand_lb, cand_ge=cand_ge,
                         n_surv=survivor_count(ub, axis=1))
    return screen


def make_batch_screen_fast(X: jax.Array, col_norm: jax.Array, h: int,
                           screen_dtype: str = "working") -> BatchScreenFn:
    """Certified mixed-precision fleet screen (parity="fast", DESIGN.md §11).

    One (B, n) x (n, p) gemm scans the fleet with inputs cast to
    ``screen_dtype`` ("working" | "float32" | "bfloat16") and an
    accumulator no narrower than f32. Safety: the safe-ball radius is
    widened by the rigorous per-dot rounding bound
    gamma_total * ||theta||_2 (:func:`repro.core.duality.widened_radius`)
    BEFORE any bound is formed, so the low-precision ub upper-bounds the
    exact ub and the ADD-stop / not-a-candidate decisions are strictly
    conservative — a feature this screen rules out is also ruled out by
    the exact working-precision screen at the same state. The top-h
    *selection* (scores/lb/violation counts) runs on the low-precision
    scores unwidened-equivalent: selection order is heuristic-grade (any
    selected feature is safe to add; Thm 1a), only the bounds are
    certificate-grade.
    """
    from repro.core.duality import (mixed_precision_gamma, unit_roundoff,
                                    widened_radius)

    n = X.shape[0]
    X = jnp.asarray(X)
    work_dt = X.dtype
    in_dt = work_dt if screen_dtype == "working" else jnp.dtype(screen_dtype)
    acc_dt = work_dt if screen_dtype == "working" else jnp.promote_types(
        jnp.float32, in_dt)
    low_precision = in_dt != work_dt
    gamma = mixed_precision_gamma(n, in_dt, acc_dt)
    gamma_work = mixed_precision_gamma(n, work_dt, work_dt)
    # post-dot scalar guard (DESIGN.md §11): the bound pipeline itself
    # (|.|, the cn * r product, the final add — and the acc_dt casts of
    # cn and r) runs in acc_dt, ~5 roundings of nonnegative terms; an
    # explicit (1 +- 8u_acc) factor on the finished bounds absorbs them,
    # so EVERY float op between the exact score and the decision is
    # accounted, not just the dot
    u_acc = unit_roundoff(acc_dt)
    one_plus = 1.0 + 8.0 * u_acc
    one_minus = 1.0 - 8.0 * u_acc
    Xc = X.astype(in_dt)

    def screen(Theta, r, in_active, do):
        b = Theta.shape[0]
        cn_w = fleet_col_norms(col_norm, b)
        r_wide = widened_radius(r, Theta, gamma)
        # the whole decision pipeline stays in acc_dt: under x64 working
        # precision the f64 top_k/sort alone is ~60x an f32 one on
        # XLA:CPU, and selection order is heuristic-grade anyway — only
        # the *bounds* carry certificates, and those are widened in
        # acc_dt with the scalar guard above
        score = jnp.abs(jnp.einsum(
            "bn,np->bp", Theta.astype(in_dt), Xc,
            preferred_element_type=acc_dt))
        cn = cn_w.astype(acc_dt)
        masked = jnp.where(in_active, jnp.asarray(-jnp.inf, acc_dt), score)
        ub = ((masked + cn * r_wide.astype(acc_dt)[:, None]) *
              jnp.asarray(one_plus, acc_dt))
        if not low_precision:
            return _candidate_out_batch(masked, ub, cn, r_wide, h)

        # Two-tier escalation (DESIGN.md §11): a genuinely low-precision
        # pass can leave the ADD-stop decision *undecidable* — the widened
        # ub refuses to certify max_ub < 1 while the anti-conservative
        # bound says the exact screen would have stopped. Refusing forever
        # stalls the delta ramp (the stop certificate can sit permanently
        # inside the bf16 noise band), so undecidable problems re-screen
        # in working precision this step — certified degradation instead
        # of non-termination; decidable problems keep the cheap pass.
        widen = (r_wide - r).astype(acc_dt)               # (B,)
        r_lo = r_wide.astype(acc_dt) - 2.0 * widen
        ub_lo = ((masked + cn * r_lo[:, None]) *
                 jnp.asarray(one_minus, acc_dt))
        undecidable = (do & (jnp.max(ub, axis=1) >= 1.0)
                       & (jnp.max(ub_lo, axis=1) < 1.0))

        def cheap(_):
            out = _candidate_out_batch(masked, ub, cn, r_wide, h)
            return ScreenOut(max_ub=out.max_ub.astype(work_dt),
                             cand_score=out.cand_score.astype(work_dt),
                             cand_idx=out.cand_idx,
                             cand_lb=out.cand_lb.astype(work_dt),
                             cand_ge=out.cand_ge, n_surv=out.n_surv)

        def escalate(_):
            score_w = jnp.where(undecidable[:, None],
                                jnp.abs(Theta @ X),
                                score.astype(work_dt))
            r_eff = jnp.where(undecidable,
                              widened_radius(r, Theta, gamma_work), r_wide)
            masked_w = jnp.where(in_active, -jnp.inf, score_w)
            ub_w = jnp.where(undecidable[:, None],
                             masked_w + cn_w * r_eff[:, None],
                             ub.astype(work_dt))
            return _candidate_out_batch(masked_w, ub_w, cn_w, r_eff, h,
                                        sel_dtype=jnp.float32)

        return jax.lax.cond(jnp.any(undecidable), escalate, cheap, None)
    return screen


def make_batch_screen(name: str, X: jax.Array, col_norm: jax.Array,
                      h: int) -> BatchScreenFn:
    """Factory used inside ``_saif_batch_jit`` (name is jit-static)."""
    if name == "pallas":
        return make_batch_screen_pallas(X, col_norm, h)
    if name == "matmul":
        return make_batch_screen_matmul(X, col_norm, h)
    return make_batch_screen_jnp(X, col_norm, h)


# Measured on the CI CPU (2 cores, x64, warm jits; numbers in DESIGN.md
# §8). The deciding mechanism is NOT gemm tiling: the raw one-gemm screen
# beats the lax.map of serial scans at EVERY fleet size when all problems
# screen (1.3-1.7x at B*p = 2k..128k). What the gemm lacks is the jnp
# path's per-problem ``do`` skip — once ADD phases desynchronize, skipped
# problems cost the jnp screen ~nothing (0.04ms vs the gemm's full 1.7ms
# at B=16 with do=0) while the matmul always pays the whole fleet. End to
# end the skip dominates small fleets (B*p=8k: matmul 2.17x vs jnp 2.61x,
# BENCH_batch.json PR 4) and the gemm amortization dominates larger ones
# (B*p=32k: matmul 1.18x faster; 64k: parity within noise across shapes).
# Crossover measured between B*p = 8k and 32k; below it an informed
# resolve call downgrades matmul to jnp on CPU.
MATMUL_MIN_BP = 32_768


def resolve_batch_screen(name: str, *, b: Optional[int] = None,
                         p: Optional[int] = None, dtype=None) -> str:
    """Fleet screen policy (DESIGN.md §8).

    ``matmul`` (the shared-X one-gemm screen, ulp-grade vs serial scans)
    is honored on accelerators unconditionally (lax.map serializes
    there), but on CPU only when the fleet's B*p crosses
    :data:`MATMUL_MIN_BP` — below that the jnp path's per-problem ``do``
    skip beats the gemm end to end (2.17x vs 2.61x fleet speedup at the
    B*p=8k CI shape; mechanism measured in the section comment above),
    so an informed call (``b``/``p`` known) downgrades it to ``jnp``.
    Name-only calls (legacy/tests that construct a screen directly) keep
    honoring the explicit opt-in. ``dtype`` feeds :func:`resolve_backend`.
    """
    if name == "matmul":
        if jax.default_backend() != "cpu":
            return name
        if b is None or p is None:          # uninformed call: honor opt-in
            return name
        return name if b * p >= MATMUL_MIN_BP else "jnp"
    return resolve_backend(name, dtype)


def mosaic_refuses(dtype) -> bool:
    """True when a compiled (TPU) Pallas kernel cannot serve a problem of
    ``dtype``: Mosaic has no float64, and the kernels compile with
    ``jax_enable_x64`` off (:func:`repro.kernels.screen.screen.refuse_x64`).
    Off TPU the kernels run interpreted and take anything; ``dtype=None``
    is an uninformed call (only the x64 mode counts)."""
    if jax.default_backend() != "tpu":
        return False
    return bool(jax.config.jax_enable_x64) or (
        dtype is not None and jnp.dtype(dtype) == jnp.float64)


def resolve_backend(name: str, dtype=None) -> str:
    """Backend-selection policy (DESIGN.md §3): explicit name wins; ``auto``
    compiles the fused kernels on TPU and keeps the XLA path elsewhere
    (the interpreter would be strictly slower than the jnp matvec). A
    float64 design (or x64 mode) on TPU stays on ``jnp`` under ``auto``,
    and an explicit ``pallas`` for it raises: Mosaic has no f64, and a
    silent down-cast would change the certificate."""
    if name == "auto":
        return ("pallas" if jax.default_backend() == "tpu"
                and not mosaic_refuses(dtype) else "jnp")
    if name not in ("jnp", "pallas"):
        raise ValueError(f"unknown screen backend {name!r}")
    if name == "pallas" and mosaic_refuses(dtype):
        raise ValueError("screen_backend='pallas' on TPU needs a float32 "
                         "design with jax_enable_x64 off (Mosaic has no "
                         "f64); use float32 or 'jnp'")
    return name
