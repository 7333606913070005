"""SAIF — Safe Active Incremental Feature selection (paper Algorithms 1 & 2).

The entire outer loop is a single jitted ``lax.while_loop``; the active set is
the fixed-capacity buffer from :mod:`repro.core.active_set`. The only O(p)
work per outer step is the screening scan (gated on the ADD phase), and that
scan is pluggable: a :class:`~repro.core.screen_backend.ScreenFn` produces
the ADD-stop bound, the top-h candidates and their violation counts in one
shot, so the ADD phase never materializes or sorts a second (p,)-shaped
array. Backends: the default jnp matvec, the fused Pallas TPU kernel pair
(``repro.kernels.screen``), and the multi-pod shard_map version
(``repro.distributed.saif_sharded``) — all computing the same function
(tested against each other; selection policy in DESIGN.md §3).

The inner solver is pluggable the same way (:mod:`repro.core.inner_backend`,
DESIGN.md §6): an :class:`~repro.core.inner_backend.InnerBackend` owns the
whole "CM burst + dual point + duality gap" of an outer step, and the
covariance-update (``gram``) engine threads its Gram buffers through the
while_loop carry — each coordinate step is then O(k_max), with no O(n) work
anywhere in the burst.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import active_set as aset_lib
from repro.core.active_set import ActiveSet
from repro.core.duality import (gap_ball, gap_precision_floor,
                                intersect_balls, sequential_ball)
from repro.core.inner_backend import (InnerCarry, _dual_and_gap,
                                      cold_inner_carry, make_inner,
                                      resolve_inner_backend)
from repro.core.losses import get_loss
from repro.core.screen_backend import (ScreenFn, ScreenOut, ScreenRule,
                                       make_screen_from_scan,
                                       make_screen_jnp, make_screen_pallas,
                                       resolve_backend, resolve_screen_rule)
from repro.core.screen_rule import SCREEN_RULES
from repro.runtime.inject import seam as _fault_seam
from repro.runtime.spans import span


@dataclasses.dataclass(frozen=True)
class SaifConfig:
    """Hyper-parameters of Algorithm 1/2 (paper defaults where given)."""
    eps: float = 1e-6            # stopping duality gap
    inner_epochs: int = 5        # K soft-threshold sweeps per outer step
    polish_factor: int = 8       # K multiplier once ADD has stopped (§Perf:
    #   the accuracy-pursuit phase has no screening decisions to make, so
    #   longer CM bursts amortize the per-outer dual/gap/gather overhead)
    c: float = 1.0               # ADD batch size constant (h formula)
    zeta: float = 1.0            # violation tolerance multiplier (h~ = zeta h)
    k_max: Optional[int] = None  # active-set capacity (None => auto)
    max_outer: int = 2000        # while_loop guard / trace length
    delta0: Optional[float] = None  # initial radius factor (None => lam/lam_max)
    use_seq_ball: bool = True    # intersect Thm-2 ball with the gap ball
    loss: str = "least_squares"
    screen_backend: str = "auto"  # "auto" | "jnp" | "pallas" (DESIGN.md §3)
    inner_backend: str = "auto"   # "auto" | "jnp" | "gram" | "pallas" (§6)
    unpen_idx: Optional[int] = None  # feature id exempt from the l1 penalty
    #   (fused LASSO's always-resident ``b`` slot, Thm 7 / DESIGN.md §7);
    #   None = plain LASSO. The slot is pinned in the active set, never
    #   DELed, its coordinate step is unthresholded, and the dual point is
    #   projected onto its equality constraint.
    parity: str = "bitwise"      # "bitwise" | "fast" (DESIGN.md §11).
    #   "bitwise" (default): fleet solves replay the serial float path
    #   bit-for-bit (DESIGN.md §8 discipline) — unchanged from PR 6.
    #   "fast" (opt-in): fleet solves may re-associate batch reductions,
    #   run lockstep CM sweeps and the one-gemm-per-step screen; every
    #   screening decision is widened by a rigorous rounding-error bound
    #   and every solve still ends with a working-precision certificate.
    screen_dtype: str = "working"  # "working" | "float32" | "bfloat16":
    #   compute dtype of the fast-parity screening gemm (inputs cast down,
    #   f32 accumulation, radius widened by the certified error bound).
    #   Anything but "working" requires parity="fast".
    screen_rule: str = "saif"     # "saif" | "gap_safe" | "hybrid" — the
    #   certificate geometry (repro.core.screen_rule, DESIGN.md §13).
    #   "saif" keeps the Theorem-2 sequential+gap ball and the delta ramp
    #   bitwise-unchanged; "gap_safe" screens on the gap sphere alone;
    #   "hybrid" discards with the strong-rule point bound and gates every
    #   stop behind a safe full-radius post-check (fallback recruits any
    #   violator in-loop, so safety is preserved by construction).

    def __post_init__(self):
        if self.parity not in ("bitwise", "fast"):
            raise ValueError(
                f"parity must be 'bitwise' or 'fast', got {self.parity!r}")
        if self.screen_dtype not in ("working", "float32", "bfloat16"):
            raise ValueError(
                "screen_dtype must be 'working', 'float32' or 'bfloat16', "
                f"got {self.screen_dtype!r}")
        if self.screen_dtype != "working" and self.parity != "fast":
            raise ValueError(
                "screen_dtype != 'working' is a fast-parity feature: "
                "low-precision screening deviates from the bitwise serial "
                "float path; set parity='fast' to opt in")
        resolve_screen_rule(self.screen_rule)   # fail fast on unknown names


class SaifResult(NamedTuple):
    beta: jax.Array          # (p,) full solution
    gap: jax.Array           # final sub-problem duality gap
    n_outer: jax.Array       # outer iterations executed
    n_active: jax.Array      # final |A_t|
    overflowed: jax.Array    # capacity overflow flag
    trace_n_active: jax.Array  # (max_outer,) |A_t| per outer step (-1 pad)
    trace_gap: jax.Array       # (max_outer,)
    # final slot state + inner-solver carry: the path engine hands these to
    # the next lambda so slot assignment (and the Gram buffers that are
    # indexed by it) survive the warm start (DESIGN.md §6)
    active_idx: jax.Array    # (k_max,) final slot -> feature map
    active_mask: jax.Array   # (k_max,) final slot validity
    inner: InnerCarry        # final inner-backend carry (placeholder if none)
    # screening observability (ISSUE 9; fleet engines carry a leading B
    # axis). Per outer step: features the ADD screen ruled out / could not
    # rule out (-1 on steps whose ADD phase did not run), and the number
    # of safe post-check violations (-1 on steps with no check — always
    # -1 for rules without one). None from engines predating the counters.
    trace_screened: Optional[jax.Array] = None    # (max_outer,) int32
    trace_survivors: Optional[jax.Array] = None   # (max_outer,) int32
    trace_post_viol: Optional[jax.Array] = None   # (max_outer,) int32


class _State(NamedTuple):
    aset: ActiveSet
    z: jax.Array        # (n,) model vector Xa beta
    gap: jax.Array
    delta: jax.Array
    is_add: jax.Array   # bool
    stop: jax.Array     # bool
    t: jax.Array        # outer counter
    inner: InnerCarry   # inner-solver carry (Gram buffers for "gram")
    trace_n_active: jax.Array
    trace_gap: jax.Array
    trace_screened: jax.Array   # int32 screening counters (ISSUE 9)
    trace_survivors: jax.Array
    trace_post_viol: jax.Array


def add_batch_size_static(c: float, lam: float, c0_max: float,
                          c0_median: float, p: int) -> int:
    """h = ceil(c log((md+mx)/lam) log p)  — paper Sec 2.2 (static value).

    Rounded up to the next power of two: h is a jit-static argument, so
    bucketing caps the number of recompiles across a lambda path at
    O(log p) instead of one per lambda (§Perf iteration 1). Takes the c0
    statistics as host floats so path drivers sync them exactly once.
    """
    h = math.ceil(max(c * math.log(max((c0_median + c0_max) / lam,
                                       1.0 + 1e-9))
                      * math.log(max(p, 2)), 1.0))
    h = 1 << (max(h, 1) - 1).bit_length()       # next pow2 bucket
    return max(min(h, p), 1)


def add_batch_size(c: float, lam: float, c0: jax.Array, p: int) -> int:
    """Device-array convenience wrapper around :func:`add_batch_size_static`."""
    return add_batch_size_static(c, lam, float(jnp.max(c0)),
                                 float(jnp.median(c0)), p)


def default_capacity(h: int, p: int) -> int:
    return int(min(p, max(8 * h, 64)))


def initial_support(c0, h: int, k_max: int, p: int,
                    unpen_idx: Optional[int] = None, b0=0.0,
                    dtype=jnp.float32):
    """Cold-start support (Algorithm 1 line 1): top-h' features by c0.

    Returns ``(init_idx (k_max,), init_beta (k_max,), n_init)``. With an
    unpenalized coordinate (fused LASSO) the slot is pinned at position 0,
    seeded at its null-fit value ``b0``, and masked out of the top-k so it
    can never occupy two slots. Shared by the single-lambda driver and the
    path engine's cold start so both produce bitwise-identical layouts.
    """
    if unpen_idx is None:
        n_init = min(h, k_max, p)
        top = jax.lax.top_k(c0, n_init)[1].astype(jnp.int32)
        init_idx = jnp.zeros((k_max,), jnp.int32).at[:n_init].set(top)
        return init_idx, jnp.zeros((k_max,), dtype), n_init
    n_init = min(h + 1, k_max, p)
    n_top = n_init - 1
    c0_top = c0.at[unpen_idx].set(-jnp.inf)     # ties at 0 must not pick it
    top = jax.lax.top_k(c0_top, max(n_top, 1))[1].astype(jnp.int32)
    init_idx = jnp.zeros((k_max,), jnp.int32).at[0].set(unpen_idx)
    init_idx = init_idx.at[1:n_init].set(top[:n_top])
    init_beta = jnp.zeros((k_max,), dtype).at[0].set(
        jnp.asarray(b0, dtype))
    return init_idx, init_beta, n_init


ScanFn = Callable[[jax.Array], jax.Array]
# legacy signature: theta (n,) -> |X^T theta| (p,)


def _n_surv32(out: ScreenOut) -> jax.Array:
    """Survivor count as int32; legacy/custom ScreenFns without the
    counter (n_surv=None) read as 0."""
    ns = out.n_surv
    if ns is None:
        return jnp.zeros((), jnp.int32)
    return ns.astype(jnp.int32)


@partial(jax.jit, static_argnames=("loss_name", "h", "k_max",
                                   "inner_epochs", "polish_factor",
                                   "max_outer", "use_seq_ball",
                                   "screen_backend", "inner_backend",
                                   "unpen_idx", "screen_fn", "scan_fn",
                                   "screen_rule"))
def _saif_jit(X, y, col_norm, c0, lam, eps, delta0, init_idx, init_beta,
              init_mask, init_G, init_rho, init_gidx, h_tilde, h_cap,
              pad_mask=None,
              *, loss_name: str, h: int, k_max: int,
              inner_epochs: int, polish_factor: int, max_outer: int,
              use_seq_ball: bool, screen_backend: str = "jnp",
              inner_backend: str = "jnp", unpen_idx: int = -1,
              screen_fn: Optional[ScreenFn] = None,
              scan_fn: Optional[ScanFn] = None,
              screen_rule: ScreenRule = SCREEN_RULES["saif"]) -> SaifResult:
    # h (static) sizes the candidate shapes; h_tilde (the violation
    # tolerance) and h_cap (the effective per-step batch size, <= h) are
    # traced — they only feed comparisons. Splitting them lets a lambda
    # path share ONE compilation at the grid-max h while every lambda
    # keeps its own tolerance and batch size, so the ADD decisions are
    # bitwise those of a per-lambda compile. The same split applies to the
    # inner carry: (init_G, init_rho, init_gidx) are traced warm-handoff
    # buffers at fixed (k_max,)-derived shapes (placeholders for stateless
    # inner backends).
    loss = get_loss(loss_name)
    n, p = X.shape
    lam = jnp.asarray(lam, X.dtype)
    if screen_fn is not None:
        screen = screen_fn
    elif scan_fn is not None:
        # legacy bare-scan hook (e.g. the shard_map scan): adapt in-trace so
        # the caller-stable function object stays the jit cache key
        screen = make_screen_from_scan(scan_fn, col_norm, h)
    elif screen_backend == "pallas":
        screen = make_screen_pallas(X, col_norm, h)
    else:
        screen = make_screen_jnp(X, col_norm, h)
    inner = make_inner(inner_backend, loss, X, y, col_norm, h, unpen_idx)

    g0 = loss.grad(jnp.zeros_like(y), y)   # f'(0)

    aset0 = aset_lib.init_active_set(p, k_max, init_idx, X.dtype, init_beta,
                                     live_mask=init_mask)
    if pad_mask is not None:
        # Bucket-pad columns (traced, so every problem in a compile bucket
        # shares this cache entry) are born "already active" without ever
        # holding a slot: the screens mask active columns to -inf, DEL
        # only touches live slots, and ADD draws from screen candidates —
        # so a pad can never be recruited, deleted, or scored, and the
        # real columns' trajectory is exactly the unpadded one.
        aset0 = aset0._replace(in_active=aset0.in_active | pad_mask)
    carry_in = InnerCarry(G=init_G, rho=init_rho, gidx=init_gidx)
    inner0 = inner.init(aset0, carry_in,
                        aset_lib.gather_columns(X, aset0))
    trace0 = jnp.full((max_outer,), -1.0, X.dtype)
    itrace0 = jnp.full((max_outer,), -1, jnp.int32)
    state0 = _State(aset=aset0, z=jnp.zeros_like(y),
                    gap=jnp.asarray(jnp.inf, X.dtype),
                    delta=jnp.asarray(delta0, X.dtype),
                    is_add=jnp.asarray(True), stop=jnp.asarray(False),
                    t=jnp.asarray(0), inner=inner0,
                    trace_n_active=trace0, trace_gap=trace0,
                    trace_screened=itrace0, trace_survivors=itrace0,
                    trace_post_viol=itrace0)

    def cond(s: _State):
        return (~s.stop) & (s.t < max_outer)

    def body(s: _State) -> _State:
        aset = s.aset
        with jax.named_scope("cm"):
            Xa = aset_lib.gather_columns(X, aset)

        # --- K epochs of coordinate minimization on the sub-problem --------
        # (K * polish_factor once recruiting is done — §Perf iteration 2;
        #  sweeps only the aset.count live slots, in the incrementally
        #  maintained aset.order — §Perf iteration 3 + PR 2 hoist.)
        # The backend absorbs last step's ADD/DEL (bounded Gram column
        # refresh for "gram", no-op otherwise), runs the burst, and returns
        # the dual point + duality gap (Eq. 11) along with (beta, z).
        newton = (screen_rule.newton_polish and inner_backend == "gram"
                  and loss_name == "least_squares" and unpen_idx < 0)
        n_ep = jnp.where(s.is_add, inner_epochs,
                         inner_epochs * polish_factor)
        with jax.named_scope("cm"):
            inner_carry = inner.refresh(s.inner, aset, Xa)
            out = inner.run(inner_carry, aset, Xa, lam, n_ep)
        beta, z, theta = out.beta, out.z, out.theta
        gap = jnp.asarray(out.gap, X.dtype)

        # --- working-set Newton polish (hybrid rule, DESIGN.md §13) --------
        # Once recruiting quiesces, the gram carry already holds the
        # working-set normal equations, so ONE masked solve of
        # G b = rho - lam*sign gives the exact sub-problem solution under
        # the current sign pattern — collapsing the O(1/rate) CM polish
        # tail into a handful of outer steps. The proposal is certified by
        # the OFFICIAL dual/gap tail and accepted only if it beats the CM
        # iterate's gap, so a wrong sign pattern, a singular working set
        # (|A| > n), or numerical junk silently falls back to the CM burst
        # — no certificate is ever derived from an unverified solve.
        if newton:
            def newton_step(args):
                beta_c, z_c, theta_c_, gap_c = args
                G, rho = inner_carry.G, inner_carry.rho
                # Solve on the CM iterate's *support*, not the whole
                # working set: soft-thresholding zeroes slots whose partial
                # correlation is < lam exactly, so recruited-but-inactive
                # extras sit at beta == 0 long before DEL evicts them —
                # forcing the equality KKT on those slots would push them
                # off zero and lose the accept test every step.
                m = aset.mask & (beta_c != 0.0)
                sgn = jnp.sign(beta_c)
                mf = m.astype(X.dtype)
                Gm = (G * (mf[:, None] * mf[None, :]) +
                      jnp.diag(1.0 - mf))
                rhs = (rho - lam * sgn) * mf
                b_n = jnp.where(m, jnp.linalg.solve(Gm, rhs), 0.0)
                z_n = Xa @ b_n
                th_n, gap_n = _dual_and_gap(loss, Xa, y, b_n, z_n, m, lam)
                gap_n = jnp.asarray(gap_n, X.dtype)
                better = gap_n < gap_c          # NaN/garbage reads False
                return (jnp.where(better, b_n, beta_c),
                        jnp.where(better, z_n, z_c),
                        jnp.where(better, th_n, theta_c_),
                        jnp.where(better, gap_n, gap_c))

            with jax.named_scope("cm"):
                beta, z, theta, gap = jax.lax.cond(
                    ~s.is_add, newton_step, lambda a: a,
                    (beta, z, theta, gap))
        aset = aset._replace(beta=beta)

        # --- ball region from the backend's dual point (Thm 2 / Eq. 12) ----
        # The radius is floored at the gap's own arithmetic precision: a
        # machine-converged sub-problem reports gap 0 (or negative) and a
        # zero radius would let the strict DEL / ADD-stop comparisons evict
        # or ignore boundary features (|x^T theta*| = 1) on float noise —
        # the near-lambda_max gaussian-design support misses (ROADMAP item).
        with jax.named_scope("gap"):
            ball = gap_ball(loss, theta, gap, lam,
                            floor=gap_precision_floor(theta, lam))
            if use_seq_ball:
                # lam_max(t) over the *active* features (paper Sec 2.2).
                c0_active = jnp.where(aset.mask, jnp.take(c0, aset.idx),
                                      -jnp.inf)
                lam0t = jnp.maximum(jnp.max(c0_active), lam * (1 + 1e-12))
                theta0t = -g0 / lam0t
                b_seq = sequential_ball(loss, y, theta0t, lam0t, lam)
                ball = intersect_balls(b_seq, ball)
        # delta shrinks the radius for the ADD-side rules only (its paper
        # role: avoid recruiting inaccurately-screened features early). DEL
        # keeps the full gap-safe radius: a delta-shrunk DEL can evict
        # genuinely-active features of the sub-problem, destroying CM
        # progress and thrashing (observed experimentally; documented
        # deviation in DESIGN.md §2).
        if screen_rule.add_bound == "point":
            # strong-rule geometry (DESIGN.md §13): the ADD screen runs at
            # radius 0 — pure KKT violation at the current dual iterate.
            # Aggressive, not safe; the post-check below gates every stop.
            r_eff = jnp.zeros_like(ball.radius)
        else:
            r_eff = s.delta * ball.radius
        r_del = ball.radius
        theta_c = ball.center

        # --- global stop check (gap target reached & recruiting finished) --
        stop_now = (~s.is_add) & (gap <= eps)

        # --- DEL (gap-safe rule on the sub-problem) ------------------------
        with jax.named_scope("gap"):
            corr_act = jnp.abs(Xa.T @ theta_c)                 # (k_max,)
            norm_act = jnp.where(aset.mask, jnp.take(col_norm, aset.idx),
                                 0.0)
            del_mask = aset.mask & (corr_act + norm_act * r_del < 1.0)
        if unpen_idx >= 0:
            # the unpenalized slot is always resident: its dual constraint
            # is an equality (Thm 7), so the <1 DEL rule never applies
            del_mask = del_mask & (aset.idx != unpen_idx)
        with jax.named_scope("add_delete"):
            aset = jax.lax.cond(
                stop_now, lambda a: a,
                lambda a: aset_lib.delete_features(a, del_mask), aset)

        # --- ADD phase ------------------------------------------------------
        def do_add_phase(args):
            aset, delta, is_add = args
            # One backend call covers the whole full-width decision: the
            # ADD-stop bound, the top-h candidates and their violation
            # counts. No (p,)-shaped sort, no second full-width pass.
            with jax.named_scope("screen"):
                out: ScreenOut = screen(theta_c, r_eff, aset.in_active)
            # stop criterion for ADD (Remark 1): max_{R_t} ub < 1
            add_done = out.max_ub < 1.0
            n_sur = _n_surv32(out)
            n_scr = (jnp.sum(~aset.in_active).astype(jnp.int32) - n_sur)

            def on_done(args):
                aset, delta, is_add = args
                if not screen_rule.delta_ramp:
                    # point-bound rules (DESIGN.md §13): no violator at the
                    # current iterate means recruiting is over — go straight
                    # to the polish phase; the safe post-check still gates
                    # the eventual stop.
                    return aset, delta, jnp.asarray(False)
                grown = jnp.minimum(10.0 * delta, 1.0)
                new_delta = jnp.where(delta < 1.0, grown, delta)
                new_is_add = jnp.where(delta < 1.0, is_add, False)
                return aset, new_delta, new_is_add

            def on_add(args):
                aset, delta, is_add = args
                # Algorithm 2: candidates = top-h by score; candidate l is
                # added iff its violation count |V_i| < h~, evaluated against
                # R_t minus the better-ranked candidates (cumulative-AND).
                ranks = jnp.arange(h)
                v_count = jnp.maximum(out.cand_ge - 1 - ranks, 0)
                keep = ((v_count < h_tilde) & (ranks < h_cap) &
                        jnp.isfinite(out.cand_score))
                if screen_rule.add_bound == "point":
                    # strong-rule recruiting: only actual KKT violators
                    # (ub = score >= 1) enter; scores sort descending so
                    # the cumulative-AND below keeps the violator prefix
                    keep = keep & (out.cand_score >= 1.0)
                keep = jnp.cumprod(keep.astype(jnp.int32)).astype(bool)
                # Progress guarantee (TPU adaptation, DESIGN.md §2): when the
                # sub-problem is already solved to near-target accuracy but no
                # candidate passes the violation test (radius floored by
                # arithmetic precision), force-recruit every candidate the
                # ball cannot rule out, and the top-scoring feature. The
                # survivor blocking the ADD stop can sit below the top score
                # (a larger column norm widens its bound); recruiting only
                # the top score, which DEL then proves zero and evicts,
                # cycles until max_outer. ADDing extra features is always
                # safe (Thm 1a) — it can only cost compute, never
                # correctness.
                stuck = gap <= 100.0 * eps
                ub_c = out.cand_score + jnp.take(col_norm, out.cand_idx) * r_eff
                keep = keep | (stuck & jnp.isfinite(out.cand_score)
                               & (ub_c >= 1.0))
                keep = keep.at[0].set(
                    keep[0] | (stuck & jnp.isfinite(out.cand_score[0])))
                return (aset_lib.add_features(aset, out.cand_idx, keep),
                        delta, is_add)

            with jax.named_scope("add_delete"):
                aset, delta, is_add = jax.lax.cond(
                    add_done, on_done, on_add, (aset, delta, is_add))
            return aset, delta, is_add, n_scr, n_sur

        if screen_rule.add_bound == "point":
            # the point screen costs one matvec, so it runs on EVERY
            # non-stopping step, polish phase included: a feature whose
            # score crosses 1 mid-convergence is recruited the burst it
            # crosses, not discovered by the final post-check after full
            # convergence (each such late discovery would otherwise pay a
            # whole re-convergence of the sub-problem — measured 3-4x the
            # total solve time on the CI benchmark shape). ``is_add``
            # still flips off at the first violator-free screen and stays
            # off (long polish bursts); late recruits don't re-enter the
            # short-burst phase.
            do_add = ~stop_now
        else:
            do_add = s.is_add & ~stop_now
        aset, delta, is_add, n_scr, n_sur = jax.lax.cond(
            do_add, do_add_phase,
            lambda args: args + (jnp.full((), -1, jnp.int32),
                                 jnp.full((), -1, jnp.int32)),
            (aset, s.delta, s.is_add))

        # --- safe post-check (hybrid rule, DESIGN.md §13) -------------------
        # A point-bound ADD phase discards aggressively, so termination is
        # gated behind ONE full screen at the certified safe radius: any
        # violator denies the stop and is recruited on the spot (the safe
        # fallback). The active set strictly grows on every failed check,
        # so at most p checks can fail — termination is preserved. All
        # ADDs are safe (Thm 1a); a solve can only stop with a passing
        # safe certificate, so hybrid keeps the SAIF guarantee.
        if screen_rule.post_check:
            def check(a):
                with jax.named_scope("screen"):
                    chk: ScreenOut = screen(theta_c, r_del, a.in_active)
                viol = chk.max_ub >= 1.0
                # recruit every candidate the safe ball cannot rule out;
                # force slot 0 so a failed check always makes progress
                # (max_ub can come from a non-candidate column, so the
                # top-score recruit is the progress guarantee, not ub_c)
                ub_c = (chk.cand_score +
                        jnp.take(col_norm, chk.cand_idx) * r_del)
                keep = viol & jnp.isfinite(chk.cand_score) & (ub_c >= 1.0)
                keep = keep.at[0].set(
                    viol & jnp.isfinite(chk.cand_score[0]))
                with jax.named_scope("add_delete"):
                    aset_c = aset_lib.add_features(a, chk.cand_idx, keep)
                return aset_c, viol.astype(jnp.int32)

            def no_check(a):
                return a, jnp.full((), -1, jnp.int32)

            aset, post_viol = jax.lax.cond(stop_now, check, no_check, aset)
            stop_final = stop_now & (post_viol != 1)
        else:
            post_viol = jnp.full((), -1, jnp.int32)
            stop_final = stop_now

        n_act = aset.count.astype(X.dtype)
        return _State(
            aset=aset, z=z, gap=gap, delta=delta, is_add=is_add,
            stop=stop_final, t=s.t + 1, inner=inner_carry,
            trace_n_active=s.trace_n_active.at[s.t].set(n_act),
            trace_gap=s.trace_gap.at[s.t].set(gap),
            trace_screened=s.trace_screened.at[s.t].set(n_scr),
            trace_survivors=s.trace_survivors.at[s.t].set(n_sur),
            trace_post_viol=s.trace_post_viol.at[s.t].set(post_viol))

    final = jax.lax.while_loop(cond, body, state0)
    beta_full = aset_lib.scatter_beta(final.aset, p)
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


def saif_jit_compile_count() -> int:
    """Number of distinct solver-core compilations alive in this process
    (the serial ``_saif_jit`` cache plus the fleet engine's
    ``_saif_batch_jit`` cache, once that module has been imported).

    The compile-first path engine, the batch engine and the benchmarks
    assert on deltas of this counter (acceptance: O(log p) compilations
    per lambda path; exactly 1 per fleet).
    """
    import sys
    total = int(_saif_jit._cache_size())
    batch_mod = sys.modules.get("repro.core.batch")
    if batch_mod is not None:
        total += int(batch_mod._saif_batch_jit._cache_size())
        total += int(batch_mod._saif_batch_fast_jit._cache_size())
    return total


class PathState(NamedTuple):
    """One-time O(np) problem preparation (c0 / col_norm / lambda_max and
    the host-side c0 statistics the h formula needs, synced exactly once).

    Shared by every driver layer: the single-lambda solver consumes one,
    the compile-first path engine (``core/path.py``) threads one through a
    whole grid, and a :class:`repro.core.api.Session` computes one at
    ``open_session`` and serves every subsequent request from it.
    """
    X: jax.Array          # (n, p)
    y: jax.Array          # (n,)
    c0: jax.Array         # (p,) |X^T f'(null model)|
    col_norm: jax.Array   # (p,)
    lam_max: float
    c0_max: float         # host copies of the c0 statistics the h formula
    c0_median: float      # needs — synced exactly once per preparation
    b0: float = 0.0       # unpenalized-slot null fit (fused problems; §7)
    # Bucket-padded preparations (DESIGN.md §12): X/y carry trailing zero
    # rows/columns up to a compile-bucket shape, while every *policy*
    # quantity (h, capacity, backend crossovers, initial support) must be
    # computed on the real problem. 0 means "unpadded: use X.shape".
    n_true: int = 0
    p_true: int = 0


def prepare_path(X, y, config: SaifConfig) -> PathState:
    """The one-time preparation pass (see :class:`PathState`).

    Penalized-null model: f'(0) for plain LASSO; with an unpenalized
    coordinate the null model sits at its partial optimum b0 (Thm 7) and
    c0[unpen] is 0, so lambda_max / h / the initial set stay exact.
    """
    from repro.core.duality import null_gradient

    loss = get_loss(config.loss)
    with span("repro.session.prepare"):
        X = jnp.asarray(X)
        y = jnp.asarray(y)
        _, c0, b0 = null_gradient(loss, X, y, config.unpen_idx)
        col_norm = jnp.linalg.norm(X, axis=0)
        stats = (jnp.max(c0), jnp.median(c0), b0)
        with span("repro.sync.path_stats"):
            c0_max, c0_median, b0 = jax.device_get(stats)
    return PathState(X=X, y=y, c0=c0, col_norm=col_norm,
                     lam_max=float(c0_max), c0_max=float(c0_max),
                     c0_median=float(c0_median), b0=float(b0))


def pad_path_state(prep: PathState, n_bucket: int,
                   p_bucket: int) -> PathState:
    """Zero-pad a real preparation up to a compile-bucket shape
    (DESIGN.md §12).

    The stats stay those of the REAL problem: c0 pads sit at -inf (they
    can never win a top-k or a max), col-norm pads at 1.0 (never read —
    pads are masked out of every screen — but a finite value keeps any
    speculative lane arithmetic NaN-free), and ``n_true``/``p_true``
    record the real dims for every policy formula. Zero pad rows are
    mathematically inert for least squares (each contributes exactly 0
    to the primal, the gradient and the column norms); column padding is
    additionally *bitwise*-inert because no engine reduction ever runs
    over the feature axis (screens score per column with the
    padding-stable ``theta @ X`` orientation, selection is top-k/max).
    """
    n, p = prep.X.shape
    if n_bucket < n or p_bucket < p:
        raise ValueError(
            f"bucket ({n_bucket}, {p_bucket}) must dominate the problem "
            f"shape ({n}, {p})")
    if (n_bucket, p_bucket) == (n, p):
        return prep
    return prep._replace(
        X=jnp.pad(prep.X, ((0, n_bucket - n), (0, p_bucket - p))),
        y=jnp.pad(prep.y, (0, n_bucket - n)),
        c0=jnp.pad(prep.c0, (0, p_bucket - p), constant_values=-jnp.inf),
        col_norm=jnp.pad(prep.col_norm, (0, p_bucket - p),
                         constant_values=1.0),
        n_true=n, p_true=p)


def solve_scalar(prep: PathState, lam: float,
                 config: SaifConfig = SaifConfig(),
                 scan_fn: Optional[ScanFn] = None,
                 screen_fn: Optional[ScreenFn] = None,
                 warm_idx: Optional[jax.Array] = None,
                 warm_beta: Optional[jax.Array] = None) -> SaifResult:
    """Solve LASSO at ``lam`` from an existing preparation. Host driver.

    Handles the static pieces (h, capacity, initial active set, screening
    backend selection) and the capacity-overflow recompile loop; everything
    else runs inside one jitted while_loop. ``screen_fn`` plugs a full
    custom backend (e.g. the sharded one); ``scan_fn`` is the legacy
    bare-scan hook, adapted on the fly. :func:`saif` is the prepare+solve
    convenience; a session (``repro.core.api``) prepares once and calls
    this per request.
    """
    # per-request preparation: h, capacity, initial active set
    with span("repro.session.prepare"):
        X, y, c0, col_norm = prep.X, prep.y, prep.c0, prep.col_norm
        n, p = X.shape
        # Bucket-padded preparations (DESIGN.md §12): the arrays carry the
        # bucket shape; every policy decision below runs on the real dims
        # so padding can never change h, capacity, or a backend crossover.
        n_true = prep.n_true or n
        p_true = prep.p_true or p
        pad_mask = (jnp.arange(p) >= p_true) if p_true < p else None
        unpen = config.unpen_idx
        lam_max = prep.lam_max
        b0 = prep.b0
        rule = resolve_screen_rule(config.screen_rule)
        # The Thm-2 sequential ball assumes the all-penalized null dual
        # theta0 = -f'(0)/lam_max — invalid once b is unpenalized
        # (DESIGN.md §7), so the gap ball alone drives screening there. The
        # rule gates it too: gap_safe/hybrid screen on the gap sphere alone
        # (§13).
        use_seq = config.use_seq_ball and unpen is None and rule.use_seq_ball

        h = add_batch_size_static(config.c, lam, prep.c0_max, prep.c0_median,
                                  p_true)
        h_tilde = max(int(math.ceil(config.zeta * h)), 1)
        k_max = config.k_max or default_capacity(h, p_true)
        delta0 = config.delta0 if config.delta0 is not None else \
            min(max(lam / lam_max, 1e-3), 1.0)
        backend = resolve_backend(config.screen_backend, X.dtype)

        # Initial active set: top-h' by |X^T f'(0)| (Algorithm 1 line 1),
        # or a warm start from a neighbouring lambda (Sec 5.3 path mode).
        # Always padded to (k_max,) so warm-started paths share one
        # compilation.
        if warm_idx is not None:
            k_max = max(k_max, default_capacity(h, p_true))
            if unpen is None:
                # plain LASSO: stay on device, no host round-trip
                n_init = min(int(warm_idx.shape[0]), k_max, p_true)
                init_idx = jnp.zeros((k_max,), jnp.int32).at[:n_init].set(
                    jnp.asarray(warm_idx)[:n_init].astype(jnp.int32))
                init_beta = jnp.zeros((k_max,), X.dtype)
                if warm_beta is not None:
                    init_beta = init_beta.at[:n_init].set(
                        jnp.asarray(warm_beta)[:n_init].astype(X.dtype))
            else:
                with span("repro.sync.warm_start"):
                    warm_ids = [int(i) for i in jnp.asarray(warm_idx).tolist()]
                with span("repro.sync.warm_start"):
                    warm_vals = (list(jnp.asarray(warm_beta).tolist())
                                 if warm_beta is not None
                                 else [0.0] * len(warm_ids))
                if unpen not in warm_ids:
                    # the unpenalized slot is always resident, even when
                    # the previous lambda left b exactly 0 — PREPEND it so
                    # a capacity-full warm support can never truncate it
                    # away
                    warm_ids.insert(0, unpen)
                    warm_vals.insert(0, float(b0))
                n_init = min(len(warm_ids), k_max, p_true)
                init_idx = jnp.zeros((k_max,), jnp.int32).at[:n_init].set(
                    jnp.asarray(warm_ids[:n_init], jnp.int32))
                init_beta = jnp.zeros((k_max,), X.dtype).at[:n_init].set(
                    jnp.asarray(warm_vals[:n_init], X.dtype))
        else:
            init_idx, init_beta, n_init = initial_support(
                c0, h, k_max, p_true, unpen, b0, X.dtype)

    while True:
        # one engine dispatch through the read that waits for it
        with span("repro.engine.run", b=1, h=h, k_max=k_max):
            init_idx = init_idx[:k_max]
            init_beta = init_beta[:k_max]
            if init_idx.shape[0] < k_max:   # capacity grew after overflow
                pad = k_max - init_idx.shape[0]
                init_idx = jnp.pad(init_idx, (0, pad))
                init_beta = jnp.pad(init_beta, (0, pad))
            # capacity growth can move the auto crossover (DESIGN.md §6)
            inner = resolve_inner_backend(config.inner_backend, config.loss,
                                          n_true, k_max, X.dtype)
            carry = cold_inner_carry(k_max, X.dtype, backend=inner)
            # the engine dispatch routes through the fault-injection seam
            # (repro.runtime.inject) — a single None-check when disarmed
            res = _fault_seam("serial", lambda: _saif_jit(
                X, y, col_norm, c0, jnp.asarray(lam, X.dtype),
                jnp.asarray(config.eps, X.dtype),
                delta0, init_idx, init_beta,
                jnp.arange(k_max) < n_init,
                carry.G, carry.rho, carry.gidx,
                jnp.asarray(h_tilde, jnp.int32),
                jnp.asarray(h, jnp.int32),
                pad_mask,
                loss_name=config.loss, h=h,
                k_max=k_max, inner_epochs=config.inner_epochs,
                polish_factor=config.polish_factor,
                max_outer=config.max_outer,
                use_seq_ball=use_seq,
                screen_backend=backend, inner_backend=inner,
                unpen_idx=-1 if unpen is None else unpen,
                screen_fn=screen_fn, scan_fn=scan_fn,
                screen_rule=rule))
            with span("repro.sync.overflow"):
                overflowed = bool(res.overflowed)
        if not overflowed or k_max >= p_true:
            return res
        k_max = min(2 * k_max, p_true)  # elastic capacity growth + recompile


def saif(X, y, lam: float, config: SaifConfig = SaifConfig(),
         scan_fn: Optional[ScanFn] = None,
         screen_fn: Optional[ScreenFn] = None,
         warm_idx: Optional[jax.Array] = None,
         warm_beta: Optional[jax.Array] = None) -> SaifResult:
    """Solve LASSO at ``lam`` with SAIF: one-shot prepare + solve.

    Thin over :func:`prepare_path` + :func:`solve_scalar`. Callers with
    more than one request on the same problem should hold a session
    instead (``repro.open_session``) so the preparation, the compile
    caches and the warm buffers persist across requests (DESIGN.md §9).
    """
    return solve_scalar(prepare_path(X, y, config), lam, config,
                        scan_fn=scan_fn, screen_fn=screen_fn,
                        warm_idx=warm_idx, warm_beta=warm_beta)
