"""Compile-first warm-started SAIF lambda-path engine (paper Sec 5.3).

The naive path driver (kept as :func:`saif_path_naive`, the benchmark
baseline) calls the single-lambda host driver per grid point, which costs
per lambda: an O(np) re-preprocessing of (c0, col_norm, lam_max), a host
sync for the overflow flag, a host round-trip to extract the warm-start
support, and — whenever the static (h, k_max) signature moves — a fresh
``_saif_jit`` compilation.

The engine here (:func:`run_path`) hoists all of that out of the lambda
loop:

  * **prepare once** — the driver consumes a prebuilt
    :class:`~repro.core.saif.PathState` (c0 / col_norm / lam_max and the
    c0 statistics feeding the h formula, computed exactly once — at
    ``open_session`` when serving through :mod:`repro.core.api`);
  * **one static signature** — the candidate-buffer size h is bucketed to
    the *grid maximum* (already a power of two) so every lambda shares a
    single ``_saif_jit`` compilation, while the per-lambda batch size
    (h_cap) and violation tolerance (h~) ride along as *traced* scalars —
    they only feed comparisons. The ADD decisions are therefore bitwise
    those of a per-lambda compile; only the compile count changes. Worst
    case over capacity growth this is O(log p) distinct compilations per
    path (assert via :func:`repro.core.saif.saif_jit_compile_count`);
  * **fixed-capacity warm buffers** — the (k_max,) warm-start index/value
    buffers are produced *on device* from the previous solution and
    *preserve the slot layout* of the previous solve, so the inter-lambda
    handoff never syncs to the host AND the inner-solver carry (the Gram
    buffers of the covariance-update backend, DESIGN.md §6) rides along
    verbatim — the next solve's init finds zero dirty slots and skips the
    O(n k^2) Gram rebuild. The same warm tuple is the engine's *boundary*
    state: ``run_path`` accepts an entry warm state and returns its exit
    warm state, which is how a session keeps the buffers device-resident
    across requests (``Scalar(lam, warm=True)`` streams);
  * **segment-batched overflow checks** — solutions are collected per path
    segment and the ``overflowed`` flags are reduced in one host sync per
    segment instead of one per lambda. On overflow the capacity doubles and
    the segment re-runs from its entry state (rare: capacity starts at the
    grid-max 8h).

The legacy frontend :func:`saif_path` is a deprecated shim over a one-shot
session (DESIGN.md §9).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core._compat import warn_deprecated
from repro.core.duality import (duality_gap, feasible_dual, gap_ball,
                                sequential_ball)
from repro.core.inner_backend import (InnerCarry, cold_inner_carry,
                                      resolve_inner_backend)
from repro.core.losses import get_loss
from repro.core.saif import (PathState, SaifConfig, SaifResult, _saif_jit,
                             add_batch_size_static, default_capacity,
                             initial_support, prepare_path, saif,
                             saif_jit_compile_count)
from repro.core.screen_backend import (ScreenFn, resolve_backend,
                                       resolve_screen_rule)
from repro.runtime.inject import seam as _fault_seam

# Device-resident inter-solve handoff: (idx (k,), beta (k,), live-mask (k,),
# InnerCarry). Produced by _warm_state / cold_start, consumed by run_path.
WarmState = Tuple[jax.Array, jax.Array, jax.Array, InnerCarry]


class SaifPathResult(NamedTuple):
    lams: np.ndarray
    betas: List[jnp.ndarray]
    results: List[SaifResult]
    n_compilations: Optional[int] = None   # _saif_jit compiles this path added


@partial(jax.jit, static_argnames=("unpen_idx",))
def _warm_state(active_idx: jax.Array, active_mask: jax.Array,
                beta_full: jax.Array, inner: InnerCarry,
                unpen_idx: int = -1) -> WarmState:
    """Device-side warm-start extraction, *slot-preserving*.

    The next lambda is seeded with the previous solve's final slot layout
    (masked down to the nonzero support), so the Gram buffers in ``inner``
    — which are indexed by slot — remain valid verbatim: the next
    ``_saif_jit``'s init finds zero dirty slots and skips the O(n k^2)
    rebuild entirely (DESIGN.md §6). No host round-trip anywhere. The
    unpenalized slot (fused paths) stays resident even at b = 0 exactly.
    """
    vals = jnp.where(active_mask, jnp.take(beta_full, active_idx), 0.0)
    live = active_mask & (vals != 0)
    if unpen_idx >= 0:
        live = live | (active_mask & (active_idx == unpen_idx))
    return active_idx, jnp.where(live, vals, 0.0), live, inner


def cold_start(prep: PathState, h0: int, k: int,
               config: SaifConfig) -> WarmState:
    """Cold entry state at capacity ``k``: the shared ``initial_support``
    constructor seeded with the FIRST lambda's own batch size ``h0`` (not
    the grid-max h) — a cold path entry must match a standalone solve at
    its first lambda exactly."""
    n, p = prep.X.shape
    idx, beta, n_init = initial_support(prep.c0, h0, k, prep.p_true or p,
                                        config.unpen_idx, prep.b0,
                                        prep.X.dtype)
    inner = resolve_inner_backend(config.inner_backend, config.loss,
                                  prep.n_true or n, k, prep.X.dtype)
    return (idx, beta, jnp.arange(k) < n_init,
            cold_inner_carry(k, prep.X.dtype, backend=inner))


def grow_warm(warm: WarmState, k: int, inner_name: str) -> WarmState:
    """Pad a warm state to capacity ``k`` (elastic growth / session handoff
    across requests of different static signatures)."""
    idx, vals, mask, carry = warm
    pad = k - idx.shape[0]
    if pad <= 0:
        return warm
    if inner_name == "gram" and carry.G.shape[0] == idx.shape[0]:
        # pad the Gram buffers in place: padded slots are dead/-1, the
        # carried warmth survives the capacity doubling
        carry = InnerCarry(
            G=jnp.pad(carry.G, ((0, pad), (0, pad))),
            rho=jnp.pad(carry.rho, (0, pad)),
            gidx=jnp.pad(carry.gidx, (0, pad), constant_values=-1))
    else:   # crossover flipped the backend: rebuild a cold carry
        carry = cold_inner_carry(k, vals.dtype, backend=inner_name)
    return (jnp.pad(idx, (0, pad)), jnp.pad(vals, (0, pad)),
            jnp.pad(mask, (0, pad)), carry)


@partial(jax.jit, static_argnames=("loss_name",))
def _seq_entry_jit(X, y, col_norm, idx, vals, mask, gidx, lam0, lam, p_true,
                   loss_name: str = "least_squares"):
    """Theorem-2 sequential-ball warm entry (DESIGN.md §14), compiled.

    Given a cached solution at ``lam0 >= lam`` (slot layout idx/vals/mask
    plus its gram carry's gidx), certify a dual ball that contains the
    *target* dual optimum theta*(lam) and pre-recruit its screening
    survivors into the free slots:

      * theta0 = feasible dual of the cached primal at lam0, with
        gap0 its duality gap — so theta*(lam0) lies in the gap sphere
        B(theta0, r_gap0) (Ndiaye et al., "Mind the duality gap");
      * the paper's Theorem-2 sequential ball maps theta*(lam0) to a
        ball around (lam0/lam) theta*(lam0); seeding it from theta0
        instead is made rigorous by widening with the *propagated* gap
        radius: theta*(lam) in B((lam0/lam) theta0,
        r_seq + (lam0/lam) r_gap0), since the center moved by at most
        (lam0/lam) ||theta0 - theta*(lam0)||.

    Features with ub_j = |x_j^T center| + ||x_j|| r < 1 are certified
    inactive at lam; the survivors (minus those already resident) fill
    the free slots with vals 0 and gidx -1, so the engine's ``init``
    reconciles the new columns in-trace (one bounded rebuild, no
    recompile). The cached live slots keep gidx untouched — an exact-
    lambda repeat enters with zero dirty slots. This only *seeds* the
    active set: the solve itself still runs SAIF's ADD loop and stop
    test, so the end result stays KKT-certified regardless of the seed.
    """
    loss = get_loss(loss_name)
    p = X.shape[1]
    k = idx.shape[0]
    vals = jnp.where(mask, vals, 0.0)
    cols = jnp.take(X, idx, axis=1)
    z = cols @ vals
    hat = -loss.grad(z, y) / lam0
    theta0 = feasible_dual(loss, X, y, hat, lam0)
    gap0 = jnp.maximum(duality_gap(loss, cols, y, vals, theta0, lam0,
                                   mask=mask), 0.0)
    r_gap0 = gap_ball(loss, theta0, gap0, lam0).radius
    ball = sequential_ball(loss, y, theta0, lam0, lam)
    r = ball.radius + (lam0 / lam) * r_gap0
    ub = jnp.abs(X.T @ ball.center) + col_norm * r
    real = jnp.arange(p) < p_true          # bucket-padded columns never seed
    survive = (ub >= 1.0) & real
    # pre-recruit survivors not already resident into the free slots
    in_slots = jnp.zeros((p,), bool).at[idx].max(mask)
    score = jnp.where(survive & ~in_slots, ub, -jnp.inf)
    cand_score, cand_idx = jax.lax.top_k(score, k)
    ok = jnp.isfinite(cand_score)
    free_pos = jnp.nonzero(~mask, size=k, fill_value=k)[0]
    pos = jnp.where(ok, free_pos, k)       # k = out of range -> dropped
    idx2 = idx.at[pos].set(cand_idx, mode="drop")
    mask2 = mask.at[pos].set(True, mode="drop")
    gidx2 = gidx.at[pos].set(-1, mode="drop")
    n_seeded = jnp.sum(ok & (free_pos < k)).astype(jnp.int32)
    return idx2, vals, mask2, gidx2, jnp.sum(survive).astype(jnp.int32), \
        n_seeded


def seq_warm_entry(prep: PathState, warm: WarmState, k_max: int,
                   lam0: float, lam: float,
                   config: SaifConfig) -> Tuple[WarmState, int]:
    """Build a certified warm-entry state at ``lam`` from a cached
    solution at ``lam0`` (the cross-request homotopy cache's hit path,
    DESIGN.md §14). Host-sync-free: one jitted call, lam/lam0 traced, so
    every (shape, capacity) pair compiles exactly once."""
    n, _ = prep.X.shape
    k_out = max(int(k_max), int(warm[0].shape[0]))
    name = resolve_inner_backend(config.inner_backend, config.loss,
                                 prep.n_true or n, k_out, prep.X.dtype)
    idx, vals, mask, carry = grow_warm(warm, k_out, name)
    X = prep.X
    p_true = prep.p_true or X.shape[1]
    idx2, vals2, mask2, gidx2, _, _ = _seq_entry_jit(
        X, prep.y, prep.col_norm, idx, vals, mask, carry.gidx,
        jnp.asarray(lam0, X.dtype), jnp.asarray(lam, X.dtype),
        jnp.asarray(p_true, jnp.int32), loss_name=config.loss)
    return ((idx2, vals2, mask2,
             InnerCarry(G=carry.G, rho=carry.rho, gidx=gidx2)), k_out)


def _segments(n_lams: int, segment_len: int) -> List[slice]:
    return [slice(i, min(i + segment_len, n_lams))
            for i in range(0, n_lams, segment_len)]


def run_path(prep: PathState, lams: Sequence[float],
             config: SaifConfig = SaifConfig(),
             make_screen: Optional[Callable[[int], ScreenFn]] = None,
             segment_len: int = 16,
             warm0: Optional[WarmState] = None,
             k_max0: Optional[int] = None
             ) -> Tuple[SaifPathResult, WarmState, int]:
    """The path engine: solve a descending lambda grid from ``prep``, each
    solve warm-starting from the last.

    ``make_screen`` threads a custom screening backend through every solve:
    it is called once with the engine's grid-max candidate count h (which
    sizes the ScreenOut arrays and is only known here) and must return the
    ScreenFn, e.g. ``lambda h: make_sharded_screen(design, h)``. Otherwise
    ``config.screen_backend`` picks a built-in backend.

    ``warm0``/``k_max0`` are the session handoff: an entry warm state from
    a previous request (padded here if this grid needs more capacity) and
    the capacity it was built at. ``None`` means a cold entry — bitwise
    the legacy ``saif_path`` behavior. Returns ``(result, exit_warm,
    k_max)`` so the caller can keep the buffers device-resident.
    """
    X = prep.X
    n, p = X.shape
    # bucket-padded preparations: policy quantities on real dims, and the
    # traced pad mask rides every engine dispatch (DESIGN.md §12)
    n_true = prep.n_true or n
    p_true = prep.p_true or p
    pad_mask = (jnp.arange(p) >= p_true) if p_true < p else None
    unpen = config.unpen_idx
    unpen_static = -1 if unpen is None else unpen
    rule = resolve_screen_rule(config.screen_rule)
    # DESIGN.md §7 (fused) + §13 (rule geometry): the rule gates the
    # Theorem-2 ball exactly like the serial driver — warm lambda-path
    # steps are where the gap-safe/hybrid radii screen hardest (the entry
    # gap from the previous grid point is already tiny)
    use_seq = config.use_seq_ball and unpen is None and rule.use_seq_ball
    lams_np = np.asarray(sorted([float(l) for l in lams], reverse=True))
    backend = resolve_backend(config.screen_backend, prep.X.dtype)
    n_compile0 = saif_jit_compile_count()

    # One static signature for the whole path: grid-max h (pow2-bucketed).
    # h sizes the candidate shapes, so it must be static; the violation
    # tolerance h~ only feeds comparisons, so it stays a per-lambda traced
    # scalar — the active set remains exactly as lean as per-lambda
    # compilation would keep it, at one compile for the whole grid.
    hs = [add_batch_size_static(config.c, lam, prep.c0_max, prep.c0_median,
                                p_true)
          for lam in lams_np]
    h = max(hs) if hs else 1
    k_max = config.k_max or default_capacity(h, p_true)
    if k_max0 is not None:
        k_max = max(k_max, k_max0)
    if warm0 is not None:
        k_max = max(k_max, int(warm0[0].shape[0]))
    # the backend's candidate arrays must be sized for the grid-max h
    screen_fn = make_screen(h) if make_screen is not None else None

    def inner_name(k: int) -> str:
        return resolve_inner_backend(config.inner_backend, config.loss,
                                     n_true, k, prep.X.dtype)

    def run_lam(lam: float, h_lam: int, warm: WarmState) -> SaifResult:
        delta0 = config.delta0 if config.delta0 is not None else \
            min(max(lam / prep.lam_max, 1e-3), 1.0)
        warm_idx, warm_beta, warm_mask, carry = warm
        # per-lambda engine dispatch through the fault-injection seam
        # (repro.runtime.inject) — identity when disarmed
        return _fault_seam("path", lambda: _saif_jit(
            X, prep.y, prep.col_norm, prep.c0, jnp.asarray(lam, X.dtype),
            jnp.asarray(config.eps, X.dtype), delta0,
            warm_idx, warm_beta, warm_mask,
            carry.G, carry.rho, carry.gidx,
            jnp.asarray(max(int(np.ceil(config.zeta * h_lam)), 1),
                        jnp.int32),
            jnp.asarray(h_lam, jnp.int32),
            pad_mask,
            loss_name=config.loss, h=h, k_max=k_max,
            inner_epochs=config.inner_epochs,
            polish_factor=config.polish_factor,
            max_outer=config.max_outer, use_seq_ball=use_seq,
            screen_backend=backend, inner_backend=inner_name(k_max),
            unpen_idx=unpen_static, screen_fn=screen_fn,
            screen_rule=rule))

    results: List[SaifResult] = [None] * len(lams_np)
    if warm0 is not None:
        warm = grow_warm(warm0, k_max, inner_name(k_max))
    else:
        warm = cold_start(prep, hs[0] if hs else 1, k_max, config)
    for seg in _segments(len(lams_np), segment_len):
        entry = warm
        while True:
            cur = entry
            seg_results = []
            for j, lam in zip(range(seg.start, seg.stop), lams_np[seg]):
                res = run_lam(float(lam), hs[j], cur)
                seg_results.append(res)
                cur = _warm_state(res.active_idx, res.active_mask,
                                  res.beta, res.inner,
                                  unpen_idx=unpen_static)
            # ONE host sync per segment: the batched overflow check
            flags = jnp.stack([r.overflowed for r in seg_results])
            if not bool(jnp.any(flags)) or k_max >= p_true:
                break
            k_max = min(2 * k_max, p_true)  # elastic growth, segment re-entry
            entry = grow_warm(entry, k_max, inner_name(k_max))
        results[seg] = seg_results
        warm = cur

    betas = [r.beta for r in results]
    n_compile1 = saif_jit_compile_count()
    n_comp = max(n_compile1 - n_compile0, 0)
    return (SaifPathResult(lams=lams_np, betas=betas, results=results,
                           n_compilations=n_comp),
            warm, k_max)


def saif_path(X, y, lams: Sequence[float],
              config: SaifConfig = SaifConfig(),
              make_screen: Optional[Callable[[int], ScreenFn]] = None,
              segment_len: int = 16) -> SaifPathResult:
    """DEPRECATED legacy frontend — one-shot session over :func:`run_path`.

    Use ``repro.open_session(Problem(X, y), config).solve(Path(lams))``;
    a held-open session keeps the preparation, the compilation and the
    warm buffers alive for the next request (DESIGN.md §9).
    """
    warn_deprecated("repro.core.saif_path",
                    "session.solve(Path(lams))")
    from repro.core.api import Path as PathRequest
    from repro.core.api import Problem, open_session

    sess = open_session(Problem(X=X, y=y, loss=config.loss), config,
                        make_screen=make_screen, segment_len=segment_len)
    return sess.solve(PathRequest(lams=tuple(float(l) for l in lams)))


def saif_path_naive(X, y, lams: Sequence[float],
                    config: SaifConfig = SaifConfig()) -> SaifPathResult:
    """Pre-engine Python-loop driver: one full host round-trip per lambda.

    Kept verbatim as the benchmark baseline (BENCH_path.json tracks the
    engine's speedup over this) and as a brute-force parity oracle.
    """
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    lams_np = np.asarray(sorted([float(l) for l in lams], reverse=True))
    betas, results = [], []
    warm_idx = warm_beta = None
    for lam in lams_np:
        res = saif(X, y, float(lam), config,
                   warm_idx=warm_idx, warm_beta=warm_beta)
        betas.append(res.beta)
        results.append(res)
        support = jnp.nonzero(jnp.abs(res.beta) > 0,
                              size=res.beta.shape[0], fill_value=0)[0]
        n_sup = int(jnp.sum(jnp.abs(res.beta) > 0))
        if n_sup > 0:
            warm_idx = support[:n_sup]
            warm_beta = res.beta[warm_idx]
        else:
            warm_idx = warm_beta = None
    return SaifPathResult(lams=lams_np, betas=betas, results=results)


def lambda_grid(lam_max: float, n: int, lo_frac: float = 1e-3) -> np.ndarray:
    """Log-evenly spaced descending grid in [lo_frac*lam_max, lam_max)."""
    return np.geomspace(lam_max * (1 - 1e-9), lam_max * lo_frac, n)
