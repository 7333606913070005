"""Unified Problem/Session serving API — one declarative spec, one
persistent compiled session for every SAIF workload (DESIGN.md §9).

The SAFE line of work (El Ghaoui et al. 2013; Liu et al. 2014) frames safe
screening as a reusable *pre-solve service*, not a one-shot call — and the
repo's engines already price their economics that way: preparation
(c0 / column norms / the Theorem-6 transform / fleet prep) is one-time,
compilations are keyed on static shapes and meant to be reused, and warm
slot buffers hand device-resident state from one solve to the next. What
was missing is the object that *owns* that state across calls. This module
is that object:

  * :class:`Problem` — the declarative spec: design ``X``, response(s)
    ``y``, ``loss``, penalty ∈ {:func:`lasso` (default), :func:`fused`
    (tree ``parent``), :func:`group` (``gsize``)}, optional sample
    ``weights``.
  * :func:`open_session` — performs preparation exactly once, resolves the
    screen/inner backends through the existing ``resolve_*`` policies, and
    returns a long-lived :class:`Session`.
  * ``session.solve(request)`` — ONE entry point for every workload. A
    request is :class:`Scalar`, :class:`Path`, :class:`Fleet` or
    :class:`CV` — any of them with ``sharded=True`` to ride the §5
    feature-sharded screening collective (the session needs a ``mesh``).
  * ``session.compile_stats()`` — the per-module compile counters
    (``saif_jit_compile_count`` / ``saif_batch_compile_count`` /
    ``group_compile_count``) unified into one report; the serving
    contract is *one compilation per static key across the whole request
    stream*, asserted in tests/test_api.py.

Dispatch lands on the existing engines — ``_saif_jit`` via
:func:`repro.core.saif.solve_scalar`, the compile-first path engine
:func:`repro.core.path.run_path`, the fleet engine
:func:`repro.core.batch.fleet_solve`, :func:`repro.core.cv.cv_solve`,
:func:`repro.core.group.group_solve` and the sharded drivers — so session
results are BITWISE those of the legacy frontends (which are now thin
deprecated shims over one-shot sessions; migration table in DESIGN.md §9).

Default requests are *cold* (bitwise-reproducible, parity-testable);
``Scalar(lam, warm=True)`` / ``Path(lams, warm=True)`` opt into the
device-resident warm handoff — the previous solve's slot layout and inner
(Gram) carry seed the next solve exactly like the intra-path warm starts,
now *across* requests. That plus the persistent jit caches is what makes a
hot session serve a request stream at solve cost instead of
compile+prep+solve cost (benchmarks/bench_serve.py).

This module imports nothing jax-heavy at module scope: ``from repro
import Problem, Scalar, open_session`` stays cheap, and the engines load
on first use (the lazy surface contract of ``repro/__init__.py``).
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Any, List, NamedTuple, Optional, Tuple

import numpy as np

# streaming / model-selection request types (DESIGN.md §14) — both
# modules are import-light (numpy+stdlib at module scope), so re-export
# here keeps `from repro import Update, Select` on the cheap path while
# serving.py can isinstance-dispatch on api.Update / api.Select
from repro.core.online import Update
from repro.core.select import Select, SelectionReport
from repro.core.serving import highest_matmul_precision

__all__ = [
    "Problem", "Session", "open_session",
    "Scalar", "Path", "Fleet", "CV", "Update", "Select",
    "SelectionReport",
    "lasso", "fused", "group",
    "LassoPenalty", "FusedPenalty", "GroupPenalty",
    "GroupPathResult", "CompileStats", "unified_compile_count",
]


# ---------------------------------------------------------------------------
# penalty specs (plain data — no engine imports)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LassoPenalty:
    """Plain l1 penalty (the paper's Sections 2-3 problem)."""


@dataclasses.dataclass(frozen=True)
class FusedPenalty:
    """Tree fused-LASSO penalty ``lam * ||D beta||_1`` over the tree
    encoded by ``parent`` (Sec 4 / DESIGN.md §7). The session performs the
    Theorem-6 transform exactly once at ``open_session``."""
    parent: Any                       # (p,) parent ids, -1 at the root
    transform_backend: str = "auto"   # "auto" | "scan" | "pallas"


@dataclasses.dataclass(frozen=True)
class GroupPenalty:
    """Disjoint equal-size group-LASSO penalty (the paper's proposed
    extension; DESIGN.md §9)."""
    gsize: int


def lasso() -> LassoPenalty:
    """Penalty spec: plain LASSO (also the default, spelled ``"lasso"``)."""
    return LassoPenalty()


def fused(parent, transform_backend: str = "auto") -> FusedPenalty:
    """Penalty spec: tree fused LASSO over ``parent`` (−1 marks the root)."""
    return FusedPenalty(parent=np.asarray(parent),
                        transform_backend=transform_backend)


def group(gsize: int) -> GroupPenalty:
    """Penalty spec: group LASSO with consecutive groups of size ``gsize``."""
    return GroupPenalty(gsize=int(gsize))


def _coerce_penalty(pen) -> Any:
    if pen is None or pen == "lasso":
        return LassoPenalty()
    if isinstance(pen, (LassoPenalty, FusedPenalty, GroupPenalty)):
        return pen
    raise TypeError(
        f"unknown penalty spec {pen!r}: use 'lasso', lasso(), "
        f"fused(parent) or group(gsize)")


# ---------------------------------------------------------------------------
# the declarative problem spec + requests
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class Problem:
    """What to solve — independent of how and how often it will be served.

    ``y`` may be omitted for a fleet-only session (every :class:`Fleet`
    request carries its own responses). ``weights`` are optional sample
    weights for the default response; weighted problems ride the fleet
    engine (DESIGN.md §8), which is the one place the weighted gradient
    algebra lives.
    """
    X: Any
    y: Any = None
    loss: str = "least_squares"
    penalty: Any = "lasso"
    weights: Any = None

    def __post_init__(self):
        # admission control (DESIGN.md §10): non-finite data, degenerate
        # zero-norm columns, shape mismatches fail HERE with a typed
        # error — they never reach the compiled path
        from repro.core.serving import validate_problem
        validate_problem(self)


@dataclasses.dataclass(frozen=True)
class Scalar:
    """One solve at ``lam``. ``warm=True`` seeds from the session's
    device-resident warm state (slot layout + inner carry of the previous
    serial solve); the default is a cold, bitwise-reproducible solve.

    ``deadline_s``/``priority`` are the serving knobs shared by the sync
    ``ServingSession.solve()`` and the async ``Server.submit()``: a
    request past its deadline fails with ``DeadlineExceeded`` instead of
    occupying a solver, and higher-priority requests dequeue first.
    """
    lam: float
    warm: bool = False
    sharded: bool = False
    deadline_s: Optional[float] = None
    priority: int = 0

    def __post_init__(self):
        from repro.core.serving import validate_request
        validate_request(self)


@dataclasses.dataclass(frozen=True, eq=False)
class Path:
    """A descending lambda grid on the compile-first path engine.
    ``warm=True`` enters the grid from the session's warm state instead of
    the cold top-h start."""
    lams: Any
    warm: bool = False
    sharded: bool = False
    deadline_s: Optional[float] = None
    priority: int = 0

    def __post_init__(self):
        from repro.core.serving import validate_request
        validate_request(self)


@dataclasses.dataclass(frozen=True, eq=False)
class Fleet:
    """B lockstep solves over the shared design: per-request responses
    ``Y`` ((B, n) — a (n,) vector is a fleet of 1), scalar-or-(B,)
    ``lams``, optional (B, n) sample ``weights``. ``screen_fn`` is the
    advanced hook for a custom batched screening backend."""
    Y: Any
    lams: Any
    weights: Any = None
    sharded: bool = False
    screen_fn: Any = None
    deadline_s: Optional[float] = None
    priority: int = 0

    def __post_init__(self):
        from repro.core.serving import validate_request
        validate_request(self)


@dataclasses.dataclass(frozen=True, eq=False)
class CV:
    """K-fold cross-validation over a lambda grid (one fold-fleet
    compilation; DESIGN.md §8), scored by mean held-out loss, optionally
    refit at the winner."""
    n_folds: int
    lams: Any
    seed: int = 0
    keep_fold_betas: bool = False
    refit: bool = True
    sharded: bool = False
    deadline_s: Optional[float] = None
    priority: int = 0

    def __post_init__(self):
        from repro.core.serving import validate_request
        validate_request(self)


class GroupPathResult(NamedTuple):
    """Lambda path over a group-LASSO problem (a session-only workload —
    the legacy surface had no group path)."""
    lams: np.ndarray
    betas: List[Any]
    results: List[Any]                    # GroupSaifResult per lambda
    n_compilations: Optional[int] = None  # _gsaif_jit compiles added


# ---------------------------------------------------------------------------
# the shared session-kwargs spec (ONE signature for the whole entry-point
# family: open_session / open_serving / open_server all accept exactly
# these passthrough knobs — no drifting copies)
# ---------------------------------------------------------------------------

SESSION_KWARG_DEFAULTS = {
    "mesh": None,          # device mesh enabling sharded=True requests
    "segment_len": 16,     # path-engine overflow-sync segment length
    "make_screen": None,   # custom ScreenFn factory (h -> ScreenFn)
    "pad_to": None,        # (n_bucket, p_bucket) compile-bucket padding
    "warm_cache": None,    # shared cross-request homotopy WarmCache (§14)
}


def session_kwargs(**kw) -> dict:
    """Validate and normalize the shared session passthrough kwargs."""
    unknown = sorted(set(kw) - set(SESSION_KWARG_DEFAULTS))
    if unknown:
        raise TypeError(
            f"unknown session kwargs {unknown}; the shared spec accepts "
            f"{sorted(SESSION_KWARG_DEFAULTS)}")
    out = dict(SESSION_KWARG_DEFAULTS)
    out.update(kw)
    return out


# ---------------------------------------------------------------------------
# unified compile accounting
# ---------------------------------------------------------------------------

class CompileStats(NamedTuple):
    """Unified view of every solver-core jit cache (DESIGN.md §9).

    ``serial``/``fleet``/``group`` are the process-wide cache sizes of
    ``_saif_jit`` / ``_saif_batch_jit`` / ``_gsaif_jit``;
    ``since_open`` is the total's delta since the
    session opened — the number every serving assertion watches: across
    any request stream it must equal the number of *distinct static
    keys*, never the number of requests.
    """
    serial: int
    fleet: int
    group: int
    total: int
    since_open: int
    requests: int


def _cache_size(mod_name: str, fn_name: str) -> int:
    """Cache size of one engine's jit, 0 if the module was never imported
    (an un-imported engine has compiled nothing)."""
    mod = sys.modules.get(mod_name)
    if mod is None:
        return 0
    return int(getattr(mod, fn_name)._cache_size())


def _engine_cache_sizes() -> Tuple[int, int, int]:
    return (_cache_size("repro.core.saif", "_saif_jit"),
            _cache_size("repro.core.batch", "_saif_batch_jit"),
            _cache_size("repro.core.group", "_gsaif_jit"))


def unified_compile_count() -> int:
    """Total solver-core compilations alive in this process: the serial,
    fleet and group engine caches in one number (supersedes reading the
    three per-module counters separately)."""
    return sum(_engine_cache_sizes())


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------

class Session:
    """A long-lived solver for one :class:`Problem`.

    Owns, for its whole lifetime:

      * the one-time preparation (``PathState`` c0/col-norm stats, the
        Theorem-6 ``FusedDesign``, the ``GroupPrep``, the sharded design
        placement) — requests never re-prepare;
      * the resolved screen-backend policy and the per-h screen-function
        memo (ScreenFn objects are jit-static arguments, so they must be
        *the same object* across requests to share a compilation);
      * the device-resident warm state — slot layout, coefficients and
        inner (Gram) carry of the last serial solve, used by
        ``warm=True`` requests;
      * the request/compile accounting behind :meth:`compile_stats`.

    Construct via :func:`open_session`. Results are exactly the legacy
    frontends' result types (``SaifResult``, ``SaifPathResult``,
    ``FusedPathResult``, ``CVPathResult``, ``GroupSaifResult``, ...), and
    for default (cold) requests they are bitwise the legacy results.
    """

    @highest_matmul_precision
    def __init__(self, problem: Problem, config=None, **kwargs):
        kw = session_kwargs(**kwargs)
        self.problem = problem
        self.penalty = _coerce_penalty(problem.penalty)
        self.mesh = kw["mesh"]
        self._segment_len = kw["segment_len"]
        self._make_screen = kw["make_screen"]
        self._pad_to = kw["pad_to"]
        self._p_real = None             # real width when pad_to is set
        self._screen_memo = {}          # h -> ScreenFn (make_screen hook)
        self._sharded = None            # ShardedDesign, built lazily
        self._sharded_screen_memo = {}  # h -> sharded ScreenFn
        self._sharded_prep = None       # PathState over the padded design
        self._sharded_fleet = None      # fleet placement (c0 slot unused)
        self._sharded_fleet_screens = {}  # h -> batched sharded ScreenFn
        self._warm = None               # serial WarmState handoff
        self._warm_k = None
        self._sharded_warm = None
        self._sharded_warm_k = None
        self._gwarm = None              # group (gidx, gmask, beta_slots)
        self._requests = 0
        # streaming + homotopy-cache state (DESIGN.md §14)
        self._warm_cache = kw["warm_cache"]  # shared WarmCache or None
        self._online = None             # OnlineState once streaming
        self._last_lam = None           # last solved lambda (Update default)
        self._pending_events = []       # provenance drained by serving
        self._cache_last = None         # (digest, lam) of last cache store
        self._digest_memo = None        # problem digest, computed once

        if problem.X is None:
            raise ValueError("Problem.X is required")

        if self._pad_to is not None:
            # compile-bucket padding (DESIGN.md §12): the session holds a
            # bucket-shaped preparation whose stats were computed on the
            # real problem; results are sliced back to the real width.
            nb, pb = (int(self._pad_to[0]), int(self._pad_to[1]))
            n0, p0 = np.shape(problem.X)
            if nb < n0 or pb < p0:
                raise ValueError(
                    f"pad_to={self._pad_to} must dominate the problem "
                    f"shape ({n0}, {p0}) — buckets only pad, never crop")
            if problem.loss == "logistic" and nb > n0:
                raise NotImplementedError(
                    "row padding a logistic problem shifts the primal by "
                    "log(2) per pad row (the zero-row trick is exact for "
                    "least squares only); bucket logistic requests on "
                    "exact n (p-only padding), DESIGN.md §12")
            if problem.weights is not None:
                raise NotImplementedError(
                    "pad_to with sample weights: weighted problems ride "
                    "the fleet engine with per-problem column norms; "
                    "serve them from an unpadded session")
            if self._make_screen is not None:
                raise NotImplementedError(
                    "pad_to with a custom make_screen: the built-in "
                    "screens mask pad columns via the traced pad mask; a "
                    "custom backend would need its own masking")
            if not isinstance(_coerce_penalty(problem.penalty),
                              LassoPenalty):
                raise NotImplementedError(
                    "pad_to serves plain-LASSO problems (the fused "
                    "transform and group layout are shape-coupled)")
            self._pad_to = (nb, pb)
            self._p_real = p0

        if isinstance(self.penalty, GroupPenalty):
            from repro.core.group import GroupSaifConfig, prepare_group
            cfg = config if config is not None else GroupSaifConfig()
            if not isinstance(cfg, GroupSaifConfig):
                # accept a SaifConfig spec-side: map the shared fields
                cfg = GroupSaifConfig(
                    eps=cfg.eps, inner_epochs=cfg.inner_epochs,
                    polish_factor=cfg.polish_factor, k_max=cfg.k_max,
                    max_outer=cfg.max_outer, loss=cfg.loss)
            if cfg.loss != problem.loss:
                cfg = dataclasses.replace(cfg, loss=problem.loss)
            self.config = cfg
            if problem.y is None:
                raise ValueError("group sessions need Problem.y")
            if problem.weights is not None:
                raise NotImplementedError(
                    "weighted group problems are not supported")
            self._gprep = prepare_group(problem.X, problem.y,
                                        self.penalty.gsize, cfg)
            self.screen_backend = None   # the group engine has no pluggable
            self.screen_rule = None      # screen backend (nor rule)
            self._compiles0 = unified_compile_count()
            return

        from repro.core.saif import SaifConfig, prepare_path
        from repro.core.screen_backend import (resolve_backend,
                                               resolve_batch_screen,
                                               resolve_screen_rule)
        cfg = config if config is not None else SaifConfig()
        if cfg.loss != problem.loss:
            cfg = dataclasses.replace(cfg, loss=problem.loss)

        if isinstance(self.penalty, FusedPenalty):
            from repro.core.fused import prepare_fused
            import jax.numpy as jnp
            if problem.weights is not None:
                raise NotImplementedError(
                    "weighted fused problems are not supported")
            # the one-time Theorem-6 transform (chain Pallas kernel or
            # level-schedule scan) — THE preparation the fused session
            # amortizes over every subsequent request
            self._design = prepare_fused(problem.X, self.penalty.parent,
                                         self.penalty.transform_backend)
            cfg = dataclasses.replace(cfg, unpen_idx=self._design.unpen_idx)
            self.config = cfg
            if problem.y is not None:
                self._y = jnp.asarray(problem.y, self._design.Xt.dtype)
                self._prep = prepare_path(self._design.Xt, self._y, cfg)
            else:
                self._y = None
                self._prep = None
        else:
            self._design = None
            self.config = cfg
            self._y = problem.y
            if problem.weights is not None and self._make_screen is not None:
                raise NotImplementedError(
                    "make_screen with a weighted problem: the fleet "
                    "engine serving weighted problems takes per-request "
                    "Fleet(..., screen_fn=...) hooks instead")
            if problem.y is not None and problem.weights is None:
                self._prep = prepare_path(problem.X, problem.y, cfg)
                if self._pad_to is not None:
                    from repro.core.saif import pad_path_state
                    self._prep = pad_path_state(self._prep, *self._pad_to)
            else:
                self._prep = None
        try:
            self.screen_backend = resolve_backend(
                cfg.screen_backend,
                None if self._prep is None else self._prep.X.dtype)
        except ValueError:
            # fleet-only screen modes (the opt-in "matmul" shared-X fast
            # path, §8) resolve through the batch policy; serial requests
            # on such a session fail at the engine boundary exactly like
            # the legacy frontends did. An unknown name raises here.
            self.screen_backend = resolve_batch_screen(cfg.screen_backend)
        # the resolved certificate geometry (DESIGN.md §13) — validated at
        # open_session so a bad rule name fails before any engine dispatch,
        # and inspectable for Verdict provenance
        self.screen_rule = resolve_screen_rule(cfg.screen_rule)
        self._compiles0 = unified_compile_count()

    # ------------------------------------------------------------------
    # the one entry point
    # ------------------------------------------------------------------

    @highest_matmul_precision
    def solve(self, request):
        """Serve one request; see :class:`Scalar` / :class:`Path` /
        :class:`Fleet` / :class:`CV` for the workload shapes and the
        module docstring for the result types."""
        self._requests += 1
        if isinstance(request, Scalar):
            return self._solve_scalar(request)
        if isinstance(request, Path):
            return self._solve_path(request)
        if isinstance(request, Fleet):
            return self._solve_fleet(request)
        if isinstance(request, CV):
            return self._solve_cv(request)
        if isinstance(request, Update):
            return self._solve_update(request)
        if isinstance(request, Select):
            return self._solve_select(request)
        raise TypeError(f"unknown request {request!r}: expected Scalar, "
                        f"Path, Fleet, CV, Update or Select")

    def update(self, rows=None, responses=None, request=None, **kw):
        """Streaming verb (DESIGN.md §14): absorb an (m, p) row block into
        the device-resident problem state and re-solve warm — sugar for
        ``solve(Update(rows, responses, ...))``."""
        if isinstance(rows, Update):     # update(Update(...)) sugar
            request = rows
        if request is None:
            request = Update(rows=rows, responses=responses, **kw)
        return self.solve(request)

    def select(self, request=None, **kw):
        """Auto-lambda verb (DESIGN.md §14): CV + 1-SE rule + stability
        selection + refit — sugar for ``solve(Select(...))``; returns a
        :class:`~repro.core.select.SelectionReport`."""
        if request is None:
            request = Select(**kw)
        return self.solve(request)

    # ------------------------------------------------------------------
    # warm boundary state (the serving runtime's checkpoint surface)
    # ------------------------------------------------------------------

    @property
    def warm_state(self):
        """The device-resident serial warm boundary state — the
        ``(idx, beta, mask, InnerCarry)`` tuple ``run_path`` hands across
        requests — or None before the first serial solve. This plus
        :attr:`warm_capacity` is exactly what a warm checkpoint must
        persist (``repro.core.serving``, DESIGN.md §10)."""
        return self._warm

    @property
    def warm_capacity(self):
        """Capacity (k_max) the warm state was built at, or None."""
        return self._warm_k

    def set_warm_state(self, warm, k_max) -> None:
        """Install a warm boundary state (e.g. restored from a
        checkpoint); the next ``Scalar/Path(warm=True)`` request enters
        from it exactly as if the previous solve had produced it."""
        self._warm = warm
        self._warm_k = None if k_max is None else int(k_max)

    def compile_stats(self) -> CompileStats:
        """Unified compile accounting; see :class:`CompileStats`."""
        serial, fleet, grp = _engine_cache_sizes()
        total = serial + fleet + grp
        since = total - getattr(self, "_compiles0", 0)
        return CompileStats(serial=serial, fleet=fleet, group=grp,
                            total=total, since_open=since,
                            requests=self._requests)

    # ------------------------------------------------------------------
    # provenance events + cross-request homotopy cache (DESIGN.md §14)
    # ------------------------------------------------------------------

    def _push_event(self, name: str) -> None:
        self._pending_events.append(name)

    def drain_events(self) -> Tuple[str, ...]:
        """Hand back (and clear) provenance events accumulated by the
        streaming / warm-cache paths — the serving layer folds these
        into the request's Verdict."""
        events, self._pending_events = tuple(self._pending_events), []
        return events

    def drop_cache_entry(self) -> int:
        """Invalidate the warm-cache entry stored by the most recent
        cache-routed solve (the serving scrub path calls this when a
        result fails certification)."""
        if self._warm_cache is None or self._cache_last is None:
            return 0
        digest, lam = self._cache_last
        self._cache_last = None
        return self._warm_cache.invalidate(digest, lam)

    def _cache_eligible(self, req) -> bool:
        """The homotopy cache serves cold, unsharded, plain-LASSO
        requests on a static (non-streaming) design with the built-in
        screens — everything else keeps its existing path untouched."""
        return (self._warm_cache is not None and not req.warm
                and not req.sharded and self._make_screen is None
                and self._design is None and self._online is None
                and self.problem.weights is None
                and isinstance(self.penalty, LassoPenalty))

    def _cached_entry_solve(self, lams: List[float]):
        """Solve through the cross-request homotopy cache: on a band hit,
        enter via the compiled Theorem-2 sequential-ball seed
        (``path.seq_warm_entry``); on a miss, run the bitwise cold path.
        Either way the exit warm state is stored for the next request."""
        from repro.core.path import run_path, seq_warm_entry
        from repro.core.warm_cache import problem_digest
        cache = self._warm_cache
        if self._digest_memo is None:
            self._digest_memo = problem_digest(self._prep.X, self._prep.y)
        digest = self._digest_memo
        lam_hi = max(lams)
        entry = cache.lookup(digest, lam_hi)
        if entry is not None:
            warm0, k0 = seq_warm_entry(self._prep, entry.warm,
                                       entry.k_max, entry.lam0, lam_hi,
                                       self.config)
            self._push_event(f"warm_cache_hit:lam0={entry.lam0:.6g}")
        else:
            warm0, k0 = None, None
            self._push_event("warm_cache_miss")
        pr, warm, k_max = run_path(self._prep, lams, self.config,
                                   segment_len=self._segment_len,
                                   warm0=warm0, k_max0=k0)
        self._warm, self._warm_k = warm, k_max
        lam_lo = min(lams)
        cache.store(digest, lam_lo, warm, k_max)
        self._cache_last = (digest, lam_lo)
        return pr

    # ------------------------------------------------------------------
    # dispatch arms
    # ------------------------------------------------------------------

    def _require_y(self):
        if self.problem.y is None:
            raise ValueError(
                "this request needs a response: the session was opened "
                "without Problem.y (fleet-only)")

    def _memo_make_screen(self, h: int):
        if h not in self._screen_memo:
            self._screen_memo[h] = self._make_screen(h)
        return self._screen_memo[h]

    def _harvest_warm(self, res):
        from repro.core.path import _warm_state
        unpen = self.config.unpen_idx
        self._warm = _warm_state(res.active_idx, res.active_mask, res.beta,
                                 res.inner,
                                 unpen_idx=-1 if unpen is None else unpen)
        self._warm_k = int(res.active_idx.shape[0])

    def _solve_scalar(self, req: Scalar):
        if isinstance(self.penalty, GroupPenalty):
            if req.sharded:
                raise NotImplementedError(
                    "sharded group screening is not implemented")
            from repro.core.group import group_solve
            res = group_solve(self._gprep, float(req.lam), self.config,
                              warm=self._gwarm if req.warm else None)
            self._gwarm = (res.gidx, res.gmask, res.beta_slots)
            return res

        self._require_y()
        if self.problem.weights is not None:
            if req.sharded:
                raise NotImplementedError(
                    "weighted sharded solves: per-problem column norms "
                    "live on the replicated path for now (DESIGN.md §8)")
            if req.warm:
                raise NotImplementedError(
                    "warm weighted solves: the fleet engine serving "
                    "weighted problems has no cross-request warm handoff "
                    "yet (DESIGN.md §9)")
            return self._weighted_scalar(float(req.lam))
        if req.sharded:
            res = self._scalar_sharded(float(req.lam), warm=req.warm)
        elif self._cache_eligible(req):
            # cross-request homotopy cache (DESIGN.md §14): band hits
            # enter via the Theorem-2 sequential-ball seed, misses run
            # the bitwise cold path; the exit warm state is cached
            pr = self._cached_entry_solve([float(req.lam)])
            res = pr.results[0]
        elif req.warm or self._make_screen is not None:
            # a single-lambda run of the path engine: bitwise the cold
            # solve_scalar when entered cold, and the only driver that
            # threads the warm handoff and the custom make_screen hook
            from repro.core.path import run_path
            pr, warm, k = run_path(self._prep, [float(req.lam)],
                                   self.config,
                                   make_screen=(None if self._make_screen
                                                is None
                                                else self._memo_make_screen),
                                   segment_len=self._segment_len,
                                   warm0=self._warm if req.warm else None,
                                   k_max0=(self._warm_k if req.warm
                                           else None))
            self._warm, self._warm_k = warm, k
            res = pr.results[0]
        else:
            from repro.core.saif import solve_scalar
            res = solve_scalar(self._prep, float(req.lam), self.config)
            self._harvest_warm(res)
        self._last_lam = float(req.lam)
        if isinstance(self.penalty, FusedPenalty):
            from repro.core.fused import recover_from_transformed
            return recover_from_transformed(res.beta, self._design), res
        if self._p_real is not None and not req.sharded:
            res = res._replace(beta=res.beta[:self._p_real])
        return res

    def _weighted_scalar(self, lam: float):
        import jax
        import jax.numpy as jnp
        from repro.core.batch import fleet_solve
        y = jnp.asarray(self.problem.y)
        w = jnp.asarray(self.problem.weights)
        res = fleet_solve(self.problem.X, y[None, :], lam, self.config,
                          weights=w[None, :])
        return jax.tree.map(lambda a: a[0], res)   # drop the B=1 axis

    def _solve_path(self, req: Path):
        lams = tuple(float(l) for l in req.lams)
        if isinstance(self.penalty, GroupPenalty):
            if req.sharded:
                raise NotImplementedError(
                    "sharded group screening is not implemented")
            return self._group_path(lams, warm=req.warm)

        self._require_y()
        if self.problem.weights is not None:
            raise NotImplementedError(
                "weighted lambda paths: submit a Fleet (one lambda per "
                "weighted problem) or a CV request instead")
        from repro.core.path import run_path
        if req.sharded:
            design = self._sharded_design()
            prep = self._sharded_path_prep(design)
            pr, warm, k = run_path(
                prep, lams, self._sharded_config(),
                make_screen=lambda h: self._memo_sharded_screen(design, h),
                segment_len=self._segment_len,
                warm0=self._sharded_warm if req.warm else None,
                k_max0=self._sharded_warm_k if req.warm else None)
            self._sharded_warm, self._sharded_warm_k = warm, k
            # slice the padding columns back off (design.p is the true
            # transformed/plain width)
            from repro.core.path import SaifPathResult
            betas = [b[:design.p] for b in pr.betas]
            pr = SaifPathResult(lams=pr.lams, betas=betas,
                                results=pr.results,
                                n_compilations=pr.n_compilations)
        else:
            if self._cache_eligible(req):
                pr = self._cached_entry_solve(list(lams))
            else:
                pr, warm, k = run_path(
                    self._prep, lams, self.config,
                    make_screen=(None if self._make_screen is None
                                 else self._memo_make_screen),
                    segment_len=self._segment_len,
                    warm0=self._warm if req.warm else None,
                    k_max0=self._warm_k if req.warm else None)
                self._warm, self._warm_k = warm, k
            self._last_lam = float(min(lams))
            if self._p_real is not None:
                from repro.core.path import SaifPathResult
                pr = SaifPathResult(
                    lams=pr.lams,
                    betas=[b[:self._p_real] for b in pr.betas],
                    results=pr.results, n_compilations=pr.n_compilations)
        if isinstance(self.penalty, FusedPenalty):
            from repro.core.fused import (FusedPathResult,
                                          recover_from_transformed)
            betas = [recover_from_transformed(b, self._design)
                     for b in pr.betas]
            return FusedPathResult(lams=pr.lams, betas=betas, path=pr)
        return pr

    def _group_path(self, lams, warm: bool) -> GroupPathResult:
        from repro.core.group import group_compile_count, group_solve
        lams_np = np.asarray(sorted(lams, reverse=True))
        n0 = group_compile_count()
        cur = self._gwarm if warm else None
        results = []
        for lam in lams_np:
            res = group_solve(self._gprep, float(lam), self.config,
                              warm=cur)
            cur = (res.gidx, res.gmask, res.beta_slots)
            results.append(res)
        self._gwarm = cur
        n1 = group_compile_count()
        n_comp = max(n1 - n0, 0)
        return GroupPathResult(lams=lams_np,
                               betas=[r.beta for r in results],
                               results=results, n_compilations=n_comp)

    def _solve_fleet(self, req: Fleet):
        if isinstance(self.penalty, GroupPenalty):
            raise NotImplementedError(
                "group fleets are not implemented (DESIGN.md §9)")
        if isinstance(self.penalty, FusedPenalty):
            raise NotImplementedError(
                "fused fleets are serial-only for now (DESIGN.md §8)")
        if self.problem.weights is not None:
            raise NotImplementedError(
                "Problem-level weights serve Scalar requests; fleets take "
                "per-request Fleet(..., weights=...) instead")
        if req.sharded:
            self._require_mesh()
            if req.weights is not None:
                raise NotImplementedError(
                    "weighted sharded fleets: per-fold column norms live "
                    "on the replicated path for now (DESIGN.md §8)")
            from repro.distributed.saif_sharded import fleet_solve_sharded
            return fleet_solve_sharded(
                self.problem.X, req.Y, req.lams, self.mesh,
                self._sharded_config(),
                design=self._sharded_fleet_design(req.Y),
                screen_cache=self._sharded_fleet_screens)
        from repro.core.batch import fleet_solve
        if self._pad_to is not None:
            import jax
            from repro.core.batch import pad_fleet_prep, prepare_fleet
            from repro.runtime.spans import span
            with span("repro.session.prepare"):
                fprep = prepare_fleet(self.problem.X, req.Y, self.config,
                                      weights=req.weights)
                fprep = pad_fleet_prep(fprep, *self._pad_to)
            res = fleet_solve(None, None, req.lams, self.config,
                              screen_fn=req.screen_fn, prep=fprep)
            return res._replace(beta=res.beta[:, :self._p_real])
        return fleet_solve(self.problem.X, req.Y, req.lams, self.config,
                           weights=req.weights, screen_fn=req.screen_fn)

    def _solve_cv(self, req: CV):
        if not isinstance(self.penalty, LassoPenalty):
            raise NotImplementedError(
                "cross-validation serves plain-LASSO problems "
                "(DESIGN.md §8)")
        if req.sharded:
            raise NotImplementedError(
                "sharded CV fleets: per-fold column norms live on the "
                "replicated path for now (DESIGN.md §8)")
        if self.problem.weights is not None:
            raise NotImplementedError(
                "weighted cross-validation is not supported: CV builds "
                "its own binary fold weights (DESIGN.md §8)")
        self._require_y()
        from repro.core.cv import cv_solve
        return cv_solve(self.problem.X, self.problem.y,
                        tuple(float(l) for l in req.lams), req.n_folds,
                        self.config, seed=req.seed,
                        keep_fold_betas=req.keep_fold_betas,
                        refit=req.refit)

    def _solve_update(self, req: Update):
        if not isinstance(self.penalty, LassoPenalty):
            raise NotImplementedError(
                "online row updates serve plain-LASSO sessions "
                "(DESIGN.md §14)")
        from repro.core.online import apply_update
        return apply_update(self, req)

    def _solve_select(self, req: Select) -> SelectionReport:
        if not isinstance(self.penalty, LassoPenalty):
            raise NotImplementedError(
                "Session.select serves plain-LASSO problems "
                "(DESIGN.md §8/§14)")
        if self.problem.weights is not None:
            raise NotImplementedError(
                "weighted selection is not supported: CV and stability "
                "selection build their own binary row weights")
        self._require_y()
        from repro.core.select import select_solve
        if self._online is not None:
            # streaming session: select on the CURRENT resident rows
            # (the first `filled` buffer rows hold exactly the live data)
            n = self._prep.n_true or self._prep.X.shape[0]
            X, y = self._prep.X[:n], self._prep.y[:n]
        else:
            X, y = self.problem.X, self.problem.y
        report = select_solve(X, y, req, self.config)
        self._last_lam = float(report.lam)
        return report

    # ------------------------------------------------------------------
    # sharded plumbing (lazy: built at the first sharded request)
    # ------------------------------------------------------------------

    def _require_mesh(self):
        if self.mesh is None:
            raise ValueError(
                "sharded=True needs a device mesh: open_session(problem, "
                "config, mesh=mesh)")

    def _sharded_config(self):
        """The config of feature-sharded solves: a Mosaic kernel cannot be
        partitioned across the mesh, so the replicated inner burst stays
        on XLA there — ``auto`` becomes ``gram`` (least squares) or
        ``jnp``, and an explicit ``pallas`` is refused (DESIGN.md §5)."""
        cfg = self.config
        if cfg.inner_backend == "pallas":
            raise ValueError(
                "inner_backend='pallas' does not compose with sharded=True: "
                "the VMEM kernel cannot be partitioned across the mesh")
        if cfg.inner_backend != "auto":
            return cfg
        return dataclasses.replace(
            cfg, inner_backend=("gram" if cfg.loss == "least_squares"
                                else "jnp"))

    def _sharded_design(self):
        self._require_mesh()
        if self._sharded is None:
            from repro.distributed.saif_sharded import design_for
            if isinstance(self.penalty, FusedPenalty):
                X, y = self._design.Xt, self._y
            else:
                X, y = self.problem.X, self.problem.y
            self._sharded = design_for(X, y, self.mesh, self.config)
        return self._sharded

    def _sharded_path_prep(self, design):
        if self._sharded_prep is None:
            from repro.core.saif import prepare_path
            y = self._y if isinstance(self.penalty, FusedPenalty) \
                else self.problem.y
            self._sharded_prep = prepare_path(design.X, y, self.config)
        return self._sharded_prep

    def _memo_sharded_screen(self, design, h: int):
        if h not in self._sharded_screen_memo:
            from repro.distributed.saif_sharded import make_sharded_screen
            self._sharded_screen_memo[h] = make_sharded_screen(design, h)
        return self._sharded_screen_memo[h]

    def _sharded_fleet_design(self, Y):
        """Fleet placement, built at the first sharded fleet request and
        reused by every later one (see ``fleet_design_for``)."""
        if self._sharded_fleet is None:
            from repro.distributed.saif_sharded import fleet_design_for
            self._sharded_fleet = fleet_design_for(self.problem.X, Y,
                                                   self.mesh, self.config)
        return self._sharded_fleet

    def _scalar_sharded(self, lam: float, warm: bool = False):
        self._require_mesh()
        design = self._sharded_design()
        if warm:
            # the sharded edition of the warm handoff: a single-lambda
            # run of the path engine over the padded prep, entered from
            # (and refreshing) the sharded warm state
            from repro.core.path import run_path
            pr, wstate, k = run_path(
                self._sharded_path_prep(design), [lam],
                self._sharded_config(),
                make_screen=lambda h: self._memo_sharded_screen(design, h),
                segment_len=self._segment_len,
                warm0=self._sharded_warm, k_max0=self._sharded_warm_k)
            self._sharded_warm, self._sharded_warm_k = wstate, k
            res = pr.results[0]
            return res._replace(beta=res.beta[:design.p])
        from repro.distributed.saif_sharded import solve_scalar_sharded
        y = self._y if isinstance(self.penalty, FusedPenalty) \
            else self.problem.y
        return solve_scalar_sharded(None, y, lam, self.mesh,
                                    self._sharded_config(), design=design,
                                    screen_cache=self._sharded_screen_memo,
                                    prep=self._sharded_path_prep(design))


def open_session(problem: Problem, config=None, **kwargs) -> Session:
    """Open a persistent solving session for ``problem``.

    Preparation (c0 / column norms / Theorem-6 transform / group norms)
    runs HERE, exactly once; every subsequent ``session.solve(request)``
    reuses it along with the process-wide solver compilations and the
    session's device-resident warm buffers. ``config`` is a
    :class:`~repro.core.saif.SaifConfig` (or
    :class:`~repro.core.group.GroupSaifConfig` for group penalties;
    defaults per penalty).

    Keyword arguments are the shared session spec
    (:data:`SESSION_KWARG_DEFAULTS` — identical for ``open_session``,
    ``open_serving`` and ``open_server``): ``mesh`` enables
    ``sharded=True`` requests; ``make_screen``/``segment_len`` are the
    path-engine hooks; ``pad_to=(n_bucket, p_bucket)`` serves every
    request from a compile-bucket-padded preparation (DESIGN.md §12).
    """
    return Session(problem, config, **kwargs)
