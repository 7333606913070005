"""Fault-tolerant serving runtime over the Session API (DESIGN.md §10).

A :class:`~repro.core.api.Session` makes SAIF *fast* to serve; this
module makes it *safe* to serve. The SAFE line of work (El Ghaoui et
al. 2013) sells screening on a machine-checkable certificate — the
duality gap — and a production runtime must extend that certificate
discipline to every failure mode between the request and the result:

* **Admission control** — :func:`validate_problem` /
  :func:`validate_request` reject non-finite data, degenerate zero-norm
  columns, lam <= 0 and shape mismatches with a *typed* error taxonomy
  (:class:`RequestError`, :class:`NumericalError`, :class:`BackendFault`,
  :class:`DeadlineExceeded`) before anything reaches a compiled program.
  The types multiply-inherit the builtin they historically surfaced as
  (``ValueError``/``ArithmeticError``/``RuntimeError``/``TimeoutError``)
  so existing callers keep working.
* **Certified results** — every ``ServingSession.solve`` returns a
  :class:`ServingResult` ``(value, verdict)``. The :class:`Verdict`
  carries the final duality gap, a converged flag, h-overflow /
  precision-floor / retry events, and a *post-hoc KKT residual* of the
  returned support (:func:`repro.core.duality.kkt_residual`) checked
  against ``max(kkt_rtol * lam, kkt_atol)``. The KKT check is its own
  tiny jit, deliberately outside the engine caches, so the serving
  contract — zero new solver compilations at steady state — still holds.
* **Certified degradation** — a failed verdict walks a ladder:
  ``grow`` (re-solve with grown capacity / outer budget), ``oracle``
  (the unscreened CM solve — screening-free, so a screening bug cannot
  survive it), ``x64`` (retry in float64). Each rung is re-verified and
  recorded in ``verdict.rungs``; no silent failures, ever.
* **Fault containment** — transient backend ``RuntimeError``s are
  retried with jittered exponential backoff under a per-request deadline
  (:func:`repro.runtime.fault.retry_step`); a lowering, compile or
  out-of-memory error is deterministic and surfaces at once as a
  :class:`BackendFault` with the compiler's message
  (:func:`repro.runtime.fault.is_deterministic_fault`); per-compile-bucket
  :class:`~repro.runtime.fault.StragglerMonitor`s flag slow steps; a
  circuit breaker durably degrades a faulting backend (pallas -> jnp)
  for the rest of the session's lifetime.
* **Warm checkpoint/restore** — the session's device-resident warm
  boundary state (slot idx / beta / mask + InnerCarry) snapshots through
  ``repro.ckpt.checkpoint``'s atomic writes, keyed by a problem digest;
  a SIGTERM'd (``PreemptionGuard``) or restarted server resumes warm
  with zero extra compilations.

Spans (``repro.runtime.spans``): ``repro.serving.solve`` over a whole
request (retries, ladder), ``repro.serving.certify`` over each
certification, whose elapsed time is ``Verdict.kkt_check_ms``, and a
``repro.sync.<what>`` around each device-to-host read of a result.

Module scope imports only stdlib, numpy and the span helper (which loads
jax on first use): constructing a :class:`~repro.core.api.Problem` (which
validates here) keeps the lazy surface contract of ``repro/__init__.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
import random
import time
import warnings
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from repro.runtime.spans import read, span

__all__ = [
    "ServingError", "RequestError", "NumericalError", "BackendFault",
    "DeadlineExceeded",
    "validate_problem", "validate_request",
    "Rung", "Verdict", "ServingResult", "ServingConfig", "ServingStats",
    "ServingSession", "open_serving",
]


# ---------------------------------------------------------------------------
# typed error taxonomy (DESIGN.md §10)
# ---------------------------------------------------------------------------

class ServingError(Exception):
    """Root of the serving error taxonomy. Every admission / runtime
    failure the serving layer raises is a ServingError, and each subtype
    also IS the builtin it historically surfaced as, so pre-taxonomy
    ``except ValueError`` call sites keep working."""


class RequestError(ServingError, ValueError):
    """The request itself is malformed: bad shapes, lam <= 0, unknown
    loss, degenerate (zero-norm) columns. Client-side; never retried."""


class NumericalError(ServingError, ArithmeticError):
    """Non-finite data in, or a result that failed numerical
    certification (NaN coefficients, KKT violation) after the full
    degradation ladder."""


class BackendFault(ServingError, RuntimeError):
    """A compiled backend faulted persistently — retries exhausted and,
    where possible, the circuit breaker's degraded backend also failed —
    or deterministically: a lowering, compile or out-of-memory error,
    raised at once with the compiler's message (never retried, never
    degraded to another backend)."""


class DeadlineExceeded(ServingError, TimeoutError):
    """The per-request wall-clock budget ran out (during retries or
    between degradation rungs)."""


def highest_matmul_precision(fn):
    """Run ``fn`` with every float32 matmul it traces at full precision.

    The certificates (screening bounds, duality gaps, KKT residuals) are
    float32-exact claims; a TPU's default float32 matmul rounds its inputs
    to bfloat16 in one pass, which breaks them. The precision is part of
    the trace context, so compiled engines carry it; on CPU it is a no-op.
    """
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        import jax
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


class _NonRetriable(Exception):
    """Internal carrier: an exception the retry loop must not eat
    (NotImplementedError and typed serving errors pass straight up)."""

    def __init__(self, cause: BaseException):
        super().__init__(cause)
        self.cause = cause


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

_KNOWN_LOSSES = ("least_squares", "logistic")


def _np(x) -> np.ndarray:
    return np.asarray(x)


def _require_finite(name: str, arr: np.ndarray) -> None:
    if not np.all(np.isfinite(arr)):
        bad = int(np.sum(~np.isfinite(arr)))
        raise NumericalError(
            f"{name} has {bad} non-finite entr{'y' if bad == 1 else 'ies'} "
            f"(NaN/Inf): admission control rejects it before it can reach "
            f"a compiled program")


def _require_lam(lam, what: str = "lam") -> None:
    arr = np.asarray(lam, dtype=np.float64)
    if arr.ndim > 1:
        raise RequestError(f"{what} must be a scalar or 1-D grid, got "
                           f"shape {arr.shape}")
    if not np.all(np.isfinite(arr)):
        raise RequestError(f"{what} must be finite, got {lam!r}")
    if not np.all(arr > 0.0):
        raise RequestError(
            f"{what} must be > 0 (lam = 0 is an unregularized fit the "
            f"screening certificate does not cover), got {lam!r}")


def validate_problem(problem) -> None:
    """Admission control for :class:`~repro.core.api.Problem` — runs at
    construction, so a malformed spec fails with a typed error before a
    session (let alone a compiled engine) ever sees it."""
    if problem.X is None:
        # a spec without a design is legal to *construct* (the legacy
        # surface allows it); open_session rejects it at serve time
        return
    X = _np(problem.X)
    if X.ndim != 2:
        raise RequestError(
            f"Problem.X must be 2-D (n, p), got shape {X.shape}")
    if X.shape[0] < 1 or X.shape[1] < 1:
        raise RequestError(f"Problem.X must be non-empty, got {X.shape}")
    _require_finite("Problem.X", X)
    # an exact zero test (no float64 copy of a deployment-size design)
    dead = np.flatnonzero(~np.any(X != 0, axis=0))
    if dead.size:
        raise RequestError(
            f"Problem.X has {dead.size} zero-norm (degenerate) column"
            f"{'s' if dead.size > 1 else ''} (e.g. {dead[:5].tolist()}): "
            f"a dead column has no screening statistic and can never "
            f"enter the support — drop it before building the Problem")
    if problem.loss not in _KNOWN_LOSSES:
        raise RequestError(
            f"unknown loss {problem.loss!r}; options: "
            f"{sorted(_KNOWN_LOSSES)}")
    n = X.shape[0]
    if problem.y is not None:
        y = _np(problem.y)
        if y.shape != (n,):
            raise RequestError(
                f"Problem.y must have shape ({n},) to match X "
                f"{X.shape}, got {y.shape}")
        _require_finite("Problem.y", y)
    if problem.weights is not None:
        w = _np(problem.weights)
        if w.shape != (n,):
            raise RequestError(
                f"Problem.weights must have shape ({n},), got {w.shape}")
        _require_finite("Problem.weights", w)
        if np.any(w < 0.0):
            raise RequestError("Problem.weights must be non-negative")
        if not np.any(w > 0.0):
            raise RequestError("Problem.weights must not be all zero")


def validate_request(req) -> None:
    """Admission control for Scalar/Path/Fleet/CV — duck-typed on the
    request's fields so this module never imports the (lazily loaded)
    api module at validation time."""
    kind = type(req).__name__
    if kind == "Scalar":
        _require_lam(req.lam, "Scalar.lam")
        if np.asarray(req.lam, dtype=np.float64).ndim != 0:
            raise RequestError(
                f"Scalar.lam must be a scalar, got shape "
                f"{np.asarray(req.lam).shape}; submit a Path for a grid")
    elif kind == "Path":
        lams = np.asarray(req.lams, dtype=np.float64)
        if lams.size == 0:
            raise RequestError("Path.lams must be a non-empty grid")
        _require_lam(lams, "Path.lams")
    elif kind == "Fleet":
        Y = _np(req.Y)
        if Y.ndim not in (1, 2):
            raise RequestError(
                f"Fleet.Y must be (n,) or (B, n), got shape {Y.shape}")
        _require_finite("Fleet.Y", Y)
        B = 1 if Y.ndim == 1 else Y.shape[0]
        lams = np.asarray(req.lams, dtype=np.float64)
        if lams.ndim == 1 and lams.shape[0] != B:
            raise RequestError(
                f"Fleet.lams must be a scalar or shape ({B},) to match "
                f"Y, got {lams.shape}")
        _require_lam(lams, "Fleet.lams")
        if req.weights is not None:
            w = _np(req.weights)
            if w.shape != Y.shape:
                raise RequestError(
                    f"Fleet.weights must match Y's shape {Y.shape}, "
                    f"got {w.shape}")
            _require_finite("Fleet.weights", w)
            if np.any(w < 0.0):
                raise RequestError("Fleet.weights must be non-negative")
            w2 = w if w.ndim == 2 else w[None, :]
            if not np.all(np.any(w2 > 0.0, axis=1)):
                raise RequestError(
                    "every Fleet.weights row needs a positive entry")
    elif kind == "CV":
        if int(req.n_folds) < 2:
            raise RequestError(
                f"CV.n_folds must be >= 2, got {req.n_folds}")
        lams = np.asarray(req.lams, dtype=np.float64)
        if lams.size == 0:
            raise RequestError("CV.lams must be a non-empty grid")
        _require_lam(lams, "CV.lams")
    elif kind == "Update":
        rows = _np(req.rows)
        if rows.ndim != 2 or rows.shape[0] < 1 or rows.shape[1] < 1:
            raise RequestError(
                f"Update.rows must be a non-empty (m, p) row block, got "
                f"shape {rows.shape}")
        _require_finite("Update.rows", rows)
        resp = _np(req.responses)
        if resp.shape != (rows.shape[0],):
            raise RequestError(
                f"Update.responses must have shape ({rows.shape[0]},) to "
                f"match rows {rows.shape}, got {resp.shape}")
        _require_finite("Update.responses", resp)
        if req.lam is not None:
            if np.asarray(req.lam, dtype=np.float64).ndim != 0:
                raise RequestError(
                    f"Update.lam must be a scalar (or None to re-solve at "
                    f"the session's last lambda), got shape "
                    f"{np.asarray(req.lam).shape}")
            _require_lam(req.lam, "Update.lam")
        if req.window is not None:
            w = int(req.window)
            if w < 1:
                raise RequestError(
                    f"Update.window must be a positive row count (or None "
                    f"for an append-only stream), got {req.window!r}")
            if w < rows.shape[0]:
                raise RequestError(
                    f"Update.window ({w}) must be >= the update batch "
                    f"({rows.shape[0]} rows); a single batch may not "
                    f"overflow the sliding window")
            # window >= resident-active-count is enforced at serve time
            # (core/online.py) where the active set is known
    elif kind == "Select":
        lams = np.asarray(req.lams, dtype=np.float64)
        if lams.size == 0:
            raise RequestError("Select.lams must be a non-empty grid")
        _require_lam(lams, "Select.lams")
        if int(req.n_folds) < 2:
            raise RequestError(
                f"Select.n_folds must be >= 2, got {req.n_folds}")
        if req.rule not in ("1se", "min"):
            raise RequestError(
                f"Select.rule must be '1se' or 'min', got {req.rule!r}")
        if req.stability:
            if int(req.n_subsamples) < 2:
                raise RequestError(
                    f"Select.n_subsamples must be >= 2 (selection "
                    f"frequencies need >= 2 subsamples), got "
                    f"{req.n_subsamples}")
            frac = float(req.subsample_frac)
            if not (0.0 < frac < 1.0):
                raise RequestError(
                    f"Select.subsample_frac must lie in (0, 1), got "
                    f"{req.subsample_frac!r}")
        pi = float(req.pi_threshold)
        if not (0.0 < pi <= 1.0):
            raise RequestError(
                f"Select.pi_threshold must lie in (0, 1], got "
                f"{req.pi_threshold!r}")
    # Serving knobs shared by every request kind (PR 8): the sync
    # ServingSession.solve() and the async Server.submit() accept the
    # same request values, so both are validated here.
    deadline = getattr(req, "deadline_s", None)
    if deadline is not None:
        d = float(deadline)
        if not math.isfinite(d) or d <= 0.0:
            raise RequestError(
                f"{kind}.deadline_s must be a finite positive number of "
                f"seconds (or None), got {deadline!r}")
    priority = getattr(req, "priority", 0)
    if not isinstance(priority, (int, np.integer)) or isinstance(
            priority, bool):
        raise RequestError(
            f"{kind}.priority must be an int (higher dequeues first), "
            f"got {priority!r}")


# ---------------------------------------------------------------------------
# verdicts
# ---------------------------------------------------------------------------

class Rung(NamedTuple):
    """One attempted degradation-ladder rung (DESIGN.md §10)."""
    name: str                   # "grow" | "oracle" | "x64"
    ok: bool                    # did the rung's result pass verification
    gap: float                  # worst duality gap of the rung's result
    kkt_residual: float         # worst KKT residual of the rung's result
    note: str = ""              # "skipped" / "error:..." / ""


class Verdict(NamedTuple):
    """The certificate attached to every served result.

    ``ok`` is the serving guarantee: the returned value passed numerical
    certification (finite + post-hoc KKT residual within tolerance; for
    penalties without a scalar KKT check, duality gap <= eps).
    ``converged`` is the stricter engine criterion ``gap <= eps`` — a
    result can be ``ok`` but not ``converged`` when the gap bottomed out
    at its arithmetic precision floor (DESIGN.md §3) yet the KKT
    residual certifies the support. ``events`` is the de-duplicated
    trail (retries, h-overflow, warm-state resets, breaker trips);
    ``rungs`` records every degradation attempt, in order."""
    ok: bool
    converged: bool
    gap: float
    kkt_residual: float
    kkt_tol: float
    events: Tuple[str, ...] = ()
    rungs: Tuple[Rung, ...] = ()
    degraded: bool = False
    retries: int = 0
    kkt_check_ms: float = 0.0
    # execution-mode provenance (DESIGN.md §11): which parity contract and
    # screening precision produced the certified value. The KKT check that
    # backs ``ok`` always runs in working precision, whatever these say.
    parity: str = "bitwise"
    screen_dtype: str = "working"
    # screening-rule provenance (DESIGN.md §13): which certificate
    # geometry produced the value — "saif" | "gap_safe" | "hybrid" | a
    # custom ScreenRule's name. The KKT certification behind ``ok`` is
    # rule-independent (it checks the returned value, not the rule).
    screen_rule: str = "saif"
    # Per-unit breakdown (one entry per lambda / fleet member), so a
    # coalescing front-end can attribute a failed certificate to the one
    # poisoned member of a microbatch instead of degrading every rider
    # (DESIGN.md §12). ``unit_ok[i]`` is unit i's final certification;
    # ``unit_degraded[i]`` marks units that failed the FIRST
    # certification pass and owe their final state to the degradation
    # ladder. None when no certification units were produced.
    unit_ok: Optional[Tuple[bool, ...]] = None
    unit_degraded: Optional[Tuple[bool, ...]] = None


class ServingResult(NamedTuple):
    value: Any                  # the engine result (type per request kind)
    verdict: Verdict


class ServingStats(NamedTuple):
    """Session-lifetime counters (benchmarks/bench_serve.py columns)."""
    requests: int
    degraded: int               # requests that needed >= 1 ladder rung
    retries: int                # transient-fault retries issued
    stragglers: int             # steps flagged by the monitors
    breaker_open: bool          # backend durably degraded to jnp
    restored: bool              # warm state came from a checkpoint
    kkt_check_ms: float         # cumulative certification time


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Policy knobs of the fault-tolerant runtime (DESIGN.md §10)."""
    max_retries: int = 2          # transient-fault retries per request
    backoff_base_s: float = 0.01  # first retry's nominal backoff
    backoff_mult: float = 2.0
    jitter: float = 0.5           # +- fraction on each backoff delay
    deadline_s: Optional[float] = None    # per-request wall-clock budget
    check_kkt: bool = True
    kkt_rtol: float = 1e-3        # tol = max(kkt_rtol * lam, kkt_atol)
    kkt_atol: float = 1e-8
    ladder: Tuple[str, ...] = ("grow", "oracle", "x64")
    oracle_tol: Optional[float] = None    # None => the engine's eps
    breaker_threshold: int = 1    # consecutive exhausted-retry failures
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0           # checkpoint every N ok requests (0=off)
    seed: int = 0                 # backoff-jitter rng seed
    straggler_factor: float = 3.0
    strict: bool = False          # raise NumericalError on a failed verdict


# ---------------------------------------------------------------------------
# the KKT certificate jit — deliberately OUTSIDE the engine caches, so
# certification never perturbs the zero-new-compilations serving contract
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _kkt_fn(loss_name: str):
    import jax
    from repro.core.duality import kkt_residual
    from repro.core.losses import get_loss
    loss = get_loss(loss_name)

    def residual(X, y, beta, lam, pen, sample_w):
        return kkt_residual(loss, X, y, beta, lam, pen=pen,
                            sample_w=sample_w)

    return jax.jit(residual)


@functools.lru_cache(maxsize=None)
def _kkt_fleet_fn(loss_name: str):
    """Vmapped fleet certificate: one dispatch for all B members
    (shared X, per-member y/beta/lam) instead of B scalar dispatches —
    the per-unit jit round-trips would dominate wide coalesced
    batches."""
    import jax
    from repro.core.duality import kkt_residual
    from repro.core.losses import get_loss
    loss = get_loss(loss_name)

    def residual(X, y, beta, lam, pen):
        return kkt_residual(loss, X, y, beta, lam, pen=pen,
                            sample_w=None)

    return jax.jit(jax.vmap(residual,
                            in_axes=(None, 0, 0, 0, None)))


def _beside(beta, X):
    """``beta`` on X's device when X is committed to one device (a
    feature-sharded solve returns a sharded beta; the certificate runs
    where the user's design lives)."""
    devices = getattr(X, "devices", None)
    if devices is None or len(devices()) != 1:
        return beta
    import jax
    return jax.device_put(beta, next(iter(devices())))


def _wmax(a: float, b: float) -> float:
    """NaN-propagating max: a non-finite entry must dominate the
    verdict's worst-case fields, never be masked by a healthy one."""
    if math.isnan(a) or math.isnan(b):
        return float("nan")
    return max(a, b)


_deadline_kwarg_warned = False


def _warn_deadline_kwarg_once() -> None:
    """One-shot DeprecationWarning for ``solve(deadline_s=...)`` — the
    knob moved onto the request objects (``Scalar(..., deadline_s=)``)
    so sync and async submission accept identical request values."""
    global _deadline_kwarg_warned
    if not _deadline_kwarg_warned:
        _deadline_kwarg_warned = True
        warnings.warn(
            "ServingSession.solve(deadline_s=...) is deprecated; set "
            "deadline_s on the request object (e.g. Scalar(lam, "
            "deadline_s=...)) so the same request works with "
            "Server.submit()", DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# the serving session
# ---------------------------------------------------------------------------

class ServingSession:
    """A :class:`~repro.core.api.Session` wrapped in the fault-tolerant
    runtime: every ``solve`` admits, retries, certifies, degrades and
    (optionally) checkpoints. Construct via :func:`open_serving`."""

    def __init__(self, problem, config=None, *, serving=None, guard=None,
                 **kwargs):
        from repro.core.api import open_session, session_kwargs
        self.serving = serving if serving is not None else ServingConfig()
        self.problem = problem
        # one shared passthrough spec (api.SESSION_KWARG_DEFAULTS) so
        # open_session / open_serving / open_server never drift
        self._opts = session_kwargs(**kwargs)
        self.session = open_session(problem, config, **self._opts)
        self.guard = guard
        self._rng = random.Random(self.serving.seed)
        self._monitors: Dict[tuple, Any] = {}
        self._breaker_failures = 0
        self.breaker_open = False
        self.restored = False
        self._preempt_ckpt = False
        self._requests = 0
        self._degraded = 0
        self._retries_total = 0
        self._stragglers = 0
        self._kkt_ms = 0.0
        self._step = 0
        self._last_unit_ok: List[bool] = []
        if self.serving.ckpt_dir:
            self.restored = self._maybe_restore()

    # -- passthrough surface -------------------------------------------

    def compile_stats(self):
        return self.session.compile_stats()

    @property
    def config(self):
        return self.session.config

    def stats(self) -> ServingStats:
        return ServingStats(
            requests=self._requests, degraded=self._degraded,
            retries=self._retries_total, stragglers=self._stragglers,
            breaker_open=self.breaker_open, restored=self.restored,
            kkt_check_ms=self._kkt_ms)

    # ------------------------------------------------------------------
    # the one entry point
    # ------------------------------------------------------------------

    @highest_matmul_precision
    def solve(self, request, *, deadline_s: Optional[float] = None
              ) -> ServingResult:
        """Serve one request under the full runtime: admission already
        ran at request construction; here the request is dispatched with
        retry/backoff and a deadline, the result is certified, and a
        failed certificate walks the degradation ladder. Returns
        ``(value, verdict)`` — a typed error (the taxonomy above) is the
        only other way out."""
        with span("repro.serving.solve"):
            return self._solve(request, deadline_s)

    def _solve(self, request, deadline_s: Optional[float]) -> ServingResult:
        ser = self.serving
        t0 = time.monotonic()
        if deadline_s is not None:
            _warn_deadline_kwarg_once()
        deadline = getattr(request, "deadline_s", None)
        if deadline is None:
            deadline = ser.deadline_s if deadline_s is None else deadline_s
        self._requests += 1
        events: List[str] = []
        self._drain_preemption(events)

        retries = 0

        def on_retry(attempt: int, e: Exception) -> None:
            nonlocal retries
            retries += 1
            events.append(f"retry:{attempt}:{type(e).__name__}")

        value = self._primary(request, t0, deadline, on_retry, events)
        drain = getattr(self.session, "drain_events", None)
        if drain is not None:
            events += list(drain())
        self._retries_total += retries
        self._breaker_failures = 0      # a served request closes the streak

        kkt_ms0 = self._kkt_ms
        ok, converged, gap, kkt, tol, ev = self._verify(request, value)
        events += ev
        first_unit = tuple(self._last_unit_ok)
        final_unit = first_unit
        rungs: List[Rung] = []
        degraded = False
        if not ok:
            self._scrub_warm(request, events)
            best_value, best_score = value, _score(kkt, gap)
            best_unit = first_unit
            for name in ser.ladder:
                self._check_deadline(t0, deadline, f"ladder rung {name!r}")
                try:
                    cand = self._run_rung(name, request, value)
                except ServingError:
                    raise
                except Exception as e:   # noqa: BLE001 - a rung crashing
                    # must surface in the verdict, not mask it
                    rungs.append(Rung(name, False, float("nan"),
                                      float("nan"),
                                      f"error:{type(e).__name__}: {e}"))
                    continue
                if cand is None:
                    rungs.append(Rung(name, False, float("nan"),
                                      float("nan"), "skipped"))
                    continue
                value2, sess2 = cand
                degraded = True
                ok2, conv2, gap2, kkt2, _, ev2 = self._verify(
                    request, value2, sess=sess2)
                rungs.append(Rung(name, ok2, gap2, kkt2))
                if _score(kkt2, gap2) < best_score:
                    best_value, best_score = value2, _score(kkt2, gap2)
                    best_unit = tuple(self._last_unit_ok)
                if ok2:
                    ok, converged, gap, kkt = True, conv2, gap2, kkt2
                    value = value2
                    final_unit = tuple(self._last_unit_ok)
                    events += [f"degraded:{name}"] + ev2
                    break
            else:
                value = best_value
                final_unit = best_unit
                events.append("ladder_exhausted")
        if degraded:
            self._degraded += 1

        cfg = self.session.config
        rule = getattr(cfg, "screen_rule", "saif")   # str or ScreenRule
        verdict = Verdict(
            ok=ok, converged=converged, gap=gap, kkt_residual=kkt,
            kkt_tol=tol, events=tuple(dict.fromkeys(events)),
            rungs=tuple(rungs), degraded=degraded, retries=retries,
            kkt_check_ms=self._kkt_ms - kkt_ms0,
            parity=getattr(cfg, "parity", "bitwise"),
            screen_dtype=getattr(cfg, "screen_dtype", "working"),
            screen_rule=getattr(rule, "name", rule),
            unit_ok=final_unit or None,
            unit_degraded=(tuple(not u for u in first_unit)
                           if first_unit else None))
        if ok and ser.ckpt_every and self._requests % ser.ckpt_every == 0:
            self.checkpoint()
        if ser.strict and not ok:
            raise NumericalError(
                f"result failed certification after the full degradation "
                f"ladder: gap={gap:g}, kkt_residual={kkt:g} (tol {tol:g}), "
                f"events={verdict.events}")
        return ServingResult(value=value, verdict=verdict)

    # ------------------------------------------------------------------
    # primary dispatch: retry / backoff / deadline / breaker / straggler
    # ------------------------------------------------------------------

    def _primary(self, request, t0, deadline, on_retry, events):
        from repro.runtime.fault import (RetryDeadlineExceeded, StepFailed,
                                         StragglerMonitor,
                                         is_deterministic_fault, retry_step)
        ser = self.serving
        bucket = self._bucket(request)
        mon = self._monitors.get(bucket)
        if mon is None:
            mon = self._monitors[bucket] = StragglerMonitor(
                factor=ser.straggler_factor)

        def attempt():
            tA = time.monotonic()
            try:
                out = self.session.solve(request)
            except ServingError as e:
                raise _NonRetriable(e) from e
            except Exception as e:
                if is_deterministic_fault(e):
                    fault = BackendFault(
                        f"deterministic backend fault (not retried): "
                        f"{type(e).__name__}: {e}")
                    fault.__cause__ = e
                    raise _NonRetriable(fault) from e
                if isinstance(e, NotImplementedError):
                    raise _NonRetriable(e) from e
                raise
            if mon.record(time.monotonic() - tA):
                self._stragglers += 1
                events.append("straggler")
            return out

        remaining = None
        if deadline is not None:
            remaining = max(deadline - (time.monotonic() - t0), 0.0)
        try:
            return retry_step(
                attempt, max_retries=ser.max_retries,
                retriable=(RuntimeError,), on_retry=on_retry,
                backoff_base_s=ser.backoff_base_s,
                backoff_mult=ser.backoff_mult, jitter=ser.jitter,
                deadline_s=remaining, rng=self._rng)
        except _NonRetriable as e:
            raise e.cause
        except RetryDeadlineExceeded as e:
            raise DeadlineExceeded(
                f"request deadline ({deadline:g}s) exceeded while "
                f"retrying a transient backend fault: {e}") from e
        except StepFailed as e:
            return self._trip_breaker(request, e, events)

    def _trip_breaker(self, request, err, events):
        """Retries exhausted: durably degrade the faulting backend
        (pallas -> jnp) and give the degraded session one clean shot;
        anything else is a typed BackendFault."""
        self._breaker_failures += 1
        events.append("backend_fault")
        if self._breaker_failures >= self.serving.breaker_threshold \
                and self._open_degraded(events):
            try:
                return self.session.solve(request)
            except Exception as e2:
                raise BackendFault(
                    f"backend fault persisted on the degraded (jnp) "
                    f"backend: {e2}") from e2
        raise BackendFault(
            f"persistent backend fault (retries exhausted"
            f"{', breaker already open' if self.breaker_open else ''}): "
            f"{err}") from err

    def _open_degraded(self, events) -> bool:
        """Pin screen/inner backends to jnp for the session's remaining
        lifetime. Returns False when there is nothing left to degrade."""
        if self.breaker_open:
            return False
        cfg = self.session.config
        repl = {}
        if getattr(cfg, "screen_backend", "jnp") != "jnp":
            repl["screen_backend"] = "jnp"
        if getattr(cfg, "inner_backend", "jnp") != "jnp":
            repl["inner_backend"] = "jnp"
        if not repl:
            return False
        from repro.core.api import open_session
        cfg2 = dataclasses.replace(cfg, **repl)
        self.session = open_session(self.problem, cfg2, **self._opts)
        self.breaker_open = True
        events.append("breaker_open:" + ",".join(
            f"{k}=jnp" for k in sorted(repl)))
        return True

    def _bucket(self, request) -> tuple:
        """Compile-bucket key for the straggler monitors: requests that
        share a static signature share a latency distribution."""
        name = type(request).__name__.lower()
        cfg = self.session.config
        prep = getattr(self.session, "_prep", None)
        if name == "scalar" and prep is not None and hasattr(cfg, "c"):
            try:
                from repro.core.saif import add_batch_size_static
                h = add_batch_size_static(
                    cfg.c, float(request.lam), float(prep.c0_max),
                    float(prep.c0_median), int(prep.X.shape[1]))
                return (name, h)
            except Exception:       # pragma: no cover - stats unreadable
                pass
        return (name, 0)

    def _check_deadline(self, t0, deadline, where: str) -> None:
        if deadline is not None and time.monotonic() - t0 > deadline:
            raise DeadlineExceeded(
                f"request deadline ({deadline:g}s) exceeded before "
                f"{where}")

    def _drain_preemption(self, events) -> None:
        g = self.guard
        if g is not None and g.preempted and not self._preempt_ckpt:
            self._preempt_ckpt = True
            if self.checkpoint() is not None:
                events.append("preempted_checkpointed")

    # ------------------------------------------------------------------
    # certification
    # ------------------------------------------------------------------

    def _verify(self, request, value, sess=None):
        """Certify ``value``: finiteness, gap convergence and — where the
        scalar KKT conditions apply — the post-hoc KKT residual. Returns
        ``(ok, converged, gap, kkt, tol, events)`` worst-cased over the
        request's units (one per lambda / fleet member). The whole of it,
        the host reads included, is the ``repro.serving.certify`` span,
        and its elapsed time the ``kkt_check_ms`` counters."""
        with span("repro.serving.certify") as sp:
            out = self._certify(request, value, sess)
        self._kkt_ms += sp.elapsed_s * 1e3
        return out

    def _certify(self, request, value, sess=None):
        sess = self.session if sess is None else sess
        ser = self.serving
        import jax.numpy as jnp
        events: List[str] = []
        eps = float(getattr(sess.config, "eps", 1e-6))
        max_outer = int(getattr(sess.config, "max_outer", 0))
        units = self._units(request, value, sess)
        ok, converged = True, True
        gap_w, kkt_w, tol_w = 0.0, 0.0, 0.0
        unit_ok: List[bool] = []
        for u in units:
            finite = bool(np.all(np.isfinite(
                read("beta", np.asarray, u["beta"]))))
            g = read("gap", float, u["gap"])
            finite = finite and math.isfinite(g)
            u_ok = finite
            if not finite:
                events.append("nonfinite")
            if u.get("overflowed"):
                events.append("h_overflow")
            if max_outer and u.get("n_outer", -1) >= max_outer:
                events.append("max_outer_exhausted")
            gap_w = _wmax(gap_w, g if math.isfinite(g) else float("nan"))
            if not (g <= eps):
                converged = False
                if finite:
                    # the engine stops at max(eps, precision floor): a
                    # finite gap above eps means the floor (or the outer
                    # budget) cut it short — the KKT check arbitrates
                    events.append("precision_floor"
                                  if u.get("n_outer", -1) < max_outer
                                  or not max_outer
                                  else "gap_above_eps")
            if u["kkt"] and ser.check_kkt:
                lam = float(u["lam"])
                tol = max(ser.kkt_rtol * lam, ser.kkt_atol)
                tol_w = max(tol_w, tol)
                X = u["X"]
                if u.get("kkt_r") is not None:   # batched fleet cert
                    r = u["kkt_r"]
                else:
                    r = read("certificate", float, _kkt_fn(
                        sess.config.loss)(
                        X, u["y"], _beside(u["beta"], X),
                        jnp.asarray(lam, X.dtype), u["pen"],
                        u["sample_w"]))
                kkt_w = _wmax(kkt_w, r)
                if not (r <= tol):           # NaN residual fails too
                    u_ok = False
                    events.append("kkt_violation")
            else:
                # no scalar KKT conditions (group penalty, CV scores) or
                # checking disabled: the duality gap is the certificate
                u_ok = u_ok and (g <= eps)
            ok = ok and u_ok
            unit_ok.append(u_ok)
        self._last_unit_ok = unit_ok
        return ok, converged, gap_w, kkt_w, tol_w, events

    def _units(self, request, value, sess) -> List[dict]:
        """Decompose a result into per-solution certification units.
        Each unit: beta/gap to check, the (X, y, lam, pen, sample_w)
        the KKT residual needs, and whether scalar KKT applies."""
        import jax.numpy as jnp
        from repro.core import api
        grouped = isinstance(sess.penalty, api.GroupPenalty)
        fusedp = isinstance(sess.penalty, api.FusedPenalty)

        def design():
            if fusedp:
                pen = jnp.ones(sess._design.Xt.shape[1],
                               sess._design.Xt.dtype
                               ).at[sess._design.unpen_idx].set(0.0)
                return sess._design.Xt, sess._y, pen
            X = jnp.asarray(sess.problem.X)
            y = None if sess.problem.y is None \
                else jnp.asarray(sess.problem.y, X.dtype)
            return X, y, None

        if isinstance(request, api.Scalar):
            if grouped:
                # group KKT is blockwise; certify by gap only
                return [dict(beta=value.beta, gap=value.gap,
                             lam=request.lam, kkt=False,
                             n_outer=read("n_outer", int, value.n_outer))]
            X, y, pen = design()
            res = value[1] if fusedp else value
            sw = None if sess.problem.weights is None \
                else jnp.asarray(sess.problem.weights, X.dtype)
            return [dict(beta=res.beta, gap=res.gap, lam=request.lam,
                         kkt=True, X=X, y=y, pen=pen, sample_w=sw,
                         overflowed=read("overflowed", bool,
                                         res.overflowed),
                         n_outer=read("n_outer", int, res.n_outer))]

        if isinstance(request, api.Path):
            if grouped:
                return [dict(beta=r.beta, gap=r.gap, lam=float(lam),
                             kkt=False,
                             n_outer=read("n_outer", int, r.n_outer))
                        for lam, r in zip(value.lams, value.results)]
            X, y, pen = design()
            pr = value.path if fusedp else value
            return [dict(beta=b, gap=r.gap, lam=float(lam), kkt=True,
                         X=X, y=y, pen=pen, sample_w=None,
                         overflowed=read("overflowed", bool,
                                         r.overflowed),
                         n_outer=read("n_outer", int, r.n_outer))
                    for lam, b, r in zip(pr.lams, pr.betas, pr.results)]

        if isinstance(request, api.Fleet):
            X, _, pen = design()
            Y = jnp.asarray(request.Y, X.dtype)
            Y = Y[None, :] if Y.ndim == 1 else Y
            B = Y.shape[0]
            lams = np.broadcast_to(
                np.asarray(request.lams, np.float64).reshape(-1), (B,)) \
                if np.asarray(request.lams).ndim else \
                np.full((B,), float(request.lams))
            W = None
            if request.weights is not None:
                W = jnp.asarray(request.weights, X.dtype)
                W = W[None, :] if W.ndim == 1 else W
            # one host transfer per batched field, then free numpy
            # slicing — per-unit device reads would cost a dispatch +
            # sync each and dominate wide coalesced batches
            beta = read("beta", np.asarray, value.beta)
            gap = read("gap", np.asarray, value.gap)
            ovf = read("overflowed", np.asarray, value.overflowed)
            nout = read("n_outer", np.asarray, value.n_outer)
            kkt_r = None
            if self.serving.check_kkt and W is None:
                kkt_r = read("certificate", np.asarray, _kkt_fleet_fn(
                    sess.config.loss)(X, Y, value.beta,
                                      jnp.asarray(lams, X.dtype), pen))
            # host y slices for the fallback path
            Y_np = read("responses", np.asarray, Y)
            return [dict(beta=beta[b], gap=gap[b],
                         lam=float(lams[b]), kkt=True, X=X, y=Y_np[b],
                         pen=pen,
                         sample_w=None if W is None else W[b],
                         kkt_r=None if kkt_r is None
                         else float(kkt_r[b]),
                         overflowed=bool(ovf[b]),
                         n_outer=int(nout[b]))
                    for b in range(B)]

        if isinstance(request, api.CV):
            X, y, pen = design()
            if value.beta is None:
                # scores-only CV: certify the score table's finiteness
                return [dict(beta=jnp.asarray(read("result", np.asarray,
                                                value.cv_mean)),
                             gap=0.0, lam=float(value.best_lam),
                             kkt=False)]
            res = value.best_result
            return [dict(beta=value.beta,
                         gap=(0.0 if res is None else res.gap),
                         lam=float(value.best_lam), kkt=True, X=X, y=y,
                         pen=pen, sample_w=None,
                         overflowed=False if res is None
                         else read("overflowed", bool, res.overflowed),
                         n_outer=0 if res is None
                         else read("n_outer", int, res.n_outer))]

        if isinstance(request, api.Update):
            if value is None:        # resolve=False: ingest-only, nothing
                return []            # to certify until the next solve
            prep = sess._prep
            lam = getattr(sess, "_last_lam", None)
            # streaming design: the capacity-padding rows are exactly
            # zero, so the full padded (X, y) gives the same LS KKT
            # residual as the logical row set (DESIGN.md §14)
            return [dict(beta=value.beta, gap=value.gap,
                         lam=float(lam), kkt=True, X=prep.X, y=prep.y,
                         pen=None, sample_w=None,
                         overflowed=read("overflowed", bool,
                                         value.overflowed),
                         n_outer=read("n_outer", int, value.n_outer))]

        if isinstance(request, api.Select):
            if value.beta is None:
                # no refit requested: certify the CV score table's
                # finiteness at the chosen lambda (the CV idiom above)
                return [dict(beta=jnp.asarray(read("result", np.asarray,
                                                value.cv_mean)),
                             gap=0.0, lam=float(value.lam), kkt=False)]
            if getattr(sess, "_online", None) is not None:
                prep = sess._prep
                X, y, pen = prep.X, prep.y, None   # zero pad rows exact
            else:
                X, y, pen = design()
            res = value.best_result
            return [dict(beta=value.beta,
                         gap=(0.0 if res is None else res.gap),
                         lam=float(value.lam), kkt=True, X=X, y=y,
                         pen=pen, sample_w=None,
                         overflowed=False if res is None
                         else read("overflowed", bool, res.overflowed),
                         n_outer=0 if res is None
                         else read("n_outer", int, res.n_outer))]

        raise RequestError(f"unknown request {request!r}")

    def _scrub_warm(self, request, events) -> None:
        """A failed solve may have harvested corrupt warm state (NaN
        coefficients in the slot buffers); reset the affected warm
        surface so later warm=True requests re-enter cold."""
        from repro.core import api
        if not isinstance(request, (api.Scalar, api.Path, api.Update)):
            return
        s = self.session
        if getattr(request, "sharded", False):
            s._sharded_warm, s._sharded_warm_k = None, None
        elif isinstance(s.penalty, api.GroupPenalty):
            s._gwarm = None
        else:
            s.set_warm_state(None, None)
            # a result seeded from the cross-request cache failed its
            # certificate: drop the seeding entry so repeat traffic
            # re-enters cold (DESIGN.md §14)
            drop = getattr(s, "drop_cache_entry", None)
            if drop is not None and drop():
                events.append("warm_cache_invalidated")
        events.append("warm_state_reset")

    # ------------------------------------------------------------------
    # the degradation ladder
    # ------------------------------------------------------------------

    def _run_rung(self, name, request, value):
        if name == "grow":
            return self._rung_grow(request)
        if name == "oracle":
            return self._rung_oracle(request, value)
        if name == "x64":
            return self._rung_x64(request)
        return None

    def _rung_grow(self, request):
        """Re-solve with grown active-set capacity and a 4x outer budget
        — the *safe-guarantee-preserving* rung: it still screens, so the
        gap certificate semantics are unchanged (DESIGN.md §10)."""
        from repro.core import api
        sess = self.session
        if isinstance(sess.penalty, api.GroupPenalty):
            return None
        if isinstance(request, api.Update):
            # replaying an Update on a fresh session of the ORIGINAL
            # problem would double-apply the rows; the oracle rung
            # re-solves the streamed problem instead
            return None
        if getattr(request, "sharded", False):
            return None
        if isinstance(request, api.Fleet) and request.screen_fn is not None:
            return None
        cfg = sess.config
        p = int(np.asarray(self.problem.X).shape[1])
        k2 = min(p, max(2 * (cfg.k_max or 0), 256))
        cfg2 = dataclasses.replace(cfg, k_max=k2,
                                   max_outer=cfg.max_outer * 4)
        tmp = api.open_session(self.problem, cfg2,
                               mesh=self._opts["mesh"],
                               segment_len=self._opts["segment_len"])
        req2 = dataclasses.replace(request, warm=False) \
            if isinstance(request, (api.Scalar, api.Path)) else request
        return tmp.solve(req2), tmp

    def _rung_oracle(self, request, value):
        """Re-solve the failed units with the unscreened CM oracle
        (``solve_lasso_cm``) — screening-free, so even a screening bug
        cannot survive it; the cost is the full O(np)-per-epoch sweep
        the paper's method exists to avoid. The safe guarantee is
        *vacuously* preserved (nothing is screened)."""
        from repro.core import api
        sess = self.session
        if isinstance(sess.penalty, api.GroupPenalty):
            return None
        fusedp = isinstance(sess.penalty, api.FusedPenalty)
        import jax.numpy as jnp
        failed = self._last_unit_ok

        if isinstance(request, api.Scalar):
            if fusedp:
                rec, res = value
                out = self._oracle_solve(sess._design.Xt, sess._y,
                                         float(request.lam), None)
                if out is None:
                    return None
                beta, gap = out
                res2 = _result_like(res, beta, gap)
                from repro.core.fused import recover_from_transformed
                return (recover_from_transformed(beta, sess._design),
                        res2), sess
            X = jnp.asarray(self.problem.X)
            y = jnp.asarray(self.problem.y, X.dtype)
            out = self._oracle_solve(X, y, float(request.lam),
                                     self.problem.weights)
            if out is None:
                return None
            beta, gap = out
            return _result_like(value, beta, gap), sess

        if isinstance(request, api.Path):
            pr = value.path if fusedp else value
            if fusedp:
                Xd, yd = sess._design.Xt, sess._y
            else:
                Xd = jnp.asarray(self.problem.X)
                yd = jnp.asarray(self.problem.y, Xd.dtype)
            betas, results = list(pr.betas), list(pr.results)
            for i, lam in enumerate(pr.lams):
                if i < len(failed) and failed[i]:
                    continue
                out = self._oracle_solve(Xd, yd, float(lam), None)
                if out is None:
                    return None
                b, g = out
                betas[i] = b
                results[i] = _result_like(results[i], b, g)
            from repro.core.path import SaifPathResult
            pr2 = SaifPathResult(lams=pr.lams, betas=betas,
                                 results=results,
                                 n_compilations=pr.n_compilations)
            if fusedp:
                from repro.core.fused import (FusedPathResult,
                                              recover_from_transformed)
                rec = [recover_from_transformed(b, sess._design)
                       for b in betas]
                return FusedPathResult(lams=pr.lams, betas=rec,
                                       path=pr2), sess
            return pr2, sess

        if isinstance(request, api.Fleet):
            X = jnp.asarray(self.problem.X)
            Y = jnp.asarray(request.Y, X.dtype)
            Y = Y[None, :] if Y.ndim == 1 else Y
            B = Y.shape[0]
            lams = np.broadcast_to(
                np.asarray(request.lams, np.float64).reshape(-1), (B,)) \
                if np.asarray(request.lams).ndim else \
                np.full((B,), float(request.lams))
            W = request.weights
            beta, gap = value.beta, value.gap
            n_act, ovf = value.n_active, value.overflowed
            for b in range(B):
                if b < len(failed) and failed[b]:
                    continue
                w_b = None
                if W is not None:
                    w_arr = np.asarray(W)
                    w_b = w_arr if w_arr.ndim == 1 else w_arr[b]
                out = self._oracle_solve(X, Y[b], float(lams[b]), w_b)
                if out is None:
                    return None
                ob, og = out
                beta = beta.at[b].set(jnp.asarray(ob, beta.dtype))
                gap = gap.at[b].set(jnp.asarray(og, gap.dtype))
                n_act = n_act.at[b].set(
                    jnp.asarray((jnp.abs(ob) > 0).sum(), n_act.dtype))
                ovf = ovf.at[b].set(False)
            return value._replace(beta=beta, gap=gap, n_active=n_act,
                                  overflowed=ovf), sess

        if isinstance(request, api.CV):
            if value.beta is None:
                return None
            X = jnp.asarray(self.problem.X)
            y = jnp.asarray(self.problem.y, X.dtype)
            out = self._oracle_solve(X, y, float(value.best_lam), None)
            if out is None:
                return None
            beta, gap = out
            res = value.best_result
            if res is not None:
                res = _result_like(res, beta, gap)
            return value._replace(beta=beta, best_result=res), sess

        if isinstance(request, api.Select):
            if value.beta is None:
                return None
            if getattr(sess, "_online", None) is not None:
                Xd, yd = sess._prep.X, sess._prep.y   # zero pad rows exact
            else:
                Xd = jnp.asarray(self.problem.X)
                yd = jnp.asarray(self.problem.y, Xd.dtype)
            out = self._oracle_solve(Xd, yd, float(value.lam), None)
            if out is None:
                return None
            beta, gap = out
            res = value.best_result
            if res is not None:
                res = _result_like(res, beta, gap)
            return value._replace(beta=beta, best_result=res), sess

        if isinstance(request, api.Update):
            prep = getattr(sess, "_prep", None)
            lam = getattr(sess, "_last_lam", None)
            if value is None or prep is None or lam is None:
                return None
            # the streamed problem lives in the session's padded prep;
            # zero pad rows make the unscreened LS oracle exact
            out = self._oracle_solve(prep.X, prep.y, float(lam), None)
            if out is None:
                return None
            beta, gap = out
            return _result_like(value, beta, gap), sess

        return None

    def _oracle_solve(self, X, y, lam: float, sample_w):
        """One unscreened CM solve to the serving tolerance, plus its
        own duality-gap certificate. Weighted least squares rides the
        sqrt-weight row rescaling; weighted non-quadratic losses have no
        oracle here (rung reports 'skipped')."""
        import jax.numpy as jnp
        from repro.core.cm import solve_lasso_cm
        from repro.core.duality import duality_gap, feasible_dual
        from repro.core.losses import get_loss
        cfg = self.session.config
        loss = get_loss(cfg.loss)
        if sample_w is not None:
            if cfg.loss != "least_squares":
                return None
            sw = jnp.sqrt(jnp.asarray(sample_w, X.dtype))
            X, y = X * sw[:, None], y * sw
        tol = self.serving.oracle_tol
        tol = float(getattr(cfg, "eps", 1e-6)) if tol is None else tol
        unpen = getattr(cfg, "unpen_idx", None)
        beta = solve_lasso_cm(loss, X, y, float(lam), tol=tol,
                              unpen_idx=unpen)
        lam_a = jnp.asarray(lam, X.dtype)
        pen = x_unpen = None
        if unpen is not None:
            pen = jnp.ones(X.shape[1], X.dtype).at[unpen].set(0.0)
            x_unpen = X[:, unpen]
        hat = -loss.grad(X @ beta, y) / lam_a
        theta = feasible_dual(loss, X, y, hat, lam_a, pen=pen,
                              x_unpen=x_unpen)
        gap = duality_gap(loss, X, y, beta, theta, lam_a, pen=pen)
        return beta, gap

    def _rung_x64(self, request):
        """Last rung: the whole problem re-cast to float64 — for
        precision-floor failures where the gap certificate bottomed out
        above the verdict tolerance in float32."""
        import jax
        from repro.core import api
        if not jax.config.jax_enable_x64:
            return None
        if isinstance(self.session.penalty, api.GroupPenalty):
            return None
        if isinstance(request, api.Update):
            return None     # same double-apply hazard as _rung_grow
        X = np.asarray(self.problem.X)
        y = self.problem.y
        y64 = None if y is None else np.asarray(y, np.float64)
        w = self.problem.weights
        already = X.dtype == np.float64 and (
            y is None or np.asarray(y).dtype == np.float64)
        if already:
            return None
        p64 = api.Problem(X.astype(np.float64), y64,
                          loss=self.problem.loss,
                          penalty=self.problem.penalty,
                          weights=None if w is None
                          else np.asarray(w, np.float64))
        tmp = api.open_session(p64, self.session.config,
                               mesh=self._opts["mesh"],
                               segment_len=self._opts["segment_len"])
        req2 = dataclasses.replace(request, warm=False) \
            if isinstance(request, (api.Scalar, api.Path)) else request
        return tmp.solve(req2), tmp

    # ------------------------------------------------------------------
    # warm checkpoint / restore (DESIGN.md §10 checkpoint layout)
    # ------------------------------------------------------------------

    def checkpoint(self) -> Optional[str]:
        """Atomically snapshot the session's device-resident warm
        boundary state. Layout: the ckpt module's one-.npy-per-leaf
        directory with leaf shapes/dtypes + the problem digest recorded
        in meta ``extra`` — restore needs no caller-supplied structure.
        No-op (None) without a ckpt_dir or before the first warm
        harvest."""
        ser = self.serving
        warm = self.session.warm_state
        if ser.ckpt_dir is None or warm is None:
            return None
        idx, beta, mask, inner = warm
        tree = {"idx": idx, "beta": beta, "mask": mask,
                "G": inner.G, "rho": inner.rho, "gidx": inner.gidx}
        leaves = {k: {"shape": list(np.shape(v)),
                      "dtype": str(np.asarray(v).dtype)}
                  for k, v in tree.items()}
        extra = {"kind": "saif-warm-state",
                 "k_max": self.session.warm_capacity,
                 "digest": self._digest(), "leaves": leaves,
                 "requests": self._requests}
        from repro.ckpt import checkpoint as ck
        self._step += 1
        return ck.save(ser.ckpt_dir, self._step, tree, extra=extra)

    def _maybe_restore(self) -> bool:
        """Resume warm from the latest matching checkpoint: digest-gated
        (a checkpoint of a *different* problem is ignored, not an
        error), structure rebuilt from the recorded shapes/dtypes."""
        from repro.ckpt import checkpoint as ck
        ser = self.serving
        step = ck.latest_step(ser.ckpt_dir)
        if step is None:
            return False
        try:
            meta = ck.load_meta(ser.ckpt_dir, step)
        except (OSError, ValueError):    # torn/garbage dir: stay cold
            return False
        extra = meta.get("extra", {})
        if extra.get("kind") != "saif-warm-state" \
                or extra.get("digest") != self._digest():
            return False
        import jax.numpy as jnp
        from repro.core.inner_backend import InnerCarry
        like = {k: jnp.zeros(tuple(v["shape"]), np.dtype(v["dtype"]))
                for k, v in extra["leaves"].items()}
        tree, _ = ck.restore(ser.ckpt_dir, step, like)
        warm = (tree["idx"], tree["beta"], tree["mask"],
                InnerCarry(G=tree["G"], rho=tree["rho"],
                           gidx=tree["gidx"]))
        self.session.set_warm_state(warm, extra["k_max"])
        self._step = step
        return True

    def _digest(self) -> str:
        """Problem identity for checkpoint gating: design + response +
        weights bytes, loss, penalty spec and the unpenalized slot.
        Backend knobs are deliberately excluded — warm state survives a
        circuit-breaker backend swap."""
        h = hashlib.sha256()
        pb = self.problem
        for arr in (pb.X, pb.y, pb.weights):
            if arr is None:
                h.update(b"<none>")
                continue
            a = np.ascontiguousarray(np.asarray(arr))
            h.update(str(a.shape).encode())
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
        h.update(pb.loss.encode())
        h.update(repr(self.session.penalty).encode())
        h.update(str(getattr(self.session.config,
                             "unpen_idx", None)).encode())
        return h.hexdigest()

    def close(self) -> None:
        """Flush pending async checkpoint writes, take a final warm
        snapshot and release the SIGTERM hook."""
        from repro.ckpt import checkpoint as ck
        ck.wait_pending()
        self.checkpoint()
        if self.guard is not None:
            self.guard.uninstall()


def _score(kkt: float, gap: float) -> float:
    """Ladder candidate ranking: lower is better, NaN is worst."""
    s = kkt if math.isfinite(kkt) else float("inf")
    g = gap if math.isfinite(gap) else float("inf")
    return s if s < float("inf") else g + 1e30


def _result_like(like, beta, gap):
    """Wrap an oracle solution in the engine's result type: beta/gap
    replaced, support fields recomputed, traces left as the failed
    solve's (the verdict's rung record is the authority on provenance)."""
    import jax.numpy as jnp
    k = like.active_idx.shape[-1]
    beta = jnp.asarray(beta, like.beta.dtype)
    nz = jnp.nonzero(jnp.abs(beta) > 0, size=k, fill_value=-1)[0]
    nz = nz.astype(like.active_idx.dtype)
    return like._replace(
        beta=beta, gap=jnp.asarray(gap, like.gap.dtype),
        n_active=jnp.asarray((jnp.abs(beta) > 0).sum(),
                             like.n_active.dtype),
        overflowed=jnp.zeros_like(like.overflowed),
        active_idx=nz, active_mask=nz >= 0)


def open_serving(problem, config=None, *, serving=None, guard=None,
                 install_sigterm: bool = False,
                 **session_kwargs) -> ServingSession:
    """Open a fault-tolerant serving session (DESIGN.md §10).

    Same signature as :func:`repro.core.api.open_session` — the
    passthrough ``session_kwargs`` are the one shared spec
    ``repro.core.api.SESSION_KWARG_DEFAULTS`` (``mesh``,
    ``segment_len``, ``make_screen``, ``pad_to``) — plus ``serving``
    (a :class:`ServingConfig`) and preemption wiring:
    ``install_sigterm=True`` installs a
    :class:`~repro.runtime.fault.PreemptionGuard` whose SIGTERM flag
    makes the next ``solve`` checkpoint the warm state; passing an
    existing ``guard`` reuses one. With ``serving.ckpt_dir`` set, a
    matching checkpoint is restored at open — a restarted server's
    first warm request re-enters exactly where the SIGTERM'd one left
    off."""
    if guard is None and install_sigterm:
        from repro.runtime.fault import PreemptionGuard
        guard = PreemptionGuard(install=True)
    return ServingSession(problem, config, serving=serving, guard=guard,
                          **session_kwargs)
