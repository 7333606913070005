"""Async serving front-end: queue → shape-bucket → microbatch → fleet
(DESIGN.md §12).

PR 5/6 made one request cheap (:class:`~repro.core.api.Session`) and
safe (:class:`~repro.core.serving.ServingSession`); this module makes a
*traffic stream* cheap. The problems it solves are compilation-shape
economics, not numerics:

* **Shape buckets** — every novel ``(n, p)`` would pay a fresh engine
  compile. Incoming problems are padded up to a small static grid of
  ``(n_bucket, p_bucket)`` buckets, so a heterogeneous request mix runs
  on a handful of compiled programs. Column (p) padding is *bitwise*
  neutral — pad columns carry ``c0 = -inf`` / ``col_norm = 1`` guards
  and are born "already active" through a traced pad mask, and the one
  full-width reduction in the engine (``theta @ X``) is column-append
  invariant — so a padded solve returns bit-identical coefficients to
  the direct unpadded solve. Row (n) padding is the opt-in second tier
  (exact in real arithmetic; support-parity + KKT-certified in floats).
* **Microbatch coalescing** — :class:`~repro.core.api.Scalar` requests
  over the *same design* (per-user responses ``y``, per-user lambdas —
  the paper's "millions of users" regime) waiting in one bucket's queue
  are coalesced (under a ``max_wait_ms``/``max_batch`` policy) into one
  :class:`~repro.core.api.Fleet` solved by the lockstep fleet engine in
  a single dispatch, whose per-member results are bitwise the serial
  solves. Each rider's future resolves to its own
  :class:`~repro.core.serving.ServingResult` with a *per-unit* verdict
  — one poisoned member degrades only its own future.
* **Warm-session LRU** — dispatch goes through a per-``(problem digest,
  bucket)`` LRU of :class:`~repro.core.serving.ServingSession`s. The
  engine jit caches are process-wide, so eviction and readmission cost
  session re-prep but *zero* new engine compilations.
* **Restart warmth** — with ``ServerConfig.cache_dir`` set, JAX's
  persistent compilation cache is enabled (min-compile-time/entry-size
  thresholds zeroed) so a restarted server replays its compiles from
  disk: zero cold-start compilations on the second life. A
  ``JAX_COMPILATION_CACHE_DIR`` in the environment takes precedence:
  nothing then sets another directory in code
  (:func:`enable_compile_cache`).

Module scope imports only stdlib, numpy and the span helper (which loads
jax on first use) — ``from repro import open_server`` keeps the
lazy-surface contract; jax and the engines load on first dispatch.

Spans (``repro.runtime.spans``): ``repro.server.submit`` (client thread,
``req`` = the entry's ``seq``), ``repro.server.coalesce_wait`` (the
microbatch window held open), ``repro.server.dispatch`` (one per
microbatch, ``reqs`` = the riders' ``seq``s) and ``repro.server.resolve``
(host copies, rider views, futures).
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import os
import pathlib
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from repro.runtime.spans import read, span

__all__ = ["ServerConfig", "ServerStats", "ServingFuture", "Server",
           "open_server", "enable_compile_cache", "CHECKOUT_CACHE_DIR"]

# fixed in-checkout compile-cache directory (ignored by git): the path is
# part of the cache key, so it must not move between runs
CHECKOUT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                         / ".jax_cache")


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Policy knobs of the async front-end (DESIGN.md §12).

    ``p_buckets``/``n_buckets`` define the static compile-bucket grid: a
    request lands in the smallest bucket that dominates its shape. With
    ``p_buckets=None`` the column bucket is the next power of two of
    ``p`` (floored at ``min_p_bucket``); with ``n_buckets=None`` rows
    are never padded (the bitwise tier — row padding is opt-in because
    it is exact in real arithmetic but only support-parity in floats,
    and is structurally wrong for the logistic loss, whose pad rows
    would shift the primal by log 2 each). A shape beyond the grid falls
    back to its power-of-two bucket (counted in ``stats().bucket_
    fallbacks``) instead of rejecting the request.
    """
    p_buckets: Optional[Tuple[int, ...]] = None
    n_buckets: Optional[Tuple[int, ...]] = None
    min_p_bucket: int = 8
    max_batch: int = 8            # coalesced microbatch size cap
    max_wait_ms: float = 5.0      # coalescing window per microbatch
    max_sessions: int = 8         # warm-session LRU capacity
    cache_dir: Optional[str] = None   # persistent compilation cache
    solver: Any = None            # solver config shared by every session
    serving: Any = None           # ServingConfig shared by every session
    warm_cache: Any = None        # shared WarmCache — cross-request
    #                               homotopy entries (DESIGN.md §14)
    autostart: bool = True        # start the dispatch thread at open


class ServerStats(NamedTuple):
    """Server-lifetime counters (benchmarks/bench_serve.py columns)."""
    submitted: int
    served: int                  # futures resolved with a result
    failed: int                  # futures rejected with a typed error
    deadline_misses: int         # expired in the queue, never dispatched
    coalesced_batches: int       # microbatches with >= 2 riders
    coalesced_requests: int      # requests served inside those batches
    sessions_opened: int         # LRU misses (includes readmissions)
    evictions: int
    bucket_fallbacks: int        # shapes beyond the configured grid
    stragglers: int              # dispatches flagged by the monitors
    pending: int                 # queued + in-flight right now
    dispatched: int = 0          # requests taken off the queues
    queue_wait_s: float = 0.0    # sum of (claim - submit) over them


# ---------------------------------------------------------------------------
# futures
# ---------------------------------------------------------------------------

class ServingFuture:
    """Resolves to the request's :class:`~repro.core.serving.
    ServingResult`; a typed serving error propagates out of
    :meth:`result` exactly as it would from the sync
    ``ServingSession.solve``."""

    __slots__ = ("_event", "_result", "_exc", "_callbacks", "_cb_lock")

    def __init__(self):
        self._event = threading.Event()
        self._result = None
        self._exc: Optional[BaseException] = None
        self._callbacks: List[Any] = []
        self._cb_lock = threading.Lock()

    def done(self) -> bool:
        return self._event.is_set()

    def add_done_callback(self, fn) -> None:
        """Call ``fn(self)`` when the future resolves (immediately if it
        already has) — the load generator's latency timestamp hook."""
        with self._cb_lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            from repro.core.serving import DeadlineExceeded
            raise DeadlineExceeded(
                f"future not resolved within {timeout!r}s")
        if self._exc is not None:
            raise self._exc
        return self._result

    def exception(self, timeout: Optional[float] = None
                  ) -> Optional[BaseException]:
        if not self._event.wait(timeout):
            from repro.core.serving import DeadlineExceeded
            raise DeadlineExceeded(
                f"future not resolved within {timeout!r}s")
        return self._exc

    # -- producer side (Server only) -----------------------------------
    def _resolve(self, result) -> None:
        self._result = result
        self._fire()

    def _reject(self, exc: BaseException) -> None:
        self._exc = exc
        self._fire()

    def _fire(self) -> None:
        with self._cb_lock:
            self._event.set()
            cbs, self._callbacks = self._callbacks, []
        for fn in cbs:
            fn(self)


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def _pick_bucket(v: int, grid: Optional[Tuple[int, ...]],
                 floor: int = 1) -> Tuple[int, bool]:
    """Smallest grid entry >= v, else the pow2 fallback (flagged)."""
    if grid:
        fits = [g for g in grid if g >= v]
        if fits:
            return min(fits), False
        return max(_next_pow2(v), floor), True
    return max(_next_pow2(v), floor), False


def _problem_digest(problem, *, design_only: bool = False) -> str:
    """Problem identity for session keying — mirrors the checkpoint
    digest in ``serving.py``: data bytes + loss + penalty spec. With
    ``design_only`` the response ``y`` is excluded: requests from
    different users over the SAME design coalesce into one fleet (the
    paper's serving regime — one shared design, per-user responses),
    so the queue keys on the design while per-problem sessions key on
    the full identity."""
    h = hashlib.sha256()
    arrs = (problem.X, problem.weights) if design_only else (
        problem.X, problem.y, problem.weights)
    for arr in arrs:
        if arr is None:
            h.update(b"<none>")
            continue
        a = np.ascontiguousarray(read("digest", np.asarray, arr))
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.reshape(-1).view(np.uint8))      # no bytes copy
    h.update(problem.loss.encode())
    h.update(repr(problem.penalty).encode())
    return h.hexdigest()


def _is_lasso(problem) -> bool:
    pen = problem.penalty
    return pen == "lasso" or type(pen).__name__ == "LassoPenalty"


# ---------------------------------------------------------------------------
# queue entries
# ---------------------------------------------------------------------------

class _Entry:
    __slots__ = ("seq", "priority", "t_submit", "problem", "request",
                 "future", "coalesce")

    def __init__(self, seq, priority, problem, request, future, coalesce):
        self.seq = seq
        self.priority = priority
        self.t_submit = time.monotonic()
        self.problem = problem
        self.request = request
        self.future = future
        self.coalesce = coalesce


def _rank(e: _Entry):
    # higher priority first; FIFO within a priority class
    return (-e.priority, e.seq)


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------

class Server:
    """Queue → shape-bucket → microbatch → fleet. Construct via
    :func:`open_server`; submit with :meth:`submit`; every future
    resolves to a :class:`~repro.core.serving.ServingResult`."""

    def __init__(self, config: Optional[ServerConfig] = None, *,
                 guard=None, **kwargs):
        from repro.core.api import session_kwargs
        self.config = config if config is not None else ServerConfig()
        opts = session_kwargs(**kwargs)
        if opts.get("pad_to") is not None:
            raise TypeError(
                "open_server() owns bucket padding; configure "
                "ServerConfig.p_buckets/n_buckets instead of pad_to")
        opts.pop("pad_to", None)
        self._opts = opts
        self._guard = guard
        if self.config.cache_dir:
            enable_compile_cache(self.config.cache_dir)
        self._cond = threading.Condition()
        self._queues: Dict[tuple, List[_Entry]] = {}
        self._inflight = 0
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._seq = itertools.count()
        # LRU of warm sessions, most-recently-used last
        self._lru: "Dict[tuple, Any]" = {}
        self._digests: Dict[int, Tuple[Any, str]] = {}
        self._monitors: Dict[tuple, Any] = {}
        # counters (read under _cond)
        self._submitted = 0
        self._served = 0
        self._failed = 0
        self._deadline_misses = 0
        self._coalesced_batches = 0
        self._coalesced_requests = 0
        self._sessions_opened = 0
        self._evictions = 0
        self._bucket_fallbacks = 0
        self._stragglers = 0
        self._dispatched = 0
        self._queue_wait_s = 0.0
        if self.config.autostart:
            self._start()

    # -- lifecycle ------------------------------------------------------

    def _start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._worker_loop, name="repro-server", daemon=True)
            self._thread.start()

    def run(self, timeout: Optional[float] = None) -> None:
        """Block the calling thread serving requests until
        :meth:`close` (from another thread) or ``timeout``."""
        self._start()
        self._thread.join(timeout)

    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every submitted request has resolved."""
        end = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self._pending_locked():
                rem = None if end is None else end - time.monotonic()
                if rem is not None and rem <= 0:
                    from repro.core.serving import DeadlineExceeded
                    raise DeadlineExceeded(
                        f"drain() timed out with "
                        f"{self._pending_locked()} requests pending")
                self._cond.wait(0.2 if rem is None else min(rem, 0.2))

    def close(self) -> None:
        """Stop the dispatcher; queued-but-unserved futures reject with
        a ``RequestError``. Warm sessions are closed."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        for sess in self._lru.values():
            sess.close()
        self._lru.clear()

    def __enter__(self) -> "Server":
        return self

    def __exit__(self, *exc_info):
        self.close()
        return False

    # -- submission -----------------------------------------------------

    def submit(self, problem, request) -> ServingFuture:
        """Validate, bucket and enqueue one request. Returns immediately
        with a :class:`ServingFuture`; admission errors raise *here*,
        synchronously, with the same typed taxonomy as the sync path."""
        from repro.core.serving import RequestError, validate_request
        seq = next(self._seq)
        with span("repro.server.submit", req=seq):
            validate_request(request)
            with self._cond:
                if self._stop:
                    raise RequestError("server is closed")
            key = self._bucket_key(problem, request)
            fut = ServingFuture()
            entry = _Entry(seq, int(getattr(request, "priority", 0)),
                           problem, request, fut,
                           self._coalescible(problem, request))
            with self._cond:
                self._submitted += 1
                self._queues.setdefault(key, []).append(entry)
                self._cond.notify_all()
        return fut

    def stats(self) -> ServerStats:
        with self._cond:
            return ServerStats(
                submitted=self._submitted, served=self._served,
                failed=self._failed,
                deadline_misses=self._deadline_misses,
                coalesced_batches=self._coalesced_batches,
                coalesced_requests=self._coalesced_requests,
                sessions_opened=self._sessions_opened,
                evictions=self._evictions,
                bucket_fallbacks=self._bucket_fallbacks,
                stragglers=self._stragglers,
                pending=self._pending_locked(),
                dispatched=self._dispatched,
                queue_wait_s=self._queue_wait_s)

    # -- bucketing ------------------------------------------------------

    def _digest(self, problem, *, design_only: bool = False) -> str:
        cache_key = (id(problem), design_only)
        hit = self._digests.get(cache_key)
        if hit is not None and hit[0] is problem:
            return hit[1]
        d = _problem_digest(problem, design_only=design_only)
        self._digests[cache_key] = (problem, d)
        return d

    def _bucket_key(self, problem, request) -> tuple:
        cfg = self.config
        n, p = np.shape(problem.X)
        # padding is the lasso fleet substrate's contract; other
        # penalties / weighted problems serve at their exact shape
        pad_ok = _is_lasso(problem) and problem.weights is None
        if pad_ok:
            p_b, fb_p = _pick_bucket(p, cfg.p_buckets, cfg.min_p_bucket)
            fb_n = False
            if cfg.n_buckets and problem.loss == "least_squares":
                n_b, fb_n = _pick_bucket(n, cfg.n_buckets)
            else:
                n_b = n
            if fb_p or fb_n:
                with self._cond:
                    self._bucket_fallbacks += 1
        else:
            n_b, p_b = n, p
        # queues key on the DESIGN digest so same-design requests from
        # different users land in one coalescing pool
        return (self._digest(problem, design_only=True), n_b, p_b)

    def _coalescible(self, problem, request) -> bool:
        """Same-design Scalars (each with its own response and lam) ride
        one fleet solve. Warm/sharded scalars and non-lasso problems
        stay serial."""
        return (type(request).__name__ == "Scalar"
                and not getattr(request, "warm", False)
                and not getattr(request, "sharded", False)
                and _is_lasso(problem)
                and problem.weights is None
                and problem.y is not None)

    # -- the dispatch loop ----------------------------------------------

    def _pending_locked(self) -> int:
        return sum(len(q) for q in self._queues.values()) + self._inflight

    def _worker_loop(self) -> None:
        from repro.core.serving import RequestError
        while True:
            with self._cond:
                while not self._stop and not any(self._queues.values()):
                    self._cond.wait(0.2)
                if self._stop:
                    err = RequestError(
                        "server closed before the request was served")
                    for q in self._queues.values():
                        for e in q:
                            e.future._reject(err)
                            self._failed += 1
                    self._queues.clear()
                    self._cond.notify_all()
                    return
                key, batch = self._claim_batch_locked()
                if not batch:
                    continue
                self._inflight += len(batch)
            try:
                self._dispatch(key, batch)
            finally:
                with self._cond:
                    self._inflight -= len(batch)
                    self._cond.notify_all()

    def _claim_batch_locked(self) -> Tuple[tuple, List[_Entry]]:
        """Pick the queue whose head outranks all others; coalescible
        heads hold the microbatch window open for riders."""
        best_key, best_rank = None, None
        for k, q in self._queues.items():
            if not q:
                continue
            r = min(_rank(e) for e in q)
            if best_rank is None or r < best_rank:
                best_key, best_rank = k, r
        if best_key is None:
            return (), []
        q = self._queues[best_key]
        head = min(q, key=_rank)
        if head.coalesce:
            window = self.config.max_wait_ms / 1e3
            deadline = head.t_submit + window
            with span("repro.server.coalesce_wait"):
                while (not self._stop
                       and len([e for e in q if e.coalesce])
                       < self.config.max_batch
                       and time.monotonic() < deadline):
                    self._cond.wait(max(deadline - time.monotonic(), 1e-4))
            q = self._queues.get(best_key, [])
            batch = sorted((e for e in q if e.coalesce),
                           key=_rank)[: self.config.max_batch]
        else:
            batch = [head]
        now = time.monotonic()
        for e in batch:
            q.remove(e)
            self._queue_wait_s += now - e.t_submit
        self._dispatched += len(batch)
        if not q:
            self._queues.pop(best_key, None)
        return best_key, batch

    # -- sessions -------------------------------------------------------

    def _session(self, problem, key: tuple):
        from repro.core.serving import open_serving
        sess = self._lru.get(key)
        if sess is not None:
            # refresh recency
            self._lru.pop(key)
            self._lru[key] = sess
            return sess
        n_b, p_b = key[-2], key[-1]
        n, p = np.shape(problem.X)
        pad_to = (n_b, p_b) if (n_b, p_b) != (n, p) else None
        opts = self._opts
        if self.config.warm_cache is not None \
                and opts.get("warm_cache") is None:
            # every session the server opens shares the configured
            # cross-request homotopy cache; an eviction/readmission
            # cycle then re-enters warm instead of cold
            opts = dict(opts, warm_cache=self.config.warm_cache)
        sess = open_serving(problem, self.config.solver,
                            serving=self.config.serving,
                            guard=self._guard, pad_to=pad_to,
                            **opts)
        with self._cond:
            self._sessions_opened += 1
        self._lru[key] = sess
        while len(self._lru) > max(self.config.max_sessions, 1):
            old_key = next(iter(self._lru))
            self._lru.pop(old_key).close()
            with self._cond:
                self._evictions += 1
        return sess

    def _monitor(self, key: tuple):
        mon = self._monitors.get(key)
        if mon is None:
            from repro.runtime.fault import StragglerMonitor
            factor = getattr(self.config.serving, "straggler_factor", 3.0)
            mon = self._monitors[key] = StragglerMonitor(factor=factor)
        return mon

    # -- dispatch -------------------------------------------------------

    def _expire_locked(self, batch: List[_Entry]) -> List[_Entry]:
        from repro.core.serving import DeadlineExceeded
        now = time.monotonic()
        live = []
        for e in batch:
            dl = getattr(e.request, "deadline_s", None)
            if dl is not None and now - e.t_submit >= dl:
                e.future._reject(DeadlineExceeded(
                    f"request deadline ({dl:g}s) expired in the queue "
                    f"after {now - e.t_submit:.3g}s"))
                with self._cond:
                    self._deadline_misses += 1
                    self._failed += 1
            else:
                live.append(e)
        return live

    def _dispatch(self, key: tuple, batch: List[_Entry]) -> None:
        with span("repro.server.dispatch",
                  reqs=" ".join(str(e.seq) for e in batch), b=len(batch)):
            self._dispatch_live(key, batch)

    def _dispatch_live(self, key: tuple, batch: List[_Entry]) -> None:
        batch = self._expire_locked(batch)
        if not batch:
            return
        _, n_b, p_b = key
        # fleet sessions serve every same-design user (requests carry
        # their own Y), so they key on the design digest; single-request
        # sessions are bound to the problem's y and key on the full one
        if batch[0].coalesce:
            skey = ("fleet",) + key
        else:
            skey = ("single", self._digest(batch[0].problem), n_b, p_b)
        try:
            sess = self._session(batch[0].problem, skey)
        except BaseException as exc:  # noqa: BLE001 - session build
            # failure must reach every rider's future, not kill the loop
            self._reject_batch(batch, exc)
            return
        mon = self._monitor(key)
        t0 = time.monotonic()
        try:
            if len(batch) == 1 and not batch[0].coalesce:
                res = sess.solve(batch[0].request)
                with span("repro.server.resolve"):
                    batch[0].future._resolve(res)
                with self._cond:
                    self._served += 1
            else:
                self._dispatch_coalesced(sess, batch)
        except BaseException as exc:  # noqa: BLE001 - typed serving
            # errors (and anything else) resolve the futures
            self._reject_batch(batch, exc)
        if mon.record(time.monotonic() - t0):
            with self._cond:
                self._stragglers += 1

    def _reject_batch(self, batch: List[_Entry], exc: BaseException):
        for e in batch:
            if not e.future.done():
                e.future._reject(exc)
        with self._cond:
            self._failed += sum(1 for e in batch)

    def _dispatch_coalesced(self, sess, batch: List[_Entry]) -> None:
        """B same-design Scalars (per-user y, per-user lam) → one fleet
        microbatch. The batch axis is padded to a power of two with
        duplicates of rider 0 so batch size joins the bucket grid
        instead of the compile-key churn; the fleet engine solves each
        member independently and bitwise-equal to its serial solve, so
        riders can't perturb each other and per-unit verdicts attribute
        any failure precisely."""
        from repro.core.api import Fleet
        from repro.core.serving import ServingResult
        b_real = len(batch)
        b_pad = _next_pow2(b_real)
        # every rider contributes its OWN response row — the shared
        # design is what the bucket key guarantees
        Y = np.stack([read("responses", np.asarray, e.problem.y)
                      for e in batch])
        lams = [float(e.request.lam) for e in batch]
        lams += [lams[0]] * (b_pad - b_real)
        deadlines = [e.request.deadline_s for e in batch
                     if e.request.deadline_s is not None]
        if b_pad > b_real:
            Y = np.concatenate(
                [Y, np.tile(Y[:1], (b_pad - b_real, 1))], axis=0)
        fleet = Fleet(Y=Y,
                      lams=np.asarray(lams),
                      deadline_s=min(deadlines) if deadlines else None,
                      priority=max(e.priority for e in batch))
        res = sess.solve(fleet)
        verdict = res.verdict
        unit_ok = verdict.unit_ok or (verdict.ok,) * b_pad
        unit_deg = verdict.unit_degraded or (False,) * b_pad
        with span("repro.server.resolve"):
            value_np = _to_host(res.value)   # one transfer per field,
            for i, e in enumerate(batch):    # then numpy views per rider
                v_i = verdict._replace(
                    ok=bool(unit_ok[i]), degraded=bool(unit_deg[i]),
                    unit_ok=(bool(unit_ok[i]),),
                    unit_degraded=(bool(unit_deg[i]),))
                e.future._resolve(
                    ServingResult(value=_unit_view(value_np, i),
                                  verdict=v_i))
        with self._cond:
            self._served += b_real
            if b_real > 1:
                self._coalesced_batches += 1
                self._coalesced_requests += b_real


def _to_host(value):
    """Materialize every leaf of a batched result on the host — done
    once per microbatch so the per-rider slices below are numpy views,
    not per-field device reads."""
    import jax
    return jax.tree_util.tree_map(
        lambda a: read("result", np.asarray, a), value)


def _unit_view(value, i: int):
    """Slice fleet member ``i`` out of a batched result — every field of
    the fleet result carries a leading problem axis."""
    import jax
    return jax.tree_util.tree_map(lambda a: a[i], value)


def enable_compile_cache(cache_dir: Optional[str] = None) -> str:
    """Wire JAX's persistent compilation cache with the thresholds
    zeroed, so even the small SAIF engines persist — a restarted server
    on the same directory replays every compile from disk.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX already reads it
    and no directory is set in code. Otherwise the cache goes to
    ``cache_dir``, or to :data:`CHECKOUT_CACHE_DIR`. Returns the
    directory in use."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        cache_dir = env
    else:
        cache_dir = str(cache_dir or CHECKOUT_CACHE_DIR)
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches the cache off at the first compile it sees with no
    # cache dir configured (_cache_initialized=True, _cache=None) — a
    # server opened mid-process would silently never persist. Reset so
    # the next compile re-initializes against the directory above.
    from jax._src import compilation_cache as _cc
    _cc.reset_cache()
    return cache_dir


def open_server(config: Optional[ServerConfig] = None, *, guard=None,
                **kwargs) -> Server:
    """Open the async serving front-end (DESIGN.md §12).

    ``config`` is a :class:`ServerConfig` (or None for defaults); its
    fields may also be passed as keyword overrides (``open_server(
    max_batch=16, cache_dir=...)``). Remaining keywords are the shared
    session passthrough spec ``repro.core.api.SESSION_KWARG_DEFAULTS``
    (``mesh``, ``segment_len``, ``make_screen``) handed to every warm
    :class:`~repro.core.serving.ServingSession` the server opens —
    ``pad_to`` is owned by the server's bucket grid.

    ::

        server = open_server(max_batch=8, max_wait_ms=5.0)
        fut = server.submit(Problem(X=X, y=y), Scalar(lam, priority=1))
        value, verdict = fut.result(timeout=30)
    """
    field_names = {f.name for f in dataclasses.fields(ServerConfig)}
    overrides = {k: kwargs.pop(k) for k in list(kwargs)
                 if k in field_names}
    if config is None:
        config = ServerConfig(**overrides)
    elif overrides:
        config = dataclasses.replace(config, **overrides)
    return Server(config, guard=guard, **kwargs)
