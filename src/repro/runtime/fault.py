"""Fault-tolerance runtime: step retries, straggler detection, preemption.

The policies below are host-side and hardware-agnostic, so they are fully
unit-testable in this CPU container with injected fakes:

* ``retry_step`` — re-executes a step closure on transient failure
  (``jaxlib`` RuntimeError / timeout) with jittered exponential backoff,
  up to ``max_retries`` and an optional wall-clock ``deadline_s`` cap; on
  persistent failure raises ``StepFailed`` (``RetryDeadlineExceeded`` when
  the deadline, not the retry budget, ran out) so the caller restores the
  last checkpoint / escalates its degradation ladder.
* ``StragglerMonitor`` — tracks per-step wall times; flags a step as
  straggling when it exceeds ``factor`` x the trailing-median of the
  *non-straggling* recent steps (a flagged outlier is excluded from the
  median, so one straggler cannot inflate the threshold its successors
  are judged against). At scale the flag triggers the collective-timeout
  path (abort + restore + exclude the slow host from the next mesh —
  i.e. elastic downsize); here we surface it via a callback.
* ``PreemptionGuard`` — cooperative SIGTERM handling: sets a flag the
  serve/train loop polls to checkpoint-and-exit cleanly (how TPU pods
  signal preemption).
"""
from __future__ import annotations

import random
import signal
import statistics
import time
from typing import Callable, List, Optional, Tuple


class StepFailed(RuntimeError):
    pass


class RetryDeadlineExceeded(StepFailed):
    """The retry loop's wall-clock budget ran out before the step
    succeeded (distinct from exhausting ``max_retries``, so callers can
    map it onto a deadline-typed serving error)."""


# XLA status codes of failures that recur on every attempt: a program the
# compiler refuses, an allocation that does not fit, an unsupported op
_DETERMINISTIC_STATUS = ("RESOURCE_EXHAUSTED", "INVALID_ARGUMENT",
                         "UNIMPLEMENTED", "FAILED_PRECONDITION", "INTERNAL")
_LOWERING_ERRORS = ("MLIRError", "LoweringException", "VerificationError")


def is_deterministic_fault(e: BaseException) -> bool:
    """A lowering, compile or out-of-memory error: the same inputs fail
    the same way on every attempt, so retrying it only hides it."""
    if type(e).__name__ in _LOWERING_ERRORS:
        return True
    msg = str(e)
    if "Mosaic" in msg or "Pallas TPU lowering" in msg:
        return True
    import jax
    if isinstance(e, jax.errors.JaxRuntimeError):
        return msg.split(":", 1)[0].strip() in _DETERMINISTIC_STATUS
    return False


def backoff_delay(attempt: int, base_s: float, mult: float, jitter: float,
                  rng: Optional[random.Random] = None) -> float:
    """Jittered exponential backoff: ``base * mult**(attempt-1)`` scaled
    by a uniform factor in ``[1-jitter, 1+jitter]`` (attempt counts from
    1). Deterministic under a seeded ``rng``."""
    if base_s <= 0.0:
        return 0.0
    delay = base_s * mult ** max(attempt - 1, 0)
    if jitter > 0.0:
        u = (rng.random() if rng is not None else random.random())
        delay *= 1.0 + jitter * (2.0 * u - 1.0)
    return max(delay, 0.0)


def retry_step(fn: Callable[[], object], *, max_retries: int = 2,
               retriable: tuple = (RuntimeError,),
               on_retry: Optional[Callable[[int, Exception], None]] = None,
               backoff_base_s: float = 0.0, backoff_mult: float = 2.0,
               jitter: float = 0.5, deadline_s: Optional[float] = None,
               rng: Optional[random.Random] = None,
               sleep: Callable[[float], None] = time.sleep,
               clock: Callable[[], float] = time.monotonic):
    """Run ``fn``; retry on transient device errors with jittered
    exponential backoff and a wall-clock deadline cap.

    ``backoff_base_s`` is the first retry's nominal delay (0.0 = the
    legacy immediate-retry behavior); each further retry multiplies it by
    ``backoff_mult`` and jitters it by ±``jitter`` (fraction). A seeded
    ``rng`` (``random.Random``) makes the schedule deterministic.
    ``deadline_s`` caps the whole attempt loop: a retry is only issued if
    wall time remains, and the pre-retry sleep never overshoots the
    budget; exhaustion raises :class:`RetryDeadlineExceeded`.
    ``sleep``/``clock`` are injectable for tests.
    """
    t0 = clock()
    attempt = 0
    while True:
        try:
            return fn()
        except retriable as e:  # noqa: PERF203
            attempt += 1
            if attempt > max_retries:
                raise StepFailed(
                    f"step failed after {max_retries} retries: {e}") from e
            delay = backoff_delay(attempt, backoff_base_s, backoff_mult,
                                  jitter, rng)
            if deadline_s is not None:
                remaining = deadline_s - (clock() - t0)
                if remaining <= 0.0:
                    raise RetryDeadlineExceeded(
                        f"retry deadline ({deadline_s:g}s) exhausted "
                        f"after {attempt - 1} retries: {e}") from e
                delay = min(delay, remaining)
            if on_retry:
                on_retry(attempt, e)
            if delay > 0.0:
                sleep(delay)


class StragglerMonitor:
    def __init__(self, factor: float = 3.0, window: int = 20,
                 min_samples: int = 5,
                 on_straggler: Optional[Callable[[int, float, float], None]]
                 = None):
        self.factor = factor
        self.window = window
        self.min_samples = min_samples
        self.on_straggler = on_straggler
        self.times: List[float] = []            # every recorded duration
        self.flagged: List[int] = []            # 1-based straggling steps
        self._samples: List[Tuple[float, bool]] = []  # (seconds, flagged)
        self._step = 0

    def record(self, seconds: float) -> bool:
        """Record a step duration; returns True if it straggled.

        The threshold is ``factor`` x the median of the trailing
        ``window`` *non-flagged* samples: an already-flagged straggler is
        excluded, so a single slow step cannot inflate the baseline its
        successors are compared against (a 10x outlier followed by 4x
        outliers must flag all of them, not just the first).
        """
        self._step += 1
        hist = [t for t, fl in self._samples[-self.window:] if not fl]
        is_straggler = False
        if len(hist) >= self.min_samples:
            med = statistics.median(hist)
            if seconds > self.factor * med:
                is_straggler = True
                self.flagged.append(self._step)
                if self.on_straggler:
                    self.on_straggler(self._step, seconds, med)
        self.times.append(seconds)
        self._samples.append((seconds, is_straggler))
        return is_straggler

    def timed(self, fn: Callable[[], object]):
        t0 = time.monotonic()
        out = fn()
        self.record(time.monotonic() - t0)
        return out


class PreemptionGuard:
    """Cooperative SIGTERM -> checkpoint-and-exit flag."""

    def __init__(self, install: bool = True):
        self.preempted = False
        self._prev = None
        if install:
            self._prev = signal.signal(signal.SIGTERM, self._handler)

    def _handler(self, signum, frame):
        self.preempted = True

    def trigger(self):          # for tests / manual drills
        self.preempted = True

    def uninstall(self):
        if self._prev is not None:
            signal.signal(signal.SIGTERM, self._prev)
