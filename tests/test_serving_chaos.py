"""Chaos suite for the fault-tolerant serving runtime (DESIGN.md §10).

The serving contract under fire: for EVERY request, exactly one of

  * a :class:`~repro.core.serving.ServingResult` whose verdict is ``ok``
    — and whose value then passes an *independent* KKT check here;
  * a ServingResult whose verdict is a typed degraded verdict
    (``ok=False`` with the ladder trail recorded);
  * a typed :class:`~repro.core.serving.ServingError` subclass.

Anything else — an untyped exception, a silently-NaN result with a green
verdict — is a failed test. The fault schedules are seeded
(``FaultInjector.from_seed``), so every sweep is reproducible, and the
happy path is additionally pinned to PR 5 semantics: bitwise-identical
values and ZERO new engine compilations at steady state.
"""
import numpy as np
import pytest

from conftest import kkt_violation, make_regression
from repro.core.api import CV, Fleet, Path, Problem, Scalar, open_session
from repro.core.losses import get_loss
from repro.core.saif import SaifConfig
from repro.core.serving import (BackendFault, DeadlineExceeded,
                                NumericalError, RequestError, ServingConfig,
                                ServingError, open_serving)
from repro.runtime.inject import FaultInjector

BACKEND_GRID = [("jnp", "jnp"), ("jnp", "gram"), ("pallas", "jnp")]


@pytest.fixture
def rng():
    return np.random.default_rng(11)


def _problem(rng, n=40, p=120):
    X, y, _ = make_regression(rng, n=n, p=p)
    from repro.core.duality import lambda_max
    import jax.numpy as jnp
    lmax = float(lambda_max(get_loss("least_squares"),
                            jnp.asarray(X), jnp.asarray(y)))
    return X, y, lmax


def _request_stream(lmax, y, rng):
    """A mixed, steady-state-shaped request stream."""
    return [
        Scalar(0.3 * lmax),
        Scalar(0.2 * lmax, warm=True),
        Path([0.5 * lmax, 0.3 * lmax, 0.2 * lmax]),
        Scalar(0.3 * lmax),
        Fleet(Y=np.stack([y, y + 0.05 * rng.normal(size=y.shape)]),
              lams=0.3 * lmax),
        Scalar(0.2 * lmax, warm=True),
    ]


# ---------------------------------------------------------------------------
# happy path: verdict plumbing must not perturb PR 5 semantics
# ---------------------------------------------------------------------------

def test_happy_path_bitwise_pr5_and_zero_steady_state_compiles(rng):
    X, y, lmax = _problem(rng)
    prob = Problem(X=X, y=y)
    cfg = SaifConfig(eps=1e-7)
    plain = open_session(prob, cfg)
    srv = open_serving(prob, cfg)
    stream = _request_stream(lmax, y, np.random.default_rng(0))
    plain_vals = [plain.solve(r) for r in stream]
    served = [srv.solve(r) for r in stream]
    def _unwrap(v):     # fused Scalar returns a plain (beta_rec, res) pair
        return v[1] if isinstance(v, tuple) and not hasattr(v, "_fields") \
            else v

    for want, got in zip(plain_vals, served):
        assert got.verdict.ok and not got.verdict.degraded
        want = _unwrap(want)
        val = _unwrap(got.value)
        if hasattr(want, "beta"):
            np.testing.assert_array_equal(np.asarray(want.beta),
                                          np.asarray(val.beta))
        else:   # path results
            for wb, gb in zip(want.betas, val.betas):
                np.testing.assert_array_equal(np.asarray(wb),
                                              np.asarray(gb))
    # steady state: replay the stream — zero new engine compilations
    # (the KKT certificate jit lives outside the engine caches)
    before = srv.compile_stats().total
    for r in stream:
        out = srv.solve(r)
        assert out.verdict.ok
    assert srv.compile_stats().total == before


# ---------------------------------------------------------------------------
# the chaos sweep: seeded faults over the screen x inner backend grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("screen,inner", BACKEND_GRID)
def test_chaos_sweep_no_silent_failures(rng, screen, inner):
    X, y, lmax = _problem(rng)
    loss = get_loss("least_squares")
    cfg = SaifConfig(eps=1e-7, screen_backend=screen, inner_backend=inner)
    srv = open_serving(Problem(X=X, y=y), cfg,
                       serving=ServingConfig(backoff_base_s=0.0))
    stream = _request_stream(lmax, y, np.random.default_rng(1))
    inj = FaultInjector.from_seed(2024, n_calls=40,
                                  p_fail=0.18, p_nan=0.12)
    outcomes = []
    with inj:
        for req in stream:
            try:
                out = srv.solve(req)
            except ServingError as e:
                outcomes.append(("typed", type(e).__name__))
                continue
            v = out.verdict
            outcomes.append(("ok" if v.ok else "degraded_verdict",
                             v.events))
            if not v.ok:
                # a failed verdict must carry its ladder trail — no
                # silent failures
                assert v.events and v.rungs
                continue
            # green verdict => independently certify the value here
            if isinstance(req, Scalar):
                val = out.value
                lam = float(req.lam)
                assert kkt_violation(loss, X, y, val.beta, lam) \
                    <= max(1e-3 * lam, 1e-8)
                assert bool(np.all(np.isfinite(np.asarray(val.beta))))
    assert inj.log, "the schedule never fired — sweep is vacuous"
    assert any(kind == "ok" for kind, _ in outcomes)


def test_nan_storm_every_result_still_certified(rng):
    """Aggressive NaN schedule: every primary engine call is poked. The
    ladder must still deliver KKT-certified solutions — the oracle rung
    is screening-free, so nothing the injector does upstream survives
    it."""
    X, y, lmax = _problem(rng, n=30, p=80)
    loss = get_loss("least_squares")
    srv = open_serving(Problem(X=X, y=y), SaifConfig(eps=1e-7))
    lam = 0.25 * lmax
    with FaultInjector(nan_at=set(range(1, 30))):
        out = srv.solve(Scalar(lam))
    v = out.verdict
    assert v.ok and v.degraded
    assert any(r.name == "oracle" and r.ok for r in v.rungs)
    assert "warm_state_reset" in v.events
    assert kkt_violation(loss, X, y, out.value.beta, lam) <= 1e-3 * lam
    # the scrub means the next warm request re-enters cold and is clean
    out2 = srv.solve(Scalar(lam, warm=True))
    assert out2.verdict.ok and not out2.verdict.degraded


def test_breaker_durably_degrades_backend(rng):
    """Persistent faults on a pallas-screened session: retries exhaust,
    the breaker pins the session to jnp for its remaining lifetime, and
    the stream keeps serving green verdicts on the degraded backend."""
    X, y, lmax = _problem(rng, n=30, p=80)
    cfg = SaifConfig(eps=1e-7, screen_backend="pallas")
    srv = open_serving(Problem(X=X, y=y), cfg,
                       serving=ServingConfig(backoff_base_s=0.0))
    with FaultInjector(fail_at={1, 2, 3}):
        out = srv.solve(Scalar(0.3 * lmax))
    assert out.verdict.ok
    assert srv.breaker_open
    assert any(e.startswith("breaker_open") for e in out.verdict.events)
    assert srv.session.config.screen_backend == "jnp"
    out2 = srv.solve(Scalar(0.2 * lmax))        # still degraded, still ok
    assert out2.verdict.ok and srv.breaker_open
    # nothing left to degrade: a second persistent fault is typed
    with FaultInjector(fail_at=set(range(1, 12))):
        with pytest.raises(BackendFault):
            srv.solve(Scalar(0.3 * lmax))


@pytest.mark.parametrize("message", [
    "INTERNAL: Mosaic failed to compile TPU kernel: failed to legalize",
    "RESOURCE_EXHAUSTED: Out of memory while trying to allocate",
])
def test_deterministic_faults_are_not_retried_or_degraded(rng, message):
    """A lowering/compile or out-of-memory error recurs on every attempt:
    it surfaces at once as a BackendFault carrying the compiler's message
    — no retry, no breaker, no silent fall back to the jnp backend."""
    import jax
    X, y, lmax = _problem(rng, n=30, p=80)
    cfg = SaifConfig(eps=1e-7, screen_backend="pallas")
    srv = open_serving(Problem(X=X, y=y), cfg,
                       serving=ServingConfig(backoff_base_s=0.0))
    with FaultInjector(fail_at={1, 2, 3}, exc=jax.errors.JaxRuntimeError,
                       message=message) as inj:
        with pytest.raises(BackendFault, match=message.split(":")[0]):
            srv.solve(Scalar(0.3 * lmax))
    assert len(inj.log) == 1                     # one attempt, no retries
    assert not srv.breaker_open
    assert srv.session.config.screen_backend == "pallas"
    assert srv.stats().retries == 0


def test_deadline_is_typed(rng):
    X, y, lmax = _problem(rng, n=30, p=80)
    srv = open_serving(Problem(X=X, y=y), SaifConfig(eps=1e-7))
    srv.solve(Scalar(0.3 * lmax))               # compile outside the clock
    with FaultInjector(fail_at={1, 2, 3}, delay_at={1, 2, 3},
                       delay_s=0.2):
        with pytest.raises(DeadlineExceeded):
            srv.solve(Scalar(0.3 * lmax), deadline_s=0.05)


# ---------------------------------------------------------------------------
# verdicts across the penalty surface
# ---------------------------------------------------------------------------

def test_fused_and_group_requests_get_verdicts(rng):
    X, y, _ = make_regression(rng, n=30, p=64)
    parent = np.arange(-1, 63)                  # chain tree
    from repro.core.api import fused, group
    fsrv = open_serving(Problem(X=X, y=y, penalty=fused(parent)),
                        SaifConfig(eps=1e-7))
    out = fsrv.solve(Scalar(2.0))
    assert out.verdict.ok
    beta_rec, res = out.value
    assert np.all(np.isfinite(np.asarray(beta_rec)))
    outp = fsrv.solve(Path([4.0, 2.0]))
    assert outp.verdict.ok and len(outp.value.betas) == 2

    from repro.core.group import GroupSaifConfig
    gsrv = open_serving(Problem(X=X, y=y, penalty=group(8)),
                        GroupSaifConfig(eps=1e-6))
    outg = gsrv.solve(Scalar(2.0))
    assert outg.verdict.ok                       # gap-certified
    assert outg.verdict.kkt_residual == 0.0      # no scalar KKT ran
    # and a group solve that misses its own eps is a *failed* verdict
    tight = open_serving(Problem(X=X, y=y, penalty=group(8)),
                         GroupSaifConfig(eps=1e-14, max_outer=4))
    outt = tight.solve(Scalar(2.0))
    assert not outt.verdict.ok and outt.verdict.rungs   # typed, not silent


def test_weighted_and_cv_verdicts(rng):
    X, y, lmax = _problem(rng, n=36, p=90)
    w = np.asarray(np.random.default_rng(5).uniform(0.5, 2.0, size=36))
    srv = open_serving(Problem(X=X, y=y, weights=w), SaifConfig(eps=1e-7))
    out = srv.solve(Scalar(0.3 * lmax))
    assert out.verdict.ok and out.verdict.kkt_residual <= out.verdict.kkt_tol
    srv2 = open_serving(Problem(X=X, y=y), SaifConfig(eps=1e-7))
    outcv = srv2.solve(CV(n_folds=3, lams=[0.5 * lmax, 0.3 * lmax]))
    assert outcv.verdict.ok


# ---------------------------------------------------------------------------
# admission chaos: malformed requests die typed, at the door
# ---------------------------------------------------------------------------

def test_admission_rejects_are_typed_and_precompile(rng):
    X, y, _ = _problem(rng, n=20, p=40)
    with pytest.raises(NumericalError):
        Problem(X=X, y=np.r_[y[:-1], np.nan])
    with pytest.raises(RequestError):
        Problem(X=np.zeros((10, 3)), y=np.ones(10))    # zero-norm cols
    with pytest.raises(RequestError):
        Problem(X=X, y=y, loss="hinge")
    with pytest.raises(RequestError):
        Problem(X=X, y=y[:-1])                         # shape mismatch
    with pytest.raises(RequestError):
        Scalar(lam=0.0)
    with pytest.raises(RequestError):
        Path(lams=[])
    with pytest.raises(RequestError):
        Fleet(Y=np.stack([y, y]), lams=[1.0, 2.0, 3.0])
    with pytest.raises(RequestError):
        CV(n_folds=1, lams=[1.0])
    # the taxonomy keeps the builtin contracts
    assert issubclass(RequestError, ValueError)
    assert issubclass(NumericalError, ArithmeticError)
    assert issubclass(BackendFault, RuntimeError)
    assert issubclass(DeadlineExceeded, TimeoutError)
