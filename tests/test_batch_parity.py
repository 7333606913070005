"""Batch engine parity: a fleet solve IS B serial solves, bit for bit.

The acceptance contract of ``core/batch.py`` (DESIGN.md §8):

  * supports, coefficients, slot layouts, gaps, traces and outer-iteration
    counts of ``saif_batch(B)`` are bitwise those of B independent serial
    ``saif`` calls — across the screen x inner backend grid;
  * the whole fleet runs in exactly ONE ``_saif_batch_jit`` compilation;
  * per-problem early finish: a fast problem's trajectory is untouched by
    a straggler sharing its fleet;
  * capacity overflow in one problem grows the fleet but leaves every
    problem's answers bitwise-identical to its serial solve;
  * CV fleets (sample-weight masking) equal serial solves on the
    row-subsampled design.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SaifConfig, cv_path, get_loss, kfold_weights, saif,
                        saif_batch, saif_batch_compile_count,
                        saif_jit_compile_count)
from repro.core.batch import resolve_batch_inner
from repro.core.duality import lambda_max
from repro.core.screen_backend import make_batch_screen_distinct


def _fleet(rng, n, p, b, frac_lo=0.05, frac_hi=0.4, loss_name="least_squares"):
    loss = get_loss(loss_name)
    X = rng.uniform(-10, 10, (n, p))
    Ys, lams = [], []
    for i in range(b):
        w = np.zeros(p)
        w[rng.choice(p, max(p // 15, 3), replace=False)] = rng.normal(
            size=max(p // 15, 3))
        if loss_name == "logistic":
            y = np.sign(X @ w + 0.3 * rng.normal(size=n))
            y[y == 0] = 1.0
        else:
            y = X @ w + 0.5 * rng.normal(size=n)
        frac = frac_lo + (frac_hi - frac_lo) * i / max(b - 1, 1)
        lams.append(frac * float(lambda_max(loss, jnp.asarray(X),
                                            jnp.asarray(y))))
        Ys.append(y)
    return X, np.stack(Ys), lams


def _assert_bitwise(res, serial, b):
    """Fleet row b must equal the serial result byte for byte."""
    assert bool(jnp.all(res.beta[b] == serial.beta))
    assert bool(res.gap[b] == serial.gap)
    assert int(res.n_outer[b]) == int(serial.n_outer)
    assert int(res.n_active[b]) == int(serial.n_active)
    assert bool(res.overflowed[b]) == bool(serial.overflowed)
    assert bool(jnp.all(res.trace_gap[b] == serial.trace_gap))
    assert bool(jnp.all(res.trace_n_active[b] == serial.trace_n_active))
    if res.active_idx.shape[1] == serial.active_idx.shape[0]:
        # same capacity => the slot layout itself must agree exactly
        assert bool(jnp.all(res.active_idx[b] == serial.active_idx))
        assert bool(jnp.all(res.active_mask[b] == serial.active_mask))


@pytest.mark.parametrize("screen,inner", [
    ("jnp", "jnp"), ("jnp", "gram"), ("pallas", "jnp"),
    ("jnp", "pallas"), ("pallas", "gram"), ("pallas", "pallas"),
])
def test_fleet_bitwise_parity_backend_grid(screen, inner):
    """All screen x inner combos: fleet == B serial solves, bitwise."""
    heavy = "pallas" in (screen, inner)     # interpret mode is slow on CPU
    n, p, b = (30, 80, 2) if heavy else (40, 150, 4)
    X, Y, lams = _fleet(np.random.default_rng(0), n, p, b)
    cfg = SaifConfig(eps=1e-7, screen_backend=screen, inner_backend=inner)
    res = saif_batch(X, Y, jnp.asarray(lams), cfg)
    for i in range(b):
        _assert_bitwise(res, saif(X, Y[i], lams[i], cfg), i)


def test_fleet_single_compilation():
    """One fleet = exactly one ``_saif_batch_jit`` compilation, counted by
    both the batch counter and the unified solver-core counter."""
    X, Y, lams = _fleet(np.random.default_rng(1), 35, 100, 3)
    cfg = SaifConfig(eps=1e-7, inner_backend="gram")
    saif_batch(X, Y, jnp.asarray(lams), cfg)        # warm the cache
    c0b, c0u = saif_batch_compile_count(), saif_jit_compile_count()
    res = saif_batch(X, Y, jnp.asarray(lams), cfg)  # cached: 0 new
    assert bool(jnp.all(res.gap <= 1e-7))
    if c0b >= 0:
        assert saif_batch_compile_count() - c0b == 0
    # a fresh fleet signature (different B) adds exactly 1 compilation
    res2 = saif_batch(X, Y[:2], jnp.asarray(lams[:2]), cfg)
    assert not bool(jnp.any(res2.overflowed))
    if c0b >= 0:
        assert saif_batch_compile_count() - c0b == 1
        assert saif_jit_compile_count() - c0u == 1


def test_fleet_early_finish_is_isolated():
    """A straggler must not perturb an early-finishing problem: its
    per-problem n_outer, gap and full traces stay bitwise-serial even
    though the fleet keeps iterating long after it froze."""
    rng = np.random.default_rng(2)
    n, p = 40, 120
    X = rng.uniform(-10, 10, (n, p))
    loss = get_loss("least_squares")
    w = np.zeros(p)
    w[rng.choice(p, 10, replace=False)] = rng.normal(size=10)
    y = X @ w + 0.5 * rng.normal(size=n)
    lmax = float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    # problem 0: trivial (converges in a handful of steps); problem 1:
    # deep solve (tiny lambda + tight eps => many more outer steps)
    lams = [0.8 * lmax, 0.02 * lmax]
    Y = np.stack([y, y])
    cfg = SaifConfig(eps=1e-9, inner_backend="gram")
    res = saif_batch(X, Y, jnp.asarray(lams), cfg)
    s_fast = saif(X, y, lams[0], cfg)
    s_slow = saif(X, y, lams[1], cfg)
    assert int(res.n_outer[1]) > int(res.n_outer[0])     # genuine straggler
    _assert_bitwise(res, s_fast, 0)
    _assert_bitwise(res, s_slow, 1)


def test_fleet_mixed_convergence_logistic():
    """Mixed-loss-landscape fleet (logistic, heterogeneous lambdas):
    per-problem convergence masks keep every trajectory serial-exact."""
    X, Y, lams = _fleet(np.random.default_rng(3), 40, 100, 3,
                        frac_lo=0.1, frac_hi=0.5, loss_name="logistic")
    cfg = SaifConfig(eps=1e-7, loss="logistic", inner_backend="jnp")
    res = saif_batch(X, Y, jnp.asarray(lams), cfg)
    for i in range(3):
        _assert_bitwise(res, saif(X, Y[i], lams[i], cfg), i)


def test_fleet_overflow_isolated_to_one_problem():
    """A tiny capacity forces one problem (the smallest lambda) through
    the elastic-growth recompile; every problem — including the ones that
    never overflowed — still reproduces its serial solve bitwise."""
    rng = np.random.default_rng(4)
    n, p = 40, 150
    X = rng.uniform(-10, 10, (n, p))
    loss = get_loss("least_squares")
    w = np.zeros(p)
    w[rng.choice(p, 20, replace=False)] = rng.normal(size=20)
    y = X @ w + 0.5 * rng.normal(size=n)
    lmax = float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    lams = [0.6 * lmax, 0.03 * lmax]        # only the second overflows k=8
    Y = np.stack([y, y])
    cfg = SaifConfig(eps=1e-7, k_max=8, inner_backend="gram")
    res = saif_batch(X, Y, jnp.asarray(lams), cfg)
    assert not bool(res.overflowed[0]) or not bool(res.overflowed[1])
    for i in range(2):
        serial = saif(X, y, lams[i], cfg)
        assert bool(jnp.all(res.beta[i] == serial.beta))
        assert bool(res.gap[i] == serial.gap)


def test_fleet_distinct_x_screen_fallback():
    """The distinct-X screen (per-problem designs, batch-dim einsum) is a
    drop-in ScreenFn for the engine and stays bitwise with serial."""
    X, Y, lams = _fleet(np.random.default_rng(5), 30, 90, 3)
    b = Y.shape[0]
    cfg = SaifConfig(eps=1e-7, inner_backend="jnp")
    Xs = jnp.broadcast_to(jnp.asarray(X), (b,) + X.shape)
    cn = jnp.linalg.norm(jnp.asarray(X), axis=0)
    from repro.core.batch import fleet_batch_sizes, prepare_fleet
    prep = prepare_fleet(X, Y, cfg)
    _, h = fleet_batch_sizes(prep, lams, cfg)
    screen_fn = make_batch_screen_distinct(
        Xs, jnp.broadcast_to(cn, (b, X.shape[1])), h)
    res = saif_batch(X, Y, jnp.asarray(lams), cfg, screen_fn=screen_fn)
    for i in range(b):
        _assert_bitwise(res, saif(X, Y[i], lams[i], cfg), i)


@pytest.mark.parametrize("inner", ["jnp", "gram"])
def test_weighted_fleet_equals_subsampled_serial(inner):
    """The CV sample-weight trick: a binary-weighted fleet problem equals
    the serial solve on the weight-1 rows (support exactly; coefficients
    to reduction-order tolerance — summing explicit zero rows re-brackets
    the reductions, so this one is allclose, not bitwise)."""
    rng = np.random.default_rng(6)
    n, p, K = 48, 120, 3
    X = rng.uniform(-10, 10, (n, p))
    loss = get_loss("least_squares")
    w = np.zeros(p)
    w[rng.choice(p, 10, replace=False)] = rng.normal(size=10)
    y = X @ w + 0.5 * rng.normal(size=n)
    W = np.asarray(kfold_weights(n, K, seed=0))
    lam = 0.15 * float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    cfg = SaifConfig(eps=1e-8, inner_backend=inner, use_seq_ball=False)
    res = saif_batch(X, np.broadcast_to(y, (K, n)), lam, cfg,
                     weights=jnp.asarray(W))
    for k in range(K):
        tr = W[k] > 0
        ref = saif(X[tr], y[tr], lam, cfg)
        assert np.array_equal(np.abs(np.asarray(res.beta[k])) > 1e-8,
                              np.abs(np.asarray(ref.beta)) > 1e-8)
        assert np.allclose(np.asarray(res.beta[k]), np.asarray(ref.beta),
                           atol=1e-9)
        assert float(res.gap[k]) <= 1e-8


def test_cv_path_selects_and_refits():
    """cv_path: one compilation for the K x L grid, fold solutions match
    subsampled serial solves, and the winner is refit on the full data."""
    rng = np.random.default_rng(7)
    n, p = 60, 140
    X = rng.uniform(-10, 10, (n, p))
    loss = get_loss("least_squares")
    w = np.zeros(p)
    w[rng.choice(p, 8, replace=False)] = rng.normal(size=8)
    y = X @ w + 0.5 * rng.normal(size=n)
    lmax = float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    lams = np.geomspace(0.8 * lmax, 0.05 * lmax, 5)
    cfg = SaifConfig(eps=1e-8, inner_backend="gram")
    res = cv_path(X, y, lams, n_folds=4, config=cfg, keep_fold_betas=True)
    assert res.n_compilations == 1
    assert res.cv_mean.shape == (5,)
    assert float(res.best_lam) in [float(l) for l in res.lams]
    assert res.beta is not None and res.beta.shape == (p,)
    # decreasing lambda must not worsen in-range CV fit catastrophically;
    # spot-check one (fold, lambda) cell against the subsampled oracle
    W = np.asarray(kfold_weights(n, 4, seed=0))
    tr = W[1] > 0
    ref = saif(X[tr], y[tr], float(res.lams[2]),
               SaifConfig(eps=1e-8, inner_backend="gram",
                          use_seq_ball=False))
    fb = np.asarray(res.fold_betas[2][1])
    assert np.array_equal(np.abs(fb) > 1e-8,
                          np.abs(np.asarray(ref.beta)) > 1e-8)
    assert np.allclose(fb, np.asarray(ref.beta), atol=1e-9)


def test_resolve_batch_inner_policy():
    """Fleet inner policy: auto == serial policy with the fleet VMEM
    budget; invalid combinations are rejected at resolve time."""
    cfg = SaifConfig()
    assert resolve_batch_inner(cfg, n=100, k_max=256, b=16) == "gram"
    assert resolve_batch_inner(
        SaifConfig(loss="logistic"), n=100, k_max=256, b=16) == "jnp"
    with pytest.raises(ValueError, match="least_squares"):
        resolve_batch_inner(
            SaifConfig(loss="logistic", inner_backend="gram"),
            n=100, k_max=256, b=16)
    with pytest.raises(ValueError, match="VMEM"):
        resolve_batch_inner(
            SaifConfig(inner_backend="pallas"),
            n=4096, k_max=4096, b=16)
    with pytest.raises(ValueError, match="unknown"):
        resolve_batch_inner(SaifConfig(inner_backend="bogus"),
                            n=10, k_max=8, b=2)


def test_fleet_rejects_fused_problems():
    with pytest.raises(NotImplementedError):
        saif_batch(np.eye(4), np.ones((2, 4)), 0.1,
                   SaifConfig(unpen_idx=0))
