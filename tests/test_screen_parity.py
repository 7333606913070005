"""Screening-backend parity: the fused Pallas kernels, the jnp backend and
the legacy sort-based violation counts must agree — exactly for the integer
decisions, to float tolerance for the scores — across padded and unpadded
tile shapes. Plus the compile-first path-engine guarantees: warm vs cold
supports identical, O(log p) compilations per path.

On this CPU container the Pallas kernels run in interpret mode; on a TPU
backend the identical entry points compile to Mosaic and the ``compiled``
parametrization activates.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_regression
from repro.core import (SaifConfig, get_loss, lambda_grid, saif, saif_path,
                        saif_path_naive, saif_jit_compile_count,
                        solve_lasso_cm)
from repro.core.duality import lambda_max
from repro.core.screen_backend import (ge_counts_from_hist, make_screen_jnp,
                                       make_screen_pallas,
                                       violation_ge_counts)
from repro.kernels.ops import (autotune_screen_blocks, on_tpu, screen_fused,
                               screen_fused_ref, ub_histogram,
                               ub_histogram_ref)

# pallas-compiled only exists on a TPU backend; interpret everywhere
MODES = ["interpret"] + (["compiled"] if on_tpu() else [])


def _interpret(mode: str) -> bool:
    return mode == "interpret"


def _support(beta, tol=1e-8):
    return set(np.where(np.abs(np.asarray(beta)) > tol)[0].tolist())


# --------------------------------------------------------------------------
# kernel-level parity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("n,p", [(64, 256), (57, 513), (100, 100),
                                 (33, 1000), (128, 384)])
@pytest.mark.parametrize("bn,bp", [(128, 128), (64, 256)])
def test_fused_screen_matches_ref(rng, mode, n, p, bn, bp):
    """(score, ub, lb, top-h, max-ub) parity incl. shapes where p % bp != 0
    and n % bn != 0 (padding paths)."""
    h = 16
    X = jnp.asarray(rng.normal(size=(n, p)))
    theta = jnp.asarray(rng.normal(size=n))
    norm = jnp.linalg.norm(X, axis=0)
    active = jnp.asarray(rng.random(p) < 0.1)
    r = 0.37
    s, u, l, tops, topi, tmax = screen_fused(
        X, theta, norm, active, r, h=h, bn=bn, bp=bp,
        interpret=_interpret(mode))
    sr, ur, lr, ts_ref, ti_ref, mu_ref = screen_fused_ref(
        X, theta, norm, active, r, h=h)
    scale = float(jnp.max(jnp.abs(sr[jnp.isfinite(sr)]))) + 1.0
    for a, b in ((s, sr), (u, ur), (l, lr)):
        fin = np.isfinite(np.asarray(b))
        np.testing.assert_allclose(np.asarray(a)[fin], np.asarray(b)[fin],
                                   atol=1e-10 * scale)
        assert (np.asarray(a)[~fin] == np.asarray(b)[~fin]).all()
    # merged tile winners == global stable top_k: ids exact on every finite
    # candidate (the -inf tail of a saturated tile is id-arbitrary but
    # never recruitable)
    cs, pos = jax.lax.top_k(tops.reshape(-1), h)
    ci = topi.reshape(-1)[pos]
    np.testing.assert_allclose(cs, ts_ref, atol=1e-10 * scale)
    fin = np.isfinite(np.asarray(ts_ref))
    assert (np.asarray(ci)[fin] == np.asarray(ti_ref)[fin]).all()
    assert float(jnp.max(tmax)) == pytest.approx(float(mu_ref), abs=1e-12)


@pytest.mark.parametrize("mode", MODES)
def test_fused_screen_saturated_tile(rng, mode):
    """A fully-active tile must emit distinct candidate ids (no duplicate
    -inf lanes) so downstream gathers stay well-defined."""
    n, p, bp, h = 32, 256, 128, 8
    X = jnp.asarray(rng.normal(size=(n, p)))
    theta = jnp.asarray(rng.normal(size=n))
    norm = jnp.linalg.norm(X, axis=0)
    active = np.ones(p, bool)
    active[252:] = False                   # tile 0 saturated, 4 finite in 1
    s, u, l, tops, topi, tmax = screen_fused(
        X, theta, norm, jnp.asarray(active), 0.3, h=h, bn=128, bp=bp,
        interpret=_interpret(mode))
    cs, pos = jax.lax.top_k(tops.reshape(-1), h)
    ci = np.asarray(topi.reshape(-1)[pos])
    assert len(set(ci.tolist())) == h      # all candidate ids distinct
    fin = np.isfinite(np.asarray(cs))
    assert sorted(ci[fin].tolist()) == [252, 253, 254, 255]
    sr, ur, lr, ts_ref, ti_ref, mu_ref = screen_fused_ref(
        X, theta, norm, jnp.asarray(active), 0.3, h=h)
    assert (ci[fin] == np.asarray(ti_ref)[fin]).all()


@pytest.mark.parametrize("mode", MODES)
def test_histogram_kernel_exact(rng, mode):
    """The streaming ub-histogram equals bincount(searchsorted) bit for bit,
    including -inf (masked) entries and tied thresholds."""
    p, h = 777, 12
    ub = rng.normal(size=p)
    ub[rng.choice(p, 60, replace=False)] = -np.inf
    lb = np.abs(rng.normal(size=h))
    lb[3] = lb[7]                       # force a tie
    lb_sorted = jnp.asarray(np.sort(lb))
    hist = np.asarray(ub_histogram(jnp.asarray(ub), lb_sorted,
                                   interpret=_interpret(mode)))
    ref = np.asarray(ub_histogram_ref(jnp.asarray(ub), lb_sorted))
    # tile padding (-inf) lands in bin 0, which the suffix counts never
    # read; every decision-relevant bin is exact
    assert (hist[1:] == ref[1:]).all()
    assert hist[0] >= ref[0]          # bin 0 grows by the pad count only
    assert int(hist.sum()) >= p


def test_violation_counts_match_legacy_sort(rng):
    """The O(p log h) count reproduces the legacy O(p log p) full-vector
    sort + searchsorted integer for integer."""
    p, h = 1201, 16
    ub = rng.normal(size=p) * 3
    ub[rng.choice(p, 100, replace=False)] = -np.inf
    lb = np.abs(rng.normal(size=h))
    lb[2] = ub[5]                       # force threshold==value tie
    new = violation_ge_counts(jnp.asarray(ub), jnp.asarray(lb))
    ub_sorted = jnp.sort(jnp.asarray(ub))
    legacy = p - jnp.searchsorted(ub_sorted, jnp.asarray(lb), side="left")
    assert (np.asarray(new) == np.asarray(legacy)).all()


def test_autotuner_blocks():
    from repro.kernels.screen.screen import VMEM_TILE_BUDGET_BYTES
    for n, p in [(1, 1), (100, 600), (100, 5000), (4096, 1_000_000),
                 (295, 8141)]:
        bn, bp = autotune_screen_blocks(n, p)
        assert bp % 128 == 0 and bn % 8 == 0
        assert 2 * bn * bp * 4 <= max(VMEM_TILE_BUDGET_BYTES,
                                      2 * 8 * 128 * 4)
        assert bn >= 8 and bp >= 128


# --------------------------------------------------------------------------
# solver-level parity: bitwise-identical active sets across backends
# --------------------------------------------------------------------------

@pytest.mark.parametrize("frac", [0.3, 0.08])
def test_saif_backends_identical_active_sets(rng, frac):
    loss = get_loss("least_squares")
    X, y, _ = make_regression(rng, n=50, p=300)
    lam = frac * float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    r_jnp = saif(X, y, lam, SaifConfig(eps=1e-8, screen_backend="jnp"))
    r_pal = saif(X, y, lam, SaifConfig(eps=1e-8, screen_backend="pallas"))
    assert _support(r_jnp.beta) == _support(r_pal.beta)
    assert int(r_jnp.n_active) == int(r_pal.n_active)
    assert int(r_jnp.n_outer) == int(r_pal.n_outer)
    # the whole recruiting trajectory matches step for step
    assert np.array_equal(np.asarray(r_jnp.trace_n_active),
                          np.asarray(r_pal.trace_n_active))


def test_screen_backend_outputs_identical(rng):
    """ScreenOut parity of the two in-process backends on one call."""
    n, p, h = 64, 500, 8
    X = jnp.asarray(rng.normal(size=(n, p)))
    norm = jnp.linalg.norm(X, axis=0)
    theta = jnp.asarray(rng.normal(size=n)) * 0.1
    active = jnp.zeros(p, bool).at[jnp.asarray([3, 99, 250])].set(True)
    o1 = make_screen_jnp(X, norm, h)(theta, 0.2, active)
    o2 = make_screen_pallas(X, norm, h)(theta, 0.2, active)
    assert (np.asarray(o1.cand_idx) == np.asarray(o2.cand_idx)).all()
    assert (np.asarray(o1.cand_ge) == np.asarray(o2.cand_ge)).all()
    np.testing.assert_allclose(o1.cand_score, o2.cand_score, rtol=1e-12)
    np.testing.assert_allclose(float(o1.max_ub), float(o2.max_ub),
                               rtol=1e-12)


# --------------------------------------------------------------------------
# path engine guarantees
# --------------------------------------------------------------------------

def test_path_engine_matches_naive_and_cold(rng):
    loss = get_loss("least_squares")
    # dedicated rng: path tests must not depend on fixture stream order
    X, y, _ = make_regression(np.random.default_rng(77), n=40, p=200)
    lmax = float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    lams = lambda_grid(0.9 * lmax, 6, lo_frac=0.02)
    cfg = SaifConfig(eps=1e-8)
    eng = saif_path(X, y, lams, cfg)
    naive = saif_path_naive(X, y, lams, cfg)
    for lam, b_eng, b_naive in zip(eng.lams, eng.betas, naive.betas):
        cold = saif(X, y, float(lam), cfg)
        assert _support(b_eng) == _support(cold.beta)       # warm == cold
        assert _support(b_eng) == _support(b_naive)         # engine == naive
        ref = solve_lasso_cm(loss, jnp.asarray(X), jnp.asarray(y),
                             float(lam), tol=1e-10)
        assert _support(b_eng) == _support(ref)             # and both safe


def test_path_make_screen_factory(rng):
    """The custom-backend hook receives the engine's grid-max h, so a
    factory-built backend threads through the whole path."""
    X, y, _ = make_regression(np.random.default_rng(79), n=40, p=200)
    loss = get_loss("least_squares")
    lmax = float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    lams = lambda_grid(0.9 * lmax, 5, lo_frac=0.05)
    Xj = jnp.asarray(X)
    norm = jnp.linalg.norm(Xj, axis=0)
    seen = []

    def factory(h):
        seen.append(h)
        return make_screen_jnp(Xj, norm, h)

    res = saif_path(X, y, lams, SaifConfig(eps=1e-8), make_screen=factory)
    base = saif_path(X, y, lams, SaifConfig(eps=1e-8))
    assert len(seen) == 1                  # called once, with grid-max h
    for a, b in zip(res.betas, base.betas):
        assert _support(a) == _support(b)


def test_path_engine_compile_count(rng):
    """Acceptance: at most O(log p) distinct _saif_jit compilations/path."""
    X, y, _ = make_regression(np.random.default_rng(80), n=40, p=256)
    loss = get_loss("least_squares")
    lmax = float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    lams = lambda_grid(0.9 * lmax, 20, lo_frac=0.02)
    res = saif_path(X, y, lams, SaifConfig(eps=1e-7))
    bound = int(np.ceil(np.log2(256))) + 2   # capacity doublings + slack
    assert 0 <= res.n_compilations <= bound
    assert len(res.betas) == 20


def test_path_engine_segmented_overflow_recovers(rng):
    """Tiny forced capacity exercises the segment re-entry growth path.

    Compared against default-capacity cold solves: the property under test
    is that elastic growth doesn't corrupt results, so cold SAIF is the
    oracle. (The lambda ~ lambda_max boundary on gaussian designs is a
    pre-existing solver-vs-CM-oracle edge unrelated to capacity — the grid
    starts at 0.5 lambda_max to stay out of it.)

    Quarantined into its own pytest process: re-running this body in the
    same interpreter as the rest of the suite trips a pre-existing XLA
    ``backend_compile`` segfault (CPU backend state, unrelated to the
    solver). The parent test re-invokes just this node id in a child
    pytest with ``REPRO_SEGMENT_OVERFLOW_INPROC=1`` so the assertions
    still gate CI, while the crash domain is the child process.
    """
    if os.environ.get("REPRO_SEGMENT_OVERFLOW_INPROC") != "1":
        env = dict(os.environ, REPRO_SEGMENT_OVERFLOW_INPROC="1")
        nodeid = (
            "tests/test_screen_parity.py::"
            "test_path_engine_segmented_overflow_recovers"
        )
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "-x", "-q", nodeid],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=env,
        )
        assert proc.returncode == 0, (
            f"quarantined segment-overflow test failed (rc={proc.returncode})"
        )
        return
    loss = get_loss("least_squares")
    X, y, _ = make_regression(np.random.default_rng(78), n=40, p=200)
    lmax = float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    lams = lambda_grid(0.5 * lmax, 4, lo_frac=0.03)
    eng = saif_path(X, y, lams, SaifConfig(eps=1e-8, k_max=8),
                    segment_len=2)
    for lam, beta in zip(eng.lams, eng.betas):
        cold = saif(X, y, float(lam), SaifConfig(eps=1e-8))
        assert _support(beta) == _support(cold.beta)
        ref = solve_lasso_cm(loss, jnp.asarray(X), jnp.asarray(y),
                             float(lam), tol=1e-10)
        assert _support(beta) == _support(ref)
