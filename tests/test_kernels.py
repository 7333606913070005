"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.

Kernels run in interpret mode here (CPU container); on a TPU backend the
same entry points compile to Mosaic.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing import given, settings, st

from repro.kernels.ops import (cm_epochs, cm_epochs_ref, screen_scores,
                               screen_scores_ref)


@pytest.mark.parametrize("n,p", [(8, 16), (100, 100), (257, 513), (512, 256),
                                 (33, 1000)])
@pytest.mark.parametrize("bn,bp", [(128, 128), (256, 512)])
def test_screen_shape_sweep(rng, n, p, bn, bp):
    X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    theta = jnp.asarray(rng.normal(size=n), jnp.float32)
    norm = jnp.linalg.norm(X, axis=0)
    r = 0.41
    s, u, l = screen_scores(X, theta, norm, r, bn=bn, bp=bp)
    sr, ur, lr = screen_scores_ref(X, theta, norm, r)
    scale = float(jnp.max(sr)) + 1.0
    np.testing.assert_allclose(s, sr, atol=2e-5 * scale)
    np.testing.assert_allclose(u, ur, atol=2e-5 * scale)
    np.testing.assert_allclose(l, lr, atol=2e-5 * scale)


@given(seed=st.integers(0, 10_000),
       n=st.integers(4, 200), k=st.integers(1, 40),
       n_epochs=st.integers(1, 4))
@settings(max_examples=25, deadline=None)
def test_cm_kernel_matches_oracle(seed, n, k, n_epochs):
    r = np.random.default_rng(seed)
    A = jnp.asarray(r.normal(size=(n, k)), jnp.float32)
    y = jnp.asarray(r.normal(size=n), jnp.float32)
    beta = jnp.asarray(r.normal(size=k) * 0.1, jnp.float32)
    csq = jnp.sum(A * A, axis=0)
    mask = jnp.asarray(r.random(k) < 0.85)
    lam = float(r.uniform(0.01, 2.0))
    b1, r1 = cm_epochs(A, y, beta, csq, mask, lam, n_epochs=n_epochs)
    b2, r2 = cm_epochs_ref(A, y, beta, csq, mask, jnp.float32(lam),
                           n_epochs=n_epochs)
    np.testing.assert_allclose(b1, b2, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(r1, r2, atol=1e-4, rtol=1e-4)


def test_cm_kernel_masked_coords_stay_zero(rng):
    n, k = 64, 12
    A = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    y = jnp.asarray(rng.normal(size=n), jnp.float32)
    beta = jnp.zeros(k, jnp.float32)
    csq = jnp.sum(A * A, axis=0)
    mask = jnp.zeros(k, bool).at[:5].set(True)
    b, _ = cm_epochs(A, y, beta, csq, mask, 0.1, n_epochs=5)
    assert (np.asarray(b)[5:] == 0).all()


def test_cm_kernel_decreases_objective(rng):
    n, k = 100, 20
    A = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    y = jnp.asarray(rng.normal(size=n), jnp.float32)
    beta = jnp.asarray(rng.normal(size=k), jnp.float32)
    csq = jnp.sum(A * A, axis=0)
    mask = jnp.ones(k, bool)
    lam = 0.3

    def obj(b):
        r = y - A @ b
        return float(0.5 * jnp.dot(r, r) + lam * jnp.sum(jnp.abs(b)))

    prev = obj(beta)
    for _ in range(4):
        beta, _ = cm_epochs(A, y, beta, csq, mask, lam, n_epochs=1)
        cur = obj(beta)
        assert cur <= prev + 1e-4
        prev = cur


def test_screen_zero_radius_is_plain_correlation(rng):
    n, p = 96, 200
    X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    theta = jnp.asarray(rng.normal(size=n), jnp.float32)
    norm = jnp.linalg.norm(X, axis=0)
    s, u, l = screen_scores(X, theta, norm, 0.0, bn=128, bp=128)
    np.testing.assert_allclose(s, u, atol=1e-6)
    np.testing.assert_allclose(s, l, atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_screen_dtype_sweep(rng, dtype):
    """bf16 inputs (the §Perf S4 variant) stay within bf16 tolerance."""
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    n, p = 128, 384
    X = jnp.asarray(rng.normal(size=(n, p))).astype(dt)
    theta = jnp.asarray(rng.normal(size=n)).astype(dt)
    norm = jnp.linalg.norm(X.astype(jnp.float32), axis=0).astype(dt)
    s, u, l = screen_scores(X, theta, norm, 0.3, bn=128, bp=128)
    sr, ur, lr = screen_scores_ref(X.astype(jnp.float32),
                                   theta.astype(jnp.float32),
                                   norm.astype(jnp.float32), 0.3)
    scale = float(jnp.max(jnp.abs(sr))) + 1.0
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(s, np.float32), sr,
                               atol=tol * scale)
    np.testing.assert_allclose(np.asarray(u, np.float32), ur,
                               atol=tol * scale)


@pytest.mark.parametrize("kernel", ["screen", "cm", "chain"])
def test_compiled_kernels_refuse_x64(rng, kernel):
    """A compiled (Mosaic) kernel refuses float64 operands and x64 mode
    with a TypeError before lowering; the interpreter takes them."""
    from repro.kernels.cm.cm import cm_burst_pallas
    from repro.kernels.fused.fused import chain_suffix_sums_pallas
    from repro.kernels.screen.screen import screen_fused_pallas
    X = jnp.asarray(rng.normal(size=(16, 128)))            # f64 under x64
    y = jnp.asarray(rng.normal(size=16))
    k = 8
    calls = {
        "screen": lambda interp: screen_fused_pallas(
            X, y, jnp.linalg.norm(X, axis=0), jnp.zeros(128, bool), 0.3,
            h=4, interpret=interp),
        "cm": lambda interp: cm_burst_pallas(
            X[:, :k], y, jnp.zeros(k), jnp.ones(k), jnp.ones(k, bool),
            jnp.arange(k), 0.1, 1, k, interpret=interp),
        "chain": lambda interp: chain_suffix_sums_pallas(
            X, interpret=interp),
    }
    with pytest.raises(TypeError, match="float64"):
        calls[kernel](False)
    calls[kernel](True)                  # the interpreter runs it
