"""Data protocols matching the paper, generated from a seed.

Kept free of jax (and of the x64 switch in ``benchmarks/common.py``) so
float32 on-chip runs use the same generators. ``dtype=np.float32`` builds
the design directly in float32 — no float64 host copy of a deployment-size
design — from its own random stream; the float64 default reproduces the
historical stream exactly.
"""
from __future__ import annotations

import numpy as np


def _uniform(rng, shape, lo, hi, dtype):
    if np.dtype(dtype) == np.float64:
        return rng.uniform(lo, hi, shape)
    X = rng.random(shape, dtype=dtype)
    X *= hi - lo
    X += lo
    return X


def _normal(rng, shape, dtype):
    if np.dtype(dtype) == np.float64:
        return rng.normal(size=shape)
    return rng.standard_normal(shape, dtype=dtype)


def simulation_data(n=100, p=5000, seed=0, dtype=np.float64):
    """Paper Sec 5.1.1: X ~ U[-10,10], 20% active betas in [-1,1], N(0,1)."""
    rng = np.random.default_rng(seed)
    X = _uniform(rng, (n, p), -10, 10, dtype)
    beta = np.zeros(p, dtype)
    idx = rng.choice(p, int(0.2 * p), replace=False)
    beta[idx] = rng.uniform(-1, 1, len(idx))
    y = X @ beta + rng.normal(0, 1, n)
    return X, y.astype(dtype, copy=False), beta


def breast_cancer_shaped(seed=1):
    """Shape/conditioning-matched synthetic for the 295x8141 microarray set:
    standardized correlated gaussian features, +-1 labels (paper regresses
    the binary label with least squares)."""
    rng = np.random.default_rng(seed)
    n, p = 295, 8141
    # low-rank + noise covariance mimics gene co-expression structure
    k = 30
    F = rng.normal(size=(p, k)) / np.sqrt(k)
    Z = rng.normal(size=(n, k))
    X = Z @ F.T + 0.7 * rng.normal(size=(n, p))
    X = (X - X.mean(0)) / (X.std(0) + 1e-12)
    w = np.zeros(p)
    w[rng.choice(p, 60, replace=False)] = rng.normal(size=60)
    y = np.sign(X @ w + 0.5 * rng.normal(size=n))
    y[y == 0] = 1.0
    return X, y


def logistic_shaped(n, p, seed=2, k=40, dtype=np.float64):
    """Gaussian design, k-sparse truth, +-1 labels (paper Sec 5.2)."""
    rng = np.random.default_rng(seed)
    X = _normal(rng, (n, p), dtype)
    w = np.zeros(p, dtype)
    w[rng.choice(p, k, replace=False)] = rng.uniform(-2, 2, k)
    y = np.sign(X @ w + 0.3 * rng.normal(size=n))
    y[y == 0] = 1.0
    return X, y.astype(dtype, copy=False)
