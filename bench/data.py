"""A run's data, made on the device in one jitted call.

The configuration names its design generator (``bench/designs/<name>.py``,
which draws X and the protocol's own responses) and the seed of its
instance (``instance_seed``); the traffic mix names the kind and number
of responses, drawn by ``bench/responses/<kind>.py``.

``--seed`` permutes the instance's features. Every seed so gets its own
arrays but the same problems: a LASSO is the same problem with its
columns reordered, so the solver does the same work, and a run's numbers
differ from seed to seed by the order of the requests, not by how hard a
drawn instance happens to be.
"""
from __future__ import annotations

import numpy as np

import bench


def key_from_seed(seed: int):
    """A PRNG key from any whole number below 2**64 (x64 stays off, so
    the seed is folded in as two 32-bit words)."""
    import jax
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"--seed must lie in [0, 2**64), got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make(seed: int, instance_seed: int, design: dict, responses: dict):
    """(X on the device, Y (count, n) float32 on the host): the instance
    drawn from ``instance_seed``, its features permuted by ``seed``."""
    import jax
    dmod = bench.find("designs", design["generator"])
    rmod = bench.find("responses", responses["kind"])

    @jax.jit
    def build(ikey, pkey):
        kx, ky = jax.random.split(ikey)
        X = dmod.design(kx, design)
        Y = rmod.make(ky, X, design, responses, dmod)
        cols = jax.random.permutation(pkey, X.shape[1])
        return X[:, cols], Y

    X, Y = build(key_from_seed(instance_seed), key_from_seed(seed))
    return X, np.asarray(Y, np.float32)
