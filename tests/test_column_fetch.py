"""The fleet engine's carried active blocks and the column-fetch kernel.

The pallas fleet step keeps each problem's (n, k_max) active block across
outer steps and fetches only the columns ADD recruits (``core/batch.py``);
on the chip the fetch kernel reads them from X in X's own row-major layout
(``kernels/screen/fetch.py``). Both are copies, so:

  * the kernel's rows are bitwise ``jnp.take(X, ids, axis=1)``;
  * a fleet solved with carried blocks is bitwise the same fleet solved by
    a reference engine that re-gathers every block from X at every outer
    step (the engine before blocks were carried).

The kernel runs in interpret mode here.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SaifConfig, get_loss
from repro.core import active_set as aset_lib
from repro.core import batch
from repro.core.duality import lambda_max
from repro.kernels.screen.fetch import fetch_columns_pallas


def _design(rng, n, p, dtype):
    X = rng.normal(size=(n, p)).astype(dtype)
    X[rng.random((n, p)) < 0.05] = -0.0        # copies keep the sign bit
    return jnp.asarray(X)


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_fetch_is_bitwise_take(dtype):
    n, p = 24, 1000                    # the last 128-lane tile is partial
    X = _design(np.random.default_rng(0), n, p, dtype)
    ids = np.array([5, 5, 7, 127, 128, 999, 998, 3, 640, 641, 5, 999],
                   np.int32)
    placed = np.array([1, 1, 1, 1, 0, 1, 1, 0, 1, 1, 0, 1], bool)
    rows = fetch_columns_pallas(X, jnp.asarray(ids), jnp.asarray(placed),
                                interpret=True)
    ref = np.asarray(jnp.take(X, jnp.asarray(ids), axis=1)).T
    rows = np.asarray(rows)
    assert rows.shape == (len(ids), n) and rows.dtype == dtype
    assert rows[placed].tobytes() == ref[placed].tobytes()
    assert not np.any(rows[~placed])


def test_fetch_with_nothing_placed_is_zero():
    X = _design(np.random.default_rng(1), 8, 300, np.float64)
    rows = fetch_columns_pallas(X, jnp.asarray([299, 0, 17], jnp.int32),
                                jnp.zeros(3, bool), interpret=True)
    assert not np.any(np.asarray(rows))


def _churn_fleet():
    """4 least-squares problems on one design: problem 0 freezes within a
    few steps, problem 3 overflows k_max=8 and the fleet re-enters at a
    larger capacity, and ADD/DEL churn moves the active sets. Feature 0
    is in every truth: slots start on feature 0, so a dead slot's stale
    column would move the dual point's scaling were it not zeroed."""
    rng = np.random.default_rng(7)
    n, p = 30, 200
    X = rng.uniform(-10, 10, (n, p))
    loss = get_loss("least_squares")
    Ys, lams = [], []
    for frac in (0.85, 0.4, 0.2, 0.03):
        w = np.zeros(p)
        w[rng.choice(p, 12, replace=False)] = rng.normal(size=12)
        w[0] = 0.3
        y = X @ w + 0.5 * rng.normal(size=n)
        lams.append(frac * float(lambda_max(loss, jnp.asarray(X),
                                            jnp.asarray(y))))
        Ys.append(y)
    return jnp.asarray(X), jnp.asarray(np.stack(Ys)), jnp.asarray(lams)


def _solve(X, Y, lams, rule, eps):
    cfg = SaifConfig(eps=eps, k_max=8, screen_backend="jnp",
                     inner_backend="pallas", screen_rule=rule)
    return batch.fleet_solve(X, Y, lams, cfg)


@contextlib.contextmanager
def _engine_with(**helpers):
    """The engine traced anew with ``helpers`` in place of the module's
    own: they are read at trace time, and JAX keeps a traced program per
    function, so its caches are cleared on the way in and out."""
    with pytest.MonkeyPatch.context() as mp:
        for name, fn in helpers.items():
            mp.setattr(batch, name, fn)
        jax.clear_caches()
        try:
            yield
        finally:
            jax.clear_caches()


# "hybrid" recruits through its post-check as well as through ADD; at a
# loose gap target its full safe radius leaves candidates to recruit there
@pytest.fixture(scope="module", params=[("saif", 1e-7), ("hybrid", 10.0)],
                ids=["saif", "hybrid"])
def regathered(request):
    """The reference: every outer step re-gathers every problem's block
    from X, as the engine did before it carried them."""
    X, Y, lams = _churn_fleet()

    def regather(block, aset):
        return aset_lib.gather_columns_batch(X, aset)

    with _engine_with(_read_block=regather):
        return request.param, _solve(X, Y, lams, *request.param)


def _assert_same(res, ref):
    for f in ("beta", "gap", "n_outer", "n_active", "overflowed",
              "active_idx", "active_mask", "trace_gap", "trace_n_active"):
        a, b = np.asarray(getattr(res, f)), np.asarray(getattr(ref, f))
        assert a.shape == b.shape and a.tobytes() == b.tobytes(), f


def test_reference_fleet_exercises_churn_freeze_and_overflow(regathered):
    (rule, _), ref = regathered
    n_outer = np.asarray(ref.n_outer)
    assert n_outer[0] < n_outer.max()               # an early freeze
    assert ref.active_idx.shape[1] > 8              # an overflow re-entry
    tn = np.asarray(ref.trace_n_active)
    live = tn >= 0
    drops = [np.any(np.diff(tn[i][live[i]]) < 0) for i in range(4)]
    grows = [np.any(np.diff(tn[i][live[i]]) > 0) for i in range(4)]
    assert any(drops) and any(grows)                # DEL and ADD churn
    if rule == "hybrid":                            # post-check recruits
        assert np.any(np.asarray(ref.trace_post_viol) == 1)


@pytest.mark.parametrize("fetch", [False, True], ids=["take", "kernel"])
def test_carried_blocks_are_bitwise_the_regathered_fleet(regathered,
                                                        fetch):
    X, Y, lams = _churn_fleet()
    args, ref = regathered
    # the fetch kernel is the engine's choice under the compiled Pallas
    # screen; here it runs interpreted beside the jnp screen
    with _engine_with(_fetches_columns=lambda *a: fetch):
        _assert_same(_solve(X, Y, lams, *args), ref)
