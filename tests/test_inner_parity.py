"""Inner-solver backend parity: the jnp residual-update epochs, the Gram
covariance-update engine and the fused Pallas burst kernel must agree — to
float tolerance on the coefficients, and bitwise on the final SAIF active
sets — plus the Gram refresh invariants and the backend-selection policies.

On this CPU container the Pallas kernel runs in interpret mode (in the
problem dtype, so x64 parity is exact-grade); on a TPU backend the identical
entry point compiles to Mosaic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_classification, make_regression
from repro.core import (SaifConfig, get_loss, lambda_grid, resolve_backend,
                        resolve_inner_backend, saif, saif_path,
                        solve_lasso_cm)
from repro.core import active_set as asl
from repro.core.cm import cm_epochs_compact, gram_epochs
from repro.core.duality import lambda_max
from repro.core.inner_backend import (GRAM_CROSSOVER, cold_inner_carry,
                                      make_inner_gram, make_inner_jnp,
                                      make_inner_pallas)
from repro.kernels.ops import cm_burst, on_tpu

INNER_BACKENDS = ["jnp", "gram", "pallas"]


def _support(beta, tol=1e-8):
    return set(np.where(np.abs(np.asarray(beta)) > tol)[0].tolist())


def _random_block(rng, n, k_max, count, dtype=jnp.float64):
    mask = jnp.zeros(k_max, bool).at[:count].set(True)
    Xa = jnp.where(mask[None, :],
                   jnp.asarray(rng.normal(size=(n, k_max)), dtype), 0.0)
    y = jnp.asarray(rng.normal(size=n), dtype)
    beta = jnp.where(mask, jnp.asarray(rng.normal(size=k_max) * 0.1, dtype),
                     0.0)
    order = jnp.arange(k_max, dtype=jnp.int32)
    return Xa, y, beta, mask, order


# --------------------------------------------------------------------------
# epoch-level parity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,k_max,count", [(64, 16, 12), (200, 32, 32),
                                           (37, 24, 7)])
@pytest.mark.parametrize("n_ep", [1, 5])
def test_gram_epochs_match_jnp(rng, n, k_max, count, n_ep):
    """Covariance updates == residual updates, step for step (LS)."""
    loss = get_loss("least_squares")
    Xa, y, beta, mask, order = _random_block(rng, n, k_max, count)
    lam = 0.3
    b_ref, _ = cm_epochs_compact(loss, Xa, y, beta, Xa @ beta, mask, lam,
                                 order, jnp.asarray(count), n_ep)
    b_gram = gram_epochs(Xa.T @ Xa, Xa.T @ y, beta, mask, lam, order,
                         jnp.asarray(count), n_ep)
    np.testing.assert_allclose(np.asarray(b_gram), np.asarray(b_ref),
                               rtol=1e-8, atol=1e-10)


@pytest.mark.parametrize("loss_name", ["least_squares", "logistic"])
@pytest.mark.parametrize("n,k_max,count", [(64, 16, 12), (100, 32, 25)])
def test_pallas_burst_matches_jnp_backend(rng, loss_name, n, k_max, count):
    """The fused kernel's (beta, z, theta, gap) == the jnp backend's, to
    fp32-grade tolerance (exact-grade here: interpret mode runs in f64)."""
    loss = get_loss(loss_name)
    Xa, y, beta, mask, order = _random_block(rng, n, k_max, count)
    if loss_name == "logistic":
        y = jnp.sign(y) + (y == 0)
    lam = jnp.asarray(0.2, Xa.dtype)
    n_ep = 3
    col_sq = jnp.sum(Xa * Xa, axis=0)

    b_ref, z_ref = cm_epochs_compact(loss, Xa, y, beta, Xa @ beta, mask,
                                     lam, order, jnp.asarray(count), n_ep)
    from repro.core.duality import duality_gap, feasible_dual
    hat = -loss.grad(Xa @ b_ref, y) / lam
    th_ref = feasible_dual(loss, Xa, y, hat, lam, mask)
    gap_ref = duality_gap(loss, Xa, y, b_ref, th_ref, lam, mask)

    b, z, th, gap = cm_burst(Xa, y, beta, col_sq, mask, order, lam,
                             n_ep, count, loss_name=loss_name)
    np.testing.assert_allclose(np.asarray(b), np.asarray(b_ref),
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(np.asarray(z), np.asarray(Xa @ b_ref),
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(np.asarray(th), np.asarray(th_ref),
                               rtol=1e-6, atol=1e-8)
    assert float(gap) == pytest.approx(float(gap_ref), rel=1e-6, abs=1e-8)


def test_pallas_burst_masked_slots_stay_zero(rng):
    n, k_max, count = 50, 12, 5
    Xa, y, beta, mask, order = _random_block(rng, n, k_max, count)
    col_sq = jnp.sum(Xa * Xa, axis=0)
    b, _, _, _ = cm_burst(Xa, y, beta, col_sq, mask, order, 0.1, 4, count)
    assert (np.asarray(b)[count:] == 0).all()


# --------------------------------------------------------------------------
# Gram refresh invariants
# --------------------------------------------------------------------------

def _check_gram_invariant(carry, aset, X):
    """G == Xa^T Xa on every live x live pair; gidx matches idx on live."""
    Xa = np.asarray(asl.gather_columns(jnp.asarray(X), aset))
    mask = np.asarray(aset.mask)
    G_ref = Xa.T @ Xa
    G = np.asarray(carry.G)
    live = np.where(mask)[0]
    np.testing.assert_allclose(G[np.ix_(live, live)],
                               G_ref[np.ix_(live, live)],
                               rtol=1e-9, atol=1e-9)
    gidx = np.asarray(carry.gidx)
    assert (gidx[mask] == np.asarray(aset.idx)[mask]).all()


def test_gram_refresh_add_delete_sequence(rng):
    """Random ADD/DEL churn: the incrementally refreshed carry always
    equals a from-scratch Gram build on the live block (invariants 1-4)."""
    loss = get_loss("least_squares")
    n, p, k_max, h = 30, 60, 16, 4
    X = jnp.asarray(rng.normal(size=(n, p)))
    y = jnp.asarray(rng.normal(size=n))
    be = make_inner_gram(loss, X, y, h)

    init = rng.choice(p, 5, replace=False)
    aset = asl.init_active_set(p, k_max, jnp.asarray(init), X.dtype)
    carry = be.init(aset, cold_inner_carry(k_max, X.dtype),
                    asl.gather_columns(X, aset))
    _check_gram_invariant(carry, aset, X)

    for _ in range(12):
        if rng.random() < 0.5:
            member = np.asarray(aset.in_active)
            cands = np.where(~member)[0]
            m = min(h, len(cands))
            if m == 0:
                continue
            chosen = rng.choice(cands, m, replace=False).astype(np.int32)
            keep = rng.random(m) < 0.8
            aset = asl.add_features(aset, jnp.asarray(chosen),
                                    jnp.asarray(keep))
        else:
            drop = jnp.asarray(rng.random(k_max) < 0.3)
            aset = asl.delete_features(aset, drop)
        carry = be.refresh(carry, aset, asl.gather_columns(X, aset))
        _check_gram_invariant(carry, aset, X)
        # rho invariant on live slots
        live = np.where(np.asarray(aset.mask))[0]
        rho_ref = np.asarray(asl.gather_columns(X, aset)).T @ np.asarray(y)
        np.testing.assert_allclose(np.asarray(carry.rho)[live],
                                   rho_ref[live], rtol=1e-9, atol=1e-9)


def test_gram_init_reconciles_warm_carry(rng):
    """A clean warm carry is kept verbatim; a stale one triggers a full
    rebuild — both end in a valid invariant state."""
    loss = get_loss("least_squares")
    n, p, k_max, h = 25, 40, 8, 4
    X = jnp.asarray(rng.normal(size=(n, p)))
    y = jnp.asarray(rng.normal(size=n))
    be = make_inner_gram(loss, X, y, h)
    aset = asl.init_active_set(p, k_max, jnp.asarray([1, 5, 9]), X.dtype)
    Xa = asl.gather_columns(X, aset)
    carry = be.init(aset, cold_inner_carry(k_max, X.dtype), Xa)
    # clean handoff: same aset -> carry unchanged
    carry2 = be.init(aset, carry, Xa)
    np.testing.assert_array_equal(np.asarray(carry2.G), np.asarray(carry.G))
    # stale handoff: slot 0 now backs a different feature -> rebuilt
    aset3 = aset._replace(idx=aset.idx.at[0].set(17),
                          in_active=aset.in_active.at[1].set(False)
                          .at[17].set(True))
    carry3 = be.init(aset3, carry, asl.gather_columns(X, aset3))
    _check_gram_invariant(carry3, aset3, X)


# --------------------------------------------------------------------------
# solver-level parity: identical final active sets across inner backends
# --------------------------------------------------------------------------

@pytest.mark.parametrize("frac", [0.3, 0.08])
def test_saif_inner_backends_identical_active_sets(rng, frac):
    """Cold solves: all three inner backends land on the oracle support."""
    loss = get_loss("least_squares")
    X, y, _ = make_regression(rng, n=50, p=300)
    lam = frac * float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    ref = solve_lasso_cm(loss, jnp.asarray(X), jnp.asarray(y), lam,
                         tol=1e-10)
    sups = {}
    for be in INNER_BACKENDS:
        res = saif(X, y, lam, SaifConfig(eps=1e-8, inner_backend=be))
        assert float(res.gap) <= 1e-8
        sups[be] = _support(res.beta)
    assert sups["jnp"] == sups["gram"] == sups["pallas"] == _support(ref)


def test_saif_inner_backends_logistic(rng):
    """General-loss parity: the pallas prox-Newton burst == the jnp path."""
    loss = get_loss("logistic")
    X, y, _ = make_classification(rng, n=60, p=250)
    lam = 0.1 * float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    sups = {}
    for be in ("jnp", "pallas"):
        res = saif(X, y, lam,
                   SaifConfig(eps=1e-8, loss="logistic", inner_backend=be))
        sups[be] = _support(res.beta)
    assert sups["jnp"] == sups["pallas"]


def test_saif_path_inner_backends_warm_equals_cold(rng):
    """Warm-started paths (Gram buffers handed across lambdas) match cold
    solves and the unscreened oracle, for every inner backend."""
    loss = get_loss("least_squares")
    X, y, _ = make_regression(np.random.default_rng(91), n=40, p=200)
    lmax = float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    lams = lambda_grid(0.9 * lmax, 5, lo_frac=0.02)
    sups_by_backend = {}
    for be in INNER_BACKENDS:
        cfg = SaifConfig(eps=1e-8, inner_backend=be)
        eng = saif_path(X, y, lams, cfg)
        assert eng.n_compilations <= 10
        sups = []
        for lam, beta in zip(eng.lams, eng.betas):
            cold = saif(X, y, float(lam), cfg)
            assert _support(beta) == _support(cold.beta)
            sups.append(_support(beta))
        sups_by_backend[be] = sups
    assert (sups_by_backend["jnp"] == sups_by_backend["gram"]
            == sups_by_backend["pallas"])


def test_gram_capacity_overflow_recovers(rng):
    """Elastic capacity growth pads the Gram carry; still exact."""
    loss = get_loss("least_squares")
    X, y, _ = make_regression(np.random.default_rng(92), n=40, p=200)
    lam = 0.05 * float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    res = saif(X, y, lam, SaifConfig(eps=1e-8, k_max=8,
                                     inner_backend="gram"))
    ref = solve_lasso_cm(loss, jnp.asarray(X), jnp.asarray(y), lam,
                         tol=1e-10)
    assert _support(res.beta) == _support(ref)


# --------------------------------------------------------------------------
# backend-selection policies (DESIGN.md §3 / §6)
# --------------------------------------------------------------------------

def test_screen_backend_auto_policy():
    """Satellite: "auto" must resolve to the jnp screen backend off-TPU
    (BENCH_path.json: pallas-interpret 1.32x vs jnp 2.12x on the CI shape)
    and to the fused kernels on TPU."""
    expected = "pallas" if on_tpu() else "jnp"
    assert resolve_backend("auto") == expected
    assert resolve_backend("jnp") == "jnp"          # explicit always wins
    assert resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError):
        resolve_backend("nope")


def test_inner_backend_auto_policy():
    # on TPU the VMEM kernel whenever the block fits; elsewhere gram
    # whenever the loss is LS and capacity is not >> n
    ls = "pallas" if on_tpu() else "gram"
    assert resolve_inner_backend("auto", "least_squares", 100, 256) == ls
    assert resolve_inner_backend("auto", "least_squares", 2000, 256) == ls
    # capacity way beyond the crossover: fall back (jnp on CPU)
    big_k = int(GRAM_CROSSOVER * 10) + 10
    fallback = resolve_inner_backend("auto", "least_squares", 10, big_k)
    assert fallback == ("pallas" if on_tpu() else "jnp")
    # non-linear gradient: no gram
    assert resolve_inner_backend("auto", "logistic", 100, 64) == \
        ("pallas" if on_tpu() else "jnp")
    # explicit names win / are validated
    assert resolve_inner_backend("jnp", "least_squares", 10**6, 8) == "jnp"
    with pytest.raises(ValueError):
        resolve_inner_backend("gram", "logistic", 100, 64)
    with pytest.raises(ValueError):
        resolve_inner_backend("turbo", "least_squares", 100, 64)
    # explicit pallas must fit the VMEM budget (DESIGN.md §6)
    assert resolve_inner_backend("pallas", "logistic", 100, 64) == "pallas"
    with pytest.raises(ValueError):
        resolve_inner_backend("pallas", "least_squares", 100_000, 1024)


def test_tpu_policy_keeps_x64_off_the_kernels(monkeypatch):
    """On TPU the Pallas kernels take float32 with x64 off (Mosaic has no
    f64): ``auto`` keeps float64 problems and x64 mode on the XLA paths,
    and an explicit ``pallas`` for them raises instead of degrading."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    f32, f64 = jnp.float32, jnp.float64
    with jax.enable_x64(False):
        assert resolve_backend("auto", f32) == "pallas"
        for loss in ("logistic", "least_squares"):
            assert resolve_inner_backend("auto", loss, 100, 64,
                                         f32) == "pallas"
        # a block beyond the VMEM budget keeps least squares on gram
        assert resolve_inner_backend("auto", "least_squares", 100_000, 64,
                                     f32) == "gram"
        assert resolve_backend("pallas", f32) == "pallas"
    with jax.enable_x64(True):
        for dt in (f32, f64):
            assert resolve_backend("auto", dt) == "jnp"
            assert resolve_inner_backend("auto", "logistic", 100, 64,
                                         dt) == "jnp"
            with pytest.raises(ValueError, match="x64"):
                resolve_backend("pallas", dt)
            with pytest.raises(ValueError, match="x64"):
                resolve_inner_backend("pallas", "logistic", 100, 64, dt)
        # the gram engine is XLA, untouched by the refusal
        assert resolve_inner_backend("auto", "least_squares", 100, 256,
                                     f64) == "gram"


def test_gram_epochs_touch_no_n_sized_arrays():
    """Acceptance: no O(n) work per coordinate step under the gram backend.
    Structural proof: the whole epoch jaxpr contains no array with a
    dimension larger than k_max (n never enters)."""
    k_max, n = 16, 10_000
    loss = get_loss("least_squares")
    closed = jax.make_jaxpr(
        lambda G, rho, beta, mask, order: gram_epochs(
            G, rho, beta, mask, 0.1, order, jnp.asarray(8), 3,
            smoothness=loss.smoothness))(
        jnp.zeros((k_max, k_max)), jnp.zeros(k_max), jnp.zeros(k_max),
        jnp.ones(k_max, bool), jnp.arange(k_max, dtype=jnp.int32))

    # walk nested jaxprs (fori_loop bodies live in eqn params)
    def walk(jaxpr, acc):
        for eqn in jaxpr.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                shape = getattr(aval, "shape", ()) if aval is not None else ()
                acc.extend(d for d in shape if isinstance(d, int))
            for p in eqn.params.values():
                if hasattr(p, "jaxpr"):
                    walk(p.jaxpr, acc)
                elif isinstance(p, (list, tuple)):
                    for q in p:
                        if hasattr(q, "jaxpr"):
                            walk(q.jaxpr, acc)
        return acc

    dims = walk(closed.jaxpr, [1])
    assert max(dims) <= k_max * k_max
    assert n not in dims          # nothing n-shaped anywhere in the burst
