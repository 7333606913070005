"""Device-native fused-LASSO subsystem tests (DESIGN.md §7).

Property tests: the chain-graph device transforms (the Pallas suffix-sum
kernel and the level-schedule ``lax.scan``) must match the dense numpy
``transform_design`` BITWISE on random designs — both are exact right
folds, so any deviation is a real indexing/carry bug, not float noise.
General trees (multiple children per level) agree to re-association only.
Plus the fused path-engine guarantees (one compilation per grid, warm ==
cold active sets) and the general-loss (logistic) end-to-end solve.

On this CPU container the Pallas kernel runs in interpret mode (f64, so
the bitwise claim is exact-grade); on TPU the same entry point compiles.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SaifConfig, build_schedule, build_tree,
                        fused_baseline_cm, fused_lambda_max,
                        fused_objective, fused_path, recover_beta,
                        recover_beta_device, saif_fused,
                        saif_fused_eliminated, transform_design,
                        transform_design_device, transform_design_scan)
from repro.kernels.ops import chain_suffix_sums, chain_suffix_sums_ref


def _support(beta, tol=1e-8):
    return set(np.where(np.abs(np.asarray(beta)) > tol)[0].tolist())


def _chain_parent(p):
    return np.arange(p) - 1


def _random_tree_parent(rng, p):
    parent = np.full(p, -1, np.int64)
    for v in range(1, p):
        parent[v] = rng.integers(0, v)
    return parent


# --------------------------------------------------------------------------
# device-transform parity (satellite: bitwise on chains, both device paths)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n,p", [(9, 12), (33, 300), (16, 257), (8, 128)])
def test_chain_transform_bitwise_pallas_and_scan(seed, n, p):
    """Property: both device paths == dense numpy bit for bit on random
    chain designs, including shapes that exercise the kernel's row/column
    padding (p % bp != 0, n % 8 != 0)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, p))
    tree = build_tree(_chain_parent(p))
    Xb_ref, xb_ref = transform_design(X, tree)

    Xb_s, xb_s = transform_design_scan(X, tree)
    assert np.array_equal(np.asarray(Xb_s), Xb_ref)
    assert np.array_equal(np.asarray(xb_s), xb_ref)

    S = chain_suffix_sums(jnp.asarray(X))      # interpret on CPU
    assert np.array_equal(np.asarray(S[:, 1:]), Xb_ref)
    assert np.array_equal(np.asarray(S[:, 0]), xb_ref)

    # and the jnp reference fold agrees with itself through the dispatcher
    Xb_d, xb_d = transform_design_device(X, tree, backend="pallas")
    assert np.array_equal(np.asarray(Xb_d), Xb_ref)
    assert np.array_equal(np.asarray(xb_d), xb_ref)
    Sr = chain_suffix_sums_ref(jnp.asarray(X))
    assert np.array_equal(np.asarray(Sr), np.asarray(S))


@pytest.mark.parametrize("seed", [0, 3])
@pytest.mark.parametrize("p", [2, 17, 60])
def test_tree_transform_scan_matches_numpy(seed, p):
    """General trees: level-schedule scan == numpy to fp re-association
    (several children can share a parent within one level)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(14, p))
    tree = build_tree(_random_tree_parent(rng, p))
    Xb_ref, xb_ref = transform_design(X, tree)
    Xb_s, xb_s = transform_design_scan(X, tree)
    np.testing.assert_allclose(np.asarray(Xb_s), Xb_ref,
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(xb_s), xb_ref,
                               rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("seed", [0, 1])
def test_recover_beta_device_bitwise(seed):
    """recover_beta_device == numpy recover_beta bitwise on ANY tree:
    one add per node, identical order (no re-association anywhere)."""
    rng = np.random.default_rng(seed)
    for p in (2, 13, 41):
        for parent in (_chain_parent(p), _random_tree_parent(rng, p)):
            tree = build_tree(parent)
            bt = rng.normal(size=p - 1)
            b = float(rng.normal())
            dev = recover_beta_device(jnp.asarray(bt), b, tree)
            ref = recover_beta(bt, b, tree)
            assert np.array_equal(np.asarray(dev), ref)


def test_schedule_chain_detection():
    assert build_schedule(build_tree(_chain_parent(20))).is_chain
    rng = np.random.default_rng(0)
    assert not build_schedule(
        build_tree(_random_tree_parent(rng, 20))).is_chain
    with pytest.raises(ValueError):
        transform_design_device(np.zeros((3, 20)),
                                build_tree(_random_tree_parent(rng, 20)),
                                backend="pallas")


# --------------------------------------------------------------------------
# unpenalized-slot solver path (Thm 7 without elimination)
# --------------------------------------------------------------------------

def test_slot_matches_exact_elimination_ls():
    """The always-resident unpenalized slot == Theorem 7's exact LS
    elimination (the legacy route, kept as the parity oracle)."""
    rng = np.random.default_rng(7)   # dedicated: order-independent data
    n, p = 40, 30
    X = rng.normal(size=(n, p))
    beta_true = np.zeros(p)
    beta_true[:10] = 1.5
    y = X @ beta_true + 0.1 * rng.normal(size=n)
    parent = _random_tree_parent(rng, p)
    for lam in (2.0, 10.0):
        b_slot, res = saif_fused(X, y, parent, lam, SaifConfig(eps=1e-10))
        b_elim, _ = saif_fused_eliminated(X, y, parent, lam,
                                          SaifConfig(eps=1e-10))
        o_s = fused_objective(X, y, parent, b_slot, lam)
        o_e = fused_objective(X, y, parent, b_elim, lam)
        assert float(res.gap) <= 1e-10
        assert abs(o_s - o_e) <= 1e-6 * max(abs(o_e), 1)
        np.testing.assert_allclose(np.asarray(b_slot), b_elim, atol=1e-4)


def test_fused_logistic_end_to_end():
    """Acceptance: fused logistic regression solves with duality gap <=
    eps and matches the unscreened general-loss baseline's objective.
    (Dedicated rng: this must not depend on fixture stream order.)"""
    rng = np.random.default_rng(48)  # historically adversarial draw: the
    # pre-polish dual produced a NEGATIVE gap here (DESIGN.md §7)
    n, p = 50, 40
    X = rng.normal(size=(n, p))
    beta_true = np.zeros(p)
    beta_true[:8] = 2.0
    y = np.sign(X @ beta_true + 0.3 * rng.normal(size=n))
    y[y == 0] = 1.0
    parent = _chain_parent(p)
    lmax = fused_lambda_max(X, y, parent, loss="logistic")
    eps = 1e-8
    for frac in (0.3, 0.1):
        lam = frac * lmax
        beta, res = saif_fused(X, y, parent, lam,
                               SaifConfig(eps=eps, loss="logistic"))
        # a NEGATIVE gap means the dual point left Omega (the pre-polish
        # failure mode): the reported gap must be a genuine certificate
        assert -1e-12 <= float(res.gap) <= eps
        o_s = fused_objective(X, y, parent, beta, lam, loss="logistic")
        base = fused_baseline_cm(X, y, parent, lam, tol=1e-10,
                                 loss="logistic")
        o_b = fused_objective(X, y, parent, base, lam, loss="logistic")
        assert o_s <= o_b + 1e-6 * max(abs(o_b), 1)


def test_fused_lambda_max_fuses_everything():
    """Above the fused lambda_max every coefficient collapses to b* —
    confirms the unpenalized-null c0 (not |X^T f'(0)|) is the right
    grid anchor."""
    rng = np.random.default_rng(3)
    n, p = 30, 20
    X = rng.normal(size=(n, p))
    y = rng.normal(size=n)
    parent = _chain_parent(p)
    lmax = fused_lambda_max(X, y, parent)
    beta, _ = saif_fused(X, y, parent, 1.01 * lmax,
                         config=SaifConfig(eps=1e-10))
    assert np.ptp(np.asarray(beta)) <= 1e-6
    beta2, _ = saif_fused(X, y, parent, 0.5 * lmax,
                          config=SaifConfig(eps=1e-10))
    assert np.ptp(np.asarray(beta2)) > 1e-6       # below it, edges activate


def test_warm_start_never_truncates_unpen_slot():
    """A capacity-full warm support that lacks b must still pin b resident:
    the driver PREPENDS the unpenalized slot before truncating to k_max
    (appending let a full warm support silently slice it off)."""
    from repro.core import saif
    from repro.core.duality import null_gradient
    from repro.core.losses import get_loss

    rng = np.random.default_rng(2)
    n, p = 30, 300
    X = jnp.asarray(rng.normal(size=(n, p)))
    y = jnp.asarray(rng.normal(size=n))
    _, c0, _ = null_gradient(get_loss("least_squares"), X, y, p - 1)
    lam = 0.8 * float(jnp.max(c0))     # near lam_max => h small => k_max 64
    cfg = SaifConfig(eps=1e-9, unpen_idx=p - 1)
    res = saif(X, y, lam, cfg,
               warm_idx=jnp.arange(64),          # fills capacity, no b
               warm_beta=jnp.zeros(64))
    final = set(np.asarray(res.active_idx)[np.asarray(res.active_mask)]
                .tolist())
    assert p - 1 in final                        # b survived the handoff
    assert float(res.gap) <= 1e-9


# --------------------------------------------------------------------------
# fused path engine (compile-first guarantees on the transformed problem)
# --------------------------------------------------------------------------

def _fused_grid(X, y, parent, n_lams=6, hi=0.7, lo=0.02):
    lmax = fused_lambda_max(X, y, parent)
    return np.geomspace(hi * lmax, lo * lmax, n_lams)


def _path_problem():
    rng = np.random.default_rng(11)
    n, p = 50, 60
    X = rng.normal(size=(n, p))
    beta_true = np.zeros(p)
    beta_true[:10] = 2.0
    beta_true[10:20] = -1.0
    y = X @ beta_true + 0.1 * rng.normal(size=n)
    return X, y, _chain_parent(p)


def test_fused_path_warm_equals_cold():
    """Satellite: fused_path (slot-preserving warm starts, b pinned) lands
    on the same transformed-space active sets as cold per-lambda solves."""
    X, y, parent = _path_problem()
    lams = _fused_grid(X, y, parent)
    cfg = SaifConfig(eps=1e-8)
    fp = fused_path(X, y, parent, lams, cfg)
    for lam, beta_t, beta_node in zip(fp.lams, fp.path.betas, fp.betas):
        beta_c, res_c = saif_fused(X, y, parent, float(lam), cfg)
        assert _support(beta_t) == _support(res_c.beta)      # warm == cold
        # coefficients agree to solver accuracy (both gaps <= eps)
        np.testing.assert_allclose(np.asarray(beta_node),
                                   np.asarray(beta_c), atol=1e-4)


def test_fused_path_compiles_once():
    """Acceptance: one _saif_jit compilation serves the whole fused grid
    (same assertion style as test_screen_parity's path compile count).
    The problem shape is unique to this test so the count is exactly the
    fresh compile of this grid, not a cache hit from a neighbour test."""
    rng = np.random.default_rng(23)
    n, p = 44, 72
    X = rng.normal(size=(n, p))
    beta_true = np.zeros(p)
    beta_true[: p // 4] = 2.0
    y = X @ beta_true + 0.1 * rng.normal(size=n)
    parent = _chain_parent(p)
    lams = _fused_grid(X, y, parent, n_lams=8)
    fp = fused_path(X, y, parent, lams, SaifConfig(eps=1e-7))
    assert fp.path.n_compilations == 1
    assert len(fp.betas) == 8


def test_fused_path_matches_baseline_objective():
    """Every grid point's objective == the unscreened fused CM baseline."""
    X, y, parent = _path_problem()
    lams = _fused_grid(X, y, parent, n_lams=4)
    fp = fused_path(X, y, parent, lams, SaifConfig(eps=1e-10))
    for lam, beta in zip(fp.lams, fp.betas):
        base = fused_baseline_cm(X, y, parent, float(lam), tol=1e-12)
        o_s = fused_objective(X, y, parent, beta, float(lam))
        o_b = fused_objective(X, y, parent, base, float(lam))
        assert abs(o_s - o_b) <= 1e-6 * max(abs(o_b), 1.0)


def test_fused_transform_backends_identical_solutions():
    """pallas- and scan-transformed designs are bitwise equal, so the
    downstream SAIF solves are too."""
    rng = np.random.default_rng(15)
    n, p = 30, 50
    X = rng.normal(size=(n, p))
    y = rng.normal(size=n)
    parent = _chain_parent(p)
    lam = 0.3 * fused_lambda_max(X, y, parent)
    b1, r1 = saif_fused(X, y, parent, lam, SaifConfig(eps=1e-9),
                        transform_backend="pallas")
    b2, r2 = saif_fused(X, y, parent, lam, SaifConfig(eps=1e-9),
                        transform_backend="scan")
    assert np.array_equal(np.asarray(b1), np.asarray(b2))
    assert int(r1.n_outer) == int(r2.n_outer)
