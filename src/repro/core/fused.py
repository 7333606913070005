"""Tree fused LASSO via the column transform of Theorem 6 — device-native.

Problem (17):  min_beta  sum_j f(x_j. beta, y_j) + lam ||D beta||_1,
where D has one row per edge of a tree G(F, E).

Theorem 6 construction, concretely: root the tree; new variables are
  beta_tilde_e = beta_child(e) - beta_parent(e)   (one per edge, penalized)
  b            = beta_root                        (unpenalized)
so beta_v = b + sum of beta_tilde along the root->v path, giving
  x_tilde_e = sum of x_v over the subtree below edge e      (transformed col)
  x_tilde_p = sum of all x_v                                (the b column)
and D T = [I 0]: the fused problem becomes a plain LASSO (18) in beta_tilde
with one unpenalized coordinate b.

Subsystem layout (DESIGN.md §7):

  * the tree's *level schedule* (nodes grouped by depth, padded to the
    widest level) is precomputed host-side once per tree — it is the only
    static piece; the subtree-sum column transform and the ``recover_beta``
    prefix sums then run on device as a ``lax.scan`` over levels
    (scatter-adds within a level), so the whole solve pipeline —
    transform, SAIF path, recovery — is jittable end to end;
  * the chain special case (1-D fused lasso, the paper's Fig-7 workload)
    collapses to column suffix sums and runs as a tiled Pallas kernel
    (``repro.kernels.fused``) whose exact right fold is bitwise-identical
    to the dense numpy reference kept below for parity tests;
  * the unpenalized coordinate ``b`` is NOT eliminated: it rides as an
    always-resident unpenalized *slot* in the SAIF active-set buffer
    (``SaifConfig.unpen_idx``), which works for every alpha-smooth loss —
    fused logistic regression included. Theorem 7's least-squares exact
    elimination (``eliminate_b_ls``) is retained as a parity oracle only.

``fused_path`` wires the transformed problem into the compile-first path
engine (``core/path.py``): one ``_saif_jit`` compilation per lambda grid,
slot-preserving warm starts with ``b`` pinned resident.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core._compat import warn_deprecated
from repro.core.saif import SaifConfig, SaifResult, saif
from repro.core.path import SaifPathResult
from repro.core.cm import solve_lasso_cm
from repro.core.losses import get_loss


class TreeTransform(NamedTuple):
    """Static description of the Theorem-6 transform for a given tree."""
    parent: np.ndarray        # (p,) parent[v] = parent node id, -1 at root
    edge_child: np.ndarray    # (p-1,) child node of edge e
    topo: np.ndarray          # (p,) nodes in topological (root-first) order
    root: int


class LevelSchedule(NamedTuple):
    """Host-side static level schedule of a tree (DESIGN.md §7).

    Nodes are grouped by depth (root = depth 0 excluded); every row is one
    level padded to the widest level's width with ``valid=False`` lanes.
    Within a level all children are distinct, and their parents live one
    level up — so a level's scatter-add reads only finished columns, and
    the device transform visits levels exactly once, deepest first.
    """
    child: np.ndarray    # (L, W) int32 node ids (-1 padding)
    parent: np.ndarray   # (L, W) int32 parent ids
    edge: np.ndarray     # (L, W) int32 edge index of child (-1 padding)
    valid: np.ndarray    # (L, W) bool
    is_chain: bool       # path graph 0-1-...-p-1 rooted at 0


def build_tree(parent: np.ndarray) -> TreeTransform:
    parent = np.asarray(parent, np.int64)
    (roots,) = np.where(parent < 0)
    if len(roots) != 1:
        raise ValueError("parent array must encode exactly one root")
    root = int(roots[0])
    p = len(parent)
    # topological order via BFS from root
    children: list[list[int]] = [[] for _ in range(p)]
    for v, pa in enumerate(parent):
        if pa >= 0:
            children[pa].append(v)
    topo, stack = [], [root]
    while stack:
        v = stack.pop()
        topo.append(v)
        stack.extend(children[v])
    if len(topo) != p:
        raise ValueError("parent array does not encode a connected tree")
    edge_child = np.asarray([v for v in range(p) if v != root], np.int64)
    return TreeTransform(parent=parent, edge_child=edge_child,
                         topo=np.asarray(topo, np.int64), root=root)


def build_schedule(tree: TreeTransform) -> LevelSchedule:
    """Group the tree's nodes by depth — the static input of the device
    transform. O(p) host work, once per tree."""
    p = len(tree.parent)
    depth = np.zeros(p, np.int64)
    for v in tree.topo:                       # parents precede children
        pa = tree.parent[v]
        if pa >= 0:
            depth[v] = depth[pa] + 1
    edge_of_child = np.full(p, -1, np.int64)
    edge_of_child[tree.edge_child] = np.arange(p - 1)
    n_levels = int(depth.max()) if p > 1 else 0
    levels = [[] for _ in range(n_levels)]
    for v in tree.topo:                       # deterministic: topo order
        if tree.parent[v] >= 0:
            levels[depth[v] - 1].append(v)
    width = max((len(l) for l in levels), default=1)
    child = np.full((n_levels, width), -1, np.int32)
    par = np.full((n_levels, width), -1, np.int32)
    edge = np.full((n_levels, width), -1, np.int32)
    valid = np.zeros((n_levels, width), bool)
    for d, nodes in enumerate(levels):
        m = len(nodes)
        child[d, :m] = nodes
        par[d, :m] = tree.parent[nodes]
        edge[d, :m] = edge_of_child[nodes]
        valid[d, :m] = True
    is_chain = bool(p >= 2 and
                    np.array_equal(tree.parent, np.arange(p) - 1))
    return LevelSchedule(child=child, parent=par, edge=edge, valid=valid,
                         is_chain=is_chain)


# --------------------------------------------------------------------------
# dense numpy reference transform (the parity oracle of the device paths)
# --------------------------------------------------------------------------

def transform_design(X: np.ndarray, tree: TreeTransform
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (X_bar (n, p-1) edge columns, xb (n,) the b column).

    x_tilde for edge e = subtree sum of X columns below e: accumulate child
    into parent in reverse topological order. Host-side numpy reference —
    the device paths (:func:`transform_design_scan` and the Pallas chain
    kernel) are tested against it bitwise on chains.
    """
    X = np.asarray(X)
    sub = X.copy()                      # sub[:, v] accumulates subtree sums
    for v in tree.topo[::-1]:
        pa = tree.parent[v]
        if pa >= 0:
            sub[:, pa] += sub[:, v]
    xb = sub[:, tree.root].copy()
    X_bar = sub[:, tree.edge_child]
    return X_bar, xb


def recover_beta(beta_tilde: np.ndarray, b: float,
                 tree: TreeTransform) -> np.ndarray:
    """beta = T [beta_tilde; b]: prefix-sum the edge deltas down the tree.
    Host-side numpy reference of :func:`recover_beta_device`."""
    p = len(tree.parent)
    edge_of_child = np.full(p, -1, np.int64)
    edge_of_child[tree.edge_child] = np.arange(p - 1)
    beta = np.zeros(p)
    for v in tree.topo:
        pa = tree.parent[v]
        if pa < 0:
            beta[v] = b
        else:
            beta[v] = beta[pa] + beta_tilde[edge_of_child[v]]
    return beta


# --------------------------------------------------------------------------
# device transform: lax.scan over the level schedule + Pallas chain kernel
# --------------------------------------------------------------------------

def transform_design_scan(X, tree: TreeTransform,
                          schedule: Optional[LevelSchedule] = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """Jittable Theorem-6 transform: ``lax.scan`` over the level schedule.

    Levels run deepest-first; each step gathers the (finished) child
    columns of one level and scatter-adds them into their parents. Chains
    (one child per level) reproduce the numpy reference bitwise; general
    trees agree to re-association of the per-parent child sums.
    """
    if schedule is None:
        schedule = build_schedule(tree)
    X = jnp.asarray(X)
    n, p = X.shape
    if schedule.child.shape[0] == 0:            # single-node tree
        return X[:, :0], X[:, tree.root]
    ch = jnp.asarray(schedule.child)[::-1]      # deepest level first
    pa = jnp.asarray(schedule.parent)[::-1]
    va = jnp.asarray(schedule.valid)[::-1]

    def level_step(sub, lvl):
        c, q, v = lvl
        cols = jnp.take(sub, jnp.clip(c, 0, p - 1), axis=1)
        cols = cols * v.astype(sub.dtype)[None, :]
        sub = sub.at[:, jnp.where(v, q, p)].add(cols, mode="drop")
        return sub, None

    sub, _ = jax.lax.scan(level_step, X, (ch, pa, va))
    xb = sub[:, tree.root]
    X_bar = sub[:, jnp.asarray(tree.edge_child)]
    return X_bar, xb


def transform_design_device(X, tree: TreeTransform,
                            schedule: Optional[LevelSchedule] = None,
                            backend: str = "auto",
                            interpret: Optional[bool] = None
                            ) -> Tuple[jax.Array, jax.Array]:
    """Device transform dispatcher: ``pallas`` (chain suffix-sum kernel),
    ``scan`` (general trees), or ``auto`` — the kernel on TPU chains, the
    scan elsewhere (off-TPU the kernel runs interpreted: parity oracle,
    not a fast path — same policy as every backend in DESIGN.md §3/§6)."""
    if schedule is None:
        schedule = build_schedule(tree)
    if backend == "auto":
        from repro.core.screen_backend import mosaic_refuses
        backend = ("pallas" if schedule.is_chain
                   and jax.default_backend() == "tpu"
                   and not mosaic_refuses(jnp.asarray(X).dtype) else "scan")
    if backend == "pallas":
        if not schedule.is_chain:
            raise ValueError("the Pallas fused transform is the chain "
                             "(1-D fused lasso) special case; use "
                             "backend='scan' for general trees")
        from repro.kernels.fused.fused import chain_suffix_sums_pallas
        S = chain_suffix_sums_pallas(jnp.asarray(X), interpret=interpret)
        return S[:, 1:], S[:, 0]
    if backend != "scan":
        raise ValueError(f"unknown fused transform backend {backend!r}")
    return transform_design_scan(X, tree, schedule)


def recover_beta_device(beta_tilde: jax.Array, b, tree: TreeTransform,
                        schedule: Optional[LevelSchedule] = None
                        ) -> jax.Array:
    """Jittable beta = T [beta_tilde; b]: top-down ``lax.scan`` prefix sums
    over the level schedule. Bitwise-identical to the numpy reference (one
    add per node, same order)."""
    if schedule is None:
        schedule = build_schedule(tree)
    p = len(tree.parent)
    beta_tilde = jnp.asarray(beta_tilde)
    beta0 = jnp.zeros((p,), beta_tilde.dtype).at[tree.root].set(
        jnp.asarray(b, beta_tilde.dtype))
    if p == 1 or schedule.child.shape[0] == 0:
        return beta0
    ch = jnp.asarray(schedule.child)
    pa = jnp.asarray(schedule.parent)
    ed = jnp.asarray(schedule.edge)
    va = jnp.asarray(schedule.valid)

    def level_step(beta, lvl):
        c, q, e, v = lvl
        vals = (jnp.take(beta, jnp.clip(q, 0, p - 1)) +
                jnp.take(beta_tilde, jnp.clip(e, 0, p - 2)))
        beta = beta.at[jnp.where(v, c, p)].set(vals, mode="drop")
        return beta, None

    beta, _ = jax.lax.scan(level_step, beta0, (ch, pa, ed, va))
    return beta


# --------------------------------------------------------------------------
# the fused problem object + SAIF drivers
# --------------------------------------------------------------------------

class FusedDesign(NamedTuple):
    """One-time transform of a fused problem (tree + device design).

    ``Xt`` holds the p-1 transformed edge columns followed by the
    unpenalized b column at ``unpen_idx`` = p-1 — the layout every driver
    below shares with :class:`~repro.core.saif.SaifConfig.unpen_idx`.
    """
    tree: TreeTransform
    schedule: LevelSchedule
    Xt: jax.Array        # (n, p) transformed design, b column last
    unpen_idx: int


class FusedPathResult(NamedTuple):
    lams: np.ndarray
    betas: List[jax.Array]     # node-space solutions (recovered)
    path: SaifPathResult       # transformed-space engine result


def prepare_fused(X, parent, backend: str = "auto",
                  interpret: Optional[bool] = None) -> FusedDesign:
    """Build the tree, its level schedule and the transformed design —
    the one-time O(p-depth) prep every fused solve/path shares."""
    tree = build_tree(np.asarray(parent))
    schedule = build_schedule(tree)
    X_bar, xb = transform_design_device(X, tree, schedule, backend,
                                        interpret)
    Xt = jnp.concatenate([X_bar, xb[:, None]], axis=1)
    return FusedDesign(tree=tree, schedule=schedule, Xt=Xt,
                       unpen_idx=Xt.shape[1] - 1)


def recover_from_transformed(beta_t: jax.Array,
                             design: FusedDesign) -> jax.Array:
    """Node-space beta from a transformed-space solution (b column last)."""
    pt = beta_t.shape[0]
    return recover_beta_device(beta_t[:pt - 1], beta_t[pt - 1],
                               design.tree, design.schedule)


def saif_fused(X, y, parent, lam: float,
               config: SaifConfig = SaifConfig(),
               transform_backend: str = "auto"
               ) -> Tuple[jax.Array, SaifResult]:
    """DEPRECATED legacy frontend — one-shot session over the fused
    subsystem. Use ``repro.open_session(Problem(X, y,
    penalty=fused(parent)), config).solve(Scalar(lam))``; the session
    performs the Theorem-6 transform exactly once and serves every
    subsequent request from it (DESIGN.md §9)."""
    warn_deprecated("repro.core.saif_fused",
                    "session.solve(Scalar(lam)) with penalty=fused(parent)")
    from repro.core.api import Problem, Scalar, fused, open_session

    sess = open_session(
        Problem(X=X, y=y, loss=config.loss,
                penalty=fused(parent, transform_backend=transform_backend)),
        config)
    return sess.solve(Scalar(lam=float(lam)))


def fused_path(X, y, parent, lams,
               config: SaifConfig = SaifConfig(),
               transform_backend: str = "auto",
               segment_len: int = 16) -> FusedPathResult:
    """DEPRECATED legacy frontend — one-shot session over
    :func:`fused_path_from_design` (DESIGN.md §9)."""
    warn_deprecated("repro.core.fused_path",
                    "session.solve(Path(lams)) with penalty=fused(parent)")
    from repro.core.api import Path, Problem, fused, open_session

    sess = open_session(
        Problem(X=X, y=y, loss=config.loss,
                penalty=fused(parent, transform_backend=transform_backend)),
        config, segment_len=segment_len)
    return sess.solve(Path(lams=tuple(float(l) for l in lams)))


def fused_lambda_max(X, y, parent, loss: str = "least_squares") -> float:
    """Smallest lam with beta_tilde* = 0 (all coefficients fused): the max
    |x_tilde^T f'| at the unpenalized null model (b at its partial
    optimum, Thm 7)."""
    from repro.core.duality import null_gradient

    design = prepare_fused(X, parent, backend="scan")
    y = jnp.asarray(y, design.Xt.dtype)
    _, c0, _ = null_gradient(get_loss(loss), design.Xt, y,
                             design.unpen_idx)
    return float(jnp.max(c0))


# --------------------------------------------------------------------------
# baselines and validation helpers
# --------------------------------------------------------------------------

def fused_baseline_cm(X, y, parent, lam: float, tol: float = 1e-9,
                      loss: str = "least_squares",
                      max_epochs: int = 100_000) -> jax.Array:
    """Unscreened fused solve (the 'CVX' stand-in baseline for Fig 7):
    full-width CM on the transformed problem, b as an unpenalized
    coordinate — any alpha-smooth loss."""
    design = prepare_fused(X, parent, backend="scan")
    y = jnp.asarray(y, design.Xt.dtype)
    beta_t = solve_lasso_cm(get_loss(loss), design.Xt, y, lam, tol=tol,
                            max_epochs=max_epochs,
                            unpen_idx=design.unpen_idx)
    return recover_from_transformed(beta_t, design)


def eliminate_b_ls(X_bar: np.ndarray, xb: np.ndarray, y: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Least-squares exact elimination of the unpenalized coordinate b
    (Theorem 7's tau-projection). Superseded by the always-resident
    unpenalized slot — kept as the LS parity oracle for it."""
    q = xb / max(np.linalg.norm(xb), 1e-30)
    Xp = X_bar - np.outer(q, q @ X_bar)
    yp = y - q * (q @ y)
    return Xp, yp


def recover_b_ls(X_bar, xb, y, beta_tilde) -> float:
    r = y - X_bar @ beta_tilde
    return float((xb @ r) / max(xb @ xb, 1e-30))


def saif_fused_eliminated(X, y, parent, lam: float,
                          config: SaifConfig = SaifConfig()
                          ) -> Tuple[np.ndarray, SaifResult]:
    """Legacy least-squares route: eliminate b exactly, solve a plain
    LASSO. Parity oracle for the unpenalized-slot path (DESIGN.md §7)."""
    if config.loss != "least_squares":
        raise ValueError("exact b-elimination is least-squares only; "
                         "saif_fused handles general losses")
    tree = build_tree(np.asarray(parent))
    X_bar, xb = transform_design(np.asarray(X), tree)
    Xp, yp = eliminate_b_ls(X_bar, xb, np.asarray(y, X_bar.dtype))
    res = saif(jnp.asarray(Xp), jnp.asarray(yp), lam, config)
    beta_tilde = np.asarray(res.beta)
    b = recover_b_ls(X_bar, xb, np.asarray(y, X_bar.dtype), beta_tilde)
    return recover_beta(beta_tilde, b, tree), res


def fused_objective(X, y, parent, beta, lam,
                    loss: str = "least_squares") -> float:
    """Direct evaluation of (17) for validation — any smooth loss."""
    tree = build_tree(np.asarray(parent))
    lo = get_loss(loss)
    beta = jnp.asarray(beta)
    z = jnp.asarray(X) @ beta
    pen = jnp.sum(jnp.abs(beta[jnp.asarray(tree.edge_child)] -
                          beta[jnp.asarray(tree.parent[tree.edge_child])]))
    return float(jnp.sum(lo.value(z, jnp.asarray(y))) + lam * pen)
