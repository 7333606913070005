"""Multi-pod SAIF: feature-parallel screening via shard_map (DESIGN.md §5).

The cost profile of SAIF (Theorem 5) is: CM epochs on a tiny active block
(O(p̄) work) + an O(p) screening scan. At cluster scale the scan is the ONLY
term that touches the full feature set, so it is the ONLY term we shard:

  * X is partitioned column-wise across ALL mesh devices (the 'feature'
    axis = every axis of the mesh, flattened — 512 shards on the production
    mesh). Each device owns X_local (n, p/devs) and its column norms.
  * screen: each device computes |X_local^T theta| (+ ball arithmetic) and
    reduces to (local top-h candidates, local max-ub). One tiny all_gather
    of h*(score, id) pairs + a pmax — 512 * h * 8 bytes on the wire instead
    of p * 4. The active block (n x k_max) and the CM sweeps are replicated:
    redundant FLOPs, zero collectives, which is the right trade at p >> p̄.
  * for tall problems the sample dim additionally shards over 'data' with a
    psum for the n-dim dots (samples_sharded=True).

``saif_distributed`` plugs the sharded scan into the identical Algorithm-1
loop from ``repro.core.saif`` — same math, same tests, different iron.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


class ShardedDesign(NamedTuple):
    X: jax.Array          # (n, p_pad) feature-sharded on all mesh axes
    col_norm: jax.Array   # (p_pad,)
    c0: jax.Array         # (p_pad,) |X^T f'(0)|
    p: int                # true feature count (p_pad >= p)
    mesh: Mesh


def _feature_axes(mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def shard_design(X, y_grad0, mesh) -> ShardedDesign:
    """Pad p to a multiple of the device count and place the shards."""
    n, p = X.shape
    devs = int(np.prod(list(mesh.shape.values())))
    p_pad = -(-p // devs) * devs
    Xp = jnp.pad(jnp.asarray(X), ((0, 0), (0, p_pad - p)))
    axes = _feature_axes(mesh)
    x_sh = NamedSharding(mesh, P(None, axes))
    v_sh = NamedSharding(mesh, P(axes))
    Xp = jax.device_put(Xp, x_sh)
    col_norm = jax.device_put(jnp.linalg.norm(Xp, axis=0), v_sh)
    c0 = jax.device_put(jnp.abs(Xp.T @ y_grad0), v_sh)
    return ShardedDesign(X=Xp, col_norm=col_norm, c0=c0, p=p, mesh=mesh)


def make_sharded_scan(design: ShardedDesign):
    """Returns scan_fn(theta) -> |X^T theta| (p_pad,), sharded end-to-end.

    Legacy bare-scan hook: pass as ``saif(..., scan_fn=...)`` and
    ``repro.core.screen_backend.make_screen_from_scan`` adapts it to the
    full backend interface in-trace (the production path uses the fused
    :func:`make_sharded_screen` instead). The output stays device-sharded;
    downstream top_k/max run as sharded reductions XLA lowers to the
    gather-of-partials pattern described above.
    """
    mesh = design.mesh
    axes = _feature_axes(mesh)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, axes), P(None)),
        out_specs=P(axes))
    def scan(X_local, theta):
        return jnp.abs(X_local.T @ theta)

    def scan_fn(theta):
        out = scan(design.X, theta)
        # padding columns are all-zero => score 0; mask them so they are
        # never recruited
        if design.p != design.X.shape[1]:
            idx = jnp.arange(design.X.shape[1])
            out = jnp.where(idx < design.p, out, -jnp.inf)
        return out
    return scan_fn


def make_sharded_screen(design: ShardedDesign, h: int):
    """Sharded :class:`~repro.core.screen_backend.ScreenFn` — the backend
    interface of ``repro.core.saif._saif_jit``, same math as the jnp and
    Pallas backends, sharded iron.

    One shard_map computes, per device: local masked scores, local ub, the
    local top-h candidates with global ids, and the pmax of ub. The gathered
    devs*h candidate pairs are merged with one small top_k; the violation
    counts stream over the still-sharded (p_pad,) ub vector (searchsorted
    against the h sorted bounds + bincount — no O(p) gather, no O(p log p)
    sort; XLA lowers the (h+1,)-sized reductions to a tiny psum).
    """
    from repro.core.screen_backend import (ScreenOut, survivor_count,
                                           violation_ge_counts)

    mesh = design.mesh
    axes = _feature_axes(mesh)
    devs = int(np.prod(list(mesh.shape.values())))
    p_pad = design.X.shape[1]
    p_local = p_pad // devs
    k = min(h, p_local)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, axes), P(axes), P(None), P(), P(axes)),
        out_specs=(P(axes), P(axes), P(axes), P()))
    def local(X_local, norm_local, theta, r, excl_local):
        ax_index = sum(jax.lax.axis_index(a) *
                       int(np.prod([mesh.shape[b]
                                    for b in axes[axes.index(a) + 1:]]))
                       for a in axes)
        offset = ax_index * p_local
        scores = jnp.abs(X_local.T @ theta)               # (p_local,)
        # exclusions: current actives + the padding columns beyond true p
        pad_col = offset + jnp.arange(p_local) >= design.p
        masked = jnp.where(excl_local | pad_col, -jnp.inf, scores)
        ub = masked + norm_local * r
        top_s, top_i = jax.lax.top_k(masked, k)
        if k < h:
            top_s = jnp.pad(top_s, (0, h - k), constant_values=-jnp.inf)
            top_i = jnp.pad(top_i, (0, h - k))
        gid = top_i + offset
        max_ub = jax.lax.pmax(jnp.max(ub), axes)
        return top_s, gid.astype(jnp.int32), ub, max_ub

    def screen(theta, r, in_active):
        r = jnp.asarray(r, design.X.dtype)
        ts, gid, ub, max_ub = local(design.X, design.col_norm, theta, r,
                                    jnp.asarray(in_active, bool))
        cand_score, pos = jax.lax.top_k(ts, h)   # merge devs*h candidates
        cand_idx = gid[pos]
        cand_lb = jnp.abs(cand_score - jnp.take(design.col_norm, cand_idx) * r)
        cand_ge = violation_ge_counts(ub, cand_lb)
        return ScreenOut(max_ub=max_ub, cand_score=cand_score,
                         cand_idx=cand_idx, cand_lb=cand_lb, cand_ge=cand_ge,
                         n_surv=survivor_count(ub))
    return screen


def make_sharded_screen_batch(design: ShardedDesign, h: int):
    """Batched sharded screen: the §5 collective serving a whole fleet.

    One shard_map round screens ALL B problems: each device computes its
    (B, p_local) masked-score block with a single local (B, n) x
    (n, p_local) matmul (the shared-X fast path on sharded iron), reduces
    per-problem local top-h and a per-problem pmax of ub, and the gathered
    devs*h candidate pairs merge per problem. Wire bytes per outer step:
    O(B * devs * h) for the candidates — B problems ride one collective
    instead of B of them (the batched ``saif_distributed`` economics,
    DESIGN.md §8). Per-problem column norms are supported (CV fleets), so
    the design carries the *shared* norms and the caller passes fleet
    norms explicitly when they differ.
    """
    from repro.core.screen_backend import (ScreenOut, survivor_count,
                                           violation_ge_counts)

    mesh = design.mesh
    axes = _feature_axes(mesh)
    devs = int(np.prod(list(mesh.shape.values())))
    p_pad = design.X.shape[1]
    p_local = p_pad // devs
    k = min(h, p_local)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, axes), P(axes), P(None, None), P(None),
                  P(None, axes)),
        out_specs=(P(None, axes), P(None, axes), P(None, axes), P(None)))
    def local(X_local, norm_local, Theta, r, excl_local):
        ax_index = sum(jax.lax.axis_index(a) *
                       int(np.prod([mesh.shape[b]
                                    for b in axes[axes.index(a) + 1:]]))
                       for a in axes)
        offset = ax_index * p_local
        scores = jnp.abs(Theta @ X_local)                 # (B, p_local)
        pad_col = offset + jnp.arange(p_local) >= design.p
        masked = jnp.where(excl_local | pad_col[None, :], -jnp.inf, scores)
        ub = masked + norm_local[None, :] * r[:, None]
        top_s, top_i = jax.lax.top_k(masked, k)           # (B, k)
        if k < h:
            top_s = jnp.pad(top_s, ((0, 0), (0, h - k)),
                            constant_values=-jnp.inf)
            top_i = jnp.pad(top_i, ((0, 0), (0, h - k)))
        gid = top_i + offset
        max_ub = jax.lax.pmax(jnp.max(ub, axis=1), axes)  # (B,)
        return top_s, gid.astype(jnp.int32), ub, max_ub

    def screen(Theta, r, in_active, do=None):
        # ``do`` (per-problem ADD gate) is unused: the collective runs for
        # the whole fleet whenever any problem screens — that is the point
        del do
        r = jnp.asarray(r, design.X.dtype)
        excl = jnp.asarray(in_active, bool)
        if excl.shape[1] != p_pad:                        # pad fleet masks
            excl = jnp.pad(excl, ((0, 0), (0, p_pad - excl.shape[1])),
                           constant_values=True)
        ts, gid, ub, max_ub = local(design.X, design.col_norm, Theta, r,
                                    excl)
        cand_score, pos = jax.lax.top_k(ts, h)            # (B, h) merge
        cand_idx = jnp.take_along_axis(gid, pos, axis=1)
        cand_lb = jnp.abs(cand_score -
                          jnp.take(design.col_norm, cand_idx) * r[:, None])
        cand_ge = jax.vmap(violation_ge_counts)(ub, cand_lb)
        return ScreenOut(max_ub=max_ub, cand_score=cand_score,
                         cand_idx=cand_idx, cand_lb=cand_lb,
                         cand_ge=cand_ge, n_surv=survivor_count(ub, axis=1))
    return screen


def fleet_solve_sharded(X, Y, lam, mesh, config=None,
                        inner_backend: str = None,
                        design: ShardedDesign = None,
                        screen_cache: dict = None):
    """Fleet SAIF with the feature-sharded screening collective: B lockstep
    solves whose O(p) scans ride one shard_map round per outer step.

    Same results as ``repro.core.batch.fleet_solve`` (which equals B serial
    solves); the active blocks, CM bursts and the per-problem Gram buffers
    replicate across the mesh exactly like the serial distributed driver —
    only the scan is sharded, now amortized over the fleet (DESIGN.md §8).
    Plain-LASSO fleets over one shared design (no sample weights: a CV
    fleet's per-fold column norms live on the replicated path for now).

    ``design``/``screen_cache`` mirror :func:`solve_scalar_sharded`: the
    session passes its cached placement and per-h batched-ScreenFn memo
    so a stream of sharded fleet requests shares one ``_saif_batch_jit``
    compilation per static key instead of recompiling on every fresh
    screen closure (the ScreenFn is a jit-static argument). The design's
    ``c0`` is ignored here — the fleet driver recomputes per-problem c0
    from ``Y`` — so one cached placement serves every response batch.
    """
    import dataclasses

    from repro.core.batch import fleet_batch_sizes, fleet_solve, prepare_fleet
    from repro.core.saif import SaifConfig

    config = config or SaifConfig()
    if inner_backend is not None:
        config = dataclasses.replace(config, inner_backend=inner_backend)
    if config.unpen_idx is not None:
        raise NotImplementedError("fused fleets are serial-only for now")
    X = jnp.asarray(X)
    Y = jnp.asarray(Y)
    if Y.ndim == 1:
        Y = Y[None, :]
    b = Y.shape[0]
    if design is None:
        design = fleet_design_for(X, Y, mesh, config)
    lam_arr = jnp.broadcast_to(jnp.asarray(lam, X.dtype).reshape(-1), (b,))
    # the screen's candidate width must equal the engine's static h, so
    # derive it through the EXACT code path the fleet driver uses on the
    # padded design (prepare_fleet's per-problem serial matvecs — a
    # differently-associated matmul here could land an ulp on a pow2
    # bucket boundary and break the kernel shapes)
    prep = prepare_fleet(design.X, Y, config)
    _, h = fleet_batch_sizes(prep, [float(l) for l in
                                    jax.device_get(lam_arr)], config)
    if screen_cache is not None and h in screen_cache:
        screen_fn = screen_cache[h]
    else:
        screen_fn = make_sharded_screen_batch(design, h)
        if screen_cache is not None:
            screen_cache[h] = screen_fn
    res = fleet_solve(design.X, Y, lam_arr, config, screen_fn=screen_fn)
    return res._replace(beta=res.beta[:, :design.p])


def saif_batch_distributed(X, Y, lam, mesh, config=None,
                           inner_backend: str = None):
    """DEPRECATED legacy frontend — one-shot session over
    :func:`fleet_solve_sharded`. Use ``repro.open_session(Problem(X),
    config, mesh=mesh).solve(Fleet(Y, lams, sharded=True))``
    (DESIGN.md §9)."""
    from repro.core._compat import warn_deprecated
    warn_deprecated("repro.distributed.saif_batch_distributed",
                    "session.solve(Fleet(Y, lams, sharded=True))")
    import dataclasses

    from repro.core.api import Fleet, Problem, open_session
    from repro.core.saif import SaifConfig

    config = config or SaifConfig()
    if inner_backend is not None:
        config = dataclasses.replace(config, inner_backend=inner_backend)
    sess = open_session(Problem(X=X, loss=config.loss), config, mesh=mesh)
    return sess.solve(Fleet(Y=Y, lams=lam, sharded=True))


class ScreenResult(NamedTuple):
    top_scores: jax.Array   # (h,)
    top_idx: jax.Array      # (h,) global feature ids
    max_ub: jax.Array       # scalar: max_i |x_i^T th| + ||x_i|| r


def make_fused_screen(design: ShardedDesign, h: int):
    """The production screening collective: local top-h + local max-ub,
    then one small all_gather — O(devs*h) wire bytes, not O(p)."""
    mesh = design.mesh
    axes = _feature_axes(mesh)
    devs = int(np.prod(list(mesh.shape.values())))
    p_local = design.X.shape[1] // devs

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, axes), P(axes), P(None), P()),
        out_specs=(P(axes), P(axes), P()))
    def screen(X_local, norm_local, theta, r):
        scores = jnp.abs(X_local.T @ theta)           # (p_local,)
        ub = scores + norm_local * r
        k = min(h, p_local)
        top_s, top_i = jax.lax.top_k(scores, k)
        if k < h:
            top_s = jnp.pad(top_s, (0, h - k), constant_values=-jnp.inf)
            top_i = jnp.pad(top_i, (0, h - k))
        # global ids: offset by this shard's position
        ax_index = sum(jax.lax.axis_index(a) *
                       int(np.prod([mesh.shape[b]
                                    for b in axes[axes.index(a) + 1:]]))
                       for a in axes)
        gid = top_i + ax_index * p_local
        max_ub = jax.lax.pmax(jnp.max(ub), axes)
        return top_s, gid.astype(jnp.int32), max_ub

    def fused(theta, r):
        s, i, mub = screen(design.X, design.col_norm, theta,
                           jnp.asarray(r, design.X.dtype))
        # merge the devs*h candidates (already gathered by out_specs P(axes))
        top_s, pos = jax.lax.top_k(s, h)
        return ScreenResult(top_scores=top_s, top_idx=i[pos], max_ub=mub)
    return fused


def fleet_design_for(X, Y, mesh, config) -> ShardedDesign:
    """Fleet placement: shard the design from a *representative* null
    gradient (the first response's). Only X and the column norms matter
    for fleet screening — per-problem c0 is recomputed from ``Y`` inside
    the fleet driver against the padded design — so one placement serves
    every response batch (the session caches it)."""
    from repro.core.losses import get_loss
    from repro.core.saif import SaifConfig

    config = config or SaifConfig()
    loss = get_loss(config.loss)
    Y = jnp.asarray(Y)
    y0 = Y if Y.ndim == 1 else Y[0]
    g0 = loss.grad(jnp.zeros_like(y0), y0)
    return shard_design(jnp.asarray(X), g0, mesh)


def design_for(X, y, mesh, config) -> ShardedDesign:
    """Build the feature-sharded design from the penalized-null gradient:
    f'(0) for plain LASSO; at the unpenalized slot's partial optimum for
    fused problems (Thm 7, DESIGN.md §7) — the same construction the
    serial driver uses internally, so every h derived from the sharded
    c0 matches the solver's static h exactly. The one-time placement a
    session performs at its first sharded request and then reuses."""
    from repro.core.duality import null_gradient
    from repro.core.losses import get_loss
    from repro.core.saif import SaifConfig

    config = config or SaifConfig()
    loss = get_loss(config.loss)
    y = jnp.asarray(y)
    X = jnp.asarray(X)
    g0, _, _ = null_gradient(loss, X, y, config.unpen_idx)
    return shard_design(X, g0, mesh)


def solve_scalar_sharded(X, y, lam: float, mesh, config=None,
                         inner_backend: str = None,
                         design: ShardedDesign = None,
                         screen_cache: dict = None,
                         prep=None):
    """SAIF with the sharded screening backend. Same result as core.saif.

    The inner solver is NOT sharded (the active block is replicated — see
    the module docstring), so every inner backend from
    ``repro.core.inner_backend`` composes with the sharded screen: the
    ``gram`` engine's (k_max, k_max) buffers replicate like the active
    block (tiny next to X), and its ADD-time column refresh gathers only
    the <= h touched columns of the feature-sharded X — an O(n h) fetch,
    not O(n p). ``inner_backend`` overrides ``config.inner_backend``
    (resolution happens in the core driver against the *padded* problem
    shape, so "auto" is deterministic across mesh sizes).

    ``design``/``screen_cache``/``prep`` are the session hooks: a
    prebuilt :class:`ShardedDesign` skips the one-time placement, a
    prebuilt :class:`~repro.core.saif.PathState` over the *padded*
    design skips the per-request O(np) preparation, and the per-h screen
    memo keeps the ScreenFn *object* stable across requests — the
    function is a jit-static argument of ``_saif_jit``, so a fresh
    closure per request would defeat the one-compilation-per-static-key
    contract.
    """
    import dataclasses

    from repro.core.saif import (SaifConfig, add_batch_size, prepare_path,
                                 solve_scalar)

    config = config or SaifConfig()
    if inner_backend is not None:
        config = dataclasses.replace(config, inner_backend=inner_backend)
    y = jnp.asarray(y)
    if design is None:
        design = design_for(X, y, mesh, config)
    # X itself is also consumed (gathers of active columns, duality gap);
    # padded to p_pad, so run SAIF on the padded problem — padding columns
    # are screened out by the backend; beta padding is sliced off.
    # h must match what saif() derives for the padded problem (same c0,
    # same p_pad), so the backend's candidate count lines up with the
    # solver's static h.
    c0 = design.c0
    if config.unpen_idx is not None:
        c0 = c0.at[config.unpen_idx].set(0.0)
    h = add_batch_size(config.c, lam, c0, design.X.shape[1])
    if screen_cache is not None and h in screen_cache:
        screen_fn = screen_cache[h]
    else:
        screen_fn = make_sharded_screen(design, h)
        if screen_cache is not None:
            screen_cache[h] = screen_fn
    if prep is None:
        prep = prepare_path(design.X, y, config)
    res = solve_scalar(prep, lam, config, screen_fn=screen_fn)
    return res._replace(beta=res.beta[:design.p])


def saif_distributed(X, y, lam: float, mesh, config=None,
                     inner_backend: str = None):
    """DEPRECATED legacy frontend — one-shot session over
    :func:`solve_scalar_sharded`. Use ``repro.open_session(Problem(X, y),
    config, mesh=mesh).solve(Scalar(lam, sharded=True))`` (DESIGN.md §9).
    """
    from repro.core._compat import warn_deprecated
    warn_deprecated("repro.distributed.saif_distributed",
                    "session.solve(Scalar(lam, sharded=True))")
    import dataclasses

    from repro.core.api import Problem, Scalar, open_session
    from repro.core.saif import SaifConfig

    config = config or SaifConfig()
    if inner_backend is not None:
        config = dataclasses.replace(config, inner_backend=inner_backend)
    sess = open_session(Problem(X=X, y=y, loss=config.loss), config,
                        mesh=mesh)
    return sess.solve(Scalar(lam=float(lam), sharded=True))


def saif_fused_distributed(X, y, parent, lam: float, mesh, config=None,
                           transform_backend: str = "auto"):
    """DEPRECATED legacy frontend — tree fused LASSO with feature-sharded
    screening (DESIGN.md §5/§7) as a one-shot session.

    The Theorem-6 transform runs once (device-native, chain Pallas kernel
    or level-schedule scan); the *transformed* design — edge columns plus
    the unpenalized b column — is then column-partitioned across the mesh
    exactly like a plain design, so the O(p) fused screening scan is the
    sharded collective while the active block, the b slot and the CM
    sweeps stay replicated. Returns (beta in node space, SaifResult).
    Use ``repro.open_session(Problem(X, y, penalty=fused(parent)), config,
    mesh=mesh).solve(Scalar(lam, sharded=True))`` (DESIGN.md §9).
    """
    from repro.core._compat import warn_deprecated
    warn_deprecated("repro.distributed.saif_fused_distributed",
                    "session.solve(Scalar(lam, sharded=True)) with "
                    "penalty=fused(parent)")
    from repro.core.api import Problem, Scalar, fused, open_session
    from repro.core.saif import SaifConfig

    config = config or SaifConfig()
    sess = open_session(
        Problem(X=X, y=y, loss=config.loss,
                penalty=fused(parent, transform_backend=transform_backend)),
        config, mesh=mesh)
    return sess.solve(Scalar(lam=float(lam), sharded=True))
