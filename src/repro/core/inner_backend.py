"""Pluggable inner-solver backends for the SAIF CM burst (DESIGN.md §6).

A SAIF outer step needs exactly four things from the inner solver, computed
on the fixed-capacity active block:

  * ``beta``  — the coefficients after the K-sweep CM burst,
  * ``z``     — the model vector Xa beta,
  * ``theta`` — the feasible dual point (Lemma 2 scaling),
  * ``gap``   — the sub-problem duality gap (drives the ball radius, the
                DEL rule and the stop test).

An :class:`InnerBackend` produces all four as one :class:`InnerOut`; the
jitted solver in :mod:`repro.core.saif` is backend-agnostic, mirroring the
PR-1 :mod:`repro.core.screen_backend` design. Three implementations ship:

  * ``jnp``    — the reference path: residual-update coordinate steps
                 (``core/cm.py::cm_epochs_compact``), each step an O(n) dot
                 plus an O(n) rank-1 model update.
  * ``gram``   — the covariance-update engine (least squares only): the
                 active-block Gram matrix ``G = Xa^T Xa`` and ``rho = Xa^T y``
                 live in an :class:`InnerCarry` threaded through the outer
                 while_loop, so each coordinate step is an O(k_max) Gram
                 axpy (``core/cm.py::gram_epochs``) — *no O(n) work per
                 coordinate step*. ADD/DEL trigger an incremental column
                 refresh (at most ``h`` new columns per outer step, O(n k h)
                 amortized; never a full O(n k^2) rebuild inside the loop).
  * ``pallas`` — the VMEM-resident fused kernel
                 (``kernels/cm/cm.py::cm_burst_pallas``): prox-Newton steps
                 for any alpha-smooth loss with the dual-point/duality-gap
                 reduction fused into the same kernel call.

Gram refresh invariants (the correctness contract of the ``gram`` carry):

  1. ``gidx[s]`` names the feature whose data currently backs row/column
     ``s`` of ``G`` and entry ``s`` of ``rho`` (-1 = nothing valid).
  2. For every pair of slots (s, t) with ``gidx == idx`` and ``mask`` live,
     ``G[s, t] = x_s^T x_t`` holds exactly. Dead rows/columns may be stale —
     the compact sweep never reads them and dead betas are 0.
  3. ``refresh`` (called at the top of every outer step) first invalidates
     ``gidx`` on dead slots, then recomputes rows+columns of every live slot
     whose ``gidx`` disagrees with ``idx``. Invalidation-on-death is what
     makes (2) inductive: a slot revived after >= 1 outer step always
     refreshes, so entries that went stale while it was dead (ADDs refresh
     against the mask-zeroed block) are never trusted.
  4. At most ``h`` slots can become live per outer step (the candidate
     buffer is (h,)-shaped), so the in-loop refresh is bounded by ``h``
     columns; unbounded reconciliation (cold starts, warm handoffs whose
     carry disagrees) happens once, outside the while_loop, in ``init``.

Backend-selection policy lives in :func:`resolve_inner_backend`; the
n-vs-k_max crossover and the VMEM gate are documented in DESIGN.md §6.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import active_set as aset_lib
from repro.core.active_set import ActiveSet
from repro.core.cm import cm_epochs_compact, gram_epochs
from repro.core.duality import duality_gap, feasible_dual, polish_unpen
from repro.core.losses import Loss


class InnerCarry(NamedTuple):
    """Inner-solver state threaded through the outer while_loop (and, for
    warm-started lambda paths, across solves). Placeholder-shaped ((1, 1) /
    (1,)) for backends that keep no state."""
    G: jax.Array      # (k_max, k_max) active-block Gram matrix
    rho: jax.Array    # (k_max,) x_j^T y per slot
    gidx: jax.Array   # (k_max,) int32 feature id backing each slot (-1=none)


class InnerOut(NamedTuple):
    beta: jax.Array   # (k_max,) post-burst coefficients
    z: jax.Array      # (n,) model vector Xa beta
    theta: jax.Array  # (n,) feasible dual point
    gap: jax.Array    # scalar sub-problem duality gap


class InnerBackend(NamedTuple):
    """The inner-solver interface ``_saif_jit`` consumes.

    ``init(aset, carry, Xa)``    — outside the while_loop: reconcile an
                                   inbound (possibly cold / stale) carry
                                   with the initial active set.
    ``refresh(carry, aset, Xa)`` — inside the loop, bounded work: absorb
                                   the previous step's ADD/DEL.
    ``run(carry, aset, Xa, lam, n_ep)`` — the CM burst + dual/gap.
    """
    name: str
    init: Callable[[ActiveSet, InnerCarry, jax.Array], InnerCarry]
    refresh: Callable[[InnerCarry, ActiveSet, jax.Array], InnerCarry]
    run: Callable[[InnerCarry, ActiveSet, jax.Array, jax.Array, jax.Array],
                  InnerOut]


def empty_inner_carry(dtype=jnp.float32) -> InnerCarry:
    """Placeholder carry for stateless backends (jnp / pallas)."""
    return InnerCarry(G=jnp.zeros((1, 1), dtype), rho=jnp.zeros((1,), dtype),
                      gidx=jnp.full((1,), -1, jnp.int32))


def cold_inner_carry(k_max: int, dtype=jnp.float32,
                     backend: str = "gram") -> InnerCarry:
    """All-invalid carry: forces a full (out-of-loop) rebuild in ``init``."""
    if backend != "gram":
        return empty_inner_carry(dtype)
    return InnerCarry(G=jnp.zeros((k_max, k_max), dtype),
                      rho=jnp.zeros((k_max,), dtype),
                      gidx=jnp.full((k_max,), -1, jnp.int32))


def _dual_and_gap(loss: Loss, Xa, y, beta, z, mask, lam,
                  pen=None, x_unpen=None, sample_w=None):
    """Shared post-burst tail of the jnp and gram backends — byte-for-byte
    the dual/gap computation the pre-backend solver did inline. ``pen`` /
    ``x_unpen`` carry the unpenalized-slot machinery (DESIGN.md §7): the
    dual point is projected onto x_unpen's equality constraint and the l1
    term of the gap skips the unpenalized coordinate.

    ``sample_w`` (optional, (n,)) is a per-sample loss weight (the K-fold
    CV row-mask trick, DESIGN.md §8): the gradient, primal value and
    conjugate sums pick up the elementwise weight. With binary weights the
    unscaled dual candidate is supported on the weight-1 rows by
    construction, so the LS tau* scaling and the constraint correlations
    against the *shared* Xa equal their row-subsampled counterparts
    exactly; the general-loss dom-f* clamp can move an exact 0 off 0, so
    theta is re-zeroed on the weight-0 rows after it."""
    if sample_w is None:
        hat = -loss.grad(z, y) / lam
        theta = feasible_dual(loss, Xa, y, hat, lam, mask, pen=pen,
                              x_unpen=x_unpen)
        gap = duality_gap(loss, Xa, y, beta, theta, lam, mask, pen=pen)
        return theta, gap
    hat = -(sample_w * loss.grad(z, y)) / lam
    theta = feasible_dual(loss, Xa, y, hat, lam, mask, pen=pen,
                          x_unpen=x_unpen)
    if loss.name != "least_squares":
        theta = jnp.where(sample_w > 0, theta, 0.0)
    beta_m = jnp.where(mask, beta, 0.0) if mask is not None else beta
    l1 = jnp.abs(beta_m) if pen is None else pen * jnp.abs(beta_m)
    p_val = (jnp.sum(sample_w * loss.value(Xa @ beta_m, y)) +
             lam * jnp.sum(l1))
    d_val = -jnp.sum(sample_w * loss.conj(-lam * theta, y))
    return theta, p_val - d_val


def make_inner_jnp(loss: Loss, X: jax.Array, y: jax.Array,
                   unpen_idx: int = -1,
                   sample_w: jax.Array | None = None) -> InnerBackend:
    """Reference backend: residual-update epochs, O(n) per coordinate step.
    ``sample_w`` weights the loss per sample (CV fleets, DESIGN.md §8);
    it composes with everything except the fused unpenalized slot."""
    if unpen_idx >= 0 and sample_w is not None:
        raise ValueError("sample weights do not compose with the fused "
                         "unpenalized slot (DESIGN.md §8)")
    x_unpen = X[:, unpen_idx] if unpen_idx >= 0 else None

    def run(carry, aset, Xa, lam, n_ep):
        pen = (aset_lib.pen_weights(aset, unpen_idx, X.dtype)
               if unpen_idx >= 0 else None)
        beta, z = cm_epochs_compact(loss, Xa, y, aset.beta, Xa @ aset.beta,
                                    aset.mask, lam, aset.order, aset.count,
                                    n_ep, pen=pen, sample_w=sample_w)
        if unpen_idx >= 0 and loss.name != "least_squares":
            # general loss: Newton-polish b to stationarity so the dual
            # point satisfies its equality constraint through the gradient
            # itself — see duality.polish_unpen (DESIGN.md §7)
            unpen_slot = aset.mask & (aset.idx == unpen_idx)
            slot = jnp.argmax(unpen_slot)
            present = jnp.any(unpen_slot)
            b_new, z_new = polish_unpen(loss, x_unpen, y, z, beta[slot])
            beta = beta.at[slot].set(jnp.where(present, b_new, beta[slot]))
            z = jnp.where(present, z_new, z)
        theta, gap = _dual_and_gap(loss, Xa, y, beta, z, aset.mask, lam,
                                   pen=pen, x_unpen=x_unpen,
                                   sample_w=sample_w)
        return InnerOut(beta=beta, z=z, theta=theta, gap=gap)

    return InnerBackend(name="jnp",
                        init=lambda aset, carry, Xa: carry,
                        refresh=lambda carry, aset, Xa: carry,
                        run=run)


def make_inner_gram(loss: Loss, X: jax.Array, y: jax.Array,
                    h: int, unpen_idx: int = -1,
                    sample_w: jax.Array | None = None) -> InnerBackend:
    """Covariance-update backend: O(k_max) coordinate steps (LS only).

    The unpenalized slot (``unpen_idx`` >= 0, fused LASSO) needs no special
    Gram handling: it is always resident, so its row/column of G stays hot
    across the whole solve — only its threshold (0) and the dual tail's
    equality projection differ.

    ``sample_w`` (CV fleets, §8) folds into the carry itself — G becomes
    Xa^T diag(w) Xa and rho becomes Xa^T diag(w) y — so the O(k_max)
    sweep needs no weight hook at all; only the carry builds and the
    dual/gap tail see the weights.
    """
    if loss.name != "least_squares":
        raise ValueError("the gram inner backend needs a linear gradient "
                         f"(least squares); got loss {loss.name!r}")
    if unpen_idx >= 0 and sample_w is not None:
        raise ValueError("sample weights do not compose with the fused "
                         "unpenalized slot (DESIGN.md §8)")
    x_unpen = X[:, unpen_idx] if unpen_idx >= 0 else None

    def _wgt(cols):
        return cols if sample_w is None else sample_w[:, None] * cols

    def _rebuild(aset, Xa):
        G = Xa.T @ _wgt(Xa)
        rho = _wgt(Xa).T @ y
        gidx = jnp.where(aset.mask, aset.idx, -1)
        return InnerCarry(G=G, rho=rho, gidx=gidx.astype(jnp.int32))

    def init(aset, carry, Xa):
        # Reconcile a warm-handoff carry: keep it when every live slot's
        # backing feature matches (the warm-started path case — slot
        # assignment is preserved across lambdas); otherwise rebuild in
        # full. This is the ONLY place an O(n k^2) Gram build can happen,
        # and it is outside the while_loop.
        gidx = jnp.where(aset.mask, carry.gidx, -1).astype(jnp.int32)
        dirty = aset.mask & (gidx != aset.idx)
        return jax.lax.cond(jnp.any(dirty),
                            lambda c: _rebuild(aset, Xa),
                            lambda c: c._replace(gidx=gidx), carry)

    def refresh(carry, aset, Xa):
        # Invalidate dead slots, then recompute the (<= h) dirty live
        # columns — invariants 1-4 in the module docstring.
        kc = carry.gidx.shape[0]
        gidx = jnp.where(aset.mask, carry.gidx, -1).astype(jnp.int32)
        dirty = aset.mask & (gidx != aset.idx)
        carry = carry._replace(gidx=gidx)

        def do_refresh(c):
            slots = jnp.nonzero(dirty, size=h, fill_value=kc)[0]
            slots = slots.astype(jnp.int32)
            valid = slots < kc
            sl = jnp.minimum(slots, kc - 1)
            ids = jnp.where(valid, jnp.take(aset.idx, sl), 0)
            cols = jnp.take(X, ids, axis=1) * valid.astype(X.dtype)[None, :]
            cols_w = _wgt(cols)
            # two dots rather than one dot + transpose: each orientation is
            # consumed in its natural layout (XLA:CPU's dot thunk rejects
            # transposed-output fusions), and the column refresh stays
            # O(n k h) either way
            Gblk = Xa.T @ cols_w                      # (k_max, h)
            GblkT = cols_w.T @ Xa                     # (h, k_max)
            G = c.G.at[:, slots].set(Gblk, mode="drop")
            G = G.at[slots, :].set(GblkT, mode="drop")
            rho = c.rho.at[slots].set(cols_w.T @ y, mode="drop")
            new_gidx = c.gidx.at[slots].set(
                jnp.where(valid, ids, -1), mode="drop")
            return InnerCarry(G=G, rho=rho, gidx=new_gidx)

        return jax.lax.cond(jnp.any(dirty), do_refresh, lambda c: c, carry)

    def run(carry, aset, Xa, lam, n_ep):
        pen = (aset_lib.pen_weights(aset, unpen_idx, X.dtype)
               if unpen_idx >= 0 else None)
        beta = gram_epochs(carry.G, carry.rho, aset.beta, aset.mask, lam,
                           aset.order, aset.count, n_ep,
                           smoothness=loss.smoothness, pen=pen)
        z = Xa @ beta                # the only O(n k) term: once per burst
        theta, gap = _dual_and_gap(loss, Xa, y, beta, z, aset.mask, lam,
                                   pen=pen, x_unpen=x_unpen,
                                   sample_w=sample_w)
        return InnerOut(beta=beta, z=z, theta=theta, gap=gap)

    return InnerBackend(name="gram", init=init, refresh=refresh, run=run)


def make_inner_pallas(loss: Loss, X: jax.Array, y: jax.Array,
                      col_norm: jax.Array,
                      interpret: bool | None = None,
                      unpen_idx: int = -1) -> InnerBackend:
    """VMEM-resident fused-kernel backend (kernels/cm/cm.py)."""
    from repro.kernels.cm.cm import cm_burst_pallas

    def run(carry, aset, Xa, lam, n_ep):
        # O(k_max) gather from the solver's precomputed column norms — not
        # an O(n k_max) reduction over the gathered block
        norms = jnp.where(aset.mask, jnp.take(col_norm, aset.idx), 0.0)
        col_sq = norms * norms
        pen = (aset_lib.pen_weights(aset, unpen_idx, X.dtype)
               if unpen_idx >= 0 else None)
        beta, z, theta, gap = cm_burst_pallas(
            Xa, y, aset.beta, col_sq, aset.mask, aset.order, lam, n_ep,
            aset.count, pen=pen, loss_name=loss.name, interpret=interpret)
        return InnerOut(beta=beta, z=z, theta=theta, gap=gap)

    return InnerBackend(name="pallas",
                        init=lambda aset, carry, Xa: carry,
                        refresh=lambda carry, aset, Xa: carry,
                        run=run)


def make_inner(name: str, loss: Loss, X: jax.Array, y: jax.Array,
               col_norm: jax.Array, h: int,
               unpen_idx: int = -1) -> InnerBackend:
    """Factory used inside ``_saif_jit`` (name is a jit-static string)."""
    if name == "gram":
        return make_inner_gram(loss, X, y, h, unpen_idx)
    if name == "pallas":
        return make_inner_pallas(loss, X, y, col_norm, unpen_idx=unpen_idx)
    return make_inner_jnp(loss, X, y, unpen_idx)


# --------------------------------------------------------------------------
# batched (problem-axis) backends — the fleet engine (core/batch.py, §8)
# --------------------------------------------------------------------------
# The same three backends lifted to a fleet of B problems. The jnp and
# gram fleet backends are ``lax.map``s of the *serial* per-problem bodies
# (the very factories above, instantiated inside the traced map body with
# that problem's response/weights as operands): each problem's burst, dual
# point and gap are the literal serial computation — same HLO shapes, same
# reduction association — which is what makes fleet coefficients bitwise
# against B serial solves (batch-dim contractions provably re-associate on
# XLA:CPU; see DESIGN.md §8). The map's per-problem *traced* trip counts
# (n_epochs, count) also mean a finished problem's burst is a genuine
# zero-trip loop — zero marginal flops, not a masked no-op. A plain
# ``vmap`` could deliver neither property. The pallas fleet backend is the
# problem-gridded kernel instead: one launch, one grid step per problem,
# each step executing the serial kernel body on that problem's VMEM block.
# Optional ``weights`` (B, n) are the K-fold CV sample-weight trick (§8).


class BatchInnerBackend(NamedTuple):
    """The batched inner-solver interface ``_saif_batch_jit`` consumes.

    Two structural paths (engine picks by which field is set):

      * ``make_one(y_b, w_b) -> InnerBackend`` — the *map-fused* path
        (jnp / gram): the engine lax.maps one per-problem body that
        gathers the active block, refreshes and runs the SERIAL backend
        built here, all under a per-problem liveness ``lax.cond`` — a
        frozen problem costs literally nothing per outer step.
      * ``fleet_step(carry, aset, Xa, lam, n_ep) -> (InnerOut, carry)``
        — the *gridded-kernel* path (pallas): one problem-gridded launch
        for every burst on the fleet's (B, n, k_max) active blocks ``Xa``,
        which the engine carries across outer steps; frozen problems
        ride along with zero-trip epoch loops (cheap, not free — the
        kernel still runs their z/dual tail).

    ``init`` is fleet-level either way (outside the while_loop).
    """
    name: str
    init: Callable[[ActiveSet, InnerCarry, jax.Array], InnerCarry]
    make_one: Optional[Callable] = None
    fleet_step: Optional[Callable] = None


def cold_inner_carry_batch(b: int, k_max: int, dtype=jnp.float32,
                           backend: str = "gram") -> InnerCarry:
    """Fleet-shaped all-invalid carry (leading problem axis)."""
    if backend != "gram":
        return InnerCarry(G=jnp.zeros((b, 1, 1), dtype),
                          rho=jnp.zeros((b, 1), dtype),
                          gidx=jnp.full((b, 1), -1, jnp.int32))
    return InnerCarry(G=jnp.zeros((b, k_max, k_max), dtype),
                      rho=jnp.zeros((b, k_max), dtype),
                      gidx=jnp.full((b, k_max), -1, jnp.int32))


def _fleet_init(make_backend, Y, weights):
    """Fleet-level init: lax.map of the serial backend's init (one
    O(n k^2) reconcile per problem, outside the while_loop)."""
    def init(aset, carry, Xa):
        def one(args):
            if weights is None:
                y_b, carry_b, aset_b, Xa_b = args
                w_b = None
            else:
                y_b, w_b, carry_b, aset_b, Xa_b = args
            return make_backend(y_b, w_b).init(aset_b, carry_b, Xa_b)
        xs = ((Y, carry, aset, Xa) if weights is None
              else (Y, weights, carry, aset, Xa))
        return jax.lax.map(one, xs)
    return init


def make_batch_inner_jnp(loss: Loss, X: jax.Array, Y: jax.Array,
                         weights=None) -> BatchInnerBackend:
    """Fleet reference backend: the serial jnp backend, map-fused."""
    def make_one(y_b, w_b):
        return make_inner_jnp(loss, X, y_b, sample_w=w_b)
    return BatchInnerBackend(name="jnp",
                             init=_fleet_init(make_one, Y, weights),
                             make_one=make_one)


def make_batch_inner_gram(loss: Loss, X: jax.Array, Y: jax.Array,
                          h: int, weights=None) -> BatchInnerBackend:
    """Fleet covariance-update backend: the serial gram backend,
    map-fused — per-problem (k_max, k_max) Gram buffers with the refresh
    invariants 1-4 applied per problem (including the per-problem
    ``lax.cond`` skip when no slots are dirty). Sample weights fold into
    each problem's G/rho (G_b = Xa^T diag(w_b) Xa). A lockstep batched
    sweep was tried and rejected: per-problem dynamic indexing across a
    batch lowers to XLA gather/scatter ops whose per-op overhead on CPU
    exceeds the serial sweep's dynamic-slice steps ~30-fold, and batched
    float updates pick up FMA contractions that break bitwise parity —
    the map keeps the sweep serial-exact and lets the fleet win where it
    structurally should, on the shared O(p) scan."""
    def make_one(y_b, w_b):
        return make_inner_gram(loss, X, y_b, h, sample_w=w_b)
    return BatchInnerBackend(name="gram",
                             init=_fleet_init(make_one, Y, weights),
                             make_one=make_one)


def make_batch_inner_pallas(loss: Loss, Y: jax.Array,
                            col_norm: jax.Array,
                            interpret: bool | None = None,
                            weights=None) -> BatchInnerBackend:
    """Fleet VMEM-resident kernel backend: ONE problem-gridded launch
    drives the whole fleet's bursts (kernels/cm/cm.py)."""
    from repro.kernels.cm.cm import cm_burst_batch_pallas

    if weights is not None:
        raise ValueError("the batched pallas inner backend does not take "
                         "sample weights; use 'jnp' or 'gram' for CV "
                         "fleets (DESIGN.md §8)")

    def fleet_step(carry, aset, Xa, lam, n_ep):
        # col_norm is the fleet (B, p) matrix (shared designs broadcast it)
        norms = jnp.where(aset.mask,
                          jnp.take_along_axis(col_norm, aset.idx, axis=1),
                          0.0)
        col_sq = norms * norms
        beta, z, theta, gap = cm_burst_batch_pallas(
            Xa, Y, aset.beta, col_sq, aset.mask, aset.order, lam, n_ep,
            aset.count, loss_name=loss.name, interpret=interpret)
        return InnerOut(beta=beta, z=z, theta=theta, gap=gap), carry

    return BatchInnerBackend(name="pallas",
                             init=lambda aset, carry, Xa: carry,
                             fleet_step=fleet_step)


def make_batch_inner(name: str, loss: Loss, X: jax.Array, Y: jax.Array,
                     col_norm: jax.Array, h: int,
                     weights=None) -> BatchInnerBackend:
    """Factory used inside ``_saif_batch_jit`` (name is jit-static)."""
    if name == "gram":
        return make_batch_inner_gram(loss, X, Y, h, weights=weights)
    if name == "pallas":
        return make_batch_inner_pallas(loss, Y, col_norm,
                                       weights=weights)
    return make_batch_inner_jnp(loss, X, Y, weights=weights)


# n/k_max crossover of the auto policy: the gram step is an O(k_max) axpy
# against the jnp step's ~3 O(n) passes (gradient, dot, rank-1 update), so
# gram wins whenever k_max is not vastly larger than n. Measured on the CI
# shape (n=100, k_max=256, BENCH_inner.json) gram is still ahead at
# k_max ~ 2.5n; the factor 4 keeps a safety margin before handing back to
# the jnp path. Policy table in DESIGN.md §6.
GRAM_CROSSOVER = 4.0


def resolve_inner_backend(name: str, loss_name: str, n: int,
                          k_max: int, dtype=None) -> str:
    """Inner-backend selection policy (DESIGN.md §6): explicit name wins.
    ``auto`` picks the fused Pallas kernel on TPU whenever the block fits
    VMEM — the burst is a sequential coordinate loop, which the kernel
    runs inside VMEM while XLA pays a while-loop iteration per coordinate
    (on a v5e the gram burst's loop dominated the outer step). Elsewhere
    (and for problems Mosaic refuses: float64 or x64 mode, where an
    explicit ``pallas`` raises) it picks the covariance-update engine
    whenever the loss gradient is linear (least squares) and the active
    capacity is not >> n, and the jnp reference path otherwise (off-TPU
    the kernel would run interpreted — a correctness oracle, strictly
    slower than XLA)."""
    from repro.core.screen_backend import mosaic_refuses
    from repro.kernels.cm.cm import cm_vmem_ok

    if name == "auto":
        if (jax.default_backend() == "tpu" and cm_vmem_ok(n, k_max)
                and not mosaic_refuses(dtype)):
            return "pallas"
        if loss_name == "least_squares" and GRAM_CROSSOVER * n >= k_max:
            return "gram"
        return "jnp"
    if name not in ("jnp", "gram", "pallas"):
        raise ValueError(f"unknown inner backend {name!r}")
    if name == "gram" and loss_name != "least_squares":
        raise ValueError("inner_backend='gram' requires loss='least_squares'"
                         " (covariance updates need a linear gradient); use"
                         " 'jnp' or 'pallas'")
    if name == "pallas" and mosaic_refuses(dtype):
        raise ValueError("inner_backend='pallas' on TPU needs a float32 "
                         "problem with jax_enable_x64 off (Mosaic has no "
                         "f64); use float32 or 'gram'/'jnp'")
    if name == "pallas" and not cm_vmem_ok(n, k_max):
        raise ValueError(
            f"inner_backend='pallas': a {n}x{k_max} active block exceeds "
            f"the VMEM budget (DESIGN.md §6); shrink k_max, shard the "
            f"sample dimension, or use 'gram'/'jnp'")
    return name


def gram_block_update(G: jax.Array, rho: jax.Array, gidx: jax.Array,
                      rows_new: jax.Array, y_new: jax.Array,
                      rows_old: jax.Array, y_old: jax.Array):
    """Rank-m streaming update/downdate of a resident gram carry
    (DESIGN.md §14): replace the (m, p) rows ``rows_old`` (responses
    ``y_old``) with ``rows_new`` (``y_new``) in the active-block state,

        G   += C_new^T C_new - C_old^T C_old
        rho += C_new^T y_new - C_old^T y_old

    where ``C = rows[:, gidx]`` gathers the per-slot feature columns of
    the row block. Traceable (no shape depends on data). Slots with
    ``gidx < 0`` are masked out of the gather; their G/rho entries may go
    stale, which invariant (2) above explicitly allows — ``init`` /
    ``refresh`` never read a slot before reconciling it. An append-only
    stream passes zero rows as ``rows_old``/``y_old`` (an exact no-op on
    the subtracted terms), so one traced expression serves both the
    update and the downdate.

    ``gidx`` is returned unchanged by construction: live slots keep
    ``gidx == idx``, so the warm re-solve's ``init`` finds zero dirty
    slots and keeps the updated carry without the O(n k^2) rebuild.
    """
    valid = gidx >= 0
    ids = jnp.where(valid, gidx, 0)
    vf = valid.astype(G.dtype)
    c_new = jnp.take(rows_new, ids, axis=1) * vf[None, :]
    c_old = jnp.take(rows_old, ids, axis=1) * vf[None, :]
    G2 = G + c_new.T @ c_new - c_old.T @ c_old
    rho2 = rho + c_new.T @ y_new - c_old.T @ y_old
    return G2, rho2
