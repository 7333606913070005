"""Pallas TPU kernel: VMEM-resident cyclic coordinate-minimization bursts.

The SAIF inner loop runs K cyclic soft-threshold sweeps over the active block
A (n x k). k is small (<= ~1k) so the whole block, the model vector, and the
coefficients fit in VMEM; after the initial HBM->VMEM load, an epoch performs
ZERO HBM traffic — the TPU-native answer to the paper's tight C inner loop.

``cm_burst_batch_pallas`` is the production inner-solver kernel
(``repro.core.inner_backend``, DESIGN.md §6), gridded over a problem axis:
one grid step owns one problem's whole "CM burst + dual + gap". The serial
burst ``cm_burst_pallas`` is the fleet kernel at B = 1 (so a fleet member
and its serial solve execute the same kernel body), and ``cm_epochs_pallas``
is its least-squares, every-slot form. Per problem:
  * **general alpha-smooth losses** via the prox-Newton-majorized step
    (exactly ``core/cm.py::_coordinate_step``): the model vector z = A beta
    is VMEM-resident and updated rank-1; the per-step gradient f'(z) is an
    elementwise VPU pass;
  * **compact sweeps**: only the ``count`` live slots listed first in
    ``order`` are visited, and both ``count`` and the epoch count
    ``n_epochs`` are *traced* scalars (read from SMEM inside the kernel) so
    one compiled kernel serves every outer step of the solver — ADD-phase
    and polish bursts alike;
  * **fused dual point + duality gap**: after the burst the kernel computes
    the feasible dual point (Lemma 2 scaling, with the LS-specific tau*
    projection) and the sub-problem duality gap from the VMEM-resident
    state, so one kernel call covers the whole "CM burst + gap" of a SAIF
    outer step — no second HBM pass over the active block;
  * **an optional unpenalized slot** (``pen`` = 0, fused LASSO's ``b``,
    DESIGN.md §7): Newton-polished before the dual point, which is then
    projected onto its Thm-7 equality constraint.

Mosaic layout: the block travels TRANSPOSED, (k, n), so slot j's column is
the sublane row ``a_ref[pl.ds(j, 1), :]`` — a dynamic row load Mosaic
lowers, where a traced column slice of a value is not. Vectors are (1, n)
or (1, k) rows; a slot's coefficient, norm, mask and weight are read and
written with a lane select (``_pick``) so no dynamic lane offset is ever
formed; the sweep order and the per-problem scalars sit in SMEM.

The cyclic j-loop is inherently sequential (that's what "cyclic CM" means and
what Lemma 1's rate analyzes); the n-dimension vectorizes across the 8x128
VPU lanes. ``cm_vmem_ok`` is the block "autotuner" for this kernel family:
with no free tiling axis the only decision is whether the burst fits the
VMEM budget at all — the inner-backend resolver uses it to gate the pallas
backend. Computation runs in A.dtype: f32 compiled (Mosaic takes no f64),
f64 under the interpreter, where the x64 test suite needs full-precision
gaps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.screen.screen import default_interpret, refuse_x64

# VMEM budget for the (n, k) active block: leave ~4 MB of the ~16 MB for the
# (n,)-shaped vectors (y, z, theta), the (k,)-shaped state and headroom.
CM_VMEM_BUDGET_BYTES = 12 * 2**20
# scoped-VMEM limit the kernel compiles under: the pipeline double-buffers
# the block (2 x budget) plus room for the vector working set
CM_VMEM_LIMIT_BYTES = 2 * CM_VMEM_BUDGET_BYTES + 8 * 2**20


def cm_vmem_ok(n: int, k: int, itemsize: int = 4, batch: int = 1) -> bool:
    """Does a (n, k) CM burst fit the VMEM budget? (block-fit autotune).

    ``batch`` > 1 is the problem-gridded fleet kernel: each grid step owns
    ONE problem's (n, k) block, but the pipeline double-buffers the next
    problem's block while the current burst runs, so the fleet budget is
    two problems' working sets — independent of the fleet size B beyond
    that. This is the "batched budget" the inner-backend resolver consults
    for fleets (DESIGN.md §8).
    """
    per_problem = (n * k + 4 * n + 6 * k) * itemsize
    return per_problem * (2 if batch > 1 else 1) <= CM_VMEM_BUDGET_BYTES


def _dot(u, a, contract=0):
    """(1, x) row times the (k, n) block, contracting ``a``'s dim
    ``contract`` — at full precision: the burst's dual point and gap are
    certificates."""
    return jax.lax.dot_general(u, a, (((1,), (contract,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=u.dtype)


def _pick(row, slots, j):
    """row[0, j] of a (1, k) row by lane select + sum (exact: one term)."""
    return jnp.sum(jnp.where(slots == j, row, jnp.zeros_like(row)))


def _cm_burst_kernel(order_ref, lam_ref, nep_ref, cnt_ref,
                     a_ref, y_ref, beta_in_ref, colsq_ref, mask_ref, pen_ref,
                     beta_ref, z_ref, theta_ref, gap_ref, *, loss,
                     has_unpen: bool):
    from repro.core.duality import polish_unpen
    bb = pl.program_id(0)               # problem
    # beta_ref shares its HBM buffer with beta_in_ref, but its VMEM block
    # starts uninitialised on the chip (only the interpreter seeds an
    # aliased output from its input): load the inbound coefficients
    beta_ref[...] = beta_in_ref[...]
    k = a_ref.shape[0]                  # a_ref: (k, n), dead slots zero
    y = y_ref[...]                      # (1, n)
    dt = y.dtype
    lam = lam_ref[bb]
    alpha = loss.smoothness             # static per-loss constant
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    colsq = colsq_ref[...]              # (1, k)
    live = mask_ref[...] > 0.5
    pen = pen_ref[...]
    z_ref[...] = _dot(beta_ref[...], a_ref[...])

    def coord_step(jj, _):
        j = order_ref[bb * k + jj]      # compact sweep: live slots only
        aj = a_ref[pl.ds(j, 1), :]      # (1, n): slot j's design column
        lj = jnp.maximum(alpha * _pick(colsq, slots, j), 1e-30)
        g = jnp.sum(aj * loss.grad(z_ref[...], y))
        beta = beta_ref[...]
        bj = _pick(beta, slots, j)
        u = bj - g / lj
        t = lam * _pick(pen, slots, j) / lj     # pen=0: exact unpenalized
        b_new = jnp.sign(u) * jnp.maximum(jnp.abs(u) - t, 0.0)
        b_new = jnp.where(_pick(live.astype(dt), slots, j) > 0.5, b_new, 0.0)
        z_ref[...] += (b_new - bj) * aj
        beta_ref[...] = jnp.where(slots == j, b_new, beta)
        return 0

    def epoch(_, carry):
        return jax.lax.fori_loop(0, cnt_ref[bb], coord_step, carry)

    jax.lax.fori_loop(0, nep_ref[bb], epoch, 0)

    # ---- fused dual-point / duality-gap tail (still VMEM-resident) -------
    a = a_ref[...]
    beta = beta_ref[...]
    z = _dot(beta, a)                                  # fresh, drift-free
    if has_unpen:
        # b's column — the one live slot with pen = 0 — shared by the
        # Newton polish and the equality projection below
        w = jnp.where(live, 1.0 - pen, 0.0).astype(dt)
        ab = _dot(w, a)                                 # (1, n)
        if loss.name != "least_squares":
            # General loss: Newton-polish the unpenalized coordinate to
            # stationarity before forming the dual point, so x_b^T f'(z)
            # ~ 0 and the equality projection is a benign ~0 correction
            # (duality.polish_unpen — the same pure-jax fold runs inside
            # the kernel, DESIGN.md §7).
            b_cur = jnp.sum(beta * w)
            b_new, z = polish_unpen(loss, ab, y, z, b_cur)
            beta = jnp.where(w > 0.5, b_new, beta)
            beta_ref[...] = beta
    z_ref[...] = z
    hat = -loss.grad(z, y) / lam                       # unscaled dual point
    if has_unpen:
        # Thm-7 equality constraint x_b^T theta = 0: project hat onto the
        # hyperplane before scaling (duality.feasible_dual, DESIGN.md §7)
        sq_b = jnp.sum(ab * ab)
        hat = hat - ab * (jnp.sum(ab * hat) / jnp.maximum(sq_b, 1e-30))
    # (1, k) slot correlations hat^T A; dead slots -> 0
    corr = _dot(hat, a, contract=1)
    max_corr = jnp.max(jnp.abs(corr) * pen)            # penalized cols only
    if loss.name == "least_squares":
        # DPP-style optimal scaling (duality.feasible_dual, LS branch)
        bound = 1.0 / jnp.maximum(max_corr, 1e-30)
        sq = jnp.sum(hat * hat)
        tau_star = jnp.sum(y * hat) / (lam * jnp.maximum(sq, 1e-30))
        tau = jnp.clip(tau_star, -bound, bound)
        tau = jnp.where(jnp.isfinite(tau), tau,
                        1.0 / jnp.maximum(max_corr, 1.0))
        theta = tau * hat
    else:
        theta = hat / jnp.maximum(max_corr, 1.0)
        theta = -loss.dual_clip(-lam * theta, y) / lam
    theta_ref[...] = theta
    p_val = jnp.sum(loss.value(z, y)) + lam * jnp.sum(pen * jnp.abs(beta))
    d_val = -jnp.sum(loss.conj(-lam * theta, y))
    gap_ref[...] = jnp.full((1, 1), p_val - d_val, dt)


@functools.partial(jax.jit, static_argnames=("loss_name", "interpret"))
def cm_burst_batch_pallas(A, Y, beta, col_sq, mask, order, lam, n_epochs,
                          count, pen=None, *,
                          loss_name: str = "least_squares",
                          interpret: bool | None = None):
    """Fleet "CM burst + gap": grid axis over problems, one launch for B.

    Args:
      A:        (B, n, k) per-problem active blocks, dead columns zeroed.
                Computation runs in A.dtype.
      Y:        (B, n) responses.
      beta:     (B, k) inbound coefficients (0 on dead slots).
      col_sq:   (B, k) squared column norms; mask (B, k) live slots.
      order:    (B, k) int32 slot permutations, the ``count`` live slots
                first.
      lam, n_epochs, count: (B,) — the epoch and live-slot counts are
                traced (the solver batches ADD vs polish bursts through
                this one compiled kernel; a finished problem runs a
                zero-trip burst).
      pen:      (B, k) optional per-slot l1 weight: 0 marks the
                always-resident unpenalized slot (fused LASSO's ``b``,
                DESIGN.md §7), which also switches the dual tail to the
                Thm-7 equality-projected scaling. None = all penalized.
    Returns (beta (B, k), z (B, n), theta (B, n), gap (B,)): the updated
    coefficients, the fresh model vector z = A beta, the feasible dual
    point, and the sub-problem duality gap — everything a SAIF outer step
    needs from the inner solver. The double-buffered fleet budget is
    checked by ``cm_vmem_ok(..., batch=B)``.
    """
    from repro.core.losses import get_loss

    loss = get_loss(loss_name)
    b, n, k = A.shape
    dt = A.dtype
    assert cm_vmem_ok(n, k, dt.itemsize, batch=b), (
        f"a fleet of {b} {n}x{k} active blocks ({dt}) exceeds the "
        f"double-buffered VMEM budget; shrink k_max or shard the sample "
        f"dimension (see DESIGN.md §5/§8)")
    if interpret is None:
        interpret = default_interpret()
    refuse_x64(interpret, dt)
    has_unpen = pen is not None
    if pen is None:
        pen = jnp.ones((b, k), dt)
    kernel = functools.partial(_cm_burst_kernel, loss=loss,
                               has_unpen=has_unpen)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    blk = pl.BlockSpec((None, k, n), lambda i: (i, 0, 0))
    row_k = pl.BlockSpec((None, 1, k), lambda i: (i, 0, 0))
    row_n = pl.BlockSpec((None, 1, n), lambda i: (i, 0, 0))

    def rows(v):
        return jnp.asarray(v).astype(dt)[:, None, :]

    beta_out, z_out, theta_out, gap_out = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[smem, smem, smem, smem,             # order/lam/nep/count
                  blk,                                # A^T
                  row_n,                              # Y
                  row_k,                              # beta (aliased)
                  row_k, row_k, row_k],               # col_sq, mask, pen
        out_specs=[row_k, row_n, row_n,
                   pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, k), dt),      # beta
            jax.ShapeDtypeStruct((b, 1, n), dt),      # z
            jax.ShapeDtypeStruct((b, 1, n), dt),      # theta
            jax.ShapeDtypeStruct((b, 1, 1), dt),      # gap
        ],
        input_output_aliases={6: 0},                  # beta updated in place
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=CM_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(order, jnp.int32).reshape(b * k),
      jnp.asarray(lam, dt).reshape(b),
      jnp.asarray(n_epochs, jnp.int32).reshape(b),
      jnp.asarray(count, jnp.int32).reshape(b),
      jnp.swapaxes(A, 1, 2), rows(Y), rows(beta), rows(col_sq), rows(mask),
      rows(pen))
    return beta_out[:, 0], z_out[:, 0], theta_out[:, 0], gap_out[:, 0, 0]


def cm_burst_pallas(A, y, beta, col_sq, mask, order, lam, n_epochs, count,
                    pen=None, *, loss_name: str = "least_squares",
                    interpret: bool | None = None):
    """One problem's fused "CM burst + gap": the fleet kernel at B = 1.
    Args as :func:`cm_burst_batch_pallas` without the problem axis (A is
    (n, k)); returns (beta (k,), z (n,), theta (n,), gap scalar)."""
    out = cm_burst_batch_pallas(
        A[None], jnp.asarray(y)[None], jnp.asarray(beta)[None],
        jnp.asarray(col_sq)[None], jnp.asarray(mask)[None],
        jnp.asarray(order)[None], jnp.reshape(lam, (1,)),
        jnp.reshape(n_epochs, (1,)), jnp.reshape(count, (1,)),
        None if pen is None else jnp.asarray(pen)[None],
        loss_name=loss_name, interpret=interpret)
    return tuple(o[0] for o in out)


def cm_epochs_pallas(A, y, beta, col_sq, mask, lam, *, n_epochs: int = 1,
                     interpret: bool | None = None):
    """K least-squares cyclic sweeps over every slot of A (n, k) in order.
    Returns (beta, residual y - A beta)."""
    k = A.shape[1]
    beta_out, z, _, _ = cm_burst_pallas(
        A, y, beta, col_sq, mask, jnp.arange(k, dtype=jnp.int32), lam,
        n_epochs, k, interpret=interpret)
    return beta_out, jnp.asarray(y, z.dtype) - z
