"""Pallas TPU kernel: fetch design columns from a row-major X.

The fleet engine keeps each problem's active block resident and only
fetches the columns ADD recruits (``core/batch.py``). ``jnp.take(X, ids,
axis=1)`` on the chip would make XLA hold X column-major, and the
screening scan, which streams (bn, bp) row-major tiles of X, would then
pay a full re-layout of the design at every screen. This kernel reads X
in its own layout instead.

TPU mapping: grid = (m,), one step per requested column. The block
indices (``id // 128``) are scalar-prefetched, so step i's input block is
the (n, 128) tile column of X that holds ``ids[i]``. The tile is
transposed into a (128, n) VMEM scratch, and the id's lane is read out
as one (1, n) row with a dynamic sublane load. Entries that are not
placed repeat the previous entry's block index: the pipeline issues no
DMA for a block index that does not change, and the scratch is
transposed again only when it does. Their rows are written as zeros.

Every value is a copy: a transpose and a row load move bits, so each
placed row is bitwise ``X[:, ids[i]]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.screen.screen import default_interpret, refuse_x64

LANES = 128


def _fetch_kernel(blk_ref, lane_ref, x_ref, out_ref, tile_t):
    i = pl.program_id(0)

    @pl.when((i == 0) | (blk_ref[i] != blk_ref[jnp.maximum(i - 1, 0)]))
    def _transpose():
        tile_t[...] = x_ref[...].T

    lane = lane_ref[i]
    row = tile_t[pl.ds(jnp.maximum(lane, 0), 1), :]
    out_ref[...] = jnp.where(lane >= 0, row, jnp.zeros_like(row))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fetch_columns_pallas(X, ids, placed, *, interpret: bool | None = None):
    """Rows ``X[:, ids[i]]`` for the placed entries of ``ids``.

    Args:
      X:      (n, p) design, row-major.
      ids:    (m,) int feature ids.
      placed: (m,) bool; an entry that is not placed costs no DMA and its
              row is zero.
    Returns (m, n): row i is ``X[:, ids[i]]`` where placed, else zeros.
    """
    n, p = X.shape
    m = ids.shape[0]
    if interpret is None:
        interpret = default_interpret()
    refuse_x64(interpret, X.dtype)
    ids = jnp.asarray(ids, jnp.int32)
    placed = jnp.asarray(placed, bool)
    # an unplaced entry takes the block of the last placed entry before
    # it (the first placed entry's, before any), so its step fetches
    # nothing new
    pos = jnp.arange(m, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(placed, pos, -1))
    src = jnp.where(last >= 0, last, jnp.argmax(placed).astype(jnp.int32))
    blk = jnp.clip(jnp.take(ids, src) // LANES, 0, (p - 1) // LANES)
    lane = jnp.where(placed, ids % LANES, -1)
    out = pl.pallas_call(
        _fetch_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m,),
            in_specs=[pl.BlockSpec((n, LANES),
                                   lambda i, blk, lane: (0, blk[i]))],
            out_specs=pl.BlockSpec((None, 1, n),
                                   lambda i, blk, lane: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((LANES, n), X.dtype)]),
        out_shape=jax.ShapeDtypeStruct((m, 1, n), X.dtype),
        interpret=interpret,
    )(blk, lane, X)
    return out.reshape(m, n)
