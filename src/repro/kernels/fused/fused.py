"""Pallas TPU kernel: the chain-graph Theorem-6 column transform.

For the 1-D fused LASSO (path graph 0-1-...-p-1 rooted at 0) the subtree
below the edge into node v is exactly {v, v+1, ..., p-1}, so the whole
Theorem-6 transform collapses to the *suffix sums* of the design columns:

    S[:, v] = sum_{u >= v} X[:, u]
    x_tilde_e = S[:, e+1]          (edge e's transformed column)
    x_b       = S[:, 0]            (the unpenalized b column)

TPU mapping: the kernel works on the TRANSPOSED design X^T (p, n), so a
design column is a sublane row and the fold reads and writes it with a
dynamic row slice ``x_ref[pl.ds(l, 1), :]`` (Mosaic lowers that, where a
traced column slice of a value is not). Grid = (p/BP,), tiles visited
BOTTOM to TOP (the index map reverses the program id — TPU grids execute
sequentially, so the (1, n)-shaped running carry can live in an output
block with a constant index map that every step revisits, the same
accumulation pattern as the screening kernels). Inside a tile the suffix is
an exact *right fold* (acc = x[:, l] + acc, one IEEE add per column):
bitwise-identical to the dense numpy reference
``repro.core.fused.transform_design``, which is what the device-transform
parity suite asserts. A triangular-matmul form would feed the MXU but
re-associates the sums; the transform runs once per fused problem, so the
exact fold wins (DESIGN.md §7), and so do the two XLA transposes around it.

Execution mode: ``interpret=None`` auto-detects like every other kernel in
``repro.kernels`` — compiled Mosaic on TPU, interpreter fallback on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.screen.screen import default_interpret, refuse_x64

# the (bp, n_pad) tile + its output + the (n_pad,) carry, double-buffered
FUSED_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def autotune_chain_block(n: int, p: int, *, dtype_bytes: int = 4) -> int:
    """Tile height bp (columns of X per tile, a multiple of 128), shrunk
    until in+out tiles fit the VMEM budget at this n."""
    n_pad = _round_up(max(n, 1), 128)
    bp = min(512, _round_up(max(p, 1), 128))
    while bp > 128 and 2 * n_pad * bp * dtype_bytes > FUSED_VMEM_BUDGET_BYTES:
        bp //= 2
    return bp


def _chain_suffix_kernel(x_ref, s_ref, tot_ref, *, bp: int):
    i = pl.program_id(0)        # i-th tile from the BOTTOM (index map flips)

    @pl.when(i == 0)
    def _init():
        tot_ref[...] = jnp.zeros_like(tot_ref)

    def fold(jj, acc):
        l = bp - 1 - jj
        acc = x_ref[pl.ds(l, 1), :] + acc   # ONE IEEE add: exact right fold
        s_ref[pl.ds(l, 1), :] = acc
        return acc

    # the carry holds the completed suffix of every tile below this one
    # int32 bounds: the index stays int32 under jax_enable_x64 too
    tot_ref[...] = jax.lax.fori_loop(jnp.int32(0), jnp.int32(bp), fold,
                                     tot_ref[...])


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def chain_suffix_sums_pallas(X, *, bp: int | None = None,
                             interpret: bool | None = None):
    """Suffix sums S[:, v] = sum_{u >= v} X[:, u] of the design columns.

    Computation runs in X.dtype (f32 compiled, f64 under the x64
    interpreter); the fold order matches the dense numpy reference exactly
    (see the module docstring), so the parity tests compare bitwise.
    """
    n, p = X.shape
    dt = X.dtype
    if bp is None:
        bp = autotune_chain_block(n, p, dtype_bytes=dt.itemsize)
    if interpret is None:
        interpret = default_interpret()
    refuse_x64(interpret, dt)
    # rows of X^T pad at the BOTTOM with zeros — a zero column leaves the
    # right fold bitwise unchanged; lanes pad to 128 and are sliced off
    Xt = jnp.pad(X.T, ((0, -p % bp), (0, -n % 128)))
    pp, np_ = Xt.shape
    p_blocks = pp // bp
    kernel = functools.partial(_chain_suffix_kernel, bp=bp)
    # visit tiles bottom-to-top so the carry always holds the completed
    # suffix of everything to the right (below, in X^T)
    tile = pl.BlockSpec((bp, np_), lambda i: (p_blocks - 1 - i, 0))
    St, _ = pl.pallas_call(
        kernel,
        grid=(p_blocks,),
        in_specs=[tile],
        out_specs=[tile,
                   pl.BlockSpec((1, np_), lambda i: (0, 0))],  # carry
        out_shape=[
            jax.ShapeDtypeStruct((pp, np_), dt),    # S^T
            jax.ShapeDtypeStruct((1, np_), dt),     # running total
        ],
        interpret=interpret,
    )(Xt)
    return St[:p, :n].T


def chain_suffix_sums_ref(X):
    """Dense jnp reference: the same exact right fold, no tiling."""
    X = jnp.asarray(X)
    n, p = X.shape

    def fold(jj, S):
        v = p - 2 - jj
        return S.at[:, v].set(X[:, v] + S[:, v + 1])

    S0 = jnp.zeros_like(X).at[:, p - 1].set(X[:, p - 1])
    return jax.lax.fori_loop(0, p - 1, fold, S0)
