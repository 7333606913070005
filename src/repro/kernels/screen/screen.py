"""Pallas TPU kernels: the SAIF screening scan (the only O(p) hot spot).

Two kernels, both gridded over a problem axis (a serial screen is a fleet
of one, so the serial and fleet screens run the same kernel body):

``screen_fused_batch_pallas`` — the compile-first ADD-phase scan. For every
feature column x_i of X (n x p) and every problem's dual ball (theta, r):
    score_i = |x_i^T theta|
    ub_i    = score_i + ||x_i|| * r      (ADD-stop / DEL upper bound)
    lb_i    = | score_i - ||x_i|| * r |  (ADD violation lower bound)
plus everything the solver's ADD decision needs so no second full-width pass
(and in particular no O(p log p) sort) happens outside the kernel:
    * the active-set exclusion mask is applied in-kernel (excluded features
      get score = ub = -inf, lb = +inf, i.e. never recruitable),
    * each p-tile emits its local top-h (score, global id) candidates —
      the global top-h is a cheap O((p/bp) h) merge of tile winners,
    * each p-tile emits its local max ub — the ADD-stop reduction.
``screen_fused_pallas`` (one problem) and ``screen_scores_pallas`` (no
mask, no winners) are thin wrappers over it.

``ub_histogram_batch_pallas`` — the violation-count reduction. Given the
(p,) ub vector and the h sorted candidate lower bounds, emits the exact
histogram hist[m] = #{i : m lower bounds <= ub_i}; suffix sums of this
histogram are the per-candidate violation counts |V_l| = #{i in R_t : ub_i
>= lb_l}. This replaces a full-vector ``jnp.sort`` + ``searchsorted``
(O(p log p)) with an O(p h / lanes) streaming compare — identical integers,
bit for bit. ``ub_histogram_pallas`` is its one-problem wrapper.

TPU mapping: grid = (p/BP, B, n/BN). Each instance streams an (BN, BP) tile
of X HBM->VMEM, does the partial matvec theta_tile @ X_tile on the VPU, and
accumulates into the (1, BP) output block (its index map is constant along
the innermost n axis, so the same VMEM block is revisited across it — TPU
grids execute sequentially, making this a safe accumulation). On the last
n-step the raw dot is finalized. Whenever n fits one tile the X tile's
index map is also constant across the problem axis, so a fleet sharing X
fetches each design tile once.

Mosaic layout rules shape every operand: the last two dims of a block are
(8k, 128m) or the full array dims. Per-problem vectors therefore travel as
(B, 1, P) arrays with (1, BP) blocks, theta as a (B, n, 1) column with
(BN, 1) blocks, tile winners as (B, P/BP, 1, h) with full-extent (1, h) blocks,
and the per-problem radius in SMEM. Mosaic takes no float64 operand and
x64 mode breaks its index arithmetic: :func:`refuse_x64` turns either into
a TypeError before lowering.

Execution mode: ``interpret=None`` resolves to compiled Mosaic on a TPU
backend and to the Pallas interpreter elsewhere (the CPU test suite runs
the kernel bodies under the interpreter, in f64, for correctness).

Block shapes: ``autotune_screen_blocks`` picks (BN, BP) from (n, p) under a
VMEM budget — lane dim a multiple of 128 for the MXU/VPU, sublane a multiple
of 8 (f32), X tile capped so HBM->VMEM double buffering fits comfortably in
the ~16 MB v5e scoped budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BN = 512
DEFAULT_BP = 256

# X-tile budget: ~1/4 of a 16 MB VMEM so the pipeline can double-buffer the
# big operand and still hold the (BP,)-shaped accumulators + candidate state.
VMEM_TILE_BUDGET_BYTES = 4 * 1024 * 1024

# element budget of one (h, BP) compare in the histogram kernel
_HIST_COMPARE_ELEMS = 128 * 1024


def default_interpret() -> bool:
    """Compiled Mosaic on TPU, interpreter everywhere else (CPU fallback)."""
    return jax.default_backend() != "tpu"


def refuse_x64(interpret: bool, *dtypes) -> None:
    """Compiled kernels run as on the chip: float32 operands with
    ``jax_enable_x64`` off (Mosaic has no f64, and x64 mode widens the
    kernels' index arithmetic past what it lowers). The backend policies
    keep such problems off the kernels; the interpreter, which the x64 CPU
    test suite uses, takes them."""
    if interpret:
        return
    if jax.config.jax_enable_x64 or any(
            jnp.dtype(d) == jnp.float64 for d in dtypes):
        raise TypeError(
            "Mosaic kernels take no float64 operand and compile with "
            "jax_enable_x64 off; run the problem in float32 with x64 off "
            "or select the 'jnp' backend")


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def autotune_screen_blocks(n: int, p: int, *, dtype_bytes: int = 4,
                           vmem_budget_bytes: int = VMEM_TILE_BUDGET_BYTES,
                           batch: int = 1) -> tuple:
    """Pick (bn, bp) for the screening kernels from the problem shape.

    bp (lane dim) is a multiple of 128, bn (sublane dim) a multiple of 8;
    both are clipped to the padded problem so tiny problems run one tile,
    and bn shrinks (keeping the wide lane dim) until a double-buffered X
    tile fits the VMEM budget.

    ``batch`` > 1 is the problem-gridded fleet kernel (DESIGN.md §8): the
    X tile is revisited across the fleet's grid axis, so it must coexist
    in VMEM with one problem's (bn,)/(bp,)-shaped vector blocks *per
    in-flight problem* — the budget is charged for the double-buffered
    vector working set of two problems in addition to the X tile.
    """
    bp = min(512, _round_up(max(p, 1), 128))
    bn = min(DEFAULT_BN, _round_up(max(n, 1), 8))
    vec_bytes = (2 * (bn + 4 * bp) * dtype_bytes) if batch > 1 else 0
    while bn > 8 and 2 * bn * bp * dtype_bytes + vec_bytes > \
            vmem_budget_bytes:
        bn = max(8, _round_up(bn // 2, 8))
        vec_bytes = (2 * (bn + 4 * bp) * dtype_bytes) if batch > 1 else 0
    return bn, bp


def _tile_top_h(masked_scores, h_tile: int):
    """Iterative max-extraction top-h of a (1, BP) tile.

    O(h * BP) VPU work per tile. Ties break to the smallest lane index,
    matching ``jax.lax.top_k``'s stable order, so the tile-merge reduction
    downstream reproduces a global top_k exactly on every finite
    candidate. An explicit availability mask (not value re-masking) keeps
    the emitted lane ids distinct even once a tile's finite entries are
    exhausted and only -inf (masked/padding) lanes remain; those -inf ids
    are never recruited downstream (keep &= isfinite), and in a deeply
    saturated tile their order may differ from a global top_k's -inf tail
    — the only regime where the merge is not literally top_k. (Sort-free
    on purpose: no O(p log p) anywhere.) The loop carries int32 and float
    vectors only and writes winners with lane selects: Mosaic lowers
    neither a boolean loop carry nor a dynamic-offset vector update.
    """
    neg = jnp.asarray(-jnp.inf, masked_scores.dtype)
    bp = masked_scores.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, bp), 1)
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, h_tile), 1)

    def body(t, carry):
        avail, ts, ti = carry
        free = avail > 0
        vals = jnp.where(free, masked_scores, neg)
        m = jnp.max(vals)
        i = jnp.min(jnp.where(free & (vals == m), lanes, bp))
        ts = jnp.where(slots == t, m, ts)
        ti = jnp.where(slots == t, i, ti)
        avail = jnp.where(lanes == i, 0, avail)
        return avail, ts, ti

    # h_tile <= bp, so an available lane always exists at every step
    init = (jnp.ones((1, bp), jnp.int32),
            jnp.full((1, h_tile), neg, masked_scores.dtype),
            jnp.zeros((1, h_tile), jnp.int32))
    # int32 bounds: the index stays int32 under jax_enable_x64 too
    _, ts, ti = jax.lax.fori_loop(jnp.int32(0), jnp.int32(h_tile), body,
                                  init)
    return ts, ti


# --------------------------------------------------------------------------
# fused ADD-phase kernel (masked score/ub/lb + tile top-h + tile max-ub)
# --------------------------------------------------------------------------

def _screen_fused_kernel(r_ref, theta_ref, x_ref, norm_ref, act_ref,
                         score_ref, ub_ref, lb_ref,
                         tops_ref, topi_ref, tmax_ref,
                         *, n_blocks: int, h_tile: int, bp: int):
    i = pl.program_id(0)                     # p-axis tile (for global ids)
    b = pl.program_id(1)                     # problem
    j = pl.program_id(2)                     # n-axis step (innermost)

    @pl.when(j == 0)
    def _init():
        score_ref[...] = jnp.zeros_like(score_ref)

    # partial matvec for THIS problem's theta against the (shared) X tile,
    # on the VPU: a (bn, 1) theta column broadcast over the tile's lanes
    # and summed over its sublanes, in the accumulator dtype. A one-row
    # MXU matmul would be weight-load bound, and on the MXU float32 is
    # exact only at the multi-pass HIGHEST precision; the bounds are
    # certificates, so every product and sum here is a plain f32 op.
    acc = score_ref.dtype
    score_ref[...] += jnp.sum(theta_ref[...].astype(acc) *
                              x_ref[...].astype(acc), axis=0, keepdims=True)

    @pl.when(j == n_blocks - 1)
    def _finalize():
        s = jnp.abs(score_ref[...])
        nr = norm_ref[...] * r_ref[b]
        neg = jnp.asarray(-jnp.inf, s.dtype)
        # active (or padding) features are not recruitable: score/ub -> -inf
        ms = jnp.where(act_ref[...] > 0.5, neg, s)
        ub = ms + nr
        score_ref[...] = ms
        ub_ref[...] = ub
        lb_ref[...] = jnp.abs(ms - nr)
        tmax_ref[...] = jnp.max(ub, axis=1, keepdims=True)
        ts, ti = _tile_top_h(ms, h_tile)
        tops_ref[...] = ts
        topi_ref[...] = ti + i * bp                   # global feature ids


def _screen_dtypes(X, in_dtype, acc_dtype):
    """Resolve the (input, accumulator) dtype pair for a screening kernel.

    ``in_dtype`` (e.g. "bfloat16") is the dtype the X / theta tiles are
    stored and streamed in; ``acc_dtype`` is the dtype the products are
    formed and accumulated in, and the output dtype (defaults to f32 when
    the input is low precision). The certified rounding bound for the pair is
    ``duality.mixed_precision_gamma(n, in_dtype, acc_dtype)``; widening
    the radius by it happens in the CALLER (screen_backend), the kernel
    just computes in the requested precisions.
    """
    dt_in = X.dtype if in_dtype is None else jnp.dtype(in_dtype)
    if acc_dtype is not None:
        dt_acc = jnp.dtype(acc_dtype)
    elif dt_in == X.dtype:
        dt_acc = X.dtype
    else:
        dt_acc = jnp.promote_types(jnp.float32, dt_in)
    return dt_in, dt_acc


@functools.partial(jax.jit,
                   static_argnames=("h", "bn", "bp", "interpret",
                                    "in_dtype", "acc_dtype"))
def screen_fused_batch_pallas(X, Theta, col_norm, active, r, *, h: int,
                              bn: int | None = None, bp: int | None = None,
                              interpret: bool | None = None,
                              in_dtype: str | None = None,
                              acc_dtype: str | None = None):
    """Fleet ADD-phase scan: one launch screens all B problems.

    Grid order is (p-tiles, problems, n-steps): the n-axis stays innermost
    so the per-(problem, p-tile) score accumulator is revisited
    consecutively (the TPU sequential-grid contract), and whenever the
    sample dim fits one tile (n <= bn — the SAIF norm) the shared X tile's
    index map is constant across the problem axis, so the VMEM-resident
    design block is fetched once and reused by the whole fleet — the
    shared-X fast path. Distinct-X fleets don't use this kernel; they take
    the einsum fallback in ``core/screen_backend.py``.

    Args:
      X:        (n, p) SHARED design.
      Theta:    (B, n) per-problem dual ball centers.
      col_norm: (B, p) per-problem column norms (CV fleets differ per
                problem; multi-response fleets broadcast one row).
      active:   (B, p) per-problem exclusion masks.
      r:        (B,) per-problem ball radii.
      h:        static per-tile candidate count.

    Returns (all padding sliced/neutralized): masked (score, ub, lb) as
    (B, p), tile winners (B, p_blocks, min(h, bp)) scores and int32 global
    ids, and tile max-ub (B, p_blocks).

    ``in_dtype``/``acc_dtype`` select a mixed-precision pass (e.g. bf16
    tiles, f32 accumulation — :func:`_screen_dtypes`): X/Theta tiles are
    cast to ``in_dtype``, the dot accumulates and every emitted quantity
    is in ``acc_dtype``. Halving the tile bytes doubles the design rows
    per VMEM fetch — the fleet's shared-X read amortization improves by
    the same factor. Callers certify the precision with the widened
    radius (DESIGN.md §11); this kernel only changes dtypes, not rules.
    """
    n, p = X.shape
    b = Theta.shape[0]
    dt_in, dt_acc = _screen_dtypes(X, in_dtype, acc_dtype)
    if bn is None or bp is None:
        abn, abp = autotune_screen_blocks(n, p,
                                          dtype_bytes=dt_in.itemsize,
                                          batch=b)
        bn = bn or abn
        bp = bp or abp
    if dt_in.itemsize == 2:
        bn = _round_up(bn, 16)       # bf16 sublane tile is 16, not 8
    if interpret is None:
        interpret = default_interpret()
    refuse_x64(interpret, dt_in, dt_acc)
    h_tile = max(1, min(h, bp))
    dt = dt_acc
    n_pad = -n % bn
    p_pad = -p % bp
    Xp = jnp.pad(X.astype(dt_in), ((0, n_pad), (0, p_pad)))
    np_, pp = Xp.shape
    n_blocks, p_blocks = np_ // bn, pp // bp
    theta_p = jnp.pad(Theta.astype(dt_in), ((0, 0), (0, n_pad)))[:, :, None]
    norm_p = jnp.pad(col_norm.astype(dt), ((0, 0), (0, p_pad)))[:, None, :]
    # padding columns are flagged "active" => excluded from recruitment
    act_p = jnp.pad(jnp.asarray(active).astype(dt), ((0, 0), (0, p_pad)),
                    constant_values=1.0)[:, None, :]
    r_arr = jnp.asarray(r, dt).reshape(b)

    vec = jax.ShapeDtypeStruct((b, 1, pp), dt)
    win = (b, p_blocks, 1, h_tile)
    out_shape = [vec, vec, vec,                            # score, ub, lb
                 jax.ShapeDtypeStruct(win, dt),            # tile top scores
                 jax.ShapeDtypeStruct(win, jnp.int32),     # tile top ids
                 jax.ShapeDtypeStruct((b, p_blocks, 1, 1), dt)]  # tile max ub
    kernel = functools.partial(_screen_fused_kernel, n_blocks=n_blocks,
                               h_tile=h_tile, bp=bp)
    vspec = pl.BlockSpec((None, 1, bp), lambda i, bb, j: (bb, 0, i))
    wspec = pl.BlockSpec((None, None, 1, h_tile),
                         lambda i, bb, j: (bb, i, 0, 0))
    score, ub, lb, tops, topi, tmax = pl.pallas_call(
        kernel,
        grid=(p_blocks, b, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # r (B,)
            pl.BlockSpec((None, bn, 1),
                         lambda i, bb, j: (bb, j, 0)),       # theta column
            pl.BlockSpec((bn, bp), lambda i, bb, j: (j, i)),  # shared X
            vspec,                                           # col_norm
            vspec,                                           # active mask
        ],
        out_specs=[
            vspec, vspec, vspec, wspec, wspec,
            pl.BlockSpec((None, None, 1, 1),
                         lambda i, bb, j: (bb, i, 0, 0)),    # tile max ub
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(r_arr, theta_p, Xp, norm_p, act_p)
    return (score[:, 0, :p], ub[:, 0, :p], lb[:, 0, :p],
            tops.reshape(b, p_blocks, h_tile),
            topi.reshape(b, p_blocks, h_tile), tmax.reshape(b, p_blocks))


def screen_fused_pallas(X, theta, col_norm, active, r, *, h: int,
                        bn: int | None = None, bp: int | None = None,
                        interpret: bool | None = None,
                        in_dtype: str | None = None,
                        acc_dtype: str | None = None):
    """One-problem fused ADD-phase scan (the fleet kernel at B = 1).

    Args:
      X:        (n, p) design (any float dtype; compute stays in X.dtype
                unless ``in_dtype``/``acc_dtype`` request a mixed-
                precision pass — see :func:`_screen_dtypes`).
      theta:    (n,) dual ball center.
      col_norm: (p,) column norms.
      active:   (p,) bool/0-1 mask of features to EXCLUDE (current actives).
      r:        scalar ball radius.
      h:        static per-tile candidate count.

    Returns (all padding sliced/neutralized):
      score (p,), ub (p,), lb (p,)           — masked quantities,
      tile_top_s (p_blocks, min(h, bp))       — tile-local top-h scores,
      tile_top_i (p_blocks, min(h, bp)) int32 — their global feature ids,
      tile_max_ub (p_blocks,)                 — tile-local max ub.
    """
    out = screen_fused_batch_pallas(
        X, jnp.asarray(theta)[None], jnp.asarray(col_norm)[None],
        jnp.asarray(active)[None], jnp.asarray(r).reshape(1), h=h, bn=bn,
        bp=bp, interpret=interpret, in_dtype=in_dtype, acc_dtype=acc_dtype)
    return tuple(o[0] for o in out)


def screen_scores_pallas(X, theta, col_norm, r, *, bn: int | None = None,
                         bp: int | None = None,
                         interpret: bool | None = None):
    """Plain scan: (score, ub, lb) (p,) with nothing masked."""
    score, ub, lb, *_ = screen_fused_pallas(
        X, theta, col_norm, jnp.zeros(X.shape[1], bool), r, h=1, bn=bn,
        bp=bp, interpret=interpret)
    return score, ub, lb


# --------------------------------------------------------------------------
# violation-count histogram kernel
# --------------------------------------------------------------------------

def _ub_hist_kernel(ub_ref, lb_ref, hist_ref):
    i = pl.program_id(1)                                 # p-tile (innermost)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    ub = ub_ref[...]                                     # (1, bp)
    lb = lb_ref[...]                                     # (h, 1)
    # c_i = #{l : lb_sorted[l] <= ub_i}  (exact searchsorted-right count)
    c = jnp.sum((lb <= ub).astype(jnp.int32), axis=0, keepdims=True,
                dtype=jnp.int32)
    n_bins = hist_ref.shape[0]
    bins = jax.lax.broadcasted_iota(jnp.int32, (n_bins, ub.shape[1]), 0)
    hist_ref[...] += jnp.sum((c == bins).astype(jnp.int32), axis=1,
                             keepdims=True, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def ub_histogram_batch_pallas(ub, lb_sorted, *, bp: int | None = None,
                              interpret: bool | None = None):
    """Per-problem histogram of c_i = #{l : lb_sorted[l] <= ub_i} over bins
    0..h: ub (B, p), lb_sorted (B, h) -> hist (B, h+1) int32.

    Exactly ``bincount(searchsorted(lb_sorted, ub, 'right'), length=h+1)``
    per problem, streamed tile by tile; suffix sums give the per-candidate
    counts #{i : ub_i >= lb_sorted[j]} without ever sorting the (p,)
    vector. Grid = (problems, p-tiles) with the tile axis innermost so each
    problem's histogram block accumulates consecutively. The candidate
    bounds travel as an (h, 1) column against the (1, bp) ub row, so the
    (h, bp) compare keeps features on the lanes.
    """
    b, p = ub.shape
    h = lb_sorted.shape[1]
    if bp is None:
        bp = max(128, min(2048, _round_up(max(p, 1), 128),
                          _HIST_COMPARE_ELEMS // _round_up(h + 1, 8)
                          // 128 * 128))
    if interpret is None:
        interpret = default_interpret()
    refuse_x64(interpret, ub.dtype, lb_sorted.dtype)
    # pad with -inf => c = 0 => only bin 0 (never used by suffix sums) grows
    ub_p = jnp.pad(ub, ((0, 0), (0, -p % bp)),
                   constant_values=-jnp.inf)[:, None, :]
    p_blocks = ub_p.shape[2] // bp
    n_bins = h + 1
    hist = pl.pallas_call(
        _ub_hist_kernel,
        grid=(b, p_blocks),
        in_specs=[
            pl.BlockSpec((None, 1, bp), lambda bb, i: (bb, 0, i)),  # ub
            pl.BlockSpec((None, h, 1), lambda bb, i: (bb, 0, 0)),   # lb
        ],
        out_specs=pl.BlockSpec((None, n_bins, 1), lambda bb, i: (bb, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_bins, 1), jnp.int32),
        interpret=interpret,
    )(ub_p, lb_sorted[:, :, None])
    return hist[:, :, 0]


def ub_histogram_pallas(ub, lb_sorted, *, bp: int | None = None,
                        interpret: bool | None = None):
    """One-problem :func:`ub_histogram_batch_pallas`: ub (p,), lb_sorted
    (h,) -> hist (h+1,)."""
    return ub_histogram_batch_pallas(ub[None], lb_sorted[None], bp=bp,
                                     interpret=interpret)[0]
