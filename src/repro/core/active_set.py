"""Fixed-capacity active-set state (TPU adaptation of the paper's A_t / R_t).

Matlab grows/shrinks arrays freely; XLA requires static shapes. The active set
is therefore a capacity-``k_max`` buffer of feature indices plus a validity
mask. ADD/DEL are masked scatters — the whole SAIF outer loop compiles to a
single XLA program with no retraces.

The buffer also maintains, incrementally, the *compact sweep order* the inner
solver consumes: ``order`` is a permutation of the slot ids with the ``count``
live slots listed first. The old solver re-derived this with a per-outer-step
``jnp.argsort(~mask)``; ADD/DEL now keep it up to date with an O(k_max)
stable partition (cumsum + scatter, no sort). Live slots keep their relative
order across mutations, so the CM sweep order is deterministic and
insertion-stable.

Overflow policy (documented in DESIGN.md §2): if an ADD wants more slots than
are free, we add as many as fit and set ``overflowed``; the non-jitted driver
in ``saif.py`` doubles capacity and re-enters (warm-started) — an explicit,
rare recompile event, analogous to elastic resharding.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class ActiveSet(NamedTuple):
    idx: jax.Array        # int32 (k_max,) feature ids; padding slots hold 0
    mask: jax.Array       # bool  (k_max,) slot validity
    beta: jax.Array       # f32   (k_max,) coefficients (0 on padding)
    in_active: jax.Array  # bool  (p,)     global membership mask
    overflowed: jax.Array  # bool scalar — an ADD ran out of slots
    order: jax.Array      # int32 (k_max,) slot permutation, live slots first
    count: jax.Array      # int32 scalar — number of live slots (= sum(mask))


def compact_order(order: jax.Array, mask: jax.Array) -> jax.Array:
    """Stable partition of ``order`` by slot liveness — live slots first.

    O(k_max) cumsum + scatter (no argsort): rank live and dead slots
    separately along the current sequence and scatter each slot to its new
    position. Relative order within both groups is preserved, so repeated
    calls are idempotent and mutations never reshuffle surviving slots.
    """
    live = jnp.take(mask, order)
    live_i = live.astype(jnp.int32)
    dead_i = 1 - live_i
    n_live = jnp.sum(live_i)
    rank_live = jnp.cumsum(live_i) - live_i
    rank_dead = jnp.cumsum(dead_i) - dead_i
    pos = jnp.where(live, rank_live, n_live + rank_dead)
    return jnp.zeros_like(order).at[pos].set(order)


def init_active_set(p: int, k_max: int, init_idx: jax.Array,
                    dtype=jnp.float32,
                    init_beta: jax.Array | None = None,
                    live_mask: jax.Array | None = None) -> ActiveSet:
    """Seed the buffer with ``init_idx``.

    Two modes:
      * static (live_mask=None): init_idx has shape (m,), m <= k_max.
      * slots  (live_mask given): init_idx/init_beta have shape (k_max,)
        and ``live_mask`` flags the live slots *in place*. The shape stays
        jit-static across warm-started lambda paths (no per-lambda
        recompiles, §Perf it. 1) and slot assignment is preserved exactly,
        which is what lets a warm-started path hand the Gram buffers of
        the previous lambda to the next solve without re-indexing
        (DESIGN.md §6).
    """
    if live_mask is None:
        m = init_idx.shape[0]
        idx = jnp.zeros((k_max,), jnp.int32).at[:m].set(
            init_idx.astype(jnp.int32))
        mask = jnp.zeros((k_max,), bool).at[:m].set(True)
        beta = jnp.zeros((k_max,), dtype)
        if init_beta is not None:
            beta = beta.at[:m].set(init_beta.astype(dtype))
        in_active = jnp.zeros((p,), bool).at[init_idx].set(True)
        order = jnp.arange(k_max, dtype=jnp.int32)
        n_live = jnp.asarray(m, jnp.int32)
    else:
        mask = jnp.asarray(live_mask, bool)
        idx = jnp.where(mask, init_idx.astype(jnp.int32), 0)
        beta = (jnp.where(mask, init_beta.astype(dtype), 0)
                if init_beta is not None else jnp.zeros((k_max,), dtype))
        in_active = jnp.zeros((p,), bool).at[
            jnp.where(mask, idx, p)].set(True, mode="drop")
        order = compact_order(jnp.arange(k_max, dtype=jnp.int32), mask)
        n_live = jnp.sum(mask).astype(jnp.int32)
    return ActiveSet(idx, mask, beta, in_active,
                     overflowed=jnp.asarray(False),
                     order=order, count=n_live)


def gather_columns(X: jax.Array, aset: ActiveSet) -> jax.Array:
    """(n, k_max) active design block; padded columns zeroed."""
    Xa = jnp.take(X, aset.idx, axis=1)
    return jnp.where(aset.mask[None, :], Xa, 0.0)


def pen_weights(aset: ActiveSet, unpen_idx: int, dtype=jnp.float32
                ) -> jax.Array:
    """(k_max,) per-slot l1 weight: 0 on the always-resident unpenalized
    slot (fused LASSO's ``b``, DESIGN.md §7), 1 everywhere else.

    ``unpen_idx`` is the *feature id* of the unpenalized coordinate (-1 =
    none); the weight follows the slot it currently occupies, so it is
    stable under ADD/DEL churn and capacity growth. Dead slots keep weight
    1 — their betas are pinned to 0 by the mask anyway.
    """
    if unpen_idx < 0:
        return jnp.ones_like(aset.beta, dtype)
    unpen_slot = aset.mask & (aset.idx == unpen_idx)
    return jnp.where(unpen_slot, 0.0, 1.0).astype(dtype)


def delete_features(aset: ActiveSet, drop_slot_mask: jax.Array) -> ActiveSet:
    """DEL: clear slots flagged in ``drop_slot_mask`` (bool (k_max,))."""
    p = aset.in_active.shape[0]
    drop = drop_slot_mask & aset.mask
    new_mask = aset.mask & ~drop
    new_beta = jnp.where(drop, 0.0, aset.beta)
    # Only dropped slots write (False) to the membership mask; padding and
    # surviving slots scatter out-of-bounds (mode="drop" discards them).
    write_idx = jnp.where(drop, aset.idx, p)
    new_in_active = aset.in_active.at[write_idx].set(False, mode="drop")
    return aset._replace(mask=new_mask, beta=new_beta,
                         in_active=new_in_active,
                         order=compact_order(aset.order, new_mask),
                         count=aset.count -
                         jnp.sum(drop).astype(jnp.int32))


def add_features(aset: ActiveSet, cand_idx: jax.Array,
                 cand_keep: jax.Array) -> ActiveSet:
    """ADD: scatter kept candidates into free slots.

    Args:
      cand_idx:  int32 (h,) candidate feature ids (descending score order).
      cand_keep: bool  (h,) which candidates to actually add.
    """
    return add_features_to_slots(aset, cand_idx, cand_keep)[0]


def add_features_to_slots(aset: ActiveSet, cand_idx: jax.Array,
                          cand_keep: jax.Array):
    """:func:`add_features`, also returning each candidate's slot: (h,)
    int32, ``k_max`` for a candidate that was not placed."""
    k_max = aset.mask.shape[0]
    h = cand_idx.shape[0]
    free = ~aset.mask                                   # (k_max,)
    # Rank free slots: free_rank[s] = number of free slots strictly before s.
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - free.astype(jnp.int32)
    n_free = jnp.sum(free.astype(jnp.int32))
    # Rank candidates among kept ones.
    keep = cand_keep
    cand_rank = jnp.cumsum(keep.astype(jnp.int32)) - keep.astype(jnp.int32)
    n_want = jnp.sum(keep.astype(jnp.int32))
    placed = keep & (cand_rank < n_free)

    # slot for candidate c: the (cand_rank[c])-th free slot. Build a map
    # free_order -> slot id via argsort of (free ? rank : big).
    big = jnp.asarray(k_max + 1, jnp.int32)
    order_key = jnp.where(free, free_rank, big)
    slot_of_rank = jnp.argsort(order_key)               # (k_max,)
    target_slot = slot_of_rank[jnp.clip(cand_rank, 0, k_max - 1)]
    target_slot = jnp.where(placed, target_slot, k_max)  # k_max => dropped

    new_idx = aset.idx.at[target_slot].set(cand_idx, mode="drop")
    new_mask = aset.mask.at[target_slot].set(True, mode="drop")
    new_beta = aset.beta.at[target_slot].set(0.0, mode="drop")
    p = aset.in_active.shape[0]
    new_in_active = aset.in_active.at[jnp.where(placed, cand_idx, p)].set(
        True, mode="drop")
    n_placed = jnp.sum(placed).astype(jnp.int32)
    return ActiveSet(new_idx, new_mask, new_beta, new_in_active,
                     overflowed=aset.overflowed | (n_want > n_free),
                     order=compact_order(aset.order, new_mask),
                     count=aset.count + n_placed), target_slot


def scatter_beta(aset: ActiveSet, p: int) -> jax.Array:
    """Inflate the compact beta back to (p,) (Algorithm 1 last line)."""
    out = jnp.zeros((p,), aset.beta.dtype)
    vals = jnp.where(aset.mask, aset.beta, 0.0)
    return out.at[jnp.where(aset.mask, aset.idx, p)].add(vals, mode="drop")


# --------------------------------------------------------------------------
# batched (problem-axis) views — the fleet engine (core/batch.py, DESIGN §8)
# --------------------------------------------------------------------------
# A *fleet* active set is the same ActiveSet NamedTuple with a leading
# problem axis B on every field: idx/mask/beta/order (B, k_max),
# in_active (B, p), overflowed/count (B,). All mutations are per-problem
# independent (cumsum/scatter over the slot axis only), so the batched
# forms are vmaps of the serial ones — each problem's slot arithmetic is
# bit-for-bit the serial computation, which is what the batch-parity
# acceptance (bitwise-identical active sets vs B serial solves) rests on.

def init_active_set_batch(p: int, k_max: int, init_idx: jax.Array,
                          dtype=jnp.float32,
                          init_beta: jax.Array | None = None,
                          live_mask: jax.Array | None = None) -> ActiveSet:
    """Batched slots-mode :func:`init_active_set` (leading problem axis)."""
    if init_beta is None:
        init_beta = jnp.zeros(init_idx.shape, dtype)
    if live_mask is None:
        raise ValueError("the batched init is slots-mode only: pass "
                         "(k_max,)-shaped per-problem buffers + live_mask")
    return jax.vmap(
        lambda i, b, m: init_active_set(p, k_max, i, dtype, b, m)
    )(init_idx, init_beta, live_mask)


def gather_columns_batch(X: jax.Array, aset: ActiveSet) -> jax.Array:
    """(B, n, k_max) active blocks from a shared (n, p) design."""
    return jax.vmap(gather_columns, in_axes=(None, 0))(X, aset)


delete_features_batch = jax.vmap(delete_features)
add_features_to_slots_batch = jax.vmap(add_features_to_slots)


def scatter_beta_batch(aset: ActiveSet, p: int) -> jax.Array:
    """(B, p) full solutions from a fleet active set."""
    return jax.vmap(scatter_beta, in_axes=(0, None))(aset, p)
