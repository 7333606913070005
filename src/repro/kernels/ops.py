"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to compiled Mosaic on a TPU backend and interpreter
fallback everywhere else (this container is CPU-only; the Pallas interpreter
executes the kernel body in Python for correctness validation). Block shapes
default to the ``autotune_screen_blocks`` choice for the problem shape.
"""
from __future__ import annotations

import jax

from repro.kernels.cm.cm import (CM_VMEM_BUDGET_BYTES, cm_burst_pallas,
                                 cm_epochs_pallas, cm_vmem_ok)
from repro.kernels.cm.ref import cm_epochs_ref
from repro.kernels.fused.fused import (autotune_chain_block,
                                       chain_suffix_sums_pallas,
                                       chain_suffix_sums_ref)
from repro.kernels.screen.ref import (screen_fused_ref, screen_scores_ref,
                                      ub_histogram_ref)
from repro.kernels.screen.screen import (autotune_screen_blocks,
                                         default_interpret,
                                         screen_fused_pallas,
                                         screen_scores_pallas,
                                         ub_histogram_pallas)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def screen_scores(X, theta, col_norm, r, *, bn=None, bp=None,
                  interpret: bool | None = None):
    """SAIF screening scan: (score, ub, lb) per feature."""
    return screen_scores_pallas(X, theta, col_norm, r, bn=bn, bp=bp,
                                interpret=interpret)


def screen_fused(X, theta, col_norm, active, r, *, h, bn=None, bp=None,
                 interpret: bool | None = None):
    """Fused ADD-phase scan: masked (score, ub, lb) + tile top-h + tile max."""
    return screen_fused_pallas(X, theta, col_norm, active, r, h=h,
                               bn=bn, bp=bp, interpret=interpret)


def ub_histogram(ub, lb_sorted, *, bp=None, interpret: bool | None = None):
    """Violation-count histogram of ub against sorted candidate bounds."""
    return ub_histogram_pallas(ub, lb_sorted, bp=bp, interpret=interpret)


def cm_epochs(A, y, beta, col_sq, mask, lam, *, n_epochs=1,
              interpret: bool | None = None):
    """VMEM-resident cyclic CM sweeps (least squares)."""
    return cm_epochs_pallas(A, y, beta, col_sq, mask, lam,
                            n_epochs=n_epochs, interpret=interpret)


def cm_burst(A, y, beta, col_sq, mask, order, lam, n_epochs, count,
             pen=None, *, loss_name="least_squares",
             interpret: bool | None = None):
    """Fused CM burst + dual point + duality gap (general smooth losses)."""
    return cm_burst_pallas(A, y, beta, col_sq, mask, order, lam, n_epochs,
                           count, pen=pen, loss_name=loss_name,
                           interpret=interpret)


def chain_suffix_sums(X, *, bp=None, interpret: bool | None = None):
    """Chain fused-LASSO column transform (suffix sums of design columns)."""
    return chain_suffix_sums_pallas(X, bp=bp, interpret=interpret)


__all__ = ["screen_scores", "screen_fused", "ub_histogram", "cm_epochs",
           "cm_burst", "cm_burst_pallas", "cm_vmem_ok",
           "chain_suffix_sums", "chain_suffix_sums_pallas",
           "chain_suffix_sums_ref", "autotune_chain_block",
           "CM_VMEM_BUDGET_BYTES",
           "screen_scores_ref", "screen_fused_ref", "ub_histogram_ref",
           "cm_epochs_ref", "on_tpu", "autotune_screen_blocks",
           "default_interpret"]
