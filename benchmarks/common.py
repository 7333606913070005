"""Shared benchmark utilities: data protocols matching the paper + timing."""
from __future__ import annotations

import time
from typing import Callable, Dict

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)   # paper-grade duality gaps

# the data protocols live in benchmarks/data.py (no jax import, no x64
# switch), so float32 chip runs can share them
from benchmarks.data import (breast_cancer_shaped,  # noqa: E402,F401
                             logistic_shaped, simulation_data)


def timed(fn: Callable, *, warmup: bool = True) -> Dict[str, float]:
    """Wall-time a solver call (after one warmup for jit compilation)."""
    if warmup:
        fn()
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(jax.tree.leaves(out)[0] if jax.tree.leaves(out)
                          else out)
    return {"seconds": time.perf_counter() - t0, "out": out}


def csv_row(name: str, us_per_call: float, derived: str = "") -> str:
    return f"{name},{us_per_call:.1f},{derived}"
