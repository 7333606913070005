"""Peaks of each device, and the operations and bytes of each kernel,
from the shapes of its call.

The least time of a call is max(bytes / HBM bandwidth, operations / peak
rate); its roofline share is that over the time the trace gives it. The
bytes counted are those the algorithm cannot avoid, so a share cannot
pass 100% unless the kernel's time leaves out part of its work.
"""
from __future__ import annotations

import json
import pathlib
import re
from typing import List, Optional, Tuple

HERE = pathlib.Path(__file__).resolve().parent
_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|pred|s8|f64)\[([0-9,]*)\]")
_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "pred": 1,
          "s8": 1, "f64": 8}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``; an unknown device
    is an error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of every array type in an HLO instruction's text."""
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def screen_scan(b: int, n: int, p: int, itemsize: int = 4
                ) -> Tuple[float, float]:
    """(operations, bytes) of one fused screening scan of B problems over
    an (n, p) design: the design read once for the fleet, each problem's
    dual centre, column norms and exclusion mask read, and its score,
    upper and lower bound written (per-tile winners are p/512-sized and
    left out). Operations: a multiply-add per design entry and problem,
    and six per column and problem to form the bounds."""
    ops = 2.0 * b * n * p + 6.0 * b * p
    nbytes = itemsize * (n * p + b * n + 5.0 * b * p)
    return ops, nbytes


def screen_call_shape(name: str) -> Optional[Tuple[int, int, int, int]]:
    """(B, n, p, itemsize) of a ``screen_fused_batch_pallas`` call, read
    from its HLO text: the first result is the (B, 1, p) score and the
    one two-dimensional operand with p columns is the design."""
    head, _, rest = name.partition(" = ")
    sh = shapes(rest)
    if not sh:
        return None
    dt, first = sh[0]
    if len(first) != 3 or first[1] != 1:
        return None
    b, _, p = first
    design = [d for dt_, d in sh[1:] if len(d) == 2 and d[1] == p]
    if not design:
        return None
    return b, design[0][0], p, _BYTES[dt]


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    return max(nbytes / peak["hbm_bytes_per_s"],
               ops / peak["bf16_flops_per_s"])
