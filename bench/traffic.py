"""Traffic: what a mix asks for, found by name.

A mix is a data file, ``bench/traffic/<name>.json``. Its ``kind`` names
the generator that reads it, ``bench/kinds/<kind>.py``, and its ``loop``
names the module that offers the load, ``bench/loops/<loop>.py``. A new
mix of a kind that exists is one data file; a new kind of request or a
new way of offering load is one file of code beside the others, and no
file that is there changes.

A kind module provides

* ``validate(mix)`` -- raises on parameters it cannot serve;
* ``warmup(mix, ctx)`` -- the batches of ``Submission`` sent in set-up,
  each batch submitted together and awaited, covering every program the
  mix reaches (so nothing compiles in the window);
* ``client(mix, ctx, seed, c)`` -- client ``c``'s endless sequence of
  ``Submission``; the same ``seed`` gives the same sequence;
* ``answers(result)`` -- the (coefficients, verdict) of each answer a
  served result holds, in the order of the submission's ``asked``.

A loop module provides ``drive(mix, seed, server, streams, seconds,
opened, span)``: it offers the streams' submissions to the server for
``seconds`` and returns the window's records (see
``bench/loops/closed.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Tuple

import numpy as np

import bench


@dataclasses.dataclass
class Submission:
    problem: Any
    request: Any
    asked: List[Tuple[int, float]]   # (response index, lam) per answer


@dataclasses.dataclass
class Context:
    """What a kind builds its submissions from."""
    api: Any                          # the system under test (``repro``)
    X: Any                            # the design, on the device
    Y: Any                            # (responses, n) on the host
    loss: str
    problems: List[Any]               # one Problem per response
    lam_max: List[float]              # per response
    max_batch: int                    # the server's microbatch limit


def kind(mix: dict):
    mod = bench.find("kinds", mix["kind"])
    mod.validate(mix)
    return mod


def loop(mix: dict):
    return bench.find("loops", mix["loop"])


def rng(seed: int, *salt: int) -> np.random.Generator:
    """A generator from any whole number below 2**64 and a salt."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *salt])
