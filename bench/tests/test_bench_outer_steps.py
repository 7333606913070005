"""The reader of outer steps (``bench/metrics/outer_steps_per_solution.py``)
on stand-in results of both shapes the server returns, a Scalar's and a
path's."""
import types

import numpy as np
import pytest

import bench


def _scalar(n_outer):
    return types.SimpleNamespace(
        value=types.SimpleNamespace(beta=None, n_outer=np.int32(n_outer)))


def _path(*n_outer):
    pts = [types.SimpleNamespace(n_outer=np.int32(k)) for k in n_outer]
    return types.SimpleNamespace(
        value=types.SimpleNamespace(betas=[None] * len(pts), results=pts))


def _reading(results):
    return types.SimpleNamespace(results=results)


def test_outer_steps_of_scalars_read_as_before():
    read = bench.find("metrics", "outer_steps_per_solution").read
    res = [_scalar(k) for k in (12, 30, 41)]
    # the reader as it stood: the mean of each result's n_outer
    before = float(np.mean([int(np.asarray(v.value.n_outer)) for v in res]))
    assert read(_reading(res)) == before == pytest.approx(83 / 3)
    assert read(_reading([])) is None


def test_outer_steps_of_a_path_count_each_point():
    read = bench.find("metrics", "outer_steps_per_solution").read
    assert read(_reading([_path(9, 3, 3, 1)])) == 4.0
    # a path's points and a Scalar weigh one solution each
    assert read(_reading([_path(9, 3), _scalar(6)])) == 6.0
