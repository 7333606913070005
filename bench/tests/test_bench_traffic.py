"""Traffic is found by name: every mix names its kind and loop, the kind
module reads the mix's parameters, and a name with no file is refused."""
import itertools
import json
import pathlib

import pytest

import bench
from bench import traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", MIXES)
def test_every_mix_finds_its_kind_loop_and_responses(name):
    mix = _mix(name)
    kind = traffic.kind(mix)
    assert callable(kind.client) and callable(kind.warmup)
    assert callable(traffic.loop(mix).drive)
    assert callable(bench.find("responses", mix["responses"]["kind"]).make)


@pytest.mark.parametrize("folder", ["kinds", "loops", "responses",
                                    "designs", "metrics"])
def test_a_name_with_no_file_is_refused(folder):
    with pytest.raises(FileNotFoundError):
        bench.find(folder, "no_such_module")


def test_every_metric_of_the_benchmark_has_a_reader():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(bench.find("metrics", m["name"]).read)


def test_pool_windows_hold_each_fraction_once():
    mix = _mix("closed1-pool4-lam80-50-30")
    kind = traffic.kind(mix)
    seq = list(itertools.islice(kind.pairs(mix, 2**40 + 3, 0), 48))
    fr = sorted(mix["lam_fracs"])
    for i in range(0, 48, 3):
        assert sorted(f for _, f in seq[i:i + 3]) == fr
    # every (response, fraction) pair once per cycle of 12
    for i in range(0, 48, 12):
        assert len(set(seq[i:i + 12])) == 12
    assert seq == list(itertools.islice(kind.pairs(mix, 2**40 + 3, 0), 48))
    assert seq != list(itertools.islice(kind.pairs(mix, 2**40 + 4, 0), 48))


def test_own_mix_warms_every_fleet_size_it_can_form():
    mix = _mix("closed8-own-lam50")
    kind = traffic.kind(mix)
    sizes = sorted({len(b) for b in kind.warmup_pairs(mix, 8)})
    assert sizes == [1, 2, 4, 8]
    seq = list(itertools.islice(kind.pairs(mix, 5, 3), 6))
    assert {r for r, _ in seq} == {3}


def test_a_mix_the_kind_cannot_serve_is_refused():
    mix = dict(_mix("closed8-own-lam50"), clients=4)
    with pytest.raises(ValueError):
        traffic.kind(mix)
