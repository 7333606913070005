"""CPU rehearsal of every cell, end to end at a tiny size: traffic,
set-up, window, trace reduction and the comparison that decides
``correct``; and the faults and the control that comparison must catch.

The cells are the ``workloads`` of ``BENCHMARK.json``, read when the
tests are collected: a cell added there, with its files, is rehearsed
with no edit here.

The harness runs in a child process (``JAX_PLATFORMS=cpu``, x64 off, the
Pallas kernels in interpret mode), as the benchmark does; the scenarios
share one child so the engine compiles once.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
DEVICE_METRICS = ("device_idle_share", "screen_roofline",
                  "screen_ms_per_solution", "cm_ms_per_solution")
# one cell is rehearsed with the profiler off, so the untraced result line
# (the end-to-end metrics) is seen too; every other cell is traced
UNTRACED = ("logit256k.scalar",)
N, P = 64, 1024


def cells(root: pathlib.Path = ROOT) -> tuple:
    """The names of the benchmark's cells, in the order it lists them."""
    bm = json.loads((root / "BENCHMARK.json").read_text())
    return tuple(w["name"] for w in bm["workloads"])


CELLS = cells()


def tiny(config: dict) -> dict:
    """Overrides that shrink a configuration to n = N, p = P: its gap
    target scaled with n, as the configuration scales it (a multiple of
    the float32 floor, which grows with n), and the Pallas kernels forced
    so their bodies run in interpret mode."""
    eps = float(config["solver"]["eps"]) * N / int(config["design"]["n"])
    return {"design": {"n": N, "p": P},
            "solver": {"eps": eps, "screen_backend": "pallas",
                       "inner_backend": "pallas"}}


SCRIPT = r"""
import json, pathlib, sys
sys.path.insert(0, @ROOT@)
import numpy as np
from bench import cell, control

TINY, UNTRACED = json.loads(@ARGS@)   # {cell: overrides}, [cell]
SEED = 2**31 + 7          # more than 32 signed bits hold


def emit(name, out, **extra):
    print(json.dumps({"scenario": name, "out": out, **extra}), flush=True)


def run(name, trace=False, keep=None):
    return cell.run(name, SEED, 1.0, trace, require_chip=False,
                    overrides=TINY[name], keep=keep)


for name in TINY:
    keep = {}
    emit(name, run(name, trace=name not in UNTRACED, keep=keep))
    ok, checks = control.judge_control(keep)
    emit("control." + name, None, control_correct=ok, control=checks)

# faults, planted where the served answer is produced
import repro.core.server as server
unit_view = server._unit_view


def flipped(value, i):
    v = unit_view(value, i)
    beta = np.array(v.beta)
    j = int(np.argmax(np.abs(beta)))
    beta[j] = 0.0                     # a support entry switched off
    return v._replace(beta=beta)


server._unit_view = flipped
emit("fault.flip", run("sim1m.scalar"))
emit("fault.flip.logit", run("logit256k.scalar"))


def swapped(value, i):
    b = np.shape(value.beta)[0]
    return unit_view(value, (i + 1) % b)   # a rider gets another's answer


server._unit_view = swapped
emit("fault.swap", run("sim1m.coalesced8"))


def half(value, i):
    b = np.shape(value.beta)[0]
    return unit_view(value, i % max(b // 2, 1))   # half the fleet left out


server._unit_view = half
emit("fault.half", run("sim1m.coalesced8"))
server._unit_view = unit_view
"""


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    cache = tmp_path_factory.mktemp("jax_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("JAX_ENABLE_X64", None)
    args = json.dumps([{c: tiny(cell.cell_spec(c).config) for c in CELLS},
                       list(UNTRACED)])
    script = SCRIPT.replace("@ROOT@", repr(str(ROOT))).replace(
        "@ARGS@", repr(args))
    p = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = {}
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            d = json.loads(line)
            out[d["scenario"]] = d
    return out


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal_is_correct(rehearsal, name):
    out = rehearsal[name]["out"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", CELLS)
def test_cpu_rehearsal_reports_no_device_metric(rehearsal, name):
    out = rehearsal[name]["out"]
    assert not set(DEVICE_METRICS) & set(out["metrics"])
    assert "breakdown" not in out
    assert "busy_s" not in out["device"]


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_reports_what_the_cpu_can_read(rehearsal, name):
    """Untraced: every end-to-end metric of the cell. Traced: every
    per-layer metric of the cell that the program counts, as the chip's
    traced run reports it."""
    spec = cell.cell_spec(name)
    m = rehearsal[name]["out"]["metrics"]
    if name in UNTRACED:
        assert set(m) == {e["name"] for e in spec.end_to_end}
    else:
        counted = {e["name"] for e in spec.per_layer
                   if e["source"] == "program_counter"}
        assert counted and counted <= set(m)


def test_traced_rehearsal_reads_program_counters(rehearsal):
    m = rehearsal["sim1m.scalar"]["out"]["metrics"]
    assert m["compiles_in_window"]["value"] == 0.0
    assert m["outer_steps_per_solution"]["value"] >= 1.0
    c = rehearsal["sim1m.coalesced8"]["out"]["metrics"]
    assert c["coalesced_share"]["value"] > 50.0


def test_untraced_rehearsal_reads_end_to_end(rehearsal):
    m = rehearsal["logit256k.scalar"]["out"]["metrics"]
    assert set(m) == {"solutions_per_s", "latency_p50_s", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())


@pytest.mark.parametrize("fault,number", [("fault.flip", "kkt_rel"),
                                          ("fault.flip.logit", "kkt_rel"),
                                          ("fault.swap", "kkt_rel"),
                                          ("fault.half", "kkt_rel")])
def test_planted_fault_is_not_correct(rehearsal, fault, number):
    out = rehearsal[fault]["out"]
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"]


def test_bfloat16_control_fails_the_comparison(rehearsal):
    d = rehearsal["control.sim1m.scalar"]
    assert d["control_correct"] is False
    assert any(d["control"][k]["value"] > d["control"][k]["limit"]
               for k in ("kkt_rel", "coef_err"))


@pytest.mark.parametrize("name", CELLS)
def test_control_of_each_cell_is_judged_not_correct(rehearsal, name):
    d = rehearsal["control." + name]
    assert d["control_correct"] is False
    failed = [k for k, c in d["control"].items() if c["value"] > c["limit"]]
    assert failed and set(failed) <= {"kkt_rel", "coef_err"}


@pytest.mark.parametrize("name", CELLS)
def test_workload_resolves_by_files_alone(name):
    """Its configuration, traffic, kind, loop, responses, design, limits
    and the reader of every metric that applies to it are found by the
    names ``BENCHMARK.json`` and the data files give."""
    import bench
    from bench import correct, traffic
    spec = cell.cell_spec(name)
    assert bench.find("designs", spec.config["design"]["generator"])
    kind = traffic.kind(spec.mix)
    assert callable(kind.client) and callable(kind.warmup)
    assert callable(kind.answers)
    assert callable(traffic.loop(spec.mix).drive)
    assert callable(bench.find("responses", spec.mix["responses"]["kind"])
                    .make)
    assert set(spec.limits) == set(correct.NUMBERS)
    assert spec.end_to_end and spec.per_layer
    for m in spec.end_to_end + spec.per_layer:
        assert callable(bench.find("metrics", m["name"]).read)


ADDED = {"name": "logit256k.lam80", "config": "logit-n1k-p256k",
         "traffic": "closed1-pool4-lam80-50-30", "chips": 1,
         "why": "a cell made of files that are already there"}

ADD_SCRIPT = r"""
import json, pathlib, sys
root = pathlib.Path.cwd()
sys.path.insert(0, str(root))
sys.path.insert(0, str(root / "bench" / "tests"))
from bench import cell
import test_bench_rehearsal as rehearsal
name = sys.argv[1]
spec = cell.cell_spec(name, root)
assert name in rehearsal.cells(root), rehearsal.cells(root)
out = cell.run(name, 3, 0.5, False, root=root, require_chip=False,
               overrides=rehearsal.tiny(spec.config))
print(json.dumps({"bench": cell.__file__, "config": spec.config["name"],
                  "traffic": spec.mix["kind"],
                  "per_layer": [m["name"] for m in spec.per_layer],
                  "out": out}))
"""


def test_a_cell_added_by_files_alone_is_found_and_rehearsed(tmp_path):
    """Copy the benchmark, add one workload from a configuration and a
    traffic mix that are there (new entries, and a limits file of the
    new cell's name), and edit nothing: ``cell.cell_spec`` and the
    rehearsal's list of cells take it up, and it runs correct."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    name = ADDED["name"]
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    assert name not in cells(tmp_path)
    bm["workloads"].append(ADDED)
    for m in bm["per_layer"]:
        if "logit256k.scalar" in m.get("workloads", ()):
            m["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm, indent=1))
    shutil.copy(tmp_path / "bench" / "limits" / "logit256k.scalar.json",
                tmp_path / "bench" / "limits" / f"{name}.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("JAX_ENABLE_X64", None)
    p = subprocess.run([sys.executable, "-c", ADD_SCRIPT, name],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.splitlines()[-1])
    assert pathlib.Path(got["bench"]).is_relative_to(tmp_path.resolve())
    assert got["config"] == "logit-n1k-p256k" and got["traffic"] == "scalar"
    assert "outer_steps_per_solution" in got["per_layer"]
    out = got["out"]
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"solutions_per_s", "latency_p50_s",
                                   "setup_s"}


def _cli(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sim1m.scalar",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _cli(ROOT, env)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "no result" in p.stderr


def test_cli_with_only_the_benchmark_files_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
