"""CPU rehearsal of every cell, end to end at a tiny size: traffic,
set-up, window, trace reduction and the comparison that decides
``correct``; and the faults and the control that comparison must catch.

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

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = ("sim1m.scalar", "logit256k.scalar", "sim1m.coalesced8")
DEVICE_METRICS = ("device_idle_share", "screen_roofline",
                  "screen_ms_per_solution", "cm_ms_per_solution")
N = 64
# the configurations' gap target (16x the float32 floor) at n = 64
TINY = {"design": {"n": N, "p": 1024},
        "solver": {"eps": 2.0 ** -7 * N / 1024, "screen_backend": "pallas",
                   "inner_backend": "pallas"}}

SCRIPT = r"""
import json, pathlib, sys
sys.path.insert(0, @ROOT@)
import numpy as np
from bench import cell, control

TINY = json.loads(@TINY@)
SEED = 2**31 + 7          # more than 32 signed bits hold


def emit(name, out, **extra):
    print(json.dumps({"scenario": name, "out": out, **extra}), flush=True)


def run(name, trace=False, keep=None):
    return cell.run(name, SEED, 1.0, trace, require_chip=False,
                    overrides=TINY, keep=keep)


for name, trace in (("sim1m.scalar", True), ("logit256k.scalar", False),
                    ("sim1m.coalesced8", True)):
    keep = {}
    emit(name, run(name, trace=trace, keep=keep))
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
    script = SCRIPT.replace("@ROOT@", repr(str(ROOT))).replace(
        "@TINY@", repr(json.dumps(TINY)))
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


def test_benchmark_lists_every_rehearsed_cell():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [w["name"] for w in bm["workloads"]] == list(CELLS)


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
