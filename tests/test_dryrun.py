"""Integration test of the multi-pod dry-run pipeline (subprocess: needs the
512 placeholder devices, which must not leak into this test process)."""
import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("multi", [False, True], ids=["1pod", "2pod"])
def test_dryrun_whisper_prefill(tmp_path, multi):
    out = tmp_path / "rec.jsonl"
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", "whisper_tiny", "--shape", "prefill_32k",
           "--out", str(out)]
    if multi:
        cmd.append("--multi-pod")
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=REPO_ROOT, timeout=1200)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    recs = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(recs) == 1 and recs[0]["status"] == "ok"
    rec = recs[0]
    assert rec["n_chips"] == (512 if multi else 256)
    # corrected costs present and physically sane
    assert rec["scan_corrected"]
    assert rec["flops"] > rec["raw_flops"] > 0
    assert rec["dominant"] in ("compute", "memory", "collective")
    assert rec["collective_bytes"] > 0      # TP really communicates
    assert 0 < rec["useful_flops_frac"] < 1.5


def test_saif_screen_row(tmp_path):
    out = tmp_path / "rec.jsonl"
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-m", "repro.launch.dryrun",
                        "--saif-screen", "--out", str(out)],
                       capture_output=True, text=True, env=env,
                       cwd=REPO_ROOT, timeout=1200)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.loads(out.read_text().splitlines()[0])
    assert rec["status"] == "ok"
    # the screening collective is tiny by design (the paper's key property:
    # O(devs * h) wire bytes, not O(p))
    assert rec["collective_s"] < 0.01 * rec["memory_s"]
