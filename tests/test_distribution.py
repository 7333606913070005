"""Distribution substrate tests: shardings, optimizer, compression,
checkpoint/resume, fault tolerance. (Single-CPU-device mesh; the 512-device
production mesh is exercised by launch/dryrun.py in its own process.)
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.launch.mesh import make_host_mesh
from repro.launch.shardings import (batch_spec, param_shardings, zero1_spec,
                                    param_spec)
from repro.models import lm
from repro.optim import adamw, compress
from repro.runtime.fault import (PreemptionGuard, StepFailed,
                                 StragglerMonitor, retry_step)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    """Shape-only stand-in so sharding rules can be tested against the
    production mesh geometry without 512 devices."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


PROD = FakeMesh({"data": 16, "model": 16})
PROD_MP = FakeMesh({"pod": 2, "data": 16, "model": 16})


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh", [PROD, PROD_MP], ids=["1pod", "2pod"])
def test_param_specs_divisible(arch, mesh):
    """Every sharded dim must divide by its mesh axis — for all 10 archs."""
    cfg = get_config(arch)
    shapes = lm.param_shapes(cfg)

    def walk(path, node):
        if isinstance(node, tuple):
            spec = param_spec(path, node, cfg, mesh)
            for dim, axis in enumerate(spec):
                if axis is None:
                    continue
                axes = axis if isinstance(axis, tuple) else (axis,)
                total = int(np.prod([mesh.shape[a] for a in axes]))
                assert node[dim] % total == 0, (path, node, spec)
        else:
            for k, v in node.items():
                walk(path + (k,), v)
    walk((), shapes)


def test_zero1_spec_adds_data_axis():
    spec = zero1_spec(P(None, None, "model"), (32, 2560, 6912), PROD)
    assert spec[0] == "data"     # L=32 divisible by 16
    # already fully sharded -> unchanged
    spec2 = zero1_spec(P("data", None, "model"), (32, 2560, 6912), PROD)
    assert spec2 == P("data", None, "model")


def test_batch_spec_small_batch_replicates():
    assert batch_spec(PROD, 256) == P(("data",),)
    assert batch_spec(PROD, 1) == P(None,)


def test_adamw_descends_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=1, total_steps=200,
                            weight_decay=0.0, clip_norm=100.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw.init(params)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}          # d/dw ||w||^2
        params, state = adamw.update(grads, state, params, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.15


def test_adamw_clipping():
    cfg = adamw.AdamWConfig(clip_norm=1.0, lr=1.0, warmup_steps=0,
                            total_steps=10, weight_decay=0.0)
    g = {"w": jnp.full((4,), 100.0)}
    p = {"w": jnp.zeros(4)}
    st = adamw.init(p)
    p2, _ = adamw.update(g, st, p, cfg)
    # clipped step magnitude bounded by lr * 1/sqrt(vhat) ~ lr
    assert float(jnp.abs(p2["w"]).max()) < 2.0


def test_error_feedback_invariant():
    """sum(applied) + residual == sum(true gradients), exactly."""
    rng = np.random.default_rng(0)
    params = {"a": jnp.zeros((64,)), "b": jnp.zeros((8, 8))}
    ef = compress.init(params)
    applied_sum = jax.tree.map(lambda p: np.zeros(p.shape), params)
    true_sum = jax.tree.map(lambda p: np.zeros(p.shape), params)
    for _ in range(20):
        g = {"a": jnp.asarray(rng.normal(size=64)),
             "b": jnp.asarray(rng.normal(size=(8, 8)))}
        q, ef = compress.compress_tree(g, ef)
        deq = compress.decompress_tree(q)
        applied_sum = jax.tree.map(lambda s, d: s + np.asarray(d),
                                   applied_sum, deq)
        true_sum = jax.tree.map(lambda s, d: s + np.asarray(d), true_sum, g)
    for k in params:
        np.testing.assert_allclose(
            applied_sum[k] + np.asarray(ef.residual[k]), true_sum[k],
            rtol=1e-5, atol=1e-5)


def test_quantize_roundtrip_bounds():
    x = jnp.asarray(np.random.default_rng(1).normal(size=1000) * 5)
    q, s = compress.quantize(x)
    err = jnp.abs(compress.dequantize(q, s) - x)
    assert float(err.max()) <= float(s) * 0.5 + 1e-9


def test_retry_step_recovers_then_fails():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert retry_step(flaky, max_retries=2) == "ok"

    def broken():
        raise RuntimeError("persistent")

    with pytest.raises(StepFailed):
        retry_step(broken, max_retries=2)


def test_straggler_monitor_flags_outlier():
    mon = StragglerMonitor(factor=3.0, min_samples=3)
    for _ in range(5):
        mon.record(1.0)
    assert mon.record(10.0) is True
    assert mon.record(1.1) is False


def test_preemption_guard_flag():
    g = PreemptionGuard(install=False)
    assert not g.preempted
    g.trigger()
    assert g.preempted


def test_checkpoint_roundtrip(tmp_path):
    from repro.ckpt import checkpoint as ckpt
    tree = {"params": {"w": jnp.arange(6.0).reshape(2, 3),
                       "b": jnp.ones(3)},
            "step": jnp.asarray(7)}
    ckpt.save(str(tmp_path), 7, tree, extra={"cursor": 123})
    assert ckpt.latest_step(str(tmp_path)) == 7
    like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    restored, extra = ckpt.restore(str(tmp_path), 7, like)
    assert extra["cursor"] == 123
    np.testing.assert_array_equal(restored["params"]["w"],
                                  tree["params"]["w"])


def test_checkpoint_gc_keeps_latest(tmp_path):
    from repro.ckpt import checkpoint as ckpt
    tree = {"x": jnp.zeros(2)}
    for s in range(6):
        ckpt.save(str(tmp_path), s, tree)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 3 and ckpt.latest_step(str(tmp_path)) == 5


def test_train_resume_end_to_end(tmp_path):
    """Train 6 steps, kill, resume to 12: loss stream must equal an
    uninterrupted 12-step run (exact determinism incl. data cursor)."""
    env = dict(os.environ, PYTHONPATH="src")
    base = [sys.executable, "-m", "repro.launch.train", "--arch",
            "stablelm_3b", "--smoke", "--batch", "4", "--seq", "32",
            "--log-every", "1", "--lr", "1e-3"]
    r1 = subprocess.run(base + ["--steps", "6", "--ckpt-dir",
                                str(tmp_path / "a"), "--ckpt-every", "3"],
                        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = subprocess.run(base + ["--steps", "12", "--ckpt-dir",
                                str(tmp_path / "a"), "--ckpt-every", "3"],
                        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "[resume] restored step 6" in r2.stdout
    r3 = subprocess.run(base + ["--steps", "12", "--ckpt-dir",
                                str(tmp_path / "b"), "--ckpt-every", "100"],
                        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    losses_resumed = [l.split()[-1] for l in r2.stdout.splitlines()
                      if l.startswith("step ")]
    losses_straight = [l.split()[-1] for l in r3.stdout.splitlines()
                       if l.startswith("step ")]
    # compare the final overlapping steps
    assert losses_resumed[-3:] == losses_straight[-3:], (
        r2.stdout, r3.stdout)


def test_distributed_saif_subprocess_8dev():
    """SAIF with the shard_map screening scan on 8 host devices == serial."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_host_mesh
from repro.distributed.saif_sharded import saif_distributed
from repro.core import saif, SaifConfig
rng = np.random.default_rng(3)
n, p = 40, 500
X = rng.uniform(-10, 10, (n, p))
b = np.zeros(p); b[rng.choice(p, 50, replace=False)] = rng.uniform(-1, 1, 50)
y = X @ b + rng.normal(0, 1, n)
lam = 0.05 * float(np.max(np.abs(X.T @ y)))
mesh = make_host_mesh()
assert jax.device_count() == 8
with mesh:
    r1 = saif_distributed(X, y, lam, mesh, SaifConfig(eps=1e-8))
r2 = saif(X, y, lam, SaifConfig(eps=1e-8))
assert np.allclose(np.asarray(r1.beta), np.asarray(r2.beta), atol=1e-6)
print("DIST_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO_ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DIST_OK" in r.stdout


def test_distributed_saif_batch_subprocess_8dev():
    """The fleet engine on the batched shard_map collective (DESIGN.md §8):
    all B problems screened per wire round == B serial solves."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_host_mesh
from repro.distributed.saif_sharded import saif_batch_distributed
from repro.core import saif, SaifConfig
rng = np.random.default_rng(5)
n, p, B = 30, 240, 3
X = rng.uniform(-10, 10, (n, p))
Ys, lams = [], []
for i in range(B):
    w = np.zeros(p); w[rng.choice(p, 12, replace=False)] = rng.uniform(-1, 1, 12)
    y = X @ w + rng.normal(0, 1, n)
    Ys.append(y)
    lams.append((0.05 + 0.05 * i) * float(np.max(np.abs(X.T @ y))))
mesh = make_host_mesh()
assert jax.device_count() == 8
cfg = SaifConfig(eps=1e-8, inner_backend="gram")
with mesh:
    res = saif_batch_distributed(X, np.stack(Ys), jnp.asarray(lams), mesh, cfg)
for i in range(B):
    ref = saif(X, Ys[i], lams[i], cfg)
    assert np.array_equal(np.abs(np.asarray(res.beta[i])) > 1e-8,
                          np.abs(np.asarray(ref.beta)) > 1e-8)
    assert np.allclose(np.asarray(res.beta[i]), np.asarray(ref.beta),
                       atol=1e-6)
print("DIST_BATCH_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO_ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DIST_BATCH_OK" in r.stdout


def test_microbatch_equivalence():
    """Grad accumulation over microbatches == full-batch step (fp32)."""
    from repro.configs import smoke_config
    from repro.launch import steps as steps_lib
    from repro.models import init as model_init
    cfg = smoke_config("stablelm_3b").scaled(dtype="float32")
    params = model_init(jax.random.PRNGKey(0), cfg)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    opt = adamw.init(params)
    state = steps_lib.TrainState(params=params, opt=opt)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    s1, l1 = steps_lib.make_train_step(cfg, opt_cfg, microbatch=1)(state, batch)
    s4, l4 = steps_lib.make_train_step(cfg, opt_cfg, microbatch=4)(state, batch)
    assert abs(float(l1) - float(l4)) < 1e-5
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     s1.params, s4.params)
    assert max(jax.tree.leaves(d)) < 5e-5   # fp32 accumulation-order noise


@pytest.mark.parametrize("arch", ["dbrx_132b", "nemotron_4_15b"])
def test_fsdp_specs_divisible(arch):
    """FSDP adds a data-axis shard on some dim; divisibility must hold."""
    from repro.launch.shardings import fsdp_spec
    cfg = get_config(arch)
    shapes = lm.param_shapes(cfg)

    def walk(path, node):
        if isinstance(node, tuple):
            spec = fsdp_spec(param_spec(path, node, cfg, PROD), node, PROD)
            used = []
            for dim, axis in enumerate(spec):
                if axis is None:
                    continue
                axes = axis if isinstance(axis, tuple) else (axis,)
                for a in axes:
                    assert a not in used
                    used.append(a)
                total = int(np.prod([PROD.shape[a] for a in axes]))
                assert node[dim] % total == 0, (path, node, spec)
        else:
            for k, v in node.items():
                walk(path + (k,), v)
    walk((), shapes)
