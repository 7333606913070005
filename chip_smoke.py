#!/usr/bin/env python3
"""On-chip smoke test of the certified LASSO service.

Drives the serving path once, through ``open_server().submit()``, on a TPU
at a size a user would deploy, and checks what comes back independently of
the engine. Run it from the repository root:

    python3 chip_smoke.py                 # one chip: the main path
    python3 chip_smoke.py --four-chips    # four chips: the sharded path

One chip (default). A float32 design of n=1024 samples x p=1,048,576
features (Sec 5.1.1 generator, 4 GiB resident on the chip) behind one
``open_server`` with the default ``auto`` backends:

  * three ``Scalar`` requests at 0.8, 0.5 and 0.3 lam_max, one 8-point
    ``Path`` and one ``Fleet`` of 8 responses on the shared design;
  * one logistic ``Scalar`` (the Pallas CM burst) and one chain-fused
    ``Scalar`` (the suffix-sum transform kernel) on smaller designs;
  * an n=1024, p=32,768 ``Scalar`` compared with the unscreened oracle
    ``solve_lasso_cm``.

Every served result must carry an ``ok`` verdict with no retry, no
degradation and no breaker or backend-fault event, each backend must
resolve to what the TPU policy names, and each result's full-width KKT
residual, recomputed here in plain ``jnp`` at ``highest`` matmul precision,
must be within the verdict's tolerance.

Four chips (``--four-chips``). The feature-sharded design (the same main
design) through ``Scalar(sharded=True)`` and ``Path(sharded=True)`` on a
4-device mesh, compared in the same process with the one-device solves of
the same requests; each device must hold p/4 columns of the design.

Precision: x64 stays off. Each design's response has unit rms (logistic
labels are +-1), so one gap target fits them all: ``GAP_FLOOR_MULT`` times
``duality.gap_precision_floor`` at that scale. The floor is one rounding
of the objective; a float32 gap over a few hundred active features
fluctuates at several times it (about 5x at 815 active on a v5e), and a
target inside that band never stops. Capacity is fixed at ``K_MAX`` active
slots, enough for the densest request here, so no solve pays the
re-solves of capacity doubling. The oracle reference runs on the host CPU
when JAX offers one, else on the chip.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero without that line when JAX finds no TPU or when
any phase fails. Data comes from ``--seed``. ``JAX_COMPILATION_CACHE_DIR``
is honoured; otherwise the compile cache is ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# oracle agreement (written-down tolerances): coefficients within
# COEF_RTOL * max|beta_ref|, and every coefficient above that level is
# nonzero in both solutions
COEF_RTOL = 1e-2
GAP_FLOOR_MULT = 16.0
K_MAX = 1024
LAM_FRACS = (0.8, 0.5, 0.3)
PATH_FRACS = tuple(np.geomspace(0.9, 0.5, 8))
FLEET_FRAC = 0.5
N_FLEET = 8


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (its own
    ``/jax/core/compile/*`` duration events)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


# ---------------------------------------------------------------------------
# data and independent checks
# ---------------------------------------------------------------------------

def unit_rms(y):
    return (y / np.sqrt(np.mean(np.square(y, dtype=np.float64)))).astype(
        np.float32)


def design(n, p, seed):
    """Sec 5.1.1 design in float32, response scaled to unit rms."""
    from benchmarks.data import simulation_data
    X, y, _ = simulation_data(n, p, seed, dtype=np.float32)
    return X, unit_rms(y)


def gap_target(n):
    """GAP_FLOOR_MULT x the float32 gap precision floor of a unit-rms
    response."""
    import jax.numpy as jnp
    from repro.core.duality import gap_precision_floor
    lam = jnp.float32(1.0)
    return GAP_FLOOR_MULT * float(
        gap_precision_floor(jnp.ones((n,), jnp.float32), lam))


def host_device():
    """The host CPU device when JAX offers one (the oracle's plain
    coordinate loop runs there), else None."""
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def lam_max(X, y, loss_name="least_squares"):
    import jax
    import jax.numpy as jnp
    from repro.core.duality import lambda_max
    from repro.core.losses import get_loss
    with jax.default_matmul_precision("highest"):
        return float(lambda_max(get_loss(loss_name), jnp.asarray(X),
                                jnp.asarray(y)))


def kkt_plain(X, y, beta, lam, loss_name="least_squares", unpen=None):
    """Max KKT violation of ``beta`` over every column of X, in plain jnp
    (independent of the engine's own certificate)."""
    import jax
    import jax.numpy as jnp
    X = jnp.asarray(X)
    y = jnp.asarray(y, X.dtype)
    beta = jnp.asarray(np.asarray(beta), X.dtype)
    with jax.default_matmul_precision("highest"):
        z = X @ beta
        if loss_name == "least_squares":
            g = z - y
        else:
            g = -y * jax.nn.sigmoid(-y * z)
        c = X.T @ g
    lam_i = jnp.full_like(c, lam)
    if unpen is not None:
        lam_i = lam_i.at[unpen].set(0.0)
    viol = jnp.where(beta != 0, jnp.abs(c + lam_i * jnp.sign(beta)),
                     jnp.maximum(jnp.abs(c) - lam_i, 0.0))
    return float(jnp.max(viol))


def agree(tag, beta, beta_ref):
    """Support and coefficient agreement within COEF_RTOL."""
    beta, beta_ref = np.asarray(beta), np.asarray(beta_ref)
    tol = COEF_RTOL * float(np.max(np.abs(beta_ref)))
    diff = float(np.max(np.abs(beta - beta_ref)))
    miss = np.flatnonzero((np.abs(beta_ref) > tol) & (beta == 0))
    extra = np.flatnonzero((np.abs(beta) > tol) & (beta_ref == 0))
    print(f"[{tag}] nnz={int(np.sum(beta != 0))} "
          f"nnz_ref={int(np.sum(beta_ref != 0))} max|diff|={diff:.6g} "
          f"tol={tol:.6g} missing={miss.size} extra={extra.size}",
          flush=True)
    check(diff <= tol and miss.size == 0 and extra.size == 0,
          f"{tag}: disagrees with the reference beyond the tolerance")


def verdict_ok(tag, out):
    v = out.verdict
    bad = [e for e in v.events if e == "backend_fault"
           or e.startswith("breaker_open") or e.startswith("degraded")]
    print(f"[{tag}] verdict ok={v.ok} gap={v.gap:.6g} "
          f"kkt={v.kkt_residual:.6g} tol={v.kkt_tol:.6g} "
          f"retries={v.retries} degraded={v.degraded} "
          f"events={list(v.events)}", flush=True)
    check(v.ok, f"{tag}: verdict is not ok")
    check(not v.degraded, f"{tag}: degraded verdict")
    check(v.retries == 0, f"{tag}: {v.retries} retries")
    check(not bad, f"{tag}: fault events {bad}")


def trace_tail(tag, res, steps=6):
    """The engine's last outer steps (active-set size, gap, screen
    survivors) — what a solve that ran out of outer steps was doing."""
    t = int(res.n_outer)
    lo = max(t - steps, 0)
    rows = zip(range(lo, t), np.asarray(res.trace_n_active)[lo:t],
               np.asarray(res.trace_gap)[lo:t],
               np.asarray(res.trace_survivors)[lo:t])
    print(f"[{tag}] last steps (t, n_active, gap, survivors): "
          + " ".join(f"({i},{int(a)},{g:.3g},{int(s)})"
                     for i, a, g, s in rows), flush=True)


def served(tag, clock, server, problem, request, timeout=300.0):
    c0 = clock.seconds
    t0 = time.perf_counter()
    out = server.submit(problem, request).result(timeout=timeout)
    dt = time.perf_counter() - t0
    verdict_ok(tag, out)
    print(f"[{tag}] served in {dt:.3f} s "
          f"(compile {clock.seconds - c0:.3f} s)", flush=True)
    return out, dt


def kkt_checked(tag, X, y, beta, lam, loss_name="least_squares",
                unpen=None):
    from repro.core.serving import ServingConfig
    ser = ServingConfig()
    tol = max(ser.kkt_rtol * lam, ser.kkt_atol)
    r = kkt_plain(X, y, beta, lam, loss_name, unpen)
    print(f"[{tag}] full-width KKT {r:.6g} <= {tol:.6g}: {r <= tol}",
          flush=True)
    check(r <= tol, f"{tag}: full-width KKT residual {r:g} above {tol:g}")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_one_chip(args, clock):
    import jax
    import jax.numpy as jnp
    from repro import Fleet, Path, Problem, Scalar, open_server
    from repro.core.api import fused
    from repro.core.cm import solve_lasso_cm
    from repro.core.inner_backend import resolve_inner_backend
    from repro.core.losses import get_loss
    from repro.core.saif import SaifConfig
    from repro.core.screen_backend import (resolve_backend,
                                           resolve_batch_screen)
    from repro.core.serving import ServingConfig

    on_tpu = jax.default_backend() == "tpu"
    n, p = args.n, args.p
    eps = gap_target(n)
    cfg = SaifConfig(eps=eps, k_max=K_MAX)
    # no degradation ladder: a result that fails its certificate fails the
    # smoke at once (an unscreened p=1M oracle rung would outlast the run)
    server = open_server(solver=cfg, serving=ServingConfig(ladder=()))
    solve_s = 0.0
    backends = {}

    # ---- main design: Scalar x3, Path, Fleet on the shared X -------------
    t0 = time.perf_counter()
    X_host, y = design(n, p, args.seed)
    X = jax.device_put(X_host)
    del X_host
    lmax = lam_max(X, y)
    print(f"[main] dtype={X.dtype} eps={eps:.6g} n={n} p={p} "
          f"lam_max={lmax:.6g} setup_s={time.perf_counter() - t0:.3f}",
          flush=True)
    prob = Problem(X=X, y=y)
    k_used = 0
    for frac in LAM_FRACS:
        lam = frac * lmax
        out, dt = served(f"scalar {frac}", clock, server, prob,
                         Scalar(lam))
        solve_s += dt
        res = out.value
        k_used = max(k_used, int(np.shape(res.active_idx)[0]))
        print(f"[scalar {frac}] nnz={int(np.sum(np.asarray(res.beta) != 0))}"
              f" n_outer={int(res.n_outer)} k_max={k_used}", flush=True)
        trace_tail(f"scalar {frac}", res)
        kkt_checked(f"scalar {frac}", X, y, res.beta, lam)
    backends["screen"] = resolve_backend(cfg.screen_backend, X.dtype)
    backends["inner(least_squares)"] = resolve_inner_backend(
        cfg.inner_backend, "least_squares", n, k_used, X.dtype)

    lams = [f * lmax for f in PATH_FRACS]
    out, dt = served("path", clock, server, prob, Path(lams))
    solve_s += dt
    for lam, beta in zip(lams, out.value.betas):
        kkt_checked(f"path {lam / lmax:.3f}", X, y, beta, lam)

    rng = np.random.default_rng(args.seed + 7)
    Bs = np.zeros((N_FLEET, p), np.float32)
    for b in range(N_FLEET):
        idx = rng.choice(p, 64, replace=False)
        Bs[b, idx] = rng.uniform(-1, 1, 64)
    with jax.default_matmul_precision("highest"):
        Y = np.asarray(jnp.asarray(Bs) @ X.T)
    Y = np.stack([unit_rms(yb + rng.normal(0, 1, n) * np.std(yb))
                  for yb in Y])
    f_lams = [FLEET_FRAC * lam_max(X, yb) for yb in Y]
    out, dt = served("fleet", clock, server, prob,
                     Fleet(Y=Y, lams=f_lams))
    solve_s += dt
    for b in range(N_FLEET):
        kkt_checked(f"fleet {b}", X, Y[b], np.asarray(out.value.beta)[b],
                    f_lams[b])
    backends["screen(fleet)"] = resolve_batch_screen(
        cfg.screen_backend, b=N_FLEET, p=p, dtype=X.dtype)
    del X, prob

    # ---- logistic: the Pallas CM burst ------------------------------------
    from benchmarks.data import logistic_shaped
    Xl, yl = logistic_shaped(n, args.logistic_p, args.seed + 1,
                             dtype=np.float32)
    Xl = jax.device_put(Xl)
    lam = 0.5 * lam_max(Xl, yl, "logistic")
    print(f"[logistic] dtype={Xl.dtype} eps={eps:.6g} n={n} "
          f"p={args.logistic_p}", flush=True)
    out, dt = served("logistic", clock, server,
                     Problem(X=Xl, y=yl, loss="logistic"), Scalar(lam))
    solve_s += dt
    kkt_checked("logistic", Xl, yl, out.value.beta, lam, "logistic")
    backends["inner(logistic)"] = resolve_inner_backend(
        cfg.inner_backend, "logistic", n,
        int(np.shape(out.value.active_idx)[0]), Xl.dtype)
    del Xl

    # ---- chain-fused: the suffix-sum transform kernel ---------------------
    pf = args.fused_p
    rng = np.random.default_rng(args.seed + 2)
    Xf = rng.random((n, pf), dtype=np.float32) * 20 - 10
    steps = np.zeros(pf, np.float32)
    steps[rng.choice(pf, 12, replace=False)] = rng.uniform(-1, 1, 12)
    yf = unit_rms(Xf @ np.cumsum(steps) + rng.normal(0, 1, n))
    parent = np.arange(-1, pf - 1)
    # lam_max of the transformed problem: suffix sums of the columns,
    # null model at the unpenalized root's least-squares fit
    S = np.cumsum(Xf[:, ::-1], axis=1, dtype=np.float64)[:, ::-1]
    b0 = S[:, 0] @ yf / (S[:, 0] @ S[:, 0])
    lmax_f = float(np.max(np.abs(S[:, 1:].T @ (yf - b0 * S[:, 0]))))
    lam = 0.5 * lmax_f
    print(f"[fused] dtype={Xf.dtype} eps={eps:.6g} n={n} p={pf} "
          f"lam_max={lmax_f:.6g}", flush=True)
    out, dt = served("fused", clock, server,
                     Problem(X=Xf, y=yf, penalty=fused(parent)),
                     Scalar(lam))
    solve_s += dt
    beta_node = np.asarray(out.value[0], np.float64)
    beta_t = np.concatenate([[beta_node[0]], np.diff(beta_node)])
    kkt_checked("fused", S.astype(np.float32), yf, beta_t.astype(np.float32),
                lam, unpen=0)
    from repro.core.fused import build_schedule, build_tree
    from repro.core.screen_backend import mosaic_refuses
    chain = build_schedule(build_tree(parent)).is_chain
    backends["fused transform"] = ("pallas" if chain and on_tpu
                                   and not mosaic_refuses(Xf.dtype)
                                   else "scan")
    del S, Xf

    # ---- oracle agreement at n=1024, p=32,768 ------------------------------
    Xo_host, yo = design(n, args.oracle_p, args.seed + 3)
    Xo = jax.device_put(Xo_host)
    lam = 0.5 * lam_max(Xo, yo)
    print(f"[oracle] dtype={Xo.dtype} eps={eps:.6g} n={n} "
          f"p={args.oracle_p}", flush=True)
    out, dt = served("oracle", clock, server, Problem(X=Xo, y=yo),
                     Scalar(lam))
    solve_s += dt
    kkt_checked("oracle", Xo, yo, out.value.beta, lam)
    t0 = time.perf_counter()
    host = host_device()
    with jax.default_matmul_precision("highest"), \
            jax.default_device(host or jax.devices()[0]):
        ref = solve_lasso_cm(get_loss("least_squares"), jnp.asarray(Xo_host),
                             jnp.asarray(yo), lam, tol=eps,
                             max_epochs=args.oracle_epochs)
        ref = np.asarray(ref)
    del Xo_host
    print(f"[oracle] solve_lasso_cm on {(host or jax.devices()[0]).platform} "
          f"in {time.perf_counter() - t0:.3f} s", flush=True)
    agree("oracle", out.value.beta, ref)

    stats = server.stats()
    sess_screens = sorted({getattr(s.session, "screen_backend", None)
                           for s in server._lru.values()} - {None})
    server.close()
    check(stats.failed == 0, f"server reported {stats.failed} failures")
    if on_tpu:
        want = {"screen": "pallas", "screen(fleet)": "pallas",
                "inner(least_squares)": "pallas",
                "inner(logistic)": "pallas", "fused transform": "pallas"}
        check(backends == want and sess_screens == ["pallas"],
              f"resolved backends {backends} / sessions {sess_screens} "
              f"differ from the TPU policy {want}")
    print(f"[backends] {json.dumps(backends, sort_keys=True)} "
          f"sessions={sess_screens}", flush=True)
    return solve_s


# ---------------------------------------------------------------------------
# four chips: the feature-sharded path
# ---------------------------------------------------------------------------

def run_four_chips(args, clock):
    import jax
    from jax.sharding import Mesh
    from repro import Path, Problem, Scalar, open_server
    from repro.core.saif import SaifConfig
    from repro.core.serving import ServingConfig

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.asarray(devs), ("feature",))
    n, p = args.n, args.p
    eps = gap_target(n)
    server = open_server(solver=SaifConfig(eps=eps, k_max=K_MAX),
                         serving=ServingConfig(ladder=()), mesh=mesh)
    X_host, y = design(n, p, args.seed)
    X = jax.device_put(X_host, devs[0])
    del X_host
    lmax = lam_max(X, y)
    print(f"[sharded] dtype={X.dtype} eps={eps:.6g} n={n} p={p} "
          f"devices={len(devs)} lam_max={lmax:.6g}", flush=True)
    prob = Problem(X=X, y=y)
    solve_s = 0.0

    lam = 0.5 * lmax
    sh, dt = served("sharded scalar", clock, server, prob,
                    Scalar(lam, sharded=True))
    solve_s += dt
    one, dt = served("one-device scalar", clock, server, prob,
                     Scalar(lam))
    solve_s += dt
    kkt_checked("sharded scalar", X, y, sh.value.beta, lam)
    agree("sharded vs one-device scalar", sh.value.beta, one.value.beta)

    lams = [f * lmax for f in PATH_FRACS[:4]]
    shp, dt = served("sharded path", clock, server, prob,
                     Path(lams, sharded=True))
    solve_s += dt
    onep, dt = served("one-device path", clock, server, prob, Path(lams))
    solve_s += dt
    for lam_i, b_sh, b_one in zip(lams, shp.value.betas, onep.value.betas):
        tag = f"path {lam_i / lmax:.3f}"
        kkt_checked(f"sharded {tag}", X, y, b_sh, lam_i)
        agree(f"sharded vs one-device {tag}", b_sh, b_one)

    designs = [getattr(s.session, "_sharded", None)
               for s in server._lru.values()]
    designs = [d for d in designs if d is not None]
    check(len(designs) == 1, "no feature-sharded design was placed")
    Xs = designs[0].X
    p_pad = Xs.shape[1]
    shards = sorted((s.device.id, s.data.shape) for s in Xs.addressable_shards)
    print(f"[sharded] X shards {shards}", flush=True)
    check(len({d for d, _ in shards}) == 4
          and all(shape == (n, p_pad // 4) for _, shape in shards),
          f"design not split p/4 per device: {shards}")
    server.close()
    return solve_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the feature-sharded 4-device phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--p", type=int, default=1 << 20)
    ap.add_argument("--logistic-p", type=int, default=1 << 18)
    ap.add_argument("--fused-p", type=int, default=1 << 14)
    ap.add_argument("--oracle-p", type=int, default=1 << 15)
    ap.add_argument("--oracle-epochs", type=int, default=3000)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this check runs on the chip only", file=sys.stderr)
        return 2
    return run(args)


def run(args) -> int:
    """All phases in this process; prints the contract line on success."""
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    from repro.core.server import enable_compile_cache

    cache = enable_compile_cache()
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        check(not jax.config.jax_enable_x64, "x64 must stay off")
        solve_s = (run_four_chips(args, clock) if args.four_chips
                   else run_one_chip(args, clock))
    except Exception as e:      # noqa: BLE001 - any phase failure fails
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    print(f"[summary] wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.seconds:.3f} solve_s={solve_s:.3f} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')} "
          f"compile_cache={cache}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    if rc:
        # a failed phase may leave the server's worker mid-solve on the
        # chip; end the process without waiting for it
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)
    sys.exit(rc)
