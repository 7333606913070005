"""Serving benchmark: hot-session request latency vs cold per-request
solves (ISSUE 5 acceptance — BENCH_serve.json).

The comparison is the session API's reason to exist. A server WITHOUT a
session answers each request from scratch: fresh process-equivalent state
(``jax.clear_caches()``), fresh preparation, fresh ``_saif_jit``
compilation, then the solve. A server WITH a session pays preparation
once at ``open_session`` and compilation once per static key, after
which every request runs at solve cost with device-resident warm
buffers.

Protocol (CI shape): R scalar requests cycling over a few lambdas inside
one pow2 h bucket (one static key — the honest serving regime: clients
ask for nearby lambdas far more often than for new shapes).

  * cold: per request, ``jax.clear_caches()`` + ``saif(X, y, lam)`` —
    prep + compile + solve every time;
  * hot: one ``open_session``; after a warmup pass over the distinct
    lambdas, the measured pass must add ZERO compilations (asserted via
    ``session.compile_stats()``).

Acceptance (asserted): hot-session latency >= 3x better than cold
per-request solves. On CPU CI the gap is dominated by the per-request
XLA compile (seconds) vs the warm solve (milliseconds), so the measured
ratio is typically 2-3 orders of magnitude; the 3x gate is deliberately
conservative — it survives a hypothetical persistent-compilation-cache
world where cold requests only re-pay preparation + dispatch.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import simulation_data

MIN_SPEEDUP = 3.0   # ISSUE 5 acceptance gate
N_REQUESTS = 6      # cold requests are expensive (a compile each)
# ISSUE 8 acceptance gates (the async server): coalesced microbatches
# must beat serially servicing the same stream through one hot
# ServingSession by >= 3x at zero engine compiles in the measured
# steady state; the seeded Poisson pass gates p99 and steady-state
# compiles across a heterogeneous shape mix. The coalesced stream runs
# parity="fast" (the lockstep fleet engine with a working-precision KKT
# certificate per member) — the bitwise fleet replays the serial float
# path step-for-step, which bounds its ceiling below the 3x gate by
# construction; fast parity is the serving configuration (DESIGN.md
# §11/§12) and every member is still individually certified.
MIN_COALESCED_SPEEDUP = 3.0
POISSON_REQUESTS = 32
POISSON_MEAN_GAP_S = 0.003   # seeded exponential inter-arrival mean
P99_BOUND_S = 2.0            # smoke bound on the reduced CI shape
# ISSUE 6 acceptance gate: the fault-tolerant runtime's verdict plumbing
# (admission + KKT certification + ladder bookkeeping) may cost the
# happy-path hot request at most 10% (+ an absolute slack for the
# certificate jit dispatch and CI timer noise)
MAX_VERDICT_OVERHEAD = 0.10
VERDICT_SLACK_S = 1.5e-3


def _problem(n, p, seed=0):
    import jax.numpy as jnp

    from repro.core import get_loss
    from repro.core.duality import lambda_max

    X, _, _ = simulation_data(n=n, p=p, seed=seed)
    rng = np.random.default_rng(seed + 1)
    w = np.zeros(p)
    w[rng.choice(p, 15, replace=False)] = rng.uniform(-1, 1, 15)
    y = X @ w + rng.normal(0, 1, n)
    lmax = float(lambda_max(get_loss("least_squares"),
                            jnp.asarray(X), jnp.asarray(y)))
    return X, y, lmax


def _block(res):
    jax.block_until_ready(jax.tree.leaves(res)[0])


def run(full: bool = False):
    from repro import Problem, SaifConfig, Scalar, open_session
    from repro.core import saif

    n, p = (100, 2000) if full else (50, 500)
    cfg = SaifConfig(eps=1e-6, inner_epochs=3, polish_factor=4)
    X, y, lmax = _problem(n, p)
    # the request stream: lambdas inside one h bucket (checked below by
    # the zero-new-compilations assertion), revisited round-robin
    fracs = [0.30, 0.28, 0.26, 0.29, 0.27, 0.25][:N_REQUESTS]
    lams = [f * lmax for f in fracs]

    # --- cold: per-request prep + compile + solve ------------------------
    t_cold = 0.0
    for lam in lams:
        jax.clear_caches()
        t0 = time.perf_counter()
        _block(saif(X, y, lam, cfg))
        t_cold += time.perf_counter() - t0
    cold_per_req = t_cold / len(lams)

    # --- hot: one session, measured pass after warmup --------------------
    jax.clear_caches()
    t0 = time.perf_counter()
    session = open_session(Problem(X=X, y=y), cfg)
    t_open = time.perf_counter() - t0
    # warmup: TWO passes over the distinct lambdas. The first pass may
    # grow the warm capacity mid-stream (a smaller lambda can bump the h
    # bucket), so a lambda served early can still map to a fresh static
    # key on its next visit; the second pass compiles any such residue —
    # after it, the key set is closed and the measured pass is pure
    # serving.
    for _ in range(2):
        for lam in lams:
            _block(session.solve(Scalar(lam, warm=True)))
    stats0 = session.compile_stats()
    t_hot = 0.0
    for lam in lams:                      # measured: the hot request loop
        t0 = time.perf_counter()
        _block(session.solve(Scalar(lam, warm=True)))
        t_hot += time.perf_counter() - t0
    hot_per_req = t_hot / len(lams)
    stats1 = session.compile_stats()
    hot_compiles = stats1.since_open - stats0.since_open
    assert hot_compiles == 0, (
        f"hot session recompiled {hot_compiles} times during the "
        f"measured pass (contract: one compilation per static key)")

    # --- served: the same hot stream through the fault-tolerant runtime --
    # (ISSUE 6): admission + retry wrapper + KKT certificate + verdict.
    # Warmup compiles the certificate jit (outside the engine caches);
    # the measured pass must stay within MAX_VERDICT_OVERHEAD of the
    # bare hot session AND keep the zero-new-engine-compiles contract.
    from repro.core.serving import open_serving
    srv = open_serving(Problem(X=X, y=y), cfg)
    for _ in range(2):
        for lam in lams:
            _block(srv.solve(Scalar(lam, warm=True)).value)
    sstats0, engine0 = srv.stats(), srv.compile_stats().total
    t_served = 0.0
    for lam in lams:
        t0 = time.perf_counter()
        out = srv.solve(Scalar(lam, warm=True))
        _block(out.value)
        t_served += time.perf_counter() - t0
        assert out.verdict.ok and not out.verdict.degraded
    served_per_req = t_served / len(lams)
    sstats1 = srv.stats()
    assert srv.compile_stats().total == engine0, (
        "verdict plumbing compiled new engine keys on the happy path")
    degraded_rate = (sstats1.degraded - sstats0.degraded) / len(lams)
    retry_count = sstats1.retries - sstats0.retries
    kkt_check_ms = (sstats1.kkt_check_ms - sstats0.kkt_check_ms) / len(lams)

    speedup = cold_per_req / max(hot_per_req, 1e-12)
    served_speedup = cold_per_req / max(served_per_req, 1e-12)
    row = {
        "n": n, "p": p, "requests": len(lams),
        "cold_s_per_req": round(cold_per_req, 4),
        "hot_s_per_req": round(hot_per_req, 6),
        "served_s_per_req": round(served_per_req, 6),
        "open_session_s": round(t_open, 4),
        "speedup": round(speedup, 1),
        "served_speedup": round(served_speedup, 1),
        "degraded_rate": degraded_rate,
        "retry_count": retry_count,
        "kkt_check_ms": round(kkt_check_ms, 3),
        "hot_pass_compilations": hot_compiles,
        "warm_compilations": stats0.since_open,
        "min_speedup": MIN_SPEEDUP,
        "max_verdict_overhead": MAX_VERDICT_OVERHEAD,
    }
    print(f"[serve] n={n} p={p} R={len(lams)} "
          f"cold={cold_per_req * 1e3:.0f}ms/req "
          f"hot={hot_per_req * 1e3:.1f}ms/req "
          f"served={served_per_req * 1e3:.1f}ms/req "
          f"(kkt {kkt_check_ms:.2f}ms, degraded {degraded_rate:.0%}, "
          f"retries {retry_count}) "
          f"speedup={speedup:.0f}x (gate {MIN_SPEEDUP}x, "
          f"hot-pass compiles={hot_compiles})")
    assert speedup >= MIN_SPEEDUP, (
        f"hot session reached only {speedup:.2f}x over cold per-request "
        f"solves (acceptance {MIN_SPEEDUP}x)")
    assert degraded_rate == 0.0 and retry_count == 0, (
        "the happy-path stream triggered the degradation ladder")
    budget = hot_per_req * (1.0 + MAX_VERDICT_OVERHEAD) + VERDICT_SLACK_S
    assert served_per_req <= budget, (
        f"verdict plumbing costs {served_per_req * 1e3:.2f}ms/req vs a "
        f"budget of {budget * 1e3:.2f}ms/req "
        f"({MAX_VERDICT_OVERHEAD:.0%} of the bare hot request + "
        f"{VERDICT_SLACK_S * 1e3:.1f}ms slack)")
    assert served_speedup >= MIN_SPEEDUP, (
        f"served hot stream reached only {served_speedup:.2f}x over cold "
        f"(acceptance {MIN_SPEEDUP}x)")

    # --- served fast-parity fleet (ISSUE 7): the relaxed-parity lockstep
    # engine with certified bf16 screening, behind the same fault-tolerant
    # runtime. Asserted: the request is served un-degraded, the verdict's
    # working-precision KKT certificate passes, and the verdict records
    # the execution-mode provenance (parity + screening precision).
    import dataclasses

    import jax.numpy as jnp

    from repro import Fleet
    from repro.core import get_loss
    from repro.core.duality import lambda_max

    B = 8
    rng = np.random.default_rng(11)
    Ys, flams = [], []
    loss = get_loss("least_squares")
    for _ in range(B):
        w = np.zeros(p)
        w[rng.choice(p, 15, replace=False)] = rng.uniform(-1, 1, 15)
        yb = X @ w + rng.normal(0, 1, n)
        Ys.append(yb)
        flams.append(0.5 * float(lambda_max(loss, jnp.asarray(X),
                                            jnp.asarray(yb))))
    Yf = np.stack(Ys)
    cfg_fast = dataclasses.replace(cfg, parity="fast",
                                   screen_dtype="bfloat16")
    srv_f = open_serving(Problem(X=X), cfg_fast)
    req = Fleet(Y=Yf, lams=np.asarray(flams))
    _block(srv_f.solve(req).value)                 # warm: one compilation
    fstats0 = srv_f.stats()
    t0 = time.perf_counter()
    fout = srv_f.solve(req)
    _block(fout.value)
    t_fleet = time.perf_counter() - t0
    fstats1 = srv_f.stats()
    v = fout.verdict
    assert v.ok and not v.degraded, (
        f"served fast fleet degraded (ok={v.ok}, degraded={v.degraded}, "
        f"rungs={v.rungs})")
    assert fstats1.degraded - fstats0.degraded == 0
    assert v.parity == "fast" and v.screen_dtype == "bfloat16", (
        f"verdict must record execution-mode provenance, got "
        f"parity={v.parity!r} screen_dtype={v.screen_dtype!r}")
    fleet_row = {
        "fleet_b": B, "n": n, "p": p,
        "parity": v.parity, "screen_dtype": v.screen_dtype,
        "served_fleet_s": round(t_fleet, 4),
        "served_fleet_ms_per_problem": round(t_fleet / B * 1e3, 3),
        "gap": float(v.gap), "kkt_residual": float(v.kkt_residual),
        "kkt_tol": float(v.kkt_tol),
        "degraded_rate": 0.0, "verdict_ok": True,
    }
    print(f"[serve] fleet B={B} n={n} p={p} parity={v.parity} "
          f"dtype={v.screen_dtype} served={t_fleet * 1e3:.1f}ms "
          f"({t_fleet / B * 1e3:.1f}ms/problem, kkt={v.kkt_residual:.2e} "
          f"<= tol {v.kkt_tol:.2e}, degraded 0%)")

    # --- ISSUE 8: the async server ---------------------------------------
    coalesce_row = _bench_coalesced(cfg)
    poisson_row = _bench_poisson(X, y, lmax, cfg, n, p)
    restart_row = _bench_restart(X, y, lmax, cfg, n, p)
    return [row, fleet_row, coalesce_row, poisson_row, restart_row]


def _serve_cfg(cfg):
    """The serving solver configuration: the relaxed-parity lockstep
    fleet engine (every member still ends with a working-precision KKT
    certificate in its verdict)."""
    import dataclasses
    return dataclasses.replace(cfg, parity="fast")


def _bench_coalesced(cfg):
    """Coalesced microbatch throughput vs one hot ServingSession
    serially draining the identical request stream.

    The stream is the ROADMAP's "millions of users" serving regime: R
    users over ONE shared design, each submitting a small personal
    problem (own response, own lambda). The serial baseline is the
    strongest single-request use of the PR 6/7 surface for that
    stream — ONE hot ServingSession on the shared design, one
    fleet-of-1 request per user — so the gate isolates exactly what
    the server adds: coalescing riders into max_batch-wide lockstep
    fleet solves that amortize the per-request dispatch + verdict
    cost across the batch. Small per-user problems are the honest
    operating point for that comparison: per-request overhead is
    size-independent, so it (not raw solver compute) dominates a
    production stream of small personalization solves. A second,
    weaker baseline (a hot per-user session per request) is reported
    as a column but not gated."""
    import jax.numpy as jnp

    from repro import Fleet, Problem, Scalar
    from repro.core import get_loss
    from repro.core.duality import lambda_max
    from repro.core.saif import saif_jit_compile_count
    from repro.core.server import open_server
    from repro.core.serving import open_serving

    cfg_srv = _serve_cfg(cfg)
    loss = get_loss("least_squares")
    n_u, p_u = 60, 96                 # the per-user problem shape
    rng = np.random.default_rng(23)
    X = rng.uniform(-10, 10, (n_u, p_u))
    Xj = jnp.asarray(X)
    users = []
    for r in range(POISSON_REQUESTS):
        w = np.zeros(p_u)
        w[rng.choice(p_u, 10, replace=False)] = rng.uniform(-1, 1, 10)
        yu = X @ w + rng.normal(0, 1, n_u)
        lam_u = (0.45 + 0.01 * (r % 8)) * float(
            lambda_max(loss, Xj, jnp.asarray(yu)))
        users.append((yu, lam_u))
    problems = [Problem(X=X, y=yu) for yu, _ in users]

    # gated baseline: one hot session on the shared design, one
    # fleet-of-1 request per user
    serial = open_serving(Problem(X=X), cfg_srv)

    def serial_pass():
        for yu, lam_u in users:
            out = serial.solve(Fleet(Y=yu, lams=lam_u))
            _block(out.value)
            assert out.verdict.ok

    serial_pass()                          # warm every static key
    c0 = saif_jit_compile_count()
    t_serial = 1e9
    for _ in range(2):                     # best-of-2: 1-core CI noise
        t0 = time.perf_counter()
        serial_pass()
        t_serial = min(t_serial, time.perf_counter() - t0)
    assert saif_jit_compile_count() == c0, (
        "serial baseline compiled during its measured pass")

    # informational baseline: a hot per-user ServingSession each (the
    # engine jit caches are process-wide, so these pay prep, not
    # compiles)
    def session_pass():
        for pb, (_, lam_u) in zip(problems, users):
            out = open_serving(pb, cfg_srv).solve(Scalar(lam_u))
            assert out.verdict.ok

    session_pass()
    t0 = time.perf_counter()
    session_pass()
    t_sessions = time.perf_counter() - t0

    # coalesced: the identical user stream through the async server
    server = open_server(max_batch=8, max_wait_ms=50.0, solver=cfg_srv)

    def pump():
        futs = [server.submit(pb, Scalar(lam_u))
                for pb, (_, lam_u) in zip(problems, users)]
        res = [f.result(timeout=600) for f in futs]
        assert all(r.verdict.ok for r in res)
        return res

    pump()                                 # warm the fleet bucket keys
    c1 = saif_jit_compile_count()
    t_coal = 1e9
    for _ in range(2):
        t0 = time.perf_counter()
        pump()
        t_coal = min(t_coal, time.perf_counter() - t0)
    steady_compiles = saif_jit_compile_count() - c1
    stats = server.stats()
    server.close()
    assert steady_compiles == 0, (
        f"coalesced steady state compiled {steady_compiles} new engine "
        f"keys (contract: zero)")
    speedup = t_serial / max(t_coal, 1e-12)
    row = {
        "mode": "coalesced", "n": n_u, "p": p_u,
        "requests": POISSON_REQUESTS,
        "serial_hot_s": round(t_serial, 4),
        "per_user_sessions_s": round(t_sessions, 4),
        "coalesced_s": round(t_coal, 4),
        "coalesced_speedup": round(speedup, 2),
        "coalesced_batches": stats.coalesced_batches,
        "steady_state_compiles": steady_compiles,
        "min_coalesced_speedup": MIN_COALESCED_SPEEDUP,
    }
    print(f"[serve] coalesced R={POISSON_REQUESTS} n={n_u} p={p_u} "
          f"serial={t_serial:.2f}s sessions={t_sessions:.2f}s "
          f"coalesced={t_coal:.2f}s "
          f"speedup={speedup:.1f}x (gate {MIN_COALESCED_SPEEDUP}x, "
          f"steady compiles={steady_compiles})")
    assert speedup >= MIN_COALESCED_SPEEDUP, (
        f"coalesced microbatching reached only {speedup:.2f}x over the "
        f"serial hot stream (acceptance {MIN_COALESCED_SPEEDUP}x)")
    return row


def _bench_poisson(X, y, lmax, cfg, n, p):
    """Seeded Poisson-arrival load over a heterogeneous shape mix:
    p50/p99 latency and req/s columns, zero steady-state compiles."""
    from repro import Problem, Scalar
    from repro.core.saif import saif_jit_compile_count
    from repro.core.server import open_server

    cfg_srv = _serve_cfg(cfg)
    rng = np.random.default_rng(7)
    # two shapes -> two compile buckets -> the heterogeneous mix
    X2, y2, lmax2 = _problem(n - 10, p - 100, seed=3)
    problems = [(Problem(X=X, y=y), lmax), (Problem(X=X2, y=y2), lmax2)]
    fracs = [0.30, 0.28, 0.26, 0.24]
    picks = rng.integers(len(problems), size=POISSON_REQUESTS)
    fpicks = rng.integers(len(fracs), size=POISSON_REQUESTS)
    gaps = rng.exponential(POISSON_MEAN_GAP_S, size=POISSON_REQUESTS)

    # Deterministic key-space prewarm: a Poisson batch's compile key is
    # (bucket, pow2-padded B, h), and h of a mixed-lam batch is one of
    # the member values — so uniform-lam bursts of every pow2 size per
    # problem cover every key any arrival grouping can produce. Paused
    # servers pin exact batch sizes; the engine caches are process-wide.
    for prob, lm in problems:
        for frac in fracs:
            for B in (1, 2, 4, 8):
                with open_server(autostart=False, max_batch=8,
                                 max_wait_ms=0.0, solver=cfg_srv) as ps:
                    futs = [ps.submit(prob, Scalar(frac * lm))
                            for _ in range(B)]
                    ps.run(timeout=0.01)
                    for f in futs:
                        assert f.result(timeout=600).verdict.ok

    server = open_server(max_batch=8, max_wait_ms=5.0, solver=cfg_srv)

    def load_pass():
        t_done = [None] * POISSON_REQUESTS
        t_sub = [None] * POISSON_REQUESTS
        futs = []
        t_start = time.perf_counter()
        for i in range(POISSON_REQUESTS):
            time.sleep(gaps[i])
            prob, lm = problems[picks[i]]
            t_sub[i] = time.perf_counter()
            fut = server.submit(prob, Scalar(fracs[fpicks[i]] * lm))
            fut.add_done_callback(
                lambda _f, i=i: t_done.__setitem__(
                    i, time.perf_counter()))
            futs.append(fut)
        res = [f.result(timeout=600) for f in futs]
        assert all(r.verdict.ok for r in res)
        wall = time.perf_counter() - t_start
        lat = np.asarray([d - s for d, s in zip(t_done, t_sub)])
        return lat, wall

    load_pass()                              # warm every bucket/key
    c0 = saif_jit_compile_count()
    lat, wall = load_pass()                  # measured steady state
    steady_compiles = saif_jit_compile_count() - c0
    server.close()
    p50 = float(np.percentile(lat, 50))
    p99 = float(np.percentile(lat, 99))
    rps = POISSON_REQUESTS / wall
    row = {
        "mode": "poisson", "seed": 7,
        "requests": POISSON_REQUESTS,
        "mean_gap_ms": POISSON_MEAN_GAP_S * 1e3,
        "shapes": [[n, p], [n - 10, p - 100]],
        "p50_ms": round(p50 * 1e3, 2), "p99_ms": round(p99 * 1e3, 2),
        "req_per_s": round(rps, 1),
        "steady_state_compiles": steady_compiles,
        "p99_bound_s": P99_BOUND_S,
    }
    print(f"[serve] poisson R={POISSON_REQUESTS} seed=7 "
          f"p50={p50 * 1e3:.1f}ms p99={p99 * 1e3:.1f}ms "
          f"{rps:.1f} req/s (steady compiles={steady_compiles})")
    assert steady_compiles == 0, (
        f"Poisson steady state compiled {steady_compiles} new engine "
        f"keys across the heterogeneous mix (contract: zero)")
    assert p99 <= P99_BOUND_S, (
        f"p99 latency {p99:.3f}s exceeds the {P99_BOUND_S}s smoke bound")
    return row


def _bench_restart(X, y, lmax, cfg, n, p):
    """Restart-on-same-cache-dir: with the persistent compilation cache
    wired, a restarted server's warmup writes ZERO new cache entries —
    every compile replays from disk. The cache is
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else a fixed directory in
    the checkout, emptied first so the first life starts cold."""
    import glob
    import os
    import shutil

    from repro import Problem, Scalar
    from repro.core.server import CHECKOUT_CACHE_DIR, open_server

    cfg_srv = _serve_cfg(cfg)
    prob = Problem(X=X, y=y)
    lams = [f * lmax for f in (0.30, 0.28, 0.26, 0.24)]
    own_dir = not os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT_CACHE_DIR, "bench_serve_restart")
    if own_dir:
        shutil.rmtree(cache_dir, ignore_errors=True)

    def cache_files():
        return len([f for f in glob.glob(
            os.path.join(cache_dir, "**"), recursive=True)
            if os.path.isfile(f)])

    files_before = cache_files()

    def life():
        """One server lifetime: open on the cache dir, serve the warmup
        mix, report wall time."""
        server = open_server(cache_dir=cache_dir, max_batch=8,
                             max_wait_ms=20.0, solver=cfg_srv)
        t0 = time.perf_counter()
        futs = [server.submit(prob, Scalar(lam)) for lam in lams]
        res = [f.result(timeout=600) for f in futs]
        assert all(r.verdict.ok for r in res)
        dt = time.perf_counter() - t0
        server.close()
        return dt

    try:
        jax.clear_caches()                   # cold first life
        t_first = life()
        files_first = cache_files()
        assert files_first > files_before or not own_dir, (
            "persistent compilation cache wrote nothing — the restart "
            "contract cannot hold")
        jax.clear_caches()                   # "restart": lose the
        t_second = life()                    # in-memory executables
        files_second = cache_files()
        row = {
            "mode": "restart", "n": n, "p": p,
            "cold_life_s": round(t_first, 3),
            "restart_life_s": round(t_second, 3),
            "cache_entries": files_first,
            "new_entries_after_restart": files_second - files_first,
        }
        print(f"[serve] restart cold={t_first:.2f}s "
              f"restarted={t_second:.2f}s cache_entries={files_first} "
              f"new_after_restart={files_second - files_first}")
        assert files_second == files_first, (
            f"restarted server wrote {files_second - files_first} new "
            f"cache entries — cold-start compiles leaked past the "
            f"persistent cache")
        assert t_second < t_first, (
            f"restart warmup ({t_second:.2f}s) not faster than the cold "
            f"first life ({t_first:.2f}s) — disk replay is not working")
        return row
    finally:
        if own_dir:
            jax.config.update("jax_compilation_cache_dir", None)


if __name__ == "__main__":
    run()
