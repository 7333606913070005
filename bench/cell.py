"""One run of one cell: set-up, the measured window, the checks.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything it
names is found by that name: the configuration in
``bench/configs/<config>.json``, the traffic mix in
``bench/traffic/<traffic>.json`` (which names its kind and its loop, see
``traffic.py``), the limits of its comparison in
``bench/limits/<cell>.json`` and each metric's reader in
``bench/metrics/<metric>.py``. Adding a cell adds files and entries and
edits none.

The run:

1. set-up -- the design and the responses on the device (one jitted
   call; ``data.py``), one ``Problem`` per response, ``open_server`` with
   the configuration's solver and serving settings, and the mix kind's
   warm-up of every program the mix reaches, so nothing compiles in the
   window;
2. the window -- the mix's loop drives the kind's submissions through
   ``submit()`` -> ``ServingFuture.result()`` for ``--seconds``;
3. with ``--trace 1`` the profiler records the window, and the trace is
   reduced to the per-layer metrics and the breakdown;
4. the device's peak memory is read, the server is shut, and every
   served result is compared with the plain reference (``correct.py``).
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import copy
import dataclasses
import gc
import glob
import json
import pathlib
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing {path}")
    return json.loads(path.read_text())


def _merge(base: dict, over: Optional[dict]) -> dict:
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def cell_spec(name: str, root: pathlib.Path = ROOT) -> Cell:
    bm = _json(root / "BENCHMARK.json")
    wl = [w for w in bm["workloads"] if w["name"] == name]
    if not wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    wl = wl[0]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bm["end_to_end"] if applies(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name=name, chips=int(wl["chips"]),
                config=_json(HERE / "configs" / f"{wl['config']}.json"),
                mix=_json(HERE / "traffic" / f"{wl['traffic']}.json"),
                limits=_json(HERE / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def reader(metric: str):
    import bench
    return bench.find("metrics", metric).read


@dataclasses.dataclass
class Reading:
    """What the metric readers read (``bench/metrics``)."""
    setup_s: float
    window_s: float
    solutions: int
    latencies: List[float]
    results: List[Any]              # ServingResult of each request served
    server: Dict[str, int]          # ServerStats deltas over the window
    compiles: int
    trace: Any = None               # trace.Reduced, with --trace 1
    peak: Optional[dict] = None     # the device's row of peaks.json


class CompileLog:
    """Times at which JAX lowered a program (``COMPILE_EVENT``)."""

    def __init__(self):
        import jax
        self.times: List[float] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        with self._lock:
            return sum(lo <= t <= hi for t in self.times)


def _span(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def run(name: str, seed: int, seconds: float, trace_on: bool, *,
        root: pathlib.Path = ROOT, require_chip: bool = True,
        overrides: Optional[dict] = None,
        t_start: Optional[float] = None, keep: Optional[dict] = None,
        log=sys.stderr) -> dict:
    """Run the cell once; returns the result object (see ``run.py``).

    ``overrides`` replaces configuration entries (a rehearsal at a tiny
    size); ``require_chip=False`` lets a rehearsal run on the CPU;
    ``keep`` receives the data, the references and the limits (the
    control, ``control.py``, reuses them)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = cell_spec(name, root)
    cfg = _merge(cell.config, overrides)
    mix = cell.mix
    src = root / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"the system under test is missing: {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))

    import jax
    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    if require_chip and (not on_tpu or len(devs) < cell.chips):
        raise NoChip(f"cell {name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    if jax.config.jax_enable_x64 != bool(cfg.get("x64", False)):
        raise RuntimeError(f"jax_enable_x64 must be {cfg.get('x64')} as "
                           f"the configuration states")
    from bench import correct, data, reference, roofline, traffic
    from bench import trace as trace_mod
    peak = roofline.peaks(devs[0].device_kind) if on_tpu else None

    import repro
    from repro.core.saif import SaifConfig
    from repro.core.server import enable_compile_cache, open_server
    from repro.core.serving import ServingConfig

    kind, loop = traffic.kind(mix), traffic.loop(mix)
    cache_dir = enable_compile_cache()
    compiles = CompileLog()
    loss = cfg["loss"]

    # ---- set-up ------------------------------------------------------
    phases = {}
    X, Y = data.make(seed, int(cfg["instance_seed"]), cfg["design"],
                     mix["responses"])
    lam_max = [reference.lam_max(X, Y[r], loss) for r in range(len(Y))]
    phases["data"] = time.perf_counter()
    # admission scans X on the host once per Problem; the scans run side
    # by side (numpy releases the interpreter lock), after one transfer
    np.asarray(X)
    with concurrent.futures.ThreadPoolExecutor(len(Y)) as pool:
        problems = list(pool.map(
            lambda r: repro.Problem(X=X, y=Y[r], loss=loss), range(len(Y))))
    phases["admit"] = time.perf_counter()
    s, v, srv = cfg["solver"], cfg["serving"], cfg["server"]
    server = open_server(
        solver=SaifConfig(eps=float(s["eps"]), k_max=int(s["k_max"]),
                          screen_backend=s["screen_backend"],
                          inner_backend=s["inner_backend"]),
        serving=ServingConfig(ladder=tuple(v["ladder"]),
                              kkt_rtol=float(v["kkt_rtol"])),
        max_batch=int(srv["max_batch"]),
        max_wait_ms=float(srv["max_wait_ms"]),
        warm_cache=srv["warm_cache"])
    ctx = traffic.Context(api=repro, X=X, Y=Y, loss=loss, problems=problems,
                          lam_max=lam_max, max_batch=int(srv["max_batch"]))
    try:
        for batch in kind.warmup(mix, ctx):
            futs = [server.submit(sub.problem, sub.request) for sub in batch]
            for fut in futs:
                fut.result(timeout=None)
        phases["digest+warm"] = time.perf_counter()

        # ---- the window ----------------------------------------------
        streams = [kind.client(mix, ctx, seed, c)
                   for c in range(int(mix["clients"]))]
        opened = {}

        def open_window():
            opened["stats"] = server.stats()._asdict()
            if trace_on:
                opened["tdir"] = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(opened["tdir"])

        win = loop.drive(mix, seed, server, streams, seconds, open_window,
                         lambda name: _span(trace_on, name))
        if trace_on:
            jax.profiler.stop_trace()
        stats1 = server.stats()._asdict()
        mem = devs[0].memory_stats() or {}
    finally:
        server.close()
    del server
    gc.collect()
    t0, t1 = win.t0, win.t1

    reduced = None
    tdir = opened.get("tdir")
    if tdir is not None:
        files = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
        if files:
            reduced = trace_mod.reduce(trace_mod.load(files[0]))
        shutil.rmtree(tdir, ignore_errors=True)

    # ---- the checks --------------------------------------------------
    ok_recs = [rec for rec in win.records if rec.error is None]
    failed = len(win.records) - len(ok_recs) + win.stuck
    answers = []
    for rec in ok_recs:
        got = kind.answers(rec.result)
        if len(got) != len(rec.sub.asked):
            raise RuntimeError(f"{len(got)} answers to "
                               f"{len(rec.sub.asked)} asked")
        answers += [(r, lam, beta, correct.verdict_bad(verdict))
                    for (r, lam), (beta, verdict) in zip(rec.sub.asked, got)]
    correct_, checks, refs = correct.judge(X, Y, loss, answers, failed,
                                           cell.limits)
    if keep is not None:
        keep.update(X=X, Y=Y, loss=loss, refs=refs, limits=cell.limits)

    reading = Reading(
        setup_s=t0 - t_start, window_s=t1 - t0, solutions=len(answers),
        latencies=[rec.t_done - rec.t_submit for rec in ok_recs],
        results=[rec.result for rec in ok_recs],
        server={k: stats1[k] - opened["stats"][k] for k in stats1},
        compiles=compiles.between(t0, t1), trace=reduced, peak=peak)
    metrics = {}
    for m in (cell.per_layer if trace_on else cell.end_to_end):
        val = reader(m["name"])(reading)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    out = {"correct": bool(correct_ and win.stuck == 0),
           "attempted": len(win.records) + win.stuck, "failed": failed,
           "metrics": metrics, "device": device}
    if reduced is not None and reduced.n_devices:
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_ns / 1e9
        out["breakdown"] = reduced.breakdown()
    out["checks"] = checks
    marks = [("start", t_start)] + list(phases.items())
    split = " ".join(f"{k}={t - marks[i][1]:.3f}"
                     for i, (k, t) in enumerate(marks[1:]))
    print(f"[bench] {name} seed={seed} setup_s={reading.setup_s:.3f} "
          f"({split}) window_s={reading.window_s:.3f} "
          f"solutions={reading.solutions} checks_s="
          f"{time.perf_counter() - t1:.3f} compile_cache={cache_dir}",
          file=log, flush=True)
    return out
