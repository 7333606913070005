"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) cell on the production mesh and emit
memory/cost/collective analysis for the roofline table.

MUST set the placeholder-device flag before ANY other import — jax locks the
device count on first init.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

# ruff: noqa: E402
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict

import jax
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, get_config, runnable_cells
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import (batch_shardings, cache_shardings,
                                    opt_shardings, param_shardings)
from repro.models import lm
from repro.optim import adamw

# v5e hardware model (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s/link

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*?=\s*(\w+)\[([0-9,{}\sx]*)\]", re.I)

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "f8e4m3": 1,
                "f8e5m2": 1, "s16": 2, "u16": 2}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output operand bytes of every collective op in the compiled HLO."""
    totals: Dict[str, float] = {}
    for m in re.finditer(
            r"=\s*(?:\(([^)]*)\)|(\w+)\[([0-9,\s]*)\][^ ]*)\s*"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)", hlo_text, re.I):
        tuple_part, dtype, dims, op = m.groups()
        nbytes = 0.0
        if tuple_part:
            for shp in re.finditer(r"(\w+)\[([0-9,\s]*)\]", tuple_part):
                d, ds = shp.groups()
                n = np.prod([int(x) for x in ds.split(",") if x.strip()]
                            or [1])
                nbytes += n * _DTYPE_BYTES.get(d, 4)
        else:
            n = np.prod([int(x) for x in dims.split(",") if x.strip()] or [1])
            nbytes = n * _DTYPE_BYTES.get(dtype, 4)
        key = op.lower()
        totals[key] = totals.get(key, 0.0) + float(nbytes)
    return totals


def roofline_terms(flops: float, bytes_hbm: float, coll: Dict[str, float],
                   n_chips: int) -> Dict[str, float]:
    """All inputs are PER-DEVICE quantities: the compiled artifact under
    SPMD partitioning is the per-device program, so cost_analysis()
    (and the HLO the collectives are parsed from) describe one chip.
    Dividing by per-chip peaks gives the per-step time lower bound each
    subsystem imposes. Caveat: XLA 'bytes accessed' counts op-level operand
    traffic, an upper bound on true HBM traffic after fusion.
    """
    compute_t = flops / PEAK_FLOPS
    memory_t = bytes_hbm / HBM_BW
    coll_bytes = sum(coll.values())
    collective_t = coll_bytes / ICI_BW
    dom = max(("compute", compute_t), ("memory", memory_t),
              ("collective", collective_t), key=lambda kv: kv[1])[0]
    return {"compute_s": compute_t, "memory_s": memory_t,
            "collective_s": collective_t, "collective_bytes": coll_bytes,
            "dominant": dom}


def _small_depths(cfg):
    """Two reduced depths for the scan-body cost extrapolation, chosen so
    every per-depth stack (cross_every groups, L//4 sLSTM layers) scales
    linearly between them."""
    if cfg.family == "vlm":
        ce = cfg.cross_every
        return ce, 2 * ce
    if cfg.family == "ssm":
        return 4, 8
    return 2, 4


def corrected_costs(arch: str, shape_name: str, mesh, cfg, *,
                    microbatch: int = 1, fsdp: bool = False):
    """XLA cost_analysis counts a while-loop (lax.scan) body ONCE, so the
    scanned-layer contribution is undercounted by ~n_layers. Lower two
    fully-unrolled reduced-depth variants and extrapolate linearly:
        total(L) = fixed + L * per_layer.
    """
    L1, L2 = _small_depths(cfg)
    variants = []
    for L in (L1, L2):
        kw = {"n_layers": L, "scan_unroll": True}
        # chunk scans stay rolled: their interior is counted once per layer
        # (documented undercount on the recurrence arithmetic — the
        # projections dominate ssm/hybrid FLOPs; fully-unrolled chunk scans
        # blow up XLA compile time at 32k+ sequence lengths)
        if cfg.family == "encdec":
            kw["n_enc_layers"] = L
        vcfg = cfg.scaled(**kw)
        variants.append(_lower_one(arch, shape_name, mesh, vcfg,
                                   microbatch=microbatch, fsdp=fsdp))
    v1, v2 = variants

    def extrap(key):
        body = (v2[key] - v1[key]) / (L2 - L1)
        fixed = v1[key] - L1 * body
        return max(fixed + cfg.n_layers * body, 0.0)

    coll_keys = set(v1["collectives"]) | set(v2["collectives"])
    coll = {}
    for k in coll_keys:
        a = v1["collectives"].get(k, 0.0)
        b = v2["collectives"].get(k, 0.0)
        body = (b - a) / (L2 - L1)
        coll[k] = max(a - L1 * body + cfg.n_layers * body, 0.0)
    return {"flops": extrap("flops"), "bytes": extrap("bytes"),
            "collectives": coll,
            "extrap_depths": (L1, L2)}


def _lower_one(arch: str, shape_name: str, mesh, cfg, *,
               microbatch: int = 1, fsdp: bool = False):
    """Lower+compile one configuration; returns raw cost dict."""
    shape = SHAPES[shape_name]
    n_chips = int(np.prod(list(mesh.shape.values())))
    shapes_tree = lm.param_shapes(cfg)
    p_sh = param_shardings(shapes_tree, cfg, mesh, fsdp=fsdp)

    with mesh:
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig()
            step_fn = steps_lib.make_train_step(cfg, opt_cfg,
                                                microbatch=microbatch)
            specs = steps_lib.input_specs(cfg, shape)
            o_sh = opt_shardings(p_sh, shapes_tree, mesh, zero1=True)
            state_sh = steps_lib.TrainState(
                params=p_sh,
                opt=adamw.AdamWState(
                    step=jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec()),
                    m=o_sh, v=o_sh))
            b_sh = batch_shardings(mesh, specs["batch"])
            jitted = jax.jit(step_fn,
                             in_shardings=(state_sh, b_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(specs["state"], specs["batch"])
        elif shape.kind == "prefill":
            step_fn = steps_lib.make_prefill(cfg)
            specs = steps_lib.input_specs(cfg, shape)
            b_sh = batch_shardings(mesh, specs["batch"])
            jitted = jax.jit(step_fn, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(specs["params"], specs["batch"])
        else:  # decode
            step_fn = steps_lib.make_serve_step(cfg)
            specs = steps_lib.input_specs(cfg, shape)
            tok_sh = batch_shardings(mesh, {"t": specs["tok"]})["t"]
            c_sh = cache_shardings(mesh, specs["state"].caches)
            st_sh = lm.DecodeState(
                caches=c_sh, pos=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()))
            jitted = jax.jit(step_fn,
                             in_shardings=(p_sh, tok_sh, st_sh),
                             out_shardings=(None, st_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(specs["params"], specs["tok"],
                                   specs["state"])

        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
        "compile_s": round(compile_s, 1),
        "peak_memory_per_device": getattr(
            mem, "temp_size_in_bytes", 0) + getattr(
            mem, "argument_size_in_bytes", 0) + getattr(
            mem, "output_size_in_bytes", 0),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
    }


def lower_cell(arch: str, shape_name: str, mesh, cfg_override=None,
               corrected: bool = True, microbatch: int = 1,
               fsdp: bool = False):
    """Full analysis of one cell: production lowering (memory + raw costs)
    plus the scan-corrected flops/bytes/collectives extrapolation."""
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    n_chips = int(np.prod(list(mesh.shape.values())))

    raw = _lower_one(arch, shape_name, mesh, cfg,
                     microbatch=microbatch, fsdp=fsdp)
    flops, bytes_hbm, coll = raw["flops"], raw["bytes"], raw["collectives"]
    corr = None
    if corrected:
        corr = corrected_costs(arch, shape_name, mesh, cfg,
                               microbatch=microbatch, fsdp=fsdp)
        flops, bytes_hbm, coll = (corr["flops"], corr["bytes"],
                                  corr["collectives"])
    terms = roofline_terms(flops, bytes_hbm, coll, n_chips)

    n_active = cfg.active_param_count()
    tokens = (shape.global_batch
              * (shape.seq_len if shape.kind != "decode" else 1))
    if shape.kind == "train":
        model_flops = 6.0 * n_active * tokens   # fwd(2) + bwd(4) per param
    else:
        model_flops = 2.0 * n_active * tokens

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(f"{k}={v}" for k, v in mesh.shape.items()),
        "n_chips": n_chips, "compile_s": raw["compile_s"],
        "flops": flops, "bytes": bytes_hbm,
        "raw_flops": raw["flops"], "raw_bytes": raw["bytes"],
        "scan_corrected": bool(corrected),
        "microbatch": microbatch, "fsdp": fsdp,
        "peak_memory_per_device": raw["peak_memory_per_device"],
        "argument_bytes": raw["argument_bytes"],
        "temp_bytes": raw["temp_bytes"],
        "collectives": coll,
        "model_flops": model_flops,
        "useful_flops_frac": (model_flops / (flops * n_chips)
                              if flops else None),
        **terms,
    }
    return rec


def lower_saif_screen(mesh, *, n: int = 4096, log2_p: int = 26,
                      h: int = 64, dtype="float32"):
    """The paper-technique roofline row: the distributed SAIF screening scan
    (fused local top-h + max-ub, one small gather) on the production mesh,
    at framework scale: p = 2^26 features sharded over every mesh axis.
    """
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.saif_sharded import ShardedDesign, make_fused_screen

    n_chips = int(np.prod(list(mesh.shape.values())))
    p = 2 ** log2_p
    axes = tuple(mesh.axis_names)
    dt = jnp.dtype(dtype)
    X = jax.ShapeDtypeStruct((n, p), dt)
    norm = jax.ShapeDtypeStruct((p,), dt)

    x_sh = NamedSharding(mesh, P(None, axes))
    v_sh = NamedSharding(mesh, P(axes))
    r_sh = NamedSharding(mesh, P())

    def step(X, norm, theta, r):
        d = ShardedDesign(X=X, col_norm=norm, c0=None, p=p, mesh=mesh)
        return make_fused_screen(d, h=h)(theta, r)

    with mesh:
        jitted = jax.jit(step, in_shardings=(x_sh, v_sh, r_sh, r_sh))
        lowered = jitted.lower(X, norm,
                               jax.ShapeDtypeStruct((n,), dt),
                               jax.ShapeDtypeStruct((), dt))
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    flops = float(cost.get("flops", 0.0))
    bytes_hbm = float(cost.get("bytes accessed", 0.0))
    terms = roofline_terms(flops, bytes_hbm, coll, n_chips)
    rec = {
        "arch": f"saif_screen_p2^{log2_p}_{dtype}", "shape": f"n{n}_h{h}",
        "mesh": "x".join(f"{k}={v}" for k, v in mesh.shape.items()),
        "n_chips": n_chips, "compile_s": round(compile_s, 1),
        "flops": flops, "bytes": bytes_hbm, "collectives": coll,
        "scan_corrected": False,
        "peak_memory_per_device": getattr(
            mem, "temp_size_in_bytes", 0) + getattr(
            mem, "argument_size_in_bytes", 0) + getattr(
            mem, "output_size_in_bytes", 0),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        # useful flops per device: the scan is 2*n*p/devices matvec MACs
        "model_flops": 2.0 * n * p,
        "useful_flops_frac": (2.0 * n * p / (flops * n_chips)
                              if flops else None),
        **terms,
    }
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run single-pod AND multi-pod for each cell")
    ap.add_argument("--out", default=None, help="write JSONL records here")
    ap.add_argument("--saif-screen", action="store_true",
                    help="only lower the SAIF screening-collective row")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--no-corrected", action="store_true",
                    help="skip the scan-cost extrapolation (1 compile/cell)")
    args = ap.parse_args(argv)

    if args.saif_screen:
        records = []
        for multi in ([False, True] if args.both_meshes
                      else [args.multi_pod]):
            mesh = make_production_mesh(multi_pod=multi)
            rec = lower_saif_screen(mesh)
            rec["status"] = "ok"
            records.append(rec)
            print(f"OK    saif_screen x {rec['mesh']}: "
                  f"dominant={rec['dominant']} "
                  f"compute={rec['compute_s']:.2e}s "
                  f"memory={rec['memory_s']:.2e}s "
                  f"coll={rec['collective_s']:.2e}s")
        if args.out:
            with open(args.out, "w") as f:
                for r in records:
                    f.write(json.dumps(r) + "\n")
        return 0

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shape_filter = (list(SHAPES) if args.shape == "all" else [args.shape])
    meshes = ([False, True] if args.both_meshes
              else [args.multi_pod])

    cells = [(a, s, st) for a, s, st in runnable_cells()
             if a in archs and s in shape_filter]
    records = []

    def flush(rec):
        records.append(rec)
        if args.out:                      # incremental append (crash-safe)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    n_fail = 0
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        for arch, shape_name, status in cells:
            tag = f"{arch} x {shape_name} x {'2x16x16' if multi else '16x16'}"
            if status != "run":
                print(f"SKIP  {tag}: {status}")
                flush({"arch": arch, "shape": shape_name,
                       "mesh": "2x16x16" if multi else "16x16",
                       "status": status})
                continue
            try:
                rec = lower_cell(arch, shape_name, mesh,
                                 corrected=not args.no_corrected,
                                 microbatch=args.microbatch,
                                 fsdp=args.fsdp)
                rec["status"] = "ok"
                flush(rec)
                print(f"OK    {tag}: dominant={rec['dominant']} "
                      f"compute={rec['compute_s']:.2e}s "
                      f"memory={rec['memory_s']:.2e}s "
                      f"coll={rec['collective_s']:.2e}s "
                      f"peak_mem/dev={rec['peak_memory_per_device']/2**30:.2f}GiB "
                      f"(compile {rec['compile_s']}s)")
            except Exception as e:  # noqa: BLE001
                n_fail += 1
                print(f"FAIL  {tag}: {type(e).__name__}: {e}")
                traceback.print_exc()
                flush({"arch": arch, "shape": shape_name,
                       "mesh": "2x16x16" if multi else "16x16",
                       "status": f"fail: {e}"})
    print(f"\n{len(records)} cells, {n_fail} failures")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
