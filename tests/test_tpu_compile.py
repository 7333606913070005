"""Compile every main-path Pallas kernel for a described TPU v5e.

The chip's own compiler (Mosaic) runs here against a v5e topology that is
described, not attached: no kernel executes, but every layout, lowering and
VMEM refusal the chip would raise is raised here, at deployment widths
(n = 1024 samples, p = 2^20 features, h = 32 candidates per tile, k = 256
active slots, fleets of 8). These are the kernels the ``auto`` policies
pick on TPU (DESIGN.md §3, §6, §7). The fleet engine itself is compiled
at the benchmark cells' shapes too: X must stay row-major inside it, with
no design-sized copy beside the parameter.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core.batch import _saif_batch_jit
from repro.kernels.cm import cm as cm_kernels
from repro.kernels.cm.cm import cm_burst_batch_pallas, cm_burst_pallas
from repro.kernels.fused.fused import chain_suffix_sums_pallas
from repro.kernels.screen import fetch as fetch_kernels
from repro.kernels.screen import screen as screen_kernels
from repro.kernels.screen.fetch import fetch_columns_pallas
from repro.kernels.screen.screen import (screen_fused_batch_pallas,
                                         screen_fused_pallas,
                                         ub_histogram_batch_pallas,
                                         ub_histogram_pallas)

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from bench import trace  # noqa: E402

N, P, H, B, K = 1024, 1 << 20, 32, 8, 256
P_CHAIN = 1 << 14
# scoped VMEM a v5e TensorCore has in all (128 MiB)
V5E_VMEM_BYTES = 128 * 2**20


def f32(*shape):
    return shape, jnp.float32


def i32(*shape):
    return shape, jnp.int32


def bool_(*shape):
    return shape, jnp.bool_


def _cm(loss, pen):
    def fn(A, y, beta, csq, mask, order, lam, ne, cnt, *rest):
        return cm_burst_pallas(A, y, beta, csq, mask, order, lam, ne, cnt,
                               *rest, loss_name=loss, interpret=False)
    return fn, [f32(N, K), f32(N), f32(K), f32(K), bool_(K), i32(K), f32(),
                i32(), i32()] + ([f32(K)] if pen else [])


def _cm_batch(loss):
    def fn(A, Y, beta, csq, mask, order, lam, ne, cnt):
        return cm_burst_batch_pallas(A, Y, beta, csq, mask, order, lam, ne,
                                     cnt, loss_name=loss, interpret=False)
    return fn, [f32(B, N, K), f32(B, N), f32(B, K), f32(B, K), bool_(B, K),
                i32(B, K), f32(B), i32(B), i32(B)]


KERNELS = {
    "screen_fused": (
        lambda X, t, c, a, r: screen_fused_pallas(X, t, c, a, r, h=H,
                                                  interpret=False),
        [f32(N, P), f32(N), f32(P), bool_(P), f32()]),
    "screen_fused_batch": (
        lambda X, t, c, a, r: screen_fused_batch_pallas(X, t, c, a, r, h=H,
                                                        interpret=False),
        [f32(N, P), f32(B, N), f32(B, P), bool_(B, P), f32(B)]),
    "ub_histogram": (
        lambda u, lb: ub_histogram_pallas(u, lb, interpret=False),
        [f32(P), f32(H)]),
    "ub_histogram_batch": (
        lambda u, lb: ub_histogram_batch_pallas(u, lb, interpret=False),
        [f32(B, P), f32(B, H)]),
    "cm_burst_logistic": _cm("logistic", pen=False),
    "cm_burst_fused_logistic": _cm("logistic", pen=True),
    "cm_burst_least_squares": _cm("least_squares", pen=False),
    "cm_burst_batch_logistic": _cm_batch("logistic"),
    "chain_suffix_sums": (
        lambda X: chain_suffix_sums_pallas(X, interpret=False),
        [f32(N, P_CHAIN)]),
    "fetch_columns": (
        lambda X, ids, placed: fetch_columns_pallas(X, ids, placed,
                                                    interpret=False),
        [f32(N, P), i32(B * H), bool_(B * H)]),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it. The
    # kernels compile as they run on the chip: with x64 off.
    from jax.experimental.compilation_cache import compilation_cache
    saved = {k: getattr(jax.config, k) for k in
             ("jax_enable_compilation_cache", "jax_enable_x64")}
    for k in saved:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = KERNELS[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # beside the operands, a kernel's temporaries must stay within what
    # one v5e core can hold: no design-sized copy of X for the screen
    assert mem.temp_size_in_bytes < V5E_VMEM_BYTES


# the kernels the benchmark's per-layer metrics find by instruction name
# (bench/metrics/screen_*.py, cm_ms_per_solution.py), each compiled as
# the engines run it: inside an outer jit, under a named scope
SCOPED = {"screen_fused_batch_pallas": ("screen", "screen_fused_batch"),
          "cm_burst_batch_pallas": ("cm", "cm_burst_batch_logistic"),
          "fetch_columns_pallas": ("add_delete", "fetch_columns")}


@pytest.mark.parametrize("kernel", sorted(SCOPED))
def test_named_scopes_keep_the_kernel_instruction_name(one_chip, kernel):
    scope, name = SCOPED[kernel]
    fn, specs = KERNELS[name]

    def scoped(*a):
        with jax.named_scope(scope):
            return fn(*a)

    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs]
    text = jax.jit(scoped).lower(*args).compile().as_text()
    calls = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    assert {trace.kernel_base(c) for c in calls} == {kernel}


# the fleet engine as the benchmark cells run it: (loss, n, p, B) with the
# compiled Pallas screen and CM burst, k_max = 1024 slots
ENGINES = {"least_squares_b1": ("least_squares", N, P, 1),
           "least_squares_b8": ("least_squares", N, P, B),
           "logistic_b1": ("logistic", N, 1 << 18, 1)}
K_ENGINE = 1024
# an instruction whose result is the design: its opcode and layout
_DESIGN_OP = r"%[\w.-]+ = f32\[{n},{p}\]\{{([^}}]*)\}} ([\w-]+)\("


@pytest.fixture(scope="module", params=sorted(ENGINES))
def engine(one_chip, request):
    """``_saif_batch_jit`` compiled for the described chip. Off the chip
    the kernels resolve to the interpreter; here they are steered to
    Mosaic as on the chip."""
    loss, n, p, b = ENGINES[request.param]
    k = K_ENGINE

    def arg(dt, *shape):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    f, i = jnp.float32, jnp.int32
    args = (arg(f, n, p), arg(f, b, n), arg(f, 1, 1), arg(f, b, p),
            arg(f, b, p), arg(f, b), arg(f, b), arg(f, b), arg(i, b, k),
            arg(f, b, k), arg(jnp.bool_, b, k), arg(f, b, 1, 1),
            arg(f, b, 1), arg(i, b, 1), arg(i, b), arg(i, b))
    with pytest.MonkeyPatch.context() as mp:
        for mod in (screen_kernels, cm_kernels, fetch_kernels):
            mp.setattr(mod, "default_interpret", lambda: False)
        compiled = _saif_batch_jit.lower(
            *args, loss_name=loss, h=H, k_max=k, inner_epochs=5,
            polish_factor=8, max_outer=2000, use_seq_ball=True,
            screen_backend="pallas", inner_backend="pallas").compile()
    return (n, p), compiled


def test_engine_keeps_the_design_row_major(engine):
    """No copy, transpose or gather relayout of X: every instruction
    whose result is the design aliases the parameter, row-major."""
    (n, p), compiled = engine
    text = compiled.as_text()
    found = re.findall(_DESIGN_OP.format(n=n, p=p), text)
    assert found
    for layout, opcode in found:
        assert opcode in ("parameter", "get-tuple-element", "bitcast"), (
            opcode, layout)
        assert layout.startswith("1,0"), (opcode, layout)
    assert "fetch_columns_pallas" in text


def test_engine_temporaries_are_a_fraction_of_the_design(engine):
    (n, p), compiled = engine
    assert compiled.memory_analysis().temp_size_in_bytes < n * p * 4 // 8
