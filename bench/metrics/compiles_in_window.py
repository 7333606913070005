"""Programs lowered inside the window: JAX's
``/jax/core/compile/jaxpr_to_mlir_module_duration`` events, one per
program traced for compilation (a compile or a load from the persistent
cache). Set-up warms every program the mix uses, so this reads 0."""


def read(r):
    return float(r.compiles)
