"""Programs JAX lowered inside the measured window (each is a compile, or
a read from the persistent cache, on the timed path), counted from JAX's
``/jax/core/compile/jaxpr_to_mlir_module_duration`` events."""


def read(w):
    return float(w.lowered)
