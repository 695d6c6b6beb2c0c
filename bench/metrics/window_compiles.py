"""Programs lowered inside the measured window (compiled, or fetched
from the persistent cache): each one is a shape the warm-up missed.
Counted from JAX's ``/jax/core/compile/jaxpr_to_mlir_module_duration``
events.  Layer: device / compile.  Moves ``ops_per_s``."""


def read(w):
    return float(w.compiles)
