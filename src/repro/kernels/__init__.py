# Pallas TPU kernels for the perf-critical substrate compute (the Helix
# paper itself has no kernel-level contribution).
# Each subpackage: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper), ref.py (pure-jnp oracle used by allclose tests).
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted on this backend.

    A TPU compiles them (False); the CPU runs them in the Pallas
    interpreter (True), which is how the tests exercise them. Any other
    backend raises: there is no silent interpreted fallback on an
    accelerator.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU; "
        f"the {backend!r} backend is neither")
