"""Where the Pallas kernels run: compiled on the TPU, interpreted on the
CPU (the test suite's path). Any other backend has no kernel path and
raises instead of silently interpreting on an accelerator."""
from __future__ import annotations

import jax


def interpret_default() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels target the TPU (compiled) or the CPU (interpret "
        f"mode); backend {backend!r} has neither")
