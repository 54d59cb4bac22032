"""Which way the Pallas kernels run on the current JAX backend.

  * TPU -> the compiled Mosaic kernels are the serving path;
  * CPU -> the XLA twins (ref oracles / gather paths) serve, and a
           kernel runs only where a test forces it, in interpret mode;
  * any other backend is refused: no kernel here is written for it,
    and quietly running the interpreter there would hide the device.

Every kernel family's ops.py dispatches through this one predicate.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True on TPU, False on CPU; raises on any other backend."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"JAX backend {backend!r} has no kernel path: the Pallas "
            "kernels compile for TPU and run in interpret mode on CPU only")
    return backend == "tpu"
