"""Where the Pallas kernels run: natively on TPU, interpreted on CPU.

The correctness suite runs on CPU (``JAX_PLATFORMS=cpu``), where every
kernel executes in Pallas interpret mode.  Any other platform is an
error rather than a silent interpreter run, so a TPU that failed to
initialise (JAX then falls back to another backend) can never pass for
a kernel run.
"""

from __future__ import annotations

import jax


def interpret_default(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` argument from the default backend."""
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run natively on TPU or interpreted on CPU; the "
        f"default backend is {platform!r}")
