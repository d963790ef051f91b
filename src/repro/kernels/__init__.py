"""Pallas TPU kernels, each with a jitted wrapper (ops.py) and an oracle
(ref.py)."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in the interpreter on this backend.

    The CPU backend interprets them (tests and tiny-shape rehearsals); a
    TPU compiles them with Mosaic. Any other backend is refused rather
    than silently interpreted, so a run never reports a device path that
    did not execute.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"the Pallas kernels compile only for a TPU and are "
                       f"interpreted only on the CPU; backend {backend!r} "
                       "is neither")
