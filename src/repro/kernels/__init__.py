"""Pallas TPU kernels of the commit path and the bucket planner."""
from __future__ import annotations

import jax


def interpret_default(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode: an explicit
    ``interpret`` wins; otherwise compiled on a TPU and interpreted on
    any other backend.  The one place that decides it, so no kernel
    entry silently runs the simulator on the chip."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
