"""Backend selection shared by the Pallas kernels."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` follows the platform: compile for the TPU, interpret the
    kernel body everywhere else (the CPU test rig)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
