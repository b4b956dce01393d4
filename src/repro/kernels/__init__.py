"""Pallas kernels: the RMW combine (`rmw`), the SSD chunk (`ssd`) and flash
attention.  Each compiles with Mosaic on a TPU and runs in the Pallas
interpreter elsewhere (CPU tests)."""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> the platform decides: compiled on TPU, the interpreter
    elsewhere.  An explicit bool is kept (tests force the interpreter)."""
    return jax.default_backend() != "tpu" if interpret is None else interpret
