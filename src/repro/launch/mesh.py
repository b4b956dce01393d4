"""Production mesh builders (functions, not constants — importing this module
never touches jax device state)."""

from __future__ import annotations

from repro.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis rides
    DCN, `data`/`model` ride ICI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist —
    used by tests that set XLA_FLAGS=--xla_force_host_platform_device_count."""
    return make_mesh((data, model), ("data", "model"))
