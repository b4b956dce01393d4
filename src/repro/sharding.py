"""Logical-axis sharding hints (MaxText-style), divisibility-aware.

Models annotate tensors with *logical* axis names ("batch", "seq", "embed",
"ffn", "heads", "kv_heads", "experts", "vocab", "layers", ...).  The launcher
installs a mapping logical-name -> mesh axes; `hint()` applies a
`jax.lax.with_sharding_constraint` **only for dimensions whose size divides
the mesh axes** (e.g. 40 heads on a 16-way model axis stay unsharded — the
framework's divisibility-aware TP policy, DESIGN.md §7).

Without an installed mesh all hints are no-ops, so the same model code runs
single-device (smoke tests) and multi-pod (dry-run/train).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

AxisVal = Union[str, Tuple[str, ...], None]


def _current() -> Tuple[Optional[Mesh], Dict[str, AxisVal]]:
    return (getattr(_state, "mesh", None), getattr(_state, "rules", {}))


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Dict[str, AxisVal]):
    """Install mesh + logical->physical rules for hint()/axis lookup."""
    prev = _current()
    _state.mesh, _state.rules = mesh, dict(rules)
    try:
        with mesh:
            yield
    finally:
        _state.mesh, _state.rules = prev


def mesh_axis_size(*names: str) -> int:
    mesh, _ = _current()
    if mesh is None:
        return 1
    size = 1
    for n in names:
        size *= mesh.shape.get(n, 1)
    return size


def active_mesh() -> Optional[Mesh]:
    return _current()[0]


def make_mesh(shape: Sequence[int], names: Sequence[str], *,
              devices=None) -> Mesh:
    """`jax.make_mesh` with every axis `AxisType.Auto`.

    The model and atomics code place arrays by sharding propagation
    (`hint`, shard_map in/out specs).  Under `AxisType.Explicit`, the
    default of `jax.make_mesh`, a gather from a sharded operand (the
    embedding lookup, indexing a sharded result) is a type error."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def shard_map_compat(fn, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` without varying-manual-axes checking (the sharded
    atomics tier runs collectives on values the checker cannot type)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def logical_to_physical(logical: Sequence[Optional[str]],
                        shape: Sequence[int]) -> P:
    """Resolve logical names to a PartitionSpec, dropping non-divisible axes."""
    mesh, rules = _current()
    if mesh is None:
        return P()
    spec = []
    used: set = set()
    for name, dim in zip(logical, shape):
        phys = rules.get(name) if name else None
        if phys is None:
            spec.append(None)
            continue
        axes = (phys,) if isinstance(phys, str) else tuple(phys)
        axes = tuple(a for a in axes if a not in used and a in mesh.shape)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if size <= 1 or dim % size != 0:
            spec.append(None)
            continue
        used.update(axes)
        spec.append(axes[0] if len(axes) == 1 else axes)
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def hint(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply a sharding constraint by logical axis names (no-op without mesh,
    and per-dimension no-op when sizes don't divide)."""
    mesh, _ = _current()
    if mesh is None:
        return x
    if len(logical) != x.ndim:
        raise ValueError(f"hint rank mismatch: {logical} vs {x.shape}")
    spec = logical_to_physical(logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(logical: Sequence[Optional[str]],
                   shape: Sequence[int]) -> Optional[NamedSharding]:
    mesh, _ = _current()
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_to_physical(logical, shape))


#: default logical->physical rules used by the launcher.  "fsdp" combines the
#: pod and data axes (params + optimizer state ZeRO-3 sharded across both).
DEFAULT_RULES: Dict[str, AxisVal] = {
    "batch": ("pod", "data"),
    "seq": None,                # sequence stays unsharded in activations
    "act_seq": None,            # residual-carry seq sharding (SP) — opt-in
                                # via rules override ("model") in the launcher
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "qkv": "model",
    "ffn": "model",
    "vocab": "model",
    "experts": "model",
    "expert_ffn": None,
    "fsdp": ("pod", "data"),
    "layers": None,
    "kv_seq": None,
    "state": None,
    # RMW tables (core/rmw_sharded.py): owner-major over the EP/model axis,
    # matching the subsystem's slot->shard layout (g // m_local)
    "rmw_table": "model",
}
