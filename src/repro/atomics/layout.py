"""TableLayout: the owner-major slot->shard contract as a first-class object.

The paper's central finding is that an atomic's cost is set by *where the
cache line lives*, not by which atomic is issued; the distributed analogue is
that an RMW's cost is set by *which shard owns the slot*.  That ownership
contract used to live implicitly in two places — ``make_table``'s
sharding-rule resolution and ``rmw_sharded``'s inline ``g // m_local``
arithmetic — which made it impossible to reason about a table whose mesh is
*changing*.  This module makes the contract explicit:

* **owner-major layout**: global slot ``g`` lives on shard ``g // m_local``
  at local row ``g % m_local``; shards are laid out major-to-minor over the
  table's ``axis`` tuple (:func:`owner_shard`, :func:`local_row` are the
  single home for that arithmetic — the sharded executor imports them).
* **replica contract**: devices along ``replica_axes`` hold identical copies
  of their shard; writers on every replica serialize replica-major.
* **device-rank arrival order**: `atomics.execute` results equal the
  serialized oracle applied to the concatenation of per-device batches
  ordered by device rank — lexicographic over ``replica_axes + axis``
  (major to minor), each device's ops in local order
  (:func:`TableLayout.arrival_rank_of_device`).

A :class:`TableLayout` is derivable from a live table + mesh
(:meth:`TableLayout.from_table`), is JSON-serializable
(:meth:`~TableLayout.to_dict` / :meth:`~TableLayout.from_dict`) so
checkpoints can carry it, and is what `repro.atomics.reshard` re-derives
under a *new* mesh when the fleet grows or shrinks — ownership is a pure
function of (slot, extent), so migration never needs to replay history.

This module is import-light on purpose (jax/numpy only): both
`repro.atomics.table` and `repro.core.rmw_sharded` import it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
AxisNames = Union[str, Tuple[str, ...], None]

#: the most slots int32 ids address; a larger sharded table takes uint32 ids
INT32_SLOTS = 2**31 - 1


def norm_axes(axis: AxisNames) -> Tuple[str, ...]:
    """Normalize an axis spec (None / str / tuple) to a tuple of names."""
    if axis is None:
        return ()
    if isinstance(axis, str):
        return (axis,)
    return tuple(axis)


# ---------------------------------------------------------------------------
# Owner-major arithmetic (the single home; rmw_sharded imports these)
# ---------------------------------------------------------------------------

def below(gidx: Array, m_global: int) -> Array:
    """``gidx < m_global``; a bound past the id dtype's range (a 2^32-slot
    table under uint32 ids) holds every id."""
    if m_global > jnp.iinfo(gidx.dtype).max:
        return jnp.ones(gidx.shape, bool)
    return gidx < gidx.dtype.type(m_global)   # a Python int past 2^31 - 1
                                              # would not convert


def owner_shard(gidx: Array, m_local: int, n_shards: int) -> Array:
    """Destination shard (int32) of each global slot id under owner-major
    layout, in the ids' own dtype (int32, or uint32 for tables past 2^31 - 1
    slots).

    Valid ids map to ``g // m_local``; anything else (already remapped to
    ``>= m_global`` by the caller's OOR pass) clamps to the last shard,
    whose resolve pass drops it via the scratch row.
    """
    return jnp.minimum(gidx // m_local, n_shards - 1).astype(jnp.int32)


def local_row(gidx: Array, shard: Array, m_local: int, m_global: int) -> Array:
    """Local row (int32) of a global slot on its owner; OOR ids -> the
    scratch row (``m_local``), matching the engine's drop convention.  The
    shard's base ``shard * m_local`` is taken in the ids' dtype, which holds
    it for uint32 ids past 2^31."""
    base = jnp.asarray(shard).astype(gidx.dtype) * gidx.dtype.type(m_local)
    return jnp.where(below(gidx, m_global), gidx - base,
                     m_local).astype(jnp.int32)


# ---------------------------------------------------------------------------
# The layout record
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TableLayout:
    """One table's distribution contract, independent of live buffers.

    Attributes:
      num_slots:    global table length (slots are dense ``0..num_slots-1``).
      dtype:        slot dtype, as a string (JSON-safe).
      axis:         mesh axis name(s) the table shards over, major-to-minor
                    (empty tuple = local table).
      replica_axes: mesh axes holding identical shard copies.
      mesh_axes:    the full mesh shape as ``((name, size), ...)`` in mesh
                    order — the extents the owner-major layout was derived
                    under.  Re-deriving the same contract under different
                    extents is exactly what `reshard` does.
    """

    num_slots: int
    dtype: str
    axis: Tuple[str, ...] = ()
    replica_axes: Tuple[str, ...] = ()
    mesh_axes: Tuple[Tuple[str, int], ...] = ()

    # --- derived extents --------------------------------------------------
    @property
    def axis_sizes(self) -> Dict[str, int]:
        return dict(self.mesh_axes)

    def _size(self, names: Sequence[str]) -> int:
        sizes = self.axis_sizes
        return math.prod(sizes.get(n, 1) for n in names)

    @property
    def n_shards(self) -> int:
        return self._size(self.axis)

    @property
    def n_replicas(self) -> int:
        return self._size(self.replica_axes)

    @property
    def m_local(self) -> int:
        if self.num_slots % max(self.n_shards, 1):
            raise ValueError(
                f"{self.num_slots} slots do not divide over "
                f"{self.n_shards} shards ({self.axis!r} x {self.mesh_axes!r})")
        return self.num_slots // max(self.n_shards, 1)

    @property
    def is_sharded(self) -> bool:
        return bool(self.axis)

    # --- per-device derivations (numpy; device order = mesh C-order) ------
    def _coords(self, flat: int) -> Dict[str, int]:
        names = [n for n, _ in self.mesh_axes]
        sizes = [s for _, s in self.mesh_axes]
        return dict(zip(names, np.unravel_index(flat, sizes)))

    def _rank_over(self, names: Sequence[str], coords: Dict[str, int]) -> int:
        rank = 0
        for n in names:
            rank = rank * self.axis_sizes[n] + coords[n]
        return rank

    def shard_of_device(self, flat: int) -> int:
        """Owner-major shard id held by the device at mesh flat index."""
        return self._rank_over(self.axis, self._coords(flat))

    def replica_rank_of_device(self, flat: int) -> int:
        return self._rank_over(self.replica_axes, self._coords(flat))

    def arrival_rank_of_device(self, flat: int) -> int:
        """The device-rank arrival order: lexicographic over
        ``replica_axes + axis`` (major to minor) — the rank at which this
        device's local batch lands in the serialized-oracle concatenation."""
        return self._rank_over(self.replica_axes + self.axis,
                               self._coords(flat))

    def arrival_order(self) -> np.ndarray:
        """Mesh flat device indices sorted by arrival rank (the order a
        serialized oracle must concatenate per-device batches in)."""
        n = self._size([n for n, _ in self.mesh_axes])
        ranks = [self.arrival_rank_of_device(i) for i in range(n)]
        return np.argsort(np.asarray(ranks), kind="stable")

    def rows_of_shard(self, shard: int) -> Tuple[int, int]:
        """[start, end) global row range owned by a shard."""
        return shard * self.m_local, (shard + 1) * self.m_local

    # --- constructors / serialization -------------------------------------
    @classmethod
    def from_mesh(cls, mesh, *, num_slots: int, dtype,
                  axis: AxisNames, replica_axes: AxisNames = ()
                  ) -> "TableLayout":
        mesh_axes = tuple((str(n), int(s))
                          for n, s in zip(mesh.axis_names,
                                          mesh.devices.shape))
        lay = cls(num_slots=int(num_slots), dtype=str(jnp.dtype(dtype)),
                  axis=norm_axes(axis), replica_axes=norm_axes(replica_axes),
                  mesh_axes=mesh_axes)
        known = lay.axis_sizes
        for name in lay.axis + lay.replica_axes:
            if name not in known:
                raise ValueError(f"axis {name!r} not on mesh "
                                 f"{list(known)!r}")
        lay.m_local  # divisibility check
        return lay

    @classmethod
    def from_table(cls, table, mesh=None) -> "TableLayout":
        """Derive the layout of a live `AtomicTable` handle.

        ``mesh`` defaults to the mesh of the table's array sharding (a
        distributed array outside shard_map carries it); a local table needs
        no mesh.  Duck-typed on the handle (``data``/``axis``/
        ``replica_axes``) so this module stays import-light.
        """
        axis = norm_axes(table.axis)
        if not axis:
            return cls(num_slots=int(table.data.shape[0]),
                       dtype=str(table.data.dtype))
        if mesh is None:
            sharding = getattr(table.data, "sharding", None)
            mesh = getattr(sharding, "mesh", None)
        if mesh is None:
            raise ValueError(
                "cannot derive the layout of a sharded table without a "
                "mesh: pass mesh=..., or use an array placed with a "
                "NamedSharding")
        return cls.from_mesh(mesh, num_slots=int(table.data.shape[0]),
                             dtype=table.data.dtype, axis=axis,
                             replica_axes=table.replica_axes)

    def to_dict(self) -> Dict:
        return {"num_slots": self.num_slots, "dtype": self.dtype,
                "axis": list(self.axis),
                "replica_axes": list(self.replica_axes),
                "mesh_axes": [[n, s] for n, s in self.mesh_axes]}

    @classmethod
    def from_dict(cls, d: Dict) -> "TableLayout":
        return cls(num_slots=int(d["num_slots"]), dtype=str(d["dtype"]),
                   axis=tuple(d.get("axis") or ()),
                   replica_axes=tuple(d.get("replica_axes") or ()),
                   mesh_axes=tuple((str(n), int(s))
                                   for n, s in d.get("mesh_axes") or ()))

    def spec(self):
        """The PartitionSpec realizing this layout (owner-major over
        ``axis``, replicated elsewhere)."""
        from jax.sharding import PartitionSpec as P
        if not self.axis:
            return P()
        return P(self.axis if len(self.axis) > 1 else self.axis[0])

    def named_sharding(self, mesh) -> "jax.sharding.NamedSharding":
        from jax.sharding import NamedSharding
        return NamedSharding(mesh, self.spec())

    def __repr__(self):
        where = (f"sharded over {self.axis!r}" if self.axis else "local")
        rep = (f", replicated over {self.replica_axes!r}"
               if self.replica_axes else "")
        return (f"TableLayout({self.num_slots} x {self.dtype}, {where}{rep}, "
                f"mesh={dict(self.mesh_axes)!r})")
