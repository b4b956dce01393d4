"""Elastic scaling: reshard a checkpoint onto a different mesh.

The fleet contract (DESIGN.md §7): when a pod is lost (or added), training
restarts on a new mesh whose `data` (or `pod`) extent changed.  Because
checkpoints store host arrays + logical metadata, restoring is a pure
device_put under the *new* mesh's shardings — no resharding collectives, no
dependence on the writer's topology.  The deterministic data pipeline then
resumes from the checkpointed step with the new shard count.

`AtomicTable` state rides the same contract: table leaves in `like` restore
through `repro.atomics.reshard` (the host-roundtrip migration path — the
old mesh is gone by definition here), re-deriving the owner-major layout
and arrival order under the new extents instead of replaying RMW history.
Live tables — no checkpoint in the loop — migrate with
:func:`reshard_tables` (`atomics.reshard.migrate` over a state tree), which
the recovery state machine (`runtime.fault_tolerance`) invokes on elastic
restarts.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

log = logging.getLogger("repro.runtime")

from repro.atomics.table import AtomicTable
from repro.checkpoint import ckpt as ckpt_lib
from repro.launch import shardings as sh
from repro.models.config import ModelConfig
from repro.sharding import make_mesh, use_mesh


def _is_table(x) -> bool:
    return isinstance(x, AtomicTable)


def reshard_restore(ckpt_dir: str, step: int, like: Any, cfg: ModelConfig,
                    new_mesh: Mesh, rules: Optional[Dict] = None,
                    shape_kind: str = "train"):
    """Restore `like`-structured state under `new_mesh` shardings.

    `like` must contain a "params" entry (model parameters); every params
    leaf gets its divisibility-aware NamedSharding computed against the NEW
    mesh; other entries ("opt" moments/master) inherit the param shardings
    leaf-wise where shapes match, else replicate.  `AtomicTable` leaves
    reshard through `atomics.reshard.restore_table` under the new mesh
    (their sharding comes from the handle's own axis contract, not the
    shape-matching heuristic).
    """
    rules = rules if rules is not None else sh.arch_rules(cfg, new_mesh,
                                                          shape_kind)
    with use_mesh(new_mesh, rules):
        params_abs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), like["params"],
            is_leaf=_is_table)
        params_sh = sh.params_shardings(cfg, params_abs, new_mesh, rules)
        shard_by_shape: Dict[tuple, Any] = {}
        for leaf, s in zip(jax.tree.leaves(params_abs),
                           jax.tree.leaves(params_sh)):
            shard_by_shape.setdefault((leaf.shape, str(leaf.dtype)), s)

        flat_like, _ = jax.tree_util.tree_flatten(like, is_leaf=_is_table)
        flat_sh = []
        for leaf in flat_like:
            if _is_table(leaf):
                continue  # ckpt.restore never consults sharding_fn for these
            key = (leaf.shape, str(leaf.dtype))
            alt = (leaf.shape, "float32")  # fp32 master of a bf16 param
            s = shard_by_shape.get(key) or shard_by_shape.get(alt)
            flat_sh.append(s if s is not None
                           else NamedSharding(new_mesh,
                                              jax.sharding.PartitionSpec()))
        it = iter(flat_sh)

        def sharding_fn(key, ref):
            return next(it)

        state, extra = ckpt_lib.restore(ckpt_dir, step, like,
                                        sharding_fn=sharding_fn)
    return state, extra


def reshard_tables(state: Any, new_mesh: Mesh, *, path: str = "auto",
                   spec=None) -> Any:
    """Migrate every live `AtomicTable` in a state tree onto `new_mesh`.

    The no-checkpoint elastic route: tables move through
    `atomics.reshard.migrate` (cost-model-chosen path — the in-collective
    slot exchange when the fleet is unchanged, the host roundtrip when it
    grew or shrank), keeping their axis contract where the new mesh still
    carries those axes.  Non-table leaves pass through untouched.

    Degradation ladder: this runs *inside the recovery loop*, where a
    failure means another restore/replay cycle — so a broken migration
    path must degrade, not crash.  Per table: the requested path (the
    in-collective ``exchange`` under ``"auto"``) -> the host-roundtrip
    ``device_put`` (always topologically feasible) -> a plain **local
    handle** (host gather, contract dropped) as the floor.  Each
    degradation is logged; the data is bit-identical on every rung, only
    placement quality degrades.
    """
    from repro.atomics import reshard as reshard_lib

    def one(x):
        if not _is_table(x) or not x.is_sharded:
            return x
        try:
            return reshard_lib.migrate(x, new_mesh, path=path, spec=spec)
        except Exception as e:  # noqa: BLE001 — mid-recovery, degrade
            log.warning("table migration (path=%s) onto %s failed (%s: %s); "
                        "degrading to device_put", path, new_mesh,
                        type(e).__name__, e)
        if path != "device_put":
            try:
                return reshard_lib.migrate(x, new_mesh, path="device_put",
                                           spec=spec)
            except Exception as e:  # noqa: BLE001
                log.warning("device_put migration failed too (%s: %s); "
                            "degrading to a local handle",
                            type(e).__name__, e)
        return AtomicTable(jnp.asarray(np.asarray(x.data)))

    return jax.tree_util.tree_map(one, state, is_leaf=_is_table)


def survivors_mesh(axis_sizes: Dict[str, int], lost_data_shards: int = 0):
    """Build the post-failure mesh: shrink the data axis by the lost shards
    (straggler/failed hosts are excluded; see runtime.fault_tolerance)."""
    sizes = dict(axis_sizes)
    sizes["data"] = sizes.get("data", 1) - lost_data_shards
    if sizes["data"] < 1:
        raise ValueError("no data shards left")
    names = tuple(sizes)
    return make_mesh(tuple(sizes[n] for n in names), names)
