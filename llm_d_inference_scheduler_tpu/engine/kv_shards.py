"""Sharded KV staging/pull layout for P/D handoff between sharded engines.

The reference's NIXL connector moves KV between vLLM engines rank-by-rank
(connector_nixlv2.go:191-253: multi-rank transfer descriptors inside
kv_transfer_params). The TPU equivalent here: a staged KV export is a
jax.Array sharded like the engine's pages (``kvcache/pages.py``
``page_sharding``), and the wire unit is the *distinct
index slice* — one single-device array per unique shard, deduped across
replicas and ordered canonically by flattened index offsets so exporter
and importer agree on shard identity without shipping index maps.

Geometry compatibility is decided by :func:`mesh_descriptor` equality:
same mesh axes/shape, same partition spec, same process count, and (for
multi-host) the same process→device layout, which holds for the intended
symmetric P/D deployments (prefill slice and decode slice built the same
way). Anything else falls back to the host-staged path (single-process)
or local prefill (multi-host).
"""

from __future__ import annotations

from typing import Any

import jax

__all__ = ["mesh_descriptor", "shard_key", "local_unique_shards",
           "local_shard_groups"]


def shard_key(shard) -> tuple[int, ...]:
    """Canonical identity of a shard's index slice (replicas collide)."""
    return tuple(int(s.start or 0) for s in shard.index)


def mesh_descriptor(mesh, spec) -> dict[str, Any]:
    """Wire-comparable description of a page sharding's geometry."""
    return {
        "axes": list(mesh.axis_names),
        "mesh_shape": [int(s) for s in mesh.devices.shape],
        "spec": [a if a is None else str(a) for a in tuple(spec)],
        "n_procs": int(jax.process_count()),
    }


def local_unique_shards(arr) -> list[Any]:
    """This process's addressable shard data, one per distinct index slice,
    in canonical (sorted-key) order."""
    seen: dict[tuple, Any] = {}
    for sh in arr.addressable_shards:
        key = shard_key(sh)
        if key not in seen:
            seen[key] = sh.data
    return [seen[k] for k in sorted(seen)]


def local_shard_groups(sharding, global_shape) -> list[tuple[tuple, list]]:
    """[(index_key, [devices])] for this process under ``sharding``:
    the devices of each group hold identical (replicated) data; the first
    device is the pull target, the rest receive copies. Canonical order."""
    groups: dict[tuple, list] = {}
    for dev, idx in sharding.addressable_devices_indices_map(
            tuple(global_shape)).items():
        key = tuple(int(s.start or 0) for s in idx)
        groups.setdefault(key, []).append(dev)
    return [(k, sorted(groups[k], key=lambda d: d.id)) for k in sorted(groups)]
