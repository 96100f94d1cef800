"""Device-local buffer access for mesh-sharded durable commits.

The host-gather flush path materializes a WHOLE pytree on host
(``np.asarray`` per leaf) before any shard pipeline starts — on a real
multi-device mesh that is one big D2H gather whose peak host footprint is
the full state, and it serializes in front of every pipeline.  This
module is the device-native alternative the sharded schedules use when a
``Mesh`` is configured:

* shard ASSIGNMENT is computed from array METADATA only (``leaf_nbytes``
  reads ``.nbytes`` off the jax array, no transfer) — and because a jax
  leaf's ``nbytes`` equals its gathered ``np.asarray(leaf).nbytes``, the
  byte-balanced ``partition_leaves`` assignment is IDENTICAL to the
  host-gather path's at the same shard count.  Same assignment + same
  leaf bytes + same frame writer = bit-identical shard files, CRCs and
  manifests (equivalence-locked by tests/test_mesh_commit.py);
* leaf MATERIALIZATION happens inside each shard's flush pipeline
  (``assemble_leaf``): every per-device buffer is copied host-side
  individually (``np.asarray(shard.data)`` — the device-local view the
  ``.cxl0`` frame writer consumes via ``stream._leaf_view``) and placed
  at its ``Shard.index``, so the full tree never exists on host at once
  and the copies overlap across pipelines;
* ``per_device_nbytes`` exposes the real per-device byte loads (again
  metadata-only) so the placement policy can price shard counts from the
  actual device layout instead of pretending the state is one host blob.

D2H accounting: ``TierManager`` counts gather-path conversions in
``d2h_gather_bytes`` and device-path per-buffer copies in
``d2h_shard_bytes`` — a device-sharded commit must leave
``d2h_gather_bytes`` untouched (asserted in tests), which is the
"no host gather of the full tree" contract in a checkable form.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax


def leaf_nbytes(leaf: Any) -> int:
    """Payload bytes of one leaf from metadata only — no transfer, and
    numerically identical to ``np.asarray(leaf).nbytes`` (what the
    host-gather path feeds ``partition_leaves``)."""
    nb = getattr(leaf, "nbytes", None)
    if nb is None:
        nb = int(np.prod(np.shape(leaf))) * np.dtype(
            getattr(leaf, "dtype", np.float64)).itemsize
    return int(nb)


def _unique_shards(leaf) -> List[Any]:
    """This process's addressable shards, replicas deduplicated (one copy
    per distinct index — replica 0, so every process picks the same)."""
    return [s for s in leaf.addressable_shards if s.replica_id == 0]


def assemble_leaf(leaf: Any, count: Optional[Callable[[int], None]] = None
                  ) -> np.ndarray:
    """Materialize ONE leaf on host from its per-device buffers.

    Called inside a shard pipeline thread, never on the commit path's
    critical section.  A plain ``np.ndarray`` passes through untouched
    (post-recovery state is host-resident); an unsharded / fully
    replicated jax array is one device buffer copied whole; a
    device-sharded array is assembled block-by-block at each
    ``Shard.index`` — each ``np.asarray(shard.data)`` is a single
    device-to-host copy of that device's buffer.  ``count`` (when given)
    receives the copied byte total — the ``d2h_shard_bytes`` feed."""
    if type(leaf) is np.ndarray:
        return leaf
    shards = getattr(leaf, "addressable_shards", None)
    if not shards:                       # np scalar / python number / ...
        a = np.asarray(leaf)
        if count is not None:
            count(a.nbytes)
        return a
    shards = _unique_shards(leaf)
    if len(shards) == 1 and shards[0].data.shape == leaf.shape:
        a = np.asarray(shards[0].data)
        if count is not None:
            count(a.nbytes)
        return a
    out = np.empty(leaf.shape, leaf.dtype)
    copied = 0
    for s in shards:
        block = np.asarray(s.data)       # ONE device buffer -> host
        out[s.index] = block
        copied += block.nbytes
    if count is not None:
        count(copied)
    return out


def assemble_leaves(leaves: List[Any]) -> List[np.ndarray]:
    """``assemble_leaf`` over many leaves as ONE batched fetch: every
    distinct per-device buffer's host copy is started before the first
    is waited on, so the copies overlap instead of running one round
    trip each.  Bit-identical to ``[assemble_leaf(l) for l in leaves]``."""
    for leaf in leaves:
        if type(leaf) is not np.ndarray \
                and getattr(leaf, "addressable_shards", None):
            for s in _unique_shards(leaf):
                s.data.copy_to_host_async()
    return [assemble_leaf(leaf) for leaf in leaves]


def per_device_nbytes(tree: Any) -> List[int]:
    """Real per-device byte loads of a flush of ``tree``, from sharding
    metadata only: for every leaf, each deduplicated shard's bytes are
    charged to its device; host-resident leaves (post-recovery numpy,
    counters) are pooled on one pseudo-device.  Sorted by device id so
    every caller derives the same vector — the ``device_bytes`` input of
    ``placement.choose_shards``."""
    per: Dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if not shards:
            per[-1] = per.get(-1, 0) + leaf_nbytes(leaf)
            continue
        for s in _unique_shards(leaf):
            d = int(s.device.id)
            per[d] = per.get(d, 0) + int(s.data.nbytes)
    return [per[k] for k in sorted(per)]


def mesh_device_count(mesh: Any) -> int:
    """Total devices of a Mesh (the device-derived shard-count ceiling)."""
    return int(np.prod(list(mesh.shape.values()))) if mesh is not None else 1
