"""Tiered KV-cache manager: per-slot cache blocks in HBM, cold sessions
in the staging/pool tiers.

The decode batch's caches live as ONE batched pytree on device (the HBM
tier) with ``n_slots`` lanes on the per-leaf batch axis (layer-stacked
groups put batch at axis 1 — the axis map comes from the cache
descriptors via ``train.step.cache_batch_axes``).  Slot surgery is jitted
primitives:

* ``write_slot(slot, cache1)`` — insert a single-sequence cache (fresh
  prefill, or a restored cold session) into a lane;
* ``read_slot(slot)``         — extract a whole lane as a single-sequence
  cache (legacy whole-lane commits, migration, spilling);
* ``read_block(slot, lo)``    — extract one token block of a lane:
  tokens ``[lo, lo + block_tokens)`` of every token-axis leaf, shaped
  like ``BlockPager.block_template`` (one fixed-shape program whatever
  the block; a block cut short by the lane's end gets a second one);
* ``read_state(slot)``        — extract a lane's recurrent-state leaves
  (no token axis), whole.

``read_blocks(slot, blocks)`` dispatches ``read_block`` per planned
block (+ ``read_state``) and fetches the results to the host in one
batched copy: the paged session commit's device-to-host traffic is the
dirty blocks' bytes, not the lane's.

Cold sessions leave HBM through the CXL0 tiers (``dsm.tiers``):

* ``stage(name, cache1)``            — LStore into the worker's host
  object tier; from there the FliT committer RFlushes it durably as part
  of a session commit (serve.sessions);
* ``spill(name, cache1, peer=...)``  — additionally RStore the copy into
  a PEER worker's host buffer (survives OUR crash without pool I/O);
* ``spill_durable(name, cache1)``    — immediate sharded RFlush into the
  pool, leaves partitioned into byte-balanced blocks
  (``pool.partition_leaves`` under ``rflush_sharded``); returns the
  manifest entry needed to restore;
* ``spill_auto(name, cache1, peer=...)`` — cost-driven routing: the
  placement policy (``dsm.placement``) prices staging vs (sharded) pool
  for this cache's size under the active emulated topology and picks the
  cheaper tier — the decision is logged on the policy;
* ``restore(name, entry=...)``       — best tier first: HBM host object,
  then peer staging, then pool — byte-identical round-trip in all cases
  (streamed ``.cxl0`` frames store each leaf's raw bytes + dtype/shape
  header, so bf16 et al. survive exactly; see ``dsm.stream``).

This manager moves WHOLE single-sequence caches between tiers.  The
serving engine's durable path no longer uses that granularity: it
commits fixed-size token-axis blocks through ``serve.paging`` +
``SessionStore.commit_paged`` so cold-session state is O(blocks
touched).  Whole-lane spill/restore stays as the legacy layout
(``ServeEngine(paged=False)``, equivalence-tested) and as the
mid-decode HBM-pressure escape hatch.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.dsm.meshio import assemble_leaves
from repro.dsm.pool import manifest_entry, partition_leaves
from repro.dsm.tiers import TierManager
from repro.serve.paging import BLOCK_TOKENS, STATE_BLOCK, cache_token_axes
from repro.train.step import cache_batch_axes


class TieredKVCache:
    def __init__(self, bundle, n_slots: int, t_max: int,
                 tiers: Optional[TierManager] = None,
                 placement=None, parallel=None,
                 block_tokens: int = BLOCK_TOKENS):
        self.n_slots = n_slots
        self.t_max = t_max
        self.block_tokens = block_tokens
        self.tiers = tiers
        #: cost-driven spill routing (repro.dsm.placement.PlacementPolicy);
        #: when set, ``spill_auto`` replaces the caller-chosen tier.
        self.placement = placement
        #: ParallelCtx (parallel.sharding): when its mesh is live, the
        #: batched KV lanes are device-sharded per the cache descriptors'
        #: logical axes (heads on the model axis), spill block counts
        #: default to the mesh's device count, and durable spills run
        #: device-local (each block pipeline drains its devices' buffers
        #: — no host gather of the whole lane).
        self.parallel = parallel
        self.axes = cache_batch_axes(bundle)
        # zero-initialized batched cache (cache descs are init="zeros")
        self.caches = bundle.init_caches(jax.random.PRNGKey(0), n_slots,
                                         t_max)
        if parallel is not None and getattr(parallel, "mesh", None) \
                is not None:
            from repro.models.params import tree_map_descs
            from repro.parallel.sharding import spec_for
            shardings = tree_map_descs(
                lambda d: jax.sharding.NamedSharding(
                    parallel.mesh, spec_for(parallel, d)),
                bundle.cache_descs(n_slots, t_max))
            self.caches = jax.tree_util.tree_map(
                jax.device_put, self.caches, shardings)
        self._template1 = bundle.abstract_caches(1, t_max)
        tm = jax.tree_util.tree_map

        def _write(full, one, slot):
            return tm(lambda f, o, a: jax.lax.dynamic_update_slice_in_dim(
                f, o.astype(f.dtype), slot, axis=a), full, one, self.axes)

        def _read(full, slot):
            return tm(lambda f, a: jax.lax.dynamic_slice_in_dim(
                f, slot, 1, axis=a), full, self.axes)

        self._write = jax.jit(_write, donate_argnums=0)
        self._read = jax.jit(_read)

        batch = [int(a) for a in jax.tree_util.tree_leaves(self.axes)]
        tok = [int(a) for a in
               jax.tree_util.tree_leaves(cache_token_axes(bundle))]
        tok_idx = [i for i, a in enumerate(tok) if a >= 0]
        state_idx = [i for i, a in enumerate(tok) if a < 0]
        self._has_state = bool(state_idx)

        def _read_span(full, slot, lo, n):
            fl = jax.tree_util.tree_leaves(full)
            out = []
            for i in tok_idx:
                start, size = [0] * fl[i].ndim, list(fl[i].shape)
                start[batch[i]], size[batch[i]] = slot, 1
                start[tok[i]], size[tok[i]] = lo, n
                out.append(jax.lax.dynamic_slice(fl[i], start, size))
            return out

        def _read_state(full, slot):
            fl = jax.tree_util.tree_leaves(full)
            return [jax.lax.dynamic_slice_in_dim(fl[i], slot, 1,
                                                 axis=batch[i])
                    for i in state_idx]

        self._read_span = jax.jit(_read_span, static_argnums=3)
        self._read_state = jax.jit(_read_state)

    # -- HBM slot surgery ----------------------------------------------------
    def write_slot(self, slot: int, cache1: Any):
        """Insert a single-sequence cache into lane ``slot``."""
        self.caches = self._write(self.caches, cache1,
                                  jnp.int32(slot))

    def read_slot(self, slot: int) -> Any:
        """Extract lane ``slot`` as a single-sequence cache."""
        return self._read(self.caches, jnp.int32(slot))

    def read_block(self, slot: int, lo: int) -> List[Any]:
        """Device slices of lane ``slot`` on tokens ``[lo, lo +
        block_tokens)``, one per token-axis leaf.  The length is fixed,
        so one compiled program serves every block — except a block the
        lane's end cuts short (``t_max % block_tokens``), which gets the
        ``t_max - lo`` tokens there are (``dynamic_slice`` would clamp
        its start and return the wrong span)."""
        n = min(self.block_tokens, self.t_max - lo)
        assert n > 0, (lo, self.t_max)
        return self._read_span(self.caches, np.int32(slot), np.int32(lo),
                               n)

    def read_state(self, slot: int) -> List[Any]:
        """Device copies of lane ``slot``'s recurrent-state leaves."""
        return self._read_state(self.caches, np.int32(slot))

    def read_blocks(self, slot: int, blocks: List[int]
                    ) -> Dict[int, List[np.ndarray]]:
        """Host copies of the token blocks ``blocks`` of lane ``slot``
        (+ ``STATE_BLOCK``, the recurrent state, when the arch has any):
        every read is dispatched before ONE batched fetch.  A block cut
        short by the lane's end comes back short; ``BlockPager.pad_block``
        pads it."""
        reads = {blk: self.read_block(slot, blk * self.block_tokens)
                 for blk in blocks}
        if self._has_state:
            reads[STATE_BLOCK] = self.read_state(slot)
        host = iter(assemble_leaves(
            [a for parts in reads.values() for a in parts]))
        return {blk: [next(host) for _ in parts]
                for blk, parts in reads.items()}

    @property
    def template1(self):
        """Single-sequence cache pytree prototype (for pool unflattening)."""
        return self._template1

    # -- tier movement -------------------------------------------------------
    def _need_tiers(self) -> TierManager:
        assert self.tiers is not None, "no TierManager configured"
        return self.tiers

    def stage(self, name: str, cache1: Any) -> int:
        """LStore a session cache into the host object tier; returns the
        version the next RFlush/commit of ``name`` will write."""
        t = self._need_tiers()
        t.lstore(name, cache1)
        return t.versions[name]

    def spill(self, name: str, cache1: Any, *,
              peer: Optional[TierManager] = None) -> int:
        """Evict to the host tier; optionally RStore-replicate to a peer's
        staging buffer (the cache then survives our crash without having
        been flushed)."""
        version = self.stage(name, cache1)
        if peer is not None:
            self._need_tiers().rstore(name, peer, tag=version)
        return version

    def spill_durable(self, name: str, cache1: Any,
                      n_blocks: Optional[int] = None) -> dict:
        """Evict straight to the pool: sharded RFlush over byte-balanced
        leaf blocks.  Returns the manifest entry for ``restore``."""
        t = self._need_tiers()
        self.stage(name, cache1)
        n = n_blocks or len(self.block_layout())
        obj = t.rflush_sharded(name, n,
                               device_local=self.parallel is not None)
        return manifest_entry(obj)

    def spill_auto(self, name: str, cache1: Any, *,
                   peer: Optional[TierManager] = None) -> dict:
        """Cost-driven eviction: the placement policy prices staging vs
        pool for THIS cache's size under the active topology and routes
        accordingly (decision logged on the policy).  Returns
        ``{"tier": ..., ...}`` — pass ``entry`` (pool spills) back into
        ``restore``.  A staging choice with no peer degrades to the host
        object tier alone (still restorable while we live)."""
        assert self.placement is not None, "no PlacementPolicy configured"
        from repro.dsm.emu import tree_nbytes
        nbytes = tree_nbytes(cache1)
        tier = self.placement.choose_spill(name, nbytes)
        if tier == "staging":
            return {"tier": "staging", "nbytes": nbytes,
                    "version": self.spill(name, cache1, peer=peer)}
        n = self.placement.choose_shards(nbytes, name)
        return {"tier": "pool", "nbytes": nbytes,
                "entry": self.spill_durable(name, cache1, n_blocks=n)}

    def restore(self, name: str, entry: Optional[dict] = None,
                *, drop_hot: bool = False) -> Optional[Any]:
        """Bring a session cache back, best tier first: the host object
        tier (still resident), then OUR staging buffer (a peer RStored it
        here), then the pool (needs the manifest ``entry`` from
        ``spill_durable`` or a session-commit manifest).  Returns None if
        no tier holds it."""
        t = self._need_tiers()
        if name in t.hbm:
            tree = t.hbm[name]
            if drop_hot:
                t.ldiscard(name)
            return tree
        staged = t.rload(name)
        if staged is not None:
            return staged
        if entry is not None:
            return t.pool.read_entry(name, entry, self._template1)
        return None

    def discard(self, name: str):
        """Drop a session cache from the host tier (session finished)."""
        self._need_tiers().ldiscard(name)

    # -- block layout --------------------------------------------------------
    def block_layout(self, n_blocks: Optional[int] = None) -> List[List[int]]:
        """Byte-balanced partition of the per-slot cache leaves into spill
        blocks (``pool.partition_leaves`` — the same layout
        ``rflush_sharded`` writes).  Default block count: one per device
        of the configured mesh (else one per local device), clamped by
        the leaf count."""
        leaves = jax.tree_util.tree_leaves(self._template1)
        nbytes = [int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves]
        mesh = getattr(self.parallel, "mesh", None)
        if n_blocks:
            n = n_blocks
        elif mesh is not None:
            from repro.dsm.meshio import mesh_device_count
            n = mesh_device_count(mesh)
        else:
            n = max(jax.local_device_count(), 1)
        return partition_leaves(nbytes, n)
