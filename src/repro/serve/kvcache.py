"""KV-cache manager: the decode batch's per-slot caches in HBM.

The decode batch's caches live as ONE batched pytree on device with
``n_slots`` lanes on the per-leaf batch axis (layer-stacked groups put
batch at axis 1 — the axis map comes from the cache descriptors via
``train.step.cache_batch_axes``).  Slot surgery is jitted primitives:

* ``write_slot(slot, cache1)`` — insert a single-sequence cache (fresh
  prefill, or a restored session) into a lane;
* ``read_slot(slot)``         — extract a whole lane as a single-sequence
  cache (migration);
* ``read_block(slot, lo)``    — extract one token block of a lane:
  tokens ``[lo, lo + block_tokens)`` of every token-axis leaf, shaped
  like ``BlockPager.block_template`` (one fixed-shape program whatever
  the block; a block cut short by the lane's end gets a second one);
* ``read_state(slot)``        — extract a lane's recurrent-state leaves
  (no token axis), whole.

``read_blocks(slot, blocks)`` dispatches ``read_block`` per planned
block (+ ``read_state``) and fetches the results to the host in one
batched copy: the session commit's device-to-host traffic is the dirty
blocks' bytes, not the lane's.  The block geometry (token axes, which
leaves have one, ``block_tokens``) is the engine's ``BlockPager``'s, so
these reads and the pager's host-side slicing agree bit for bit.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.dsm.meshio import assemble_leaves
from repro.serve.paging import STATE_BLOCK, BlockPager
from repro.train.step import cache_batch_axes


class TieredKVCache:
    def __init__(self, bundle, n_slots: int, pager: BlockPager,
                 parallel=None):
        self.n_slots = n_slots
        self.t_max = pager.t_max
        self.block_tokens = pager.block_tokens
        self.pager = pager
        self.axes = cache_batch_axes(bundle)
        # zero-initialized batched cache (cache descs are init="zeros")
        self.caches = bundle.init_caches(jax.random.PRNGKey(0), n_slots,
                                         self.t_max)
        if parallel is not None and getattr(parallel, "mesh", None) \
                is not None:
            # a ParallelCtx with a live mesh: the lanes are device-sharded
            # per the cache descriptors' logical axes (heads on the model
            # axis)
            from repro.models.params import tree_map_descs
            from repro.parallel.sharding import spec_for
            shardings = tree_map_descs(
                lambda d: jax.sharding.NamedSharding(
                    parallel.mesh, spec_for(parallel, d)),
                bundle.cache_descs(n_slots, self.t_max))
            self.caches = jax.tree_util.tree_map(
                jax.device_put, self.caches, shardings)
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
        tok, tok_idx, state_idx = (pager.token_axes, pager.tok_idx,
                                   pager.state_idx)

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
        if self.pager.state_idx:
            reads[STATE_BLOCK] = self.read_state(slot)
        host = iter(assemble_leaves(
            [a for parts in reads.values() for a in parts]))
        return {blk: [next(host) for _ in parts]
                for blk, parts in reads.items()}

    @property
    def template1(self):
        """Single-sequence cache pytree prototype (for pool unflattening)."""
        return self.pager.template
