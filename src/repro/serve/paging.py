"""Paged KV layout: a session's cache is durable as fixed-size
token-axis blocks.

Every cache leaf that HAS a token axis (logical axis name ``seq_kv``
in the cache descriptors — attention K/V; recurrent mamba/rwkv state
has none and rides in a separate always-dirty STATE block) is split
into fixed-``block_tokens`` spans:

* block ``k`` of session ``rid`` covers decode positions
  ``[k*bt, (k+1)*bt)`` and lives in the pool as object
  ``kv/<rid>/b<k>`` — a LIST of the per-leaf token slices, written
  through the same LStore -> RFlush path as everything else, so it gets
  the PR-7 streamed ``.cxl0`` frames + ``SpillArena`` buffers for free;
* the decode cache is append-only along the token axis, so a block is
  IMMUTABLE once the session's position passes its upper edge — a
  session commit re-flushes only the blocks its position touched since
  the last commit (the partial tail + the recurrent STATE block), making
  cold state O(blocks touched) instead of O(whole cache).  The commit
  plans those blocks from the position and the block table alone
  (``BlockPager.dirty_blocks``, no cache bytes) and reads just their
  token spans on the device (``TieredKVCache.read_blocks``), so the
  device-to-host copy is O(blocks touched) too, never a whole lane;
* a per-session **block table** (ordinal -> ``BlockRef``) records each
  block's pool object name, version-entry and valid-token count.  The
  table rides in the session-commit manifest meta, and the manifest's
  object dict carries BOTH the freshly flushed blocks and the carried
  entries of every clean block (``SessionStore`` merges them in a
  delegated completeOp) — so any single manifest is a complete,
  self-contained description of every live session's cache.

**Free-list allocator.**  ``BlockAllocator`` models the pool's hot
block-frame budget: every materialized block holds one frame id
(``bid``), freed when its session retires.  Admission at fleet scale is
bounded by frames, not whole-lane caches — a million idle sessions cost
table entries, not HBM lanes.  ``alloc``/``free``/``adopt`` never
double-assign a frame (property-tested); ``adopt`` claims a specific id
recorded in a recovered or migrated-in block table.

**Content-addressed prefix blocks.**  A prompt-pure block (entirely
inside the prompt) is a deterministic function of (arch key, prompt
prefix up to its upper edge) — two sessions sharing a prompt prefix
share those block BYTES.  ``prefix_hash`` keys them as pool objects
``kvblk/<hash>`` published once (plus a ``kvhead/<hash-of-full-prompt>``
object holding the partial tail + recurrent state + first sampled
token), so a second engine serving the same prompt restores blocks and
skips the prefill entirely (serve.sessions ``publish_prefix`` /
``load_prefix``).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.dsm.meshio import assemble_leaves

BLOCK_TOKENS = 16
#: ordinal of the recurrent-state pseudo-block (leaves with no token
#: axis — mamba conv/ssm state, rwkv state).  Always dirty while the
#: session runs: recurrent state genuinely changes every token.
STATE_BLOCK = -1


def cache_token_axes(bundle):
    """Per-leaf index of the TOKEN axis (logical name ``seq_kv``) in the
    decode-cache pytree, or -1 for leaves without one (recurrent state).
    Mirror of ``train.step.cache_batch_axes`` — slot caches are sliced
    into token blocks by descriptor axis names, never fixed positions."""
    from repro.models.params import tree_map_descs
    return tree_map_descs(
        lambda d: d.logical.index("seq_kv") if "seq_kv" in d.logical else -1,
        bundle.cache_descs(1, 2))


def block_object_name(rid: str, blk: int, ns: str = "") -> str:
    """Pool object name of session ``rid``'s block ``blk`` under an
    engine namespace (``e<i>/`` in a fleet, empty for engine 0)."""
    if blk == STATE_BLOCK:
        return f"{ns}kv/{rid}/state"
    return f"{ns}kv/{rid}/b{blk}"


def shared_block_name(h: int) -> str:
    """Content-addressed prompt-prefix block (cross-engine, unnamespaced
    on purpose: the pool is the shared substrate)."""
    return f"kvblk/{h:08x}"


def shared_head_name(h: int) -> str:
    """Content-addressed prefill head: partial tail block + recurrent
    state + the first sampled token, keyed by the FULL prompt hash."""
    return f"kvhead/{h:08x}"


def prefix_hash(key: str, tokens: Sequence[int], block_tokens: int) -> int:
    """Deterministic content address of a prompt prefix under one model
    identity (``key`` folds arch + params seed: reuse across engines is
    only sound when their weights are bit-identical)."""
    doc = f"{key}|bt{block_tokens}|".encode()
    return zlib.crc32(np.asarray(tokens, np.int32).tobytes(), zlib.crc32(doc))


class OutOfBlocksError(RuntimeError):
    """The pool's hot block-frame budget is exhausted — admission control
    should shed or migrate load instead of overcommitting frames."""


class BlockAllocator:
    """Free-list over ``n_blocks`` frame ids.  The invariant (property-
    tested in tests/test_paging.py): a frame is owned by at most one
    holder at any time — ``alloc``/``adopt`` never hand out an id that is
    already assigned, ``free`` rejects ids it does not own."""

    def __init__(self, n_blocks: int):
        assert n_blocks >= 1, n_blocks
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self._owned: set = set()

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def allocated(self) -> frozenset:
        return frozenset(self._owned)

    def alloc(self) -> int:
        if not self._free:
            raise OutOfBlocksError(
                f"all {self.n_blocks} block frames are assigned")
        bid = self._free.pop()
        self._owned.add(bid)
        return bid

    def adopt(self, bid: int):
        """Claim a SPECIFIC frame id — a recovered or migrated-in block
        table re-asserts ownership of the frames it recorded."""
        if not (0 <= bid < self.n_blocks):
            raise ValueError(f"bid {bid} outside pool of {self.n_blocks}")
        if bid in self._owned:
            raise OutOfBlocksError(f"bid {bid} is already assigned")
        self._owned.add(bid)
        self._free.remove(bid)

    def free(self, bid: int):
        if bid not in self._owned:
            raise ValueError(f"bid {bid} is not assigned")
        self._owned.discard(bid)
        self._free.append(bid)


@dataclasses.dataclass
class BlockRef:
    """One block-table entry: where block ``blk`` of a session lives."""
    blk: int                      # ordinal (STATE_BLOCK for recurrent state)
    bid: int                      # allocator frame id
    tokens: int                   # valid tokens in the span (0 for STATE)
    name: str                     # pool object name (may be a shared kvblk/)
    entry: Optional[dict] = None  # manifest entry once durable

    def to_meta(self) -> dict:
        return {"blk": self.blk, "bid": self.bid, "tokens": self.tokens,
                "name": self.name, "entry": self.entry}

    @classmethod
    def from_meta(cls, d: dict) -> "BlockRef":
        return cls(blk=int(d["blk"]), bid=int(d["bid"]),
                   tokens=int(d["tokens"]), name=d["name"],
                   entry=d.get("entry"))


@dataclasses.dataclass
class BlockTable:
    """Per-session block map.  ``refs[k]`` covers tokens
    ``[k*bt, (k+1)*bt)``; ``refs[STATE_BLOCK]`` is the recurrent-state
    pseudo-block.  Round-trips bit-identically through manifest meta
    (property-tested)."""
    refs: Dict[int, BlockRef] = dataclasses.field(default_factory=dict)

    def to_meta(self) -> dict:
        return {"blocks": [self.refs[k].to_meta()
                           for k in sorted(self.refs)]}

    @classmethod
    def from_meta(cls, d: dict) -> "BlockTable":
        t = cls()
        for bd in d.get("blocks", ()):
            ref = BlockRef.from_meta(bd)
            t.refs[ref.blk] = ref
        return t

    def bids(self) -> List[int]:
        return [r.bid for r in self.refs.values()]

    def entries(self) -> Dict[str, dict]:
        """Manifest entries of every DURABLE block — what the session
        store carries forward into each completeOp so one manifest
        references the whole cache without re-flushing clean blocks."""
        return {r.name: r.entry for r in self.refs.values()
                if r.entry is not None}


class BlockPager:
    """Block geometry, dirty-block planning, and host-side slicing/
    assembly between whole slot caches and token blocks.  Planning
    (``dirty_blocks``) reads only positions and block tables; the
    session commit reads the planned spans on the device
    (``TieredKVCache.read_blocks``, the same token slices bit for bit)
    and never slices here.  Host slicing of a whole cache
    (``slice_dirty``, ``slice_block``) serves the paths that hold one
    anyway — migration, prefix publication — and restore assembles
    blocks back into a whole cache."""

    def __init__(self, bundle, t_max: int,
                 block_tokens: int = BLOCK_TOKENS):
        assert block_tokens >= 1, block_tokens
        self.t_max = t_max
        self.block_tokens = block_tokens
        #: single-sequence cache pytree prototype (abstract shapes)
        self.template = bundle.abstract_caches(1, t_max)
        self._leaves, self._treedef = jax.tree_util.tree_flatten(
            self.template)
        axes = jax.tree_util.tree_leaves(cache_token_axes(bundle))
        assert len(axes) == len(self._leaves)
        #: per-leaf token axis (-1: recurrent state) and the leaf indices
        #: on each side — the one source of block geometry, which the
        #: device-side block reads (``TieredKVCache``) share bit for bit
        self.token_axes = [int(a) for a in axes]
        self.tok_idx = [i for i, a in enumerate(self.token_axes) if a >= 0]
        self.state_idx = [i for i, a in enumerate(self.token_axes) if a < 0]

        def _blk_struct(i):
            l = self._leaves[i]
            shape = list(l.shape)
            shape[self.token_axes[i]] = block_tokens
            return jax.ShapeDtypeStruct(tuple(shape), l.dtype)

        #: pytree template of one block object (list of token slices) —
        #: independent of t_max, so blocks outlive lane-geometry changes
        self.block_template = [_blk_struct(i) for i in self.tok_idx]
        self.state_template = [self._leaves[i] for i in self.state_idx]
        #: head object = tail block slices + recurrent state + token0
        self.head_template = (self.block_template + self.state_template
                              + [jax.ShapeDtypeStruct((1,), np.int32)])

    # -- geometry ------------------------------------------------------------
    @property
    def token_nbytes(self) -> int:
        """Cache bytes per decode position across every token-axis leaf —
        the unit the fleet cost model prices admissions/migrations in."""
        per = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in self.block_template)
        return max(1, per // self.block_tokens)

    def n_blocks(self, pos: int) -> int:
        return -(-pos // self.block_tokens) if pos > 0 else 0

    def tokens_in_block(self, blk: int, pos: int) -> int:
        return max(0, min(self.block_tokens, pos - blk * self.block_tokens))

    # -- slicing -------------------------------------------------------------
    def _host_leaves(self, cache1: Any) -> List[np.ndarray]:
        leaves = jax.tree_util.tree_leaves(cache1)
        assert len(leaves) == len(self._leaves), \
            (len(leaves), len(self._leaves))
        # assemble_leaves copies mesh-sharded lanes per device buffer (and
        # passes host/unsharded leaves through np.asarray-equivalently),
        # so paged spills of a device-sharded cache never demand one
        # monolithic transfer — bit-identical output either way
        return assemble_leaves(leaves)

    def pad_block(self, parts: List[np.ndarray]) -> List[np.ndarray]:
        """Zero-pad a block's token slices to ``block_tokens`` (uniform
        shape: one template fits every block incl. one cut short by the
        lane's end, and a partial block's unseen positions are zeros in
        the source cache anyway)."""
        bt = self.block_tokens
        out = []
        for i, part in zip(self.tok_idx, parts):
            ax = self.token_axes[i]
            if part.shape[ax] < bt:
                pad = [(0, bt - part.shape[ax]) if j == ax else (0, 0)
                       for j in range(part.ndim)]
                part = np.pad(part, pad)
            out.append(np.ascontiguousarray(part))
        return out

    def slice_block(self, host: List[np.ndarray], blk: int
                    ) -> List[np.ndarray]:
        """Token slices of block ``blk`` over every token-axis leaf,
        zero-padded to ``block_tokens``."""
        lo = blk * self.block_tokens
        return self.pad_block([
            host[i][tuple(slice(lo, lo + self.block_tokens)
                          if j == self.token_axes[i] else slice(None)
                          for j in range(host[i].ndim))]
            for i in self.tok_idx])

    def slice_state(self, host: List[np.ndarray]) -> List[np.ndarray]:
        return [np.ascontiguousarray(host[i]) for i in self.state_idx]

    def dirty_blocks(self, pos: int, table: BlockTable) -> List[int]:
        """Token blocks needing (re)staging for a commit at position
        ``pos``: every span the position entered or grew inside since the
        block was last durable.  Full durable blocks are skipped — the
        append-only token axis makes them immutable, which is the whole
        O(blocks touched) claim.  Needs no cache bytes; the STATE
        pseudo-block (when the arch has one) is always dirty besides."""
        out = []
        for blk in range(self.n_blocks(pos)):
            ref = table.refs.get(blk)
            if ref is None or ref.entry is None \
                    or ref.tokens < self.tokens_in_block(blk, pos):
                out.append(blk)
        return out

    def slice_dirty(self, cache1: Any, pos: int, table: BlockTable
                    ) -> Dict[int, List[np.ndarray]]:
        """Host slices of ``dirty_blocks(pos, table)`` out of a whole
        slot cache, plus the STATE pseudo-block."""
        host = self._host_leaves(cache1)
        out: Dict[int, List[np.ndarray]] = {
            blk: self.slice_block(host, blk)
            for blk in self.dirty_blocks(pos, table)}
        if self.state_idx:
            out[STATE_BLOCK] = self.slice_state(host)
        return out

    # -- assembly ------------------------------------------------------------
    def assemble(self, blocks: Dict[int, List[np.ndarray]]) -> Any:
        """Rebuild a single-slot cache pytree from block payloads.
        Unfilled positions are zeros — exactly what the source cache held
        beyond its decode position, so restore is bit-identical."""
        bt = self.block_tokens
        leaves = [np.zeros(l.shape, l.dtype) for l in self._leaves]
        for blk, parts in blocks.items():
            if blk == STATE_BLOCK:
                for i, part in zip(self.state_idx, parts):
                    leaves[i] = np.asarray(part).astype(
                        leaves[i].dtype, copy=False)
                continue
            lo = blk * bt
            for i, part in zip(self.tok_idx, parts):
                ax = self.token_axes[i]
                hi = min(lo + bt, leaves[i].shape[ax])
                if hi <= lo:
                    continue
                dst = tuple(slice(lo, hi) if j == ax else slice(None)
                            for j in range(leaves[i].ndim))
                src = tuple(slice(0, hi - lo) if j == ax else slice(None)
                            for j in range(np.asarray(part).ndim))
                leaves[i][dst] = np.asarray(part)[src]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    # -- prefix-reuse payloads ----------------------------------------------
    def head_payload(self, host: List[np.ndarray], prompt_len: int,
                     tok0: int) -> List[np.ndarray]:
        """The ``kvhead`` object: the partial tail block of the prompt
        (possibly all-zero when the prompt length is block-aligned) + the
        recurrent state + the first sampled token."""
        tail = prompt_len // self.block_tokens
        return (self.slice_block(host, tail) + self.slice_state(host)
                + [np.asarray([tok0], np.int32)])

    def split_head(self, payload: List[np.ndarray]):
        """Inverse of ``head_payload`` -> (tail slices, state, tok0)."""
        nt = len(self.tok_idx)
        ns = len(self.state_idx)
        tail, state, tok0 = (payload[:nt], payload[nt:nt + ns],
                             int(np.asarray(payload[nt + ns])[0]))
        return tail, state, tok0

    def prompt_block_hashes(self, key: str, prompt: Sequence[int]
                            ) -> List[int]:
        """Content hashes of every FULL prompt-pure block: block k is
        keyed by the prompt prefix up to its upper edge, so two prompts
        sharing a prefix share the early block objects."""
        bt = self.block_tokens
        return [prefix_hash(key, prompt[:(k + 1) * bt], bt)
                for k in range(len(prompt) // bt)]
