"""Durable session store: serving state committed through the FliT path.

One *session commit* at decode tick ``s`` is the paper's Alg. 2 over the
serving worker's live state, exactly as a training checkpoint commit but
with a DYNAMIC object set.  Two layouts share the machinery:

* **paged** (the default engine path since the fleet refactor) — one
  pool object per token BLOCK, ``kv/<rid>/b<k>`` (serve.paging): the
  commit flushes only the blocks a session's position touched since the
  last commit, and the manifest's object dict is the union of those
  fresh flushes and the CARRIED entries of every clean block (merged in
  a delegated completeOp), so any single manifest still describes every
  live cache completely.  The per-session block tables ride in the
  manifest meta next to the session table — tokens, tables and block
  bytes become durable in ONE atomic rename;
* **legacy** (kept for the equivalence tests) — one whole-lane
  ``kv/<rid>`` object per running session, re-flushed every commit.

A killed serving worker restarts and calls ``recover()``: the newest
manifest for THIS engine (fleet manifests are tagged ``engine: i`` and
block objects live under an ``e<i>/`` namespace) whose every referenced
object CRC-validates wins; torn commits fall back to older manifests
exactly as in training recovery.  Finished sessions come back as
results; running sessions come back as (tokens emitted, restored cache)
and the engine resumes them — bit-identically, because the restored
bytes equal the committed HBM bytes and the slot-masked decode is
independent of batch composition (train.step.make_slot_decode_step).

Cross-engine prefix reuse: prompt-pure blocks are ALSO published as
content-addressed pool objects ``kvblk/<hash>`` + a ``kvhead/<hash>``
prefill head (serve.paging), written once via MStore; ``load_prefix``
restores them so a second engine serving the same prompt skips its
prefill.  A torn publish is invisible — the streamed frames self-
validate, and any read failure degrades to a normal prefill.

Fault injection: the committer's ``fault_hook`` fires at the usual
pre_flush / mid_flush / post_completeOp points, which is what the
serve-worker kill scenario (repro.scenarios.serve_worker) drives.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from repro import obs
from repro.dsm.api import CXL0Context, open_cxl0
from repro.dsm.pool import (CorruptObjectError, DSMPool, manifest_entry)
from repro.serve.paging import (BlockPager, BlockRef, BlockTable,
                                STATE_BLOCK, block_object_name,
                                prefix_hash, shared_block_name,
                                shared_head_name)

KV_PREFIX = "kv/"


def kv_name(rid: str) -> str:
    return KV_PREFIX + rid


def engine_ns(engine_id: int) -> str:
    """Per-engine object namespace in a fleet pool.  Engine 0 writes
    unprefixed names so single-engine pools look exactly as before."""
    return f"e{engine_id}/" if engine_id else ""


@dataclasses.dataclass
class Session:
    """One admitted request's serving state."""
    rid: str
    prompt: Tuple[int, ...]
    max_new_tokens: int
    emitted: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    cache_version: Optional[int] = None
    #: set by a migration handoff commit: this engine no longer owns the
    #: session — the target engine (or its restart) serves it
    migrated_to: Optional[int] = None

    @property
    def pos(self) -> int:
        """Decode position the cache currently covers: the prompt plus
        every emitted token that has been FED BACK.  The newest emitted
        token is the next decode's input, so it is not in the cache yet —
        hence the ``- 1`` (emitted is never empty once admitted: prefill
        emits the first token)."""
        return len(self.prompt) + len(self.emitted) - 1

    def to_meta(self) -> dict:
        d = {"prompt": list(self.prompt), "max_new": self.max_new_tokens,
             "emitted": list(self.emitted), "done": self.done,
             "cache_version": self.cache_version}
        if self.migrated_to is not None:
            d["migrated_to"] = self.migrated_to
        return d

    @classmethod
    def from_meta(cls, rid: str, d: dict) -> "Session":
        return cls(rid=rid, prompt=tuple(int(t) for t in d["prompt"]),
                   max_new_tokens=int(d["max_new"]),
                   emitted=[int(t) for t in d["emitted"]],
                   done=bool(d["done"]),
                   cache_version=d.get("cache_version"),
                   migrated_to=d.get("migrated_to"))


@dataclasses.dataclass
class RecoveredState:
    sessions: Dict[str, Session]     # full table (done + running)
    caches: Dict[str, Any]           # rid -> restored cache (running only)
    step: int                        # decode tick of the commit
    seq: int                         # manifest sequence
    #: paged commits only: the recovered per-session block tables (the
    #: engine re-adopts their frame ids into its allocator)
    tables: Dict[str, BlockTable] = dataclasses.field(default_factory=dict)


class SessionStore:
    def __init__(self, pool: Optional[DSMPool] = None, *, worker_id: int = 0,
                 mode: str = "sync", n_shards: Optional[int] = None,
                 retention: Optional[int] = 2,
                 fault_hook=None, placement=None,
                 ctx: Optional[CXL0Context] = None,
                 engine_id: int = 0):
        """Either hand in an already-open ``CXL0Context`` (the launchers'
        ``CXL0Config`` path) or a pool + the legacy kwargs — the latter are
        routed through ``open_cxl0`` so there is ONE wiring path.
        ``engine_id`` namespaces this store's objects and manifests inside
        a fleet pool (0 = the single-engine layout, unprefixed)."""
        if ctx is None:
            ctx = open_cxl0(pool, worker_id, schedule=mode,
                            n_shards=n_shards, retention=retention,
                            fault_hook=fault_hook, placement=placement)
        self.ctx = ctx
        self.pool = ctx.pool
        self.placement = ctx.placement  # cost-driven shard count/schedule
        self.recovery = ctx.recovery
        self.engine_id = engine_id
        self.ns = engine_ns(engine_id)
        #: clean-block manifest entries carried into the next completeOp
        #: (rebuilt from the live block tables at every paged commit)
        self._carried: Dict[str, dict] = {}
        #: entries of the most recent completeOp's fresh flushes —
        #: captured inside the delegated completeOp, absorbed into block
        #: tables right after the commit call returns
        self._last_written: Dict[str, dict] = {}

    @property
    def tiers(self):
        return self.ctx.tiers

    @property
    def committer(self):
        return self.ctx.committer

    def block_name(self, rid: str, blk: int) -> str:
        return block_object_name(rid, blk, self.ns)

    # -- legacy commit side (whole-lane kv/<rid> objects) --------------------
    def stage(self, session: Session, cache1: Any):
        """LStore a running session's slot cache for the next commit and
        record the version it will be durable at."""
        self.tiers.lstore(self.ns + kv_name(session.rid), cache1)
        session.cache_version = \
            self.tiers.versions[self.ns + kv_name(session.rid)]

    def discard(self, rid: str):
        """Session finished (or evicted): its cache leaves the host tier so
        the next commit stops flushing it."""
        self.tiers.ldiscard(self.ns + kv_name(rid))

    def commit(self, sessions: Dict[str, Session], step: int):
        """Alg. 2 commit as ONE commit region: RFlush every staged cache,
        then exactly one completeOp manifest carrying the session table."""
        meta = {"kind": "serve", "engine": self.engine_id,
                "sessions": {rid: s.to_meta()
                             for rid, s in sessions.items()}}
        if not self.engine_id:
            meta.pop("engine")        # single-engine meta unchanged
        with self.ctx.commit(step, meta=meta) as txn:
            pass                # caches were staged via ``stage``
        return txn.stats

    # -- paged commit side ---------------------------------------------------
    def stage_block(self, session: Session, ref: BlockRef, leaves):
        """LStore one dirty block payload; the next commit flushes it."""
        self.tiers.lstore(ref.name, leaves)
        ref.entry = None                      # durable entry now stale
        session.cache_version = self.tiers.versions[ref.name]

    def commit_paged(self, sessions: Dict[str, Session],
                     tables: Dict[str, BlockTable], step: int, *,
                     block_tokens: int):
        """Paged Alg. 2 commit: flush ONLY the staged dirty blocks, then
        one completeOp whose manifest carries (a) the session table and
        every block table in meta and (b) the union of fresh + carried
        block entries in the object dict.  The carried merge happens in a
        delegated completeOp (``complete_fn``), the cluster extension
        point — which also disables the committer's retention GC:
        multi-writer fleet pools must not drop a sibling's manifests
        (repro.dsm.flit_runtime)."""
        if self.committer.complete_fn is None:
            self.committer.complete_fn = self._complete_paged
        with obs.span("serve.commit.table", sessions=len(sessions)) as sp:
            meta = {"kind": "serve", "paged": True,
                    "engine": self.engine_id,
                    "block_tokens": block_tokens,
                    "sessions": {rid: s.to_meta()
                                 for rid, s in sessions.items()},
                    "tables": {rid: t.to_meta()
                               for rid, t in tables.items()}}
            self._carried = {}
            for t in tables.values():
                self._carried.update(t.entries())
            sp.set(blocks=sum(len(t.refs) for t in tables.values()))
        with self.ctx.commit(step, meta=meta) as txn:
            pass                # dirty blocks were staged via stage_block
        self.absorb_written(tables)
        return txn.stats

    def _complete_paged(self, step: int, written: Dict[str, Any],
                        meta: Optional[dict]) -> int:
        """Delegated completeOp: ONE manifest referencing the fresh
        flushes AND every carried clean block, atomically with the
        session/block tables in ``meta``."""
        entries = {n: manifest_entry(o) for n, o in written.items()}
        merged = dict(self._carried)
        merged.update(entries)
        self._last_written = entries
        return self.pool.commit_manifest(step, merged, meta)

    def absorb_written(self, tables: Dict[str, BlockTable]):
        """Record the freshly published entries into their block refs and
        drop the flushed payloads from the host tier — a clean block is
        carried by name from here on, never re-flushed.  Async schedules
        publish one commit late; their entries are absorbed at the next
        call (double-buffering semantics unchanged)."""
        if not self._last_written:
            return
        for t in tables.values():
            for ref in t.refs.values():
                e = self._last_written.get(ref.name)
                if e is not None:
                    ref.entry = e
                    if ref.blk != STATE_BLOCK \
                            and ref.name in self.tiers.hbm:
                        self.tiers.ldiscard(ref.name)
        self._last_written = {}

    def discard_session_blocks(self, rid: str):
        """Drop a finished/migrated session's staged blocks from the host
        tier (its carried entries disappear with its table at the next
        commit)."""
        prefix = f"{self.ns}{KV_PREFIX}{rid}/"
        for name in [n for n in self.tiers.hbm if n.startswith(prefix)]:
            self.tiers.ldiscard(name)

    # -- cross-engine prefix reuse -------------------------------------------
    def publish_prefix(self, pager: BlockPager, key: str,
                       prompt: Tuple[int, ...], cache1: Any, tok0: int
                       ) -> int:
        """Publish the prompt-pure blocks of a freshly prefilled session
        as content-addressed shared objects (write-once: a block whose
        hash already exists in the pool is skipped).  Returns how many
        objects were newly written."""
        host = pager._host_leaves(cache1)
        wrote = 0
        for k, h in enumerate(pager.prompt_block_hashes(key, prompt)):
            name = shared_block_name(h)
            if self.pool.max_version(name) == 0:
                self.tiers.mstore(name, pager.slice_block(host, k))
                self.tiers.ldiscard(name)     # durable; keep out of commits
                wrote += 1
        hname = shared_head_name(
            prefix_hash(key, prompt, pager.block_tokens))
        if self.pool.max_version(hname) == 0:
            self.tiers.mstore(hname, pager.head_payload(host, len(prompt),
                                                        tok0))
            self.tiers.ldiscard(hname)
            wrote += 1
        return wrote

    def load_prefix(self, pager: BlockPager, key: str,
                    prompt: Tuple[int, ...]):
        """Restore a session's prefill state from shared prefix blocks:
        returns ``(blocks, shared_refs, tok0)`` on a full-prompt hit, or
        None (missing or torn objects — the frames self-validate, and any
        failure means 'prefill normally')."""
        names = [shared_block_name(h)
                 for h in pager.prompt_block_hashes(key, prompt)]
        hname = shared_head_name(
            prefix_hash(key, prompt, pager.block_tokens))
        blocks: Dict[int, Any] = {}
        shared: Dict[int, Tuple[str, dict]] = {}
        try:
            for k, name in enumerate(names):
                v = self.pool.max_version(name)
                if v == 0:
                    return None
                blocks[k] = self.pool.read_object(name, v,
                                                  pager.block_template)
                shared[k] = (name, {"name": name, "version": v,
                                    "crc": None})
            v = self.pool.max_version(hname)
            if v == 0:
                return None
            head = self.pool.read_object(hname, v, pager.head_template)
        except (CorruptObjectError, OSError, ValueError):
            return None
        tail, state, tok0 = pager.split_head(head)
        if tail:
            blocks[len(names)] = tail
        if state:
            blocks[STATE_BLOCK] = state
        return blocks, shared, tok0

    def drain(self):
        return self.ctx.drain()

    def close(self):
        self.ctx.close()

    # -- recovery side -------------------------------------------------------
    def _manifests_for_engine(self) -> List[dict]:
        out = []
        for m in self.pool.manifests_desc():
            meta = m.get("meta") or {}
            if "sessions" not in meta:
                continue                      # not a serve commit
            if int(meta.get("engine", 0)) != self.engine_id:
                continue                      # a fleet sibling's commit
            out.append(m)
        return out

    def recover(self, cache_template, *,
                pager: Optional[BlockPager] = None
                ) -> Optional[RecoveredState]:
        """Newest fully-valid session commit FOR THIS ENGINE, or None on
        a cold pool.  Handles both layouts: paged manifests restore each
        running session by assembling its block table's objects
        (``pager`` required); legacy manifests read whole-lane
        ``kv/<rid>`` objects against ``cache_template``.  Any torn or
        unreadable object fails the WHOLE manifest and recovery falls
        back to an older one — a session table can never pair with torn
        bytes."""
        for m in self._manifests_for_engine():
            meta = m.get("meta") or {}
            got = (self._read_paged(m, meta, pager) if meta.get("paged")
                   else self._read_legacy(m, meta, cache_template))
            if got is None:
                continue                      # torn commit: older manifest
            sessions, caches, tables = got
            return RecoveredState(sessions, caches, m["step"], m["seq"],
                                  tables=tables)
        return None

    def _read_legacy(self, m: dict, meta: dict, cache_template):
        sessions = {rid: Session.from_meta(rid, d)
                    for rid, d in meta["sessions"].items()}
        caches: Dict[str, Any] = {}
        try:
            for name, entry in m["objects"].items():
                caches[name] = self.pool.read_entry(name, entry,
                                                    cache_template)
        except (CorruptObjectError, KeyError, ValueError):
            return None
        caches = {rid: caches[self.ns + kv_name(rid)] for rid in sessions
                  if self.ns + kv_name(rid) in caches}
        return sessions, caches, {}

    def _read_paged(self, m: dict, meta: dict,
                    pager: Optional[BlockPager]):
        if pager is None:
            return None       # paged pool read without a pager: no match
        sessions = {rid: Session.from_meta(rid, d)
                    for rid, d in meta["sessions"].items()}
        tables = {rid: BlockTable.from_meta(d)
                  for rid, d in (meta.get("tables") or {}).items()}
        # backfill durable entries the tables were serialized WITHOUT:
        # a block staged for this very commit had entry=None at meta
        # capture time (its flush entry only exists post-completeOp), but
        # the manifest's object dict references it — so a recovered table
        # (including a migration-handoff tombstone's) always carries a
        # valid pool entry per block
        for t in tables.values():
            for ref in t.refs.values():
                e = m["objects"].get(ref.name)
                if e is not None:
                    ref.entry = e
        caches: Dict[str, Any] = {}
        for rid, s in sessions.items():
            if s.done or s.migrated_to is not None or rid not in tables:
                continue
            blocks: Dict[int, Any] = {}
            try:
                for blk, ref in tables[rid].refs.items():
                    entry = m["objects"].get(ref.name) or ref.entry
                    if entry is None:
                        return None           # table references a block
                        #                       the manifest does not carry
                    tpl = (pager.state_template if blk == STATE_BLOCK
                           else pager.block_template)
                    blocks[blk] = self.pool.read_entry(ref.name, entry,
                                                       tpl)
            except (CorruptObjectError, KeyError, ValueError):
                return None
            caches[rid] = pager.assemble(blocks)
        return sessions, caches, tables

    def peek_engine(self, engine_id: int) -> Optional[dict]:
        """Newest serve manifest of a SIBLING engine (its meta carries
        the session + block tables) — how a fleet restart discovers
        handoffs whose target never committed its adoption."""
        for m in self.pool.manifests_desc():
            meta = m.get("meta") or {}
            if "sessions" not in meta:
                continue
            if int(meta.get("engine", 0)) != engine_id:
                continue
            return m
        return None
