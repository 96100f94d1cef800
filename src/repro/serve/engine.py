"""ServeEngine: continuous batching + tiered KV caches + durable sessions.

The serving loop per decode tick (``tick()`` — ``run()`` just loops it,
and a fleet controller interleaves many engines' ticks over one pool):

1. **admit** — free slots refill FIFO from the scheduler; each admission
   prefills ONE sequence (B=1, compiled once per distinct prompt length),
   writes its cache into the slot lane and emits its first token — or,
   with prefix reuse enabled, restores the prompt's content-addressed
   pool blocks and skips the prefill entirely;
2. **decode** — one slot-masked batched decode step advances every
   running slot at its own position (``train.step.make_slot_decode_step``
   — a per-slot vmap, so slot contents never influence each other);
3. **retire** — sequences that hit their token budget free their slot in
   the same tick (the scheduler contract), their block frames return to
   the allocator and their staged blocks leave the host tier;
4. **commit** (every ``commit_every`` ticks, durable pools only) — the
   PAGED layout (serve.paging, the default): only the token blocks each
   session's position touched since the last commit are staged + flushed;
   the manifest carries every clean block by reference (serve.sessions).
   ``paged=False`` keeps the legacy whole-lane path for the equivalence
   tests.

Crash recovery: a restarted worker calls ``resume()`` — finished
sessions come back as results; running sessions re-enter the admission
queue AHEAD of fresh requests with their committed cache restored into a
lane (``restore_mode="cache"``) or replayed from the prompt
(``restore_mode="replay"``).  Both are bit-identical to the
uninterrupted run: the restored bytes ARE the committed HBM bytes, and a
replay re-executes the identical deterministic computation.

Live migration (driven by serve.fleet): ``begin_migration`` freezes a
session and frees its slot mid-flight, ``stage_migration`` RStores its
dirty blocks into the target's staging buffer, ``commit_handoff`` makes
the handoff durable, and the target's ``install_session`` re-admits it
at the FRONT of the queue — the token stream is bit-identical across
the handoff because the adopted cache bytes equal the frozen lane bytes.

``run_static`` is the old static-batch loop kept as the benchmark
baseline: batched prefill, then decode until the LONGEST sequence of the
batch finishes — the behaviour whose hostage effect continuous batching
removes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.serve.kvcache import TieredKVCache
from repro.serve.paging import (BLOCK_TOKENS, BlockAllocator, BlockPager,
                                BlockRef, BlockTable, STATE_BLOCK)
from repro.serve.scheduler import Request, SlotScheduler
from repro.serve.sessions import Session, SessionStore
from repro.train.step import make_serve_steps, make_slot_decode_step


@dataclasses.dataclass
class ServeResult:
    outputs: Dict[str, List[int]]     # rid -> emitted token ids
    decode_ticks: int
    prefills: int
    emitted_tokens: int
    mode: str
    resumed_step: Optional[int] = None
    resumed_sessions: int = 0
    commits: int = 0
    prefix_hits: int = 0              # admissions served from shared blocks
    migrated_in: int = 0
    migrated_out: int = 0


class ServeEngine:
    def __init__(self, bundle, params, *, n_slots: int = 4,
                 t_max: int = 96, ctx=None,
                 store: Optional[SessionStore] = None,
                 commit_every: int = 0,
                 restore_mode: str = "cache",
                 retire_done: bool = False,
                 paged: bool = True,
                 block_tokens: int = BLOCK_TOKENS,
                 allocator: Optional[BlockAllocator] = None,
                 prefix_reuse: bool = False,
                 prefix_key: str = ""):
        assert restore_mode in ("cache", "replay"), restore_mode
        if bundle.cfg.is_encdec:
            raise ValueError(
                "the serving subsystem is decoder-only (the slot-masked "
                "decode has no encoder-state plumbing); encoder-decoder "
                "archs are not servable — see serve.engine.servable_archs")
        self.bundle = bundle
        self.params = params
        self.n_slots = n_slots
        self.t_max = t_max
        self.store = store
        self.engine_id = store.engine_id if store is not None else 0
        self.commit_every = commit_every if store is not None else 0
        self.restore_mode = restore_mode
        self.retire_done = retire_done
        self.paged = paged and store is not None
        self.block_tokens = block_tokens
        #: reuse is sound only within one model identity: ``prefix_key``
        #: must fold arch + params seed (build_serve_engine sets it)
        self.prefix_reuse = prefix_reuse and self.paged
        self.prefix_key = prefix_key

        prefill_step, decode_step = make_serve_steps(bundle, ctx)
        self._prefill = jax.jit(prefill_step)
        self._decode = jax.jit(decode_step)           # static baseline
        self._slot_decode = jax.jit(make_slot_decode_step(bundle, ctx),
                                    donate_argnums=(2,))

        self.kv = TieredKVCache(bundle, n_slots, t_max,
                                tiers=store.tiers if store else None,
                                placement=getattr(store, "placement", None),
                                parallel=ctx)
        self._caches1 = bundle.init_caches(jax.random.PRNGKey(0), 1, t_max)
        self.sched = SlotScheduler(n_slots)
        self.sessions: Dict[str, Session] = {}
        self.results: Dict[str, List[int]] = {}
        #: rid -> perf_counter time of its ``submit``, until admitted
        self._submitted: Dict[str, float] = {}
        self._resume_cache: Dict[str, Any] = {}
        #: recovered handoff tables of sessions we migrated OUT whose
        #: target never committed its adoption — the fleet resume
        #: completes these (serve.fleet.FleetController.resume)
        self._handoffs: Dict[str, Optional[BlockTable]] = {}
        if self.paged:
            self.pager = BlockPager(bundle, t_max, block_tokens)
            frames = n_slots * (self.pager.n_blocks(t_max) + 1) + 8
            self.allocator = allocator or BlockAllocator(max(64, 4 * frames))
            self.tables: Dict[str, BlockTable] = {}
        # host-side slot state
        self.pos = np.zeros(n_slots, np.int32)
        self.last_token = np.zeros(n_slots, np.int32)
        self.active = np.zeros(n_slots, bool)
        self._tick = 0
        self._resumed_step: Optional[int] = None
        self._n_resumed = 0
        self._n_prefills = 0
        self._n_commits = 0
        self._n_prefix_hits = 0
        self._n_migrated_in = 0
        self._n_migrated_out = 0

    # -- request intake ------------------------------------------------------
    def submit(self, requests: Sequence[Request]):
        fresh = []
        now = time.perf_counter()
        for r in requests:
            assert len(r.prompt) + r.max_new_tokens <= self.t_max, \
                (r.rid, len(r.prompt), r.max_new_tokens, self.t_max)
            if r.rid in self.sessions or r.rid in self.results:
                continue    # recovered, resuming, migrated, or retired —
                #             this engine already accounts for the rid
            fresh.append(r)
            self._submitted[r.rid] = now
        self.sched.submit(fresh)

    # -- crash recovery ------------------------------------------------------
    def resume(self) -> Optional[int]:
        """Recover the newest session commit from the pool.  Finished
        sessions become results; unfinished ones are queued for admission
        AHEAD of any fresh request (they were admitted first in the killed
        incarnation).  Sessions handed off to another engine stay as
        tombstones: ``submit`` skips them and the adopting engine (or the
        fleet resume) serves them.  Returns the recovered tick or None
        (cold pool)."""
        if self.store is None:
            return None
        rec = self.store.recover(self.kv.template1,
                                 pager=self.pager if self.paged else None)
        if rec is None:
            return None
        for rid, s in rec.sessions.items():
            self.sessions[rid] = s
            if s.migrated_to is not None:
                # owned by the target engine; keep the handoff table so
                # the fleet resume can finish an interrupted adoption
                self._handoffs[rid] = rec.tables.get(rid)
                continue
            if s.done:
                self.results[rid] = list(s.emitted)
            else:
                self._resume_cache[rid] = rec.caches.get(rid)
                if self.paged and rid in rec.tables:
                    self.tables[rid] = rec.tables[rid]
                    for bid in rec.tables[rid].bids():
                        self.allocator.adopt(bid)
                self._n_resumed += 1
                self.sched.submit([Request(rid, s.prompt,
                                           s.max_new_tokens)])
        self._resumed_step = rec.step
        self._tick = rec.step + 1
        return rec.step

    # -- the continuous-batching loop ---------------------------------------
    def tick(self):
        """One scheduler round: admit, decode, commit-on-cadence.  The
        unit a fleet controller interleaves across engines."""
        with obs.span("serve.tick", tick=self._tick) as sp:
            for slot, req in self.sched.admit():
                self._admit(slot, req)
            sp.set(slots=self.sched.n_running)
            if self.sched.n_running:
                self._decode_tick()
            self._tick += 1
            if self.commit_every and self._tick % self.commit_every == 0:
                self._commit()

    def run(self, requests: Optional[Sequence[Request]] = None
            ) -> ServeResult:
        if requests:
            self.submit(requests)
        ticks0 = self._tick
        while not self.sched.done:
            self.tick()
        return self.finish(ticks0)

    def finish(self, ticks0: int = 0) -> ServeResult:
        """Final commit + drain, then the result record (split out of
        ``run`` so a fleet controller can drive ticks itself)."""
        if self.store is not None:
            self._commit()            # final table (all sessions done)
            self.store.drain()
        return ServeResult(
            outputs=dict(self.results),
            decode_ticks=self._tick - ticks0,
            prefills=self._n_prefills,
            emitted_tokens=sum(len(v) for v in self.results.values()),
            mode="continuous",
            resumed_step=self._resumed_step,
            resumed_sessions=self._n_resumed,
            commits=self._n_commits,
            prefix_hits=self._n_prefix_hits,
            migrated_in=self._n_migrated_in,
            migrated_out=self._n_migrated_out)

    def _admit(self, slot: int, req: Request):
        rid = req.rid
        queued = self._submitted.pop(rid, None)
        with obs.span("serve.admit", rid=rid, prompt=len(req.prompt),
                      queued_ms=None if queued is None else
                      1e3 * (time.perf_counter() - queued)):
            self._admit_slot(slot, req)

    def _admit_slot(self, slot: int, req: Request):
        rid = req.rid
        s = self.sessions.get(rid)
        if s is not None and not s.done:
            cache1 = self._resume_cache.pop(rid, None)
            if (self.restore_mode == "cache" and cache1 is not None
                    and s.emitted):
                # fast-forward: committed cache bytes back into a lane
                self.kv.write_slot(slot, cache1)
                self.pos[slot] = s.pos
                self.last_token[slot] = s.emitted[-1]
                self.active[slot] = True
                return
            s.emitted = []            # replay: re-decode from the prompt
        else:
            s = Session(rid, tuple(req.prompt), req.max_new_tokens)
            self.sessions[rid] = s
            if self.prefix_reuse and self._admit_from_prefix(slot, s):
                return
        tokens = jnp.asarray(np.asarray(s.prompt, np.int32)[None])
        logits, st = self._prefill(self.params, {"tokens": tokens},
                                   self._caches1)
        self._n_prefills += 1
        with obs.span("serve.prefill.wait"):
            tok0 = int(jnp.argmax(logits, -1)[0])
        self.kv.write_slot(slot, st.caches)
        self.pos[slot] = len(s.prompt)
        self.last_token[slot] = tok0
        self.active[slot] = True
        s.emitted.append(tok0)
        if self.prefix_reuse:
            self.store.publish_prefix(self.pager, self.prefix_key,
                                      s.prompt, st.caches, tok0)
        if len(s.emitted) >= s.max_new_tokens:
            self._finish(rid, slot)

    def _admit_from_prefix(self, slot: int, s: Session) -> bool:
        """Admission fast path: restore the prompt's shared blocks from
        the pool instead of prefilling.  Bit-identical to the prefill it
        replaces — the blocks were published from an identical-weights
        prefill of the identical prompt."""
        hit = self.store.load_prefix(self.pager, self.prefix_key, s.prompt)
        if hit is None:
            return False
        blocks, shared, tok0 = hit
        self.kv.write_slot(slot, self.pager.assemble(blocks))
        table = BlockTable()
        for k, (name, entry) in shared.items():
            # the table references the SHARED objects: carried by name
            # into this engine's manifests, no bytes copied
            table.refs[k] = BlockRef(blk=k, bid=self.allocator.alloc(),
                                     tokens=self.pager.block_tokens,
                                     name=name, entry=entry)
        self.tables[s.rid] = table
        self.pos[slot] = len(s.prompt)
        self.last_token[slot] = tok0
        self.active[slot] = True
        s.emitted.append(tok0)
        self._n_prefix_hits += 1
        if len(s.emitted) >= s.max_new_tokens:
            self._finish(s.rid, slot)
        return True

    def _decode_tick(self):
        next_toks, _, new_caches, new_pos = self._slot_decode(
            self.params, jnp.asarray(self.last_token[:, None]),
            self.kv.caches, jnp.asarray(self.pos),
            jnp.asarray(self.active))
        self.kv.caches = new_caches
        with obs.span("serve.decode.wait"):
            self.pos = np.array(new_pos)  # copy: np.asarray of a jax
            #                               array is a read-only view
            toks = np.asarray(next_toks)
        for rid, slot in list(self.sched.running.items()):
            s = self.sessions[rid]
            tok = int(toks[slot])
            s.emitted.append(tok)
            self.last_token[slot] = tok
            if len(s.emitted) >= s.max_new_tokens:
                self._finish(rid, slot)

    def _finish(self, rid: str, slot: int):
        self.sched.release(rid)
        self.active[slot] = False
        s = self.sessions[rid]
        s.done = True
        self.results[rid] = list(s.emitted)
        if self.store is not None:
            if self.paged:
                t = self.tables.pop(rid, None)
                if t is not None:
                    for bid in t.bids():
                        self.allocator.free(bid)
                self.store.discard_session_blocks(rid)
            else:
                self.store.discard(rid)

    def _stage_paged(self, rid: str, cache1: Any):
        """Stage a running session's DIRTY blocks for the next commit —
        the O(blocks touched) replacement for whole-lane ``store.stage``.
        ``cache1`` is the slot's cache or the list of its host leaves."""
        s = self.sessions[rid]
        table = self.tables.setdefault(rid, BlockTable())
        for blk, leaves in self.pager.slice_dirty(cache1, s.pos,
                                                  table).items():
            ref = table.refs.get(blk)
            if ref is None:
                ref = BlockRef(blk=blk, bid=self.allocator.alloc(),
                               tokens=0,
                               name=self.store.block_name(rid, blk))
                table.refs[blk] = ref
            if blk != STATE_BLOCK:
                ref.tokens = self.pager.tokens_in_block(blk, s.pos)
            self.store.stage_block(s, ref, leaves)

    def _commit(self):
        assert self.store is not None
        with obs.span("serve.commit", tick=self._tick,
                      sessions=len(self.sessions)):
            self._commit_sessions()

    def _commit_sessions(self):
        if self.paged:
            for rid, slot in self.sched.running.items():
                with obs.span("serve.commit.d2h", rid=rid) as sp:
                    host = self.pager._host_leaves(self.kv.read_slot(slot))
                    if sp:
                        sp.set(bytes=sum(a.nbytes for a in host))
                self._stage_paged(rid, host)
            self.store.commit_paged(self.sessions, self.tables,
                                    self._tick,
                                    block_tokens=self.block_tokens)
        else:
            for rid, slot in self.sched.running.items():
                self.store.stage(self.sessions[rid],
                                 self.kv.read_slot(slot))
            self.store.commit(self.sessions, self._tick)
        self._n_commits += 1
        if self.retire_done:
            # done sessions were durable in the table just committed;
            # retire them so commit cost stays O(live sessions) instead of
            # O(total request history).  Their outputs remain in
            # self.results (delivered to the caller) but a later restart
            # will no longer replay them — the long-lived-service policy.
            for rid in [r for r, s in self.sessions.items() if s.done]:
                del self.sessions[rid]

    # -- live migration mechanics (driven by serve.fleet) --------------------
    def begin_migration(self, rid: str):
        """Freeze an in-flight session: extract its lane and free the
        slot — freed via MIGRATION, not completion, so the scheduler
        refills it with the next pending request this very tick."""
        slot = self.sched.running[rid]
        cache1 = self.kv.read_slot(slot)
        self.active[slot] = False
        self.sched.release(rid)
        self._n_migrated_out += 1
        return self.sessions[rid], \
            self.tables.setdefault(rid, BlockTable()), cache1

    def stage_migration(self, rid: str, cache1: Any, proxy, tag: int
                        ) -> BlockTable:
        """mig_stage: LStore the session's dirty blocks (the handoff
        commit will flush them — the pool arm of staging-or-pool) and
        RStore each into the TARGET's staging buffer (the hot arm).
        Clean blocks move zero bytes: the target reads them from the pool
        entries the block table already carries."""
        s = self.sessions[rid]
        table = self.tables[rid]
        for blk, leaves in self.pager.slice_dirty(cache1, s.pos,
                                                  table).items():
            ref = table.refs.get(blk)
            if ref is None:
                ref = BlockRef(blk=blk, bid=self.allocator.alloc(),
                               tokens=0,
                               name=self.store.block_name(rid, blk))
                table.refs[blk] = ref
            if blk != STATE_BLOCK:
                ref.tokens = self.pager.tokens_in_block(blk, s.pos)
            self.store.stage_block(s, ref, leaves)
            self.store.tiers.rstore(ref.name, proxy, tag=tag)
        return table

    def commit_handoff(self, rid: str, target_id: int):
        """mig_commit: mark the session migrated and commit — ONE paged
        commit makes the marker, the block table and the staged dirty
        blocks durable atomically.  After this manifest lands the target
        owns the session, crash or no crash."""
        self.sessions[rid].migrated_to = target_id
        self._commit()

    def release_migrated(self, rid: str):
        """mig_release: the target's adoption commit landed — drop our
        copy.  Frame ids move WITH the table (same pool frames); staged
        payloads leave the host tier; the tombstone leaves the committed
        table at our next commit."""
        self.sessions.pop(rid, None)
        self.tables.pop(rid, None)
        self.store.discard_session_blocks(rid)

    def install_session(self, s: Session, table: BlockTable, cache1: Any,
                        *, claim_frames: bool = False):
        """Adopt a migrated-in session: re-admit it AHEAD of fresh
        requests with its cache ready to fast-forward into a lane.
        ``claim_frames`` re-asserts the table's frame ids in OUR
        allocator (restart recovery — a live in-process handoff moves
        already-owned frames of the shared fleet allocator)."""
        s.migrated_to = None
        self.sessions[s.rid] = s
        self.tables[s.rid] = table
        if claim_frames:
            for bid in table.bids():
                self.allocator.adopt(bid)
        self._resume_cache[s.rid] = cache1
        self._n_migrated_in += 1
        self.sched.submit_front(Request(s.rid, s.prompt, s.max_new_tokens))

    # -- static baseline -----------------------------------------------------
    def run_static(self, requests: Sequence[Request]) -> ServeResult:
        """FIFO batches of ``n_slots``; each batch decodes until its
        LONGEST sequence finishes (the hostage effect)."""
        outputs: Dict[str, List[int]] = {}
        ticks = prefills = 0
        reqs = list(requests)
        for i in range(0, len(reqs), self.n_slots):
            batch = reqs[i:i + self.n_slots]
            lens = {len(r.prompt) for r in batch}
            assert len(lens) == 1, \
                "static baseline batches unpadded prompts (uniform length)"
            toks = jnp.asarray(np.asarray([r.prompt for r in batch],
                                          np.int32))
            caches = self.bundle.init_caches(jax.random.PRNGKey(0),
                                             len(batch), self.t_max)
            logits, st = self._prefill(self.params, {"tokens": toks},
                                       caches)
            prefills += 1
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            emitted = [[int(t)] for t in np.asarray(tok[:, 0])]
            for _ in range(max(r.max_new_tokens for r in batch) - 1):
                logits, st = self._decode(self.params, tok, st)
                tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
                ticks += 1
                for row, t in enumerate(np.asarray(tok[:, 0])):
                    emitted[row].append(int(t))
            for r, row in zip(batch, emitted):
                outputs[r.rid] = row[:r.max_new_tokens]
        return ServeResult(
            outputs=outputs, decode_ticks=ticks, prefills=prefills,
            emitted_tokens=sum(len(v) for v in outputs.values()),
            mode="static")

    # -- utilities -----------------------------------------------------------
    def warmup(self, prompt_lens: Sequence[int]):
        """Compile prefill per distinct prompt length + the decode step,
        outside any timed region."""
        for L in sorted(set(int(l) for l in prompt_lens)):
            tokens = jnp.zeros((1, L), jnp.int32)
            logits, _ = self._prefill(self.params, {"tokens": tokens},
                                      self._caches1)
            jax.block_until_ready(logits)
        nt, _, self.kv.caches, _ = self._slot_decode(
            self.params, jnp.asarray(self.last_token[:, None]),
            self.kv.caches, jnp.asarray(self.pos),
            jnp.asarray(self.active))
        jax.block_until_ready(nt)

    def close(self):
        if self.store is not None:
            self.store.close()


def servable_archs():
    """Arch ids the serving subsystem supports (decoder-only — the
    slot-masked decode has no encoder-state plumbing).  Used by the CLI
    front-ends as argparse choices so encoder-decoder archs are rejected
    up front instead of deep in engine construction."""
    from repro.configs import ARCH_IDS, get_smoke_config
    return [a for a in ARCH_IDS if not get_smoke_config(a).is_encdec]


def build_serve_engine(arch: str = "olmo-1b", *, smoke: bool = True,
                       n_slots: int = 4, t_max: int = 96, ctx=None,
                       pool_path: Optional[str] = None,
                       commit_every: int = 0, commit_mode: str = "sync",
                       n_shards: Optional[int] = None, retention: int = 2,
                       fault_hook=None, restore_mode: str = "cache",
                       retire_done: bool = False, seed: int = 0,
                       topology: Optional[str] = None,
                       dsm: Optional["CXL0Config"] = None,
                       engine_id: int = 0,
                       paged: bool = True,
                       block_tokens: int = BLOCK_TOKENS,
                       allocator: Optional[BlockAllocator] = None,
                       prefix_reuse: bool = False,
                       bundle=None, params=None):
    """One-stop construction shared by the launcher, the example, the
    fleet controller and the killable scenario worker: config -> bundle
    -> (sharded) params -> optional durable session store -> engine.
    Returns (engine, cfg).

    The durable tier stack is wired from ONE ``CXL0Config``: pass it
    directly via ``dsm`` (the launchers do) or let the legacy kwargs
    (``pool_path``/``commit_mode``/``n_shards``/``retention``/``topology``)
    be folded into one here.  ``ctx`` is the parallelism context (mesh),
    not the DSM context.

    Params are initialized from ``seed`` deterministically, so two
    processes built with the same arguments hold bit-identical weights —
    the property crash-replay bit-identity AND cross-engine prefix reuse
    rest on (the reuse key folds arch + smoke + seed).  Pass ``bundle``
    + ``params`` to share ONE weight pytree across engines (how the
    fleet controller hosts N engines of the same model)."""
    from repro.configs import get_config, get_smoke_config
    from repro.dsm.api import CXL0Config
    from repro.models.registry import build as build_model

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if bundle is None:
        bundle = build_model(cfg, dec_pos_len=t_max)
    if params is None:
        params = bundle.init_params(jax.random.PRNGKey(seed))
    if ctx is not None and ctx.mesh is not None:
        from repro.train.elastic import shardings_for
        params = jax.tree_util.tree_map(
            jax.device_put, params, shardings_for(ctx, bundle.descs))
    store = None
    if dsm is None and pool_path is not None:
        # cost-driven shard count (and, with commit_mode="auto", the
        # schedule) come from the topology's placement policy, built by
        # the config at open time
        dsm = CXL0Config(path=pool_path, schedule=commit_mode,
                         n_shards=n_shards, retention=retention,
                         topology=topology, fault_hook=fault_hook)
    if dsm is not None:
        store = SessionStore(ctx=dsm.open(), engine_id=engine_id)
    engine = ServeEngine(
        bundle, params, n_slots=n_slots, t_max=t_max, ctx=ctx,
        store=store, commit_every=commit_every, restore_mode=restore_mode,
        retire_done=retire_done, paged=paged, block_tokens=block_tokens,
        allocator=allocator, prefix_reuse=prefix_reuse,
        prefix_key=f"{arch}|{'smoke' if smoke else 'full'}|s{seed}")
    return engine, cfg
