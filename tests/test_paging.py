"""Paged KV layout (repro.serve.paging + the paged SessionStore path):
allocator single-ownership, block-table round-trips, slice/assemble
bit-identity, and paged-engine equivalence against the legacy
whole-lane layout.

The allocator/table invariants also run as hypothesis properties in
tests/test_paging_props.py; the versions here are deterministic seeded
sweeps so the invariants are exercised even where hypothesis is not
installed.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.dsm.pool import DSMPool
from repro.serve.paging import (BLOCK_TOKENS, BlockAllocator, BlockPager,
                                BlockRef, BlockTable, OutOfBlocksError,
                                STATE_BLOCK, cache_token_axes, prefix_hash)
from repro.serve.scheduler import Request, SlotScheduler
from repro.serve.trace import synthetic_trace, trace_t_max

TRACE_KW = dict(prompt_lens=(8, 12), new_tokens=(4, 8, 16), seed=3)


@pytest.fixture(scope="module")
def smoke():
    from repro.configs import get_smoke_config
    from repro.models.registry import build
    cfg = get_smoke_config("olmo-1b")
    trace = synthetic_trace(6, vocab_size=cfg.vocab_size, **TRACE_KW)
    t_max = trace_t_max(trace)
    bundle = build(cfg, dec_pos_len=t_max)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return cfg, bundle, params, trace, t_max


def _filled_cache1(smoke, seed=1, plen=16):
    cfg, bundle, params, _, t_max = smoke
    toks = jax.random.randint(jax.random.PRNGKey(seed), (1, plen), 0,
                              cfg.vocab_size)
    _, st = bundle.prefill(params, {"tokens": toks},
                           bundle.init_caches(jax.random.PRNGKey(0), 1,
                                              t_max))
    return st.caches


# ---------------------------------------------------------------------------
# allocator: single ownership (no jax)
# ---------------------------------------------------------------------------

def test_allocator_never_double_assigns_seeded_sweep():
    """1000 random alloc/free/adopt ops: a frame id is owned by at most
    one holder at every step, frees return exactly what was taken."""
    rng = np.random.default_rng(0)
    a = BlockAllocator(24)
    held = set()
    for _ in range(1000):
        op = rng.integers(0, 3)
        if op == 0 and a.n_free:
            bid = a.alloc()
            assert bid not in held
            held.add(bid)
        elif op == 1 and held:
            bid = int(rng.choice(sorted(held)))
            a.free(bid)
            held.discard(bid)
        elif op == 2:
            bid = int(rng.integers(0, 24))
            if bid in held:
                with pytest.raises(OutOfBlocksError):
                    a.adopt(bid)
            else:
                a.adopt(bid)
                held.add(bid)
        assert a.allocated == frozenset(held)
        assert a.n_free == 24 - len(held)


def test_allocator_exhaustion_and_bad_ops():
    a = BlockAllocator(2)
    b1, b2 = a.alloc(), a.alloc()
    assert b1 != b2
    with pytest.raises(OutOfBlocksError):
        a.alloc()
    with pytest.raises(ValueError):
        a.free(99)                     # never assigned
    with pytest.raises(ValueError):
        a.adopt(5)                     # outside the pool
    a.free(b1)
    a.adopt(b1)                        # explicit re-claim of a freed id
    with pytest.raises(OutOfBlocksError):
        a.adopt(b1)


# ---------------------------------------------------------------------------
# block table round-trip
# ---------------------------------------------------------------------------

def _table():
    t = BlockTable()
    t.refs[0] = BlockRef(blk=0, bid=3, tokens=16, name="kv/r1/b0",
                         entry={"name": "kv/r1/b0", "version": 2,
                                "crc": 123})
    t.refs[1] = BlockRef(blk=1, bid=7, tokens=5, name="kv/r1/b1")
    t.refs[STATE_BLOCK] = BlockRef(blk=STATE_BLOCK, bid=9, tokens=0,
                                   name="kv/r1/state")
    return t


def test_block_table_meta_roundtrip_bit_identical():
    t = _table()
    back = BlockTable.from_meta(json.loads(json.dumps(t.to_meta())))
    assert back.to_meta() == t.to_meta()
    assert sorted(back.bids()) == sorted(t.bids())
    assert back.entries() == t.entries()
    assert back.refs[1].entry is None


def test_block_table_roundtrip_through_pool_manifest(tmp_path):
    """The table rides in manifest meta: through an actual manifest
    commit + read-back it must survive byte-identically (json-safe)."""
    pool = DSMPool(str(tmp_path))
    o = pool.write_object("x", 1, {"a": np.zeros(3, np.float32)})
    meta = {"kind": "serve", "tables": {"r1": _table().to_meta()}}
    pool.commit_manifest(0, {"x": o}, meta)
    m = DSMPool(str(tmp_path)).latest_manifest()
    back = BlockTable.from_meta(m["meta"]["tables"]["r1"])
    assert back.to_meta() == _table().to_meta()


# ---------------------------------------------------------------------------
# pager: slice / assemble
# ---------------------------------------------------------------------------

def test_cache_token_axes_match_leaf_count(smoke):
    _, bundle, _, _, t_max = smoke
    pager = BlockPager(bundle, t_max)
    assert pager.tok_idx, "attention arch must have seq_kv leaves"
    assert len(pager.tok_idx) + len(pager.state_idx) \
        == len(jax.tree_util.tree_leaves(cache_token_axes(bundle)))


@pytest.mark.parametrize("pos_frac", [0.3, 0.6, 1.0])
def test_slice_assemble_roundtrip_bit_identical(smoke, pos_frac):
    """Splitting a prefilled cache into blocks and reassembling them is
    the identity — including at pos == t_max (the edge block)."""
    _, bundle, _, _, t_max = smoke
    pager = BlockPager(bundle, t_max, block_tokens=8)
    pos = max(1, int(t_max * pos_frac))
    cache1 = _filled_cache1(smoke, plen=min(pos, 16))
    host = pager._host_leaves(cache1)
    blocks = {blk: pager.slice_block(host, blk)
              for blk in range(pager.n_blocks(t_max))}
    if pager.state_idx:
        blocks[STATE_BLOCK] = pager.slice_state(host)
    back = pager.assemble(blocks)
    fa = jax.tree_util.tree_leaves(cache1)
    fb = jax.tree_util.tree_leaves(back)
    for x, y in zip(fa, fb):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


def test_slice_dirty_skips_clean_full_blocks(smoke):
    _, bundle, _, _, t_max = smoke
    pager = BlockPager(bundle, t_max, block_tokens=8)
    cache1 = _filled_cache1(smoke, plen=16)
    table = BlockTable()
    dirty = pager.slice_dirty(cache1, 20, table)
    # pos 20, bt 8 -> blocks 0,1 full + block 2 partial (+ state if any)
    assert set(b for b in dirty if b != STATE_BLOCK) == {0, 1, 2}
    # mark 0 and 1 durable and full: only the growing tail stays dirty
    for blk in (0, 1):
        table.refs[blk] = BlockRef(blk=blk, bid=blk, tokens=8,
                                   name=f"kv/r/b{blk}",
                                   entry={"name": f"kv/r/b{blk}",
                                          "version": 1, "crc": 0})
    dirty = pager.slice_dirty(cache1, 20, table)
    assert set(b for b in dirty if b != STATE_BLOCK) == {2}
    # a partial durable block goes dirty again once the position grows
    table.refs[2] = BlockRef(blk=2, bid=2, tokens=4, name="kv/r/b2",
                             entry={"name": "kv/r/b2", "version": 1,
                                    "crc": 0})
    dirty = pager.slice_dirty(cache1, 21, table)
    assert set(b for b in dirty if b != STATE_BLOCK) == {2}


def _random_lanes(kv, seed=0):
    """Every position of every lane filled with distinct random values,
    so a wrong span or lane cannot read as zeros that happen to match."""
    leaves, treedef = jax.tree_util.tree_flatten(kv.caches)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    kv.caches = jax.tree_util.tree_unflatten(treedef, [
        jax.device_put(jax.random.normal(k, l.shape, jnp.float32)
                       .astype(l.dtype), l.sharding)
        for k, l in zip(keys, leaves)])


# (arch, t_max, block_tokens, slot, block, prefill length or None,
#  data x model mesh or None)
READ_BLOCK_CASES = {
    "first": ("olmo-1b", 36, 8, 1, 0, None, None),
    "middle": ("olmo-1b", 36, 8, 2, 2, None, None),
    "partial_tail": ("olmo-1b", 36, 8, 0, 2, 21, None),
    "overrun": ("olmo-1b", 36, 8, 2, 4, None, None),
    "mesh_sharded": ("olmo-1b", 36, 8, 3, 1, None, (2, 4)),
    "hybrid_state": ("jamba-1.5-large-398b", 36, 8, 1, 3, None, None),
    "recurrent_only": ("rwkv6-7b", 36, 8, 1, None, None, None),
}


@pytest.mark.parametrize("case", list(READ_BLOCK_CASES))
def test_read_block_matches_host_slice_of_whole_lane(case, request):
    """The paged commit's device-side read of one block (+ the recurrent
    state) is bit-identical to slicing the whole lane on the host — the
    first, a middle, the partial tail at the position, a block the
    lane's end cuts short, lanes sharded over a mesh, and
    recurrent-state archs."""
    from repro.configs import get_smoke_config
    from repro.models.registry import build
    from repro.serve.kvcache import TieredKVCache
    arch, t_max, bt, slot, blk, plen, mesh = READ_BLOCK_CASES[case]
    ctx = None
    if mesh is not None:
        from repro.launch.mesh import make_mesh
        from repro.parallel.sharding import ctx_for_mesh
        request.getfixturevalue("host_devices_8")
        ctx = ctx_for_mesh(make_mesh(mesh, ("data", "model")))
    cfg = get_smoke_config(arch)
    bundle = build(cfg, dec_pos_len=t_max)
    kv = TieredKVCache(bundle, 4, t_max, parallel=ctx, block_tokens=bt)
    pager = BlockPager(bundle, t_max, block_tokens=bt)
    _random_lanes(kv)
    if plen is not None:
        toks = jax.random.randint(jax.random.PRNGKey(5), (1, plen), 0,
                                  cfg.vocab_size)
        _, st = bundle.prefill(bundle.init_params(jax.random.PRNGKey(0)),
                               {"tokens": toks},
                               bundle.init_caches(jax.random.PRNGKey(0), 1,
                                                  t_max))
        kv.write_slot(slot, st.caches)
    if ctx is not None:
        assert all(not l.sharding.is_fully_replicated
                   for l in jax.tree_util.tree_leaves(kv.caches))
    host = pager._host_leaves(kv.read_slot(slot))
    plan = [] if blk is None else [blk]
    got = kv.read_blocks(slot, plan)
    want = {b: pager.slice_block(host, b) for b in plan}
    if pager.state_idx:
        want[STATE_BLOCK] = pager.slice_state(host)
    assert set(got) == set(want) and want
    for b, parts in got.items():
        if b != STATE_BLOCK:
            short = min(bt, t_max - b * bt)
            assert all(p.shape[ax] == short for p, ax in zip(
                parts, (pager._axes[i] for i in pager.tok_idx)))
            parts = pager.pad_block(parts)
            assert [(p.shape, p.dtype) for p in parts] \
                == [(t.shape, t.dtype) for t in pager.block_template]
        assert len(parts) == len(want[b])
        for x, y in zip(parts, want[b]):
            assert x.dtype == y.dtype and x.shape == y.shape
            assert x.tobytes() == y.tobytes()


def test_dirty_blocks_agrees_with_slice_dirty_sweep(smoke):
    """The commit's plan (``dirty_blocks``, from the position and the
    table alone) names exactly the blocks ``slice_dirty`` slices, and
    both follow the rule: no ref, no durable entry, or fewer durable
    tokens than the position now puts in the block."""
    _, bundle, _, _, t_max = smoke
    bt = 4
    pager = BlockPager(bundle, t_max, block_tokens=bt)
    cache1 = _filled_cache1(smoke, plen=16)
    rng = np.random.default_rng(7)
    for pos in range(0, t_max + 1):
        for _ in range(4):
            table = BlockTable()
            for blk in range(pager.n_blocks(t_max)):
                kind = rng.integers(0, 3)
                if kind == 0:
                    continue
                table.refs[blk] = BlockRef(
                    blk=blk, bid=blk, tokens=int(rng.integers(0, bt + 1)),
                    name=f"kv/r/b{blk}",
                    entry=None if kind == 1 else
                    {"name": f"kv/r/b{blk}", "version": 1, "crc": 0})
            plan = pager.dirty_blocks(pos, table)
            sliced = pager.slice_dirty(cache1, pos, table)
            assert plan == sorted(b for b in sliced if b != STATE_BLOCK)
            rule = [b for b in range(pager.n_blocks(pos))
                    if b not in table.refs
                    or table.refs[b].entry is None
                    or table.refs[b].tokens
                    < pager.tokens_in_block(b, pos)]
            assert plan == rule


def test_prefix_hash_is_prefix_stable():
    a = prefix_hash("k", [1, 2, 3, 4], 4)
    assert prefix_hash("k", [1, 2, 3, 4], 4) == a
    assert prefix_hash("k", [1, 2, 3, 5], 4) != a
    assert prefix_hash("k2", [1, 2, 3, 4], 4) != a          # model identity
    assert prefix_hash("k", [1, 2, 3, 4], 2) != a           # block geometry


# ---------------------------------------------------------------------------
# scheduler: slots freed by MIGRATION keep FIFO fairness
# ---------------------------------------------------------------------------

def test_fifo_fairness_when_slots_free_via_migration():
    """A slot released by migration (not completion) admits the next
    pending request in arrival order, and the migrated-in session enters
    the TARGET's queue ahead of fresh requests (submit_front)."""
    s = SlotScheduler(2)
    reqs = [Request(f"r{i}", (1, 2, 3), 4) for i in range(5)]
    s.submit(reqs)
    s.admit()                                  # r0, r1 running
    s.release("r0")                            # migrated out, NOT done
    placed = s.admit()
    assert [r.rid for _, r in placed] == ["r2"]   # FIFO refill
    t = SlotScheduler(2)
    t.submit([Request("x0", (1,), 2), Request("x1", (1,), 2)])
    t.submit_front(Request("r0", (1, 2, 3), 4))   # migrated-in
    placed = t.admit()
    assert [r.rid for _, r in placed] == ["r0", "x0"]
    with pytest.raises(AssertionError):
        t.submit_front(Request("r0", (1, 2, 3), 4))   # dup rid


# ---------------------------------------------------------------------------
# paged engine: equivalence + recovery
# ---------------------------------------------------------------------------

def _build(smoke, tmp, **kw):
    from repro.serve.engine import ServeEngine
    from repro.serve.sessions import SessionStore
    _, bundle, params, _, t_max = smoke
    store = SessionStore(DSMPool(str(tmp)),
                         engine_id=kw.pop("engine_id", 0))
    return ServeEngine(bundle, params, n_slots=2, t_max=t_max,
                       store=store, commit_every=2, **kw)


def test_paged_engine_equivalent_to_legacy(smoke, tmp_path):
    _, _, _, trace, _ = smoke
    legacy = _build(smoke, tmp_path / "legacy", paged=False)
    r0 = legacy.run(trace)
    legacy.close()
    paged = _build(smoke, tmp_path / "paged", paged=True, block_tokens=8)
    r1 = paged.run(trace)
    paged.close()
    assert r1.outputs == r0.outputs
    assert (r1.decode_ticks, r1.prefills, r1.commits) \
        == (r0.decode_ticks, r0.prefills, r0.commits)


def test_paged_commit_is_o_blocks_touched(smoke, tmp_path):
    """The paged layout's whole point: a mid-stream commit flushes only
    the dirty tail blocks, while every clean block is carried by
    reference — the newest manifest still describes the full cache."""
    _, _, _, trace, _ = smoke
    eng = _build(smoke, tmp_path, paged=True, block_tokens=4)
    eng.submit(trace)
    for _ in range(10):
        eng.tick()
    eng.store.drain()
    ms = DSMPool(str(tmp_path)).manifests_desc()
    assert len(ms) >= 2
    newest, prev = ms[0], ms[1]
    tables = newest["meta"]["tables"]
    names = {b["name"] for t in tables.values() for b in t["blocks"]}
    assert names <= set(newest["objects"]), \
        "every table block must be referenced by its manifest"
    assert any(len(t["blocks"]) > 2 for t in tables.values()), \
        "trace too short for a multi-block session"
    # at least one clean block was CARRIED by reference, not re-flushed:
    # same (name, version) in two consecutive manifests
    carried = [n for n, e in newest["objects"].items()
               if prev["objects"].get(n, {}).get("version")
               == e["version"]]
    assert carried, "no clean block carried across commits"
    eng.close()


def test_paged_commit_copies_only_dirty_blocks(smoke, tmp_path):
    """Traced: each ``serve.commit.d2h`` span copies ``blocks`` whole
    token blocks (+ the recurrent state) — never a whole lane — and the
    blocks a commit reads are exactly the token blocks it stages."""
    _, _, _, trace, t_max = smoke
    eng = _build(smoke, tmp_path / "pool", paged=True, block_tokens=4)
    pager = eng.pager
    block_bytes = sum(int(np.prod(t.shape)) * t.dtype.itemsize
                      for t in pager.block_template)
    state_bytes = sum(int(np.prod(t.shape)) * t.dtype.itemsize
                      for t in pager.state_template)
    lane_bytes = block_bytes * t_max // 4 + state_bytes
    staged, per_commit = [0], []
    stage_block, commit = eng.store.stage_block, eng._commit

    def counting_stage(session, ref, leaves):
        staged[0] += ref.blk != STATE_BLOCK
        return stage_block(session, ref, leaves)

    def counting_commit():
        staged[0] = 0
        commit()
        per_commit.append(staged[0])

    eng.store.stage_block = counting_stage
    eng._commit = counting_commit
    eng.submit(trace)
    t0 = time.perf_counter()
    with jax.profiler.trace(str(tmp_path / "trace")):
        for _ in range(10):
            eng.tick()
    got = obs.spans(t0, time.perf_counter())
    eng.close()
    assert got is not None
    commits = sorted((s for s in got if s.name == "serve.commit"),
                     key=lambda s: s.t0)
    assert len(commits) == len(per_commit) == 5
    reads = [s for s in got if s.name == "serve.commit.d2h"]
    for s in reads:
        assert s.attrs["bytes"] \
            == s.attrs["blocks"] * block_bytes + state_bytes
        assert s.attrs["bytes"] < lane_bytes
    for c, n_staged in zip(commits, per_commit):
        assert sum(s.attrs["blocks"] for s in reads
                   if s.parent == c.id) == n_staged
    assert sum(per_commit) > 0
    # a session already durable at the previous commit re-reads only
    # its growing tail block
    assert min(s.attrs["blocks"] for s in reads) == 1


def test_paged_resume_bit_identical(smoke, tmp_path):
    _, _, _, trace, _ = smoke
    ref = _build(smoke, tmp_path / "ref", paged=True)
    r0 = ref.run(trace)
    ref.close()
    half = _build(smoke, tmp_path / "kill", paged=True)
    half.submit(trace)
    for _ in range(7):
        half.tick()
    half.store.drain()
    half.close()
    back = _build(smoke, tmp_path / "kill", paged=True)
    step = back.resume()
    assert step is not None
    res = back.run(trace)
    back.close()
    assert res.outputs == r0.outputs
    assert res.resumed_sessions > 0


def test_paged_recover_falls_back_on_torn_block(smoke, tmp_path):
    """Corrupting a block referenced ONLY by the newest paged manifest
    sends recovery to the previous manifest — a session table never
    pairs with torn bytes."""
    _, _, _, trace, _ = smoke
    eng = _build(smoke, tmp_path, paged=True)
    eng.submit(trace)
    for _ in range(9):
        eng.tick()
    eng.store.drain()
    eng.close()
    pool = DSMPool(str(tmp_path))
    manifests = pool.manifests_desc()
    assert len(manifests) >= 2
    newest, prev = manifests[0], manifests[1]
    meta = newest["meta"]
    # corrupt a freshly-flushed block of a RUNNING session — one the
    # recovery of the newest manifest must read and the previous
    # manifest does not reference
    victim = None
    for rid, s in meta["sessions"].items():
        if s["done"] or "migrated_to" in s or rid not in meta["tables"]:
            continue
        for b in meta["tables"][rid]["blocks"]:
            e = newest["objects"][b["name"]]
            if prev["objects"].get(b["name"]) != e:
                victim = (b["name"], e["version"])
                break
        if victim:
            break
    assert victim is not None, "no fresh flush in the newest commit"
    path = pool.payload_path(*victim)
    data = open(path, "rb").read()
    open(path, "wb").write(data[:max(1, len(data) // 2)])
    back = _build(smoke, tmp_path, paged=True)
    step = back.resume()
    assert step == prev["step"]
    back.close()


def test_prefix_reuse_skips_prefill_bit_identically(smoke, tmp_path):
    _, _, _, trace, _ = smoke
    shared = [Request(rid=f"a{i}", prompt=trace[0].prompt,
                      max_new_tokens=6) for i in range(3)]
    e1 = _build(smoke, tmp_path, paged=True, engine_id=1,
                prefix_reuse=True, prefix_key="t")
    r1 = e1.run(shared)
    e1.close()
    assert r1.prefills >= 1
    again = [Request(rid=f"b{i}", prompt=trace[0].prompt,
                     max_new_tokens=6) for i in range(3)]
    e2 = _build(smoke, tmp_path, paged=True, engine_id=2,
                prefix_reuse=True, prefix_key="t")
    r2 = e2.run(again)
    e2.close()
    assert r2.prefills == 0 and r2.prefix_hits == 3
    assert [r2.outputs[f"b{i}"] for i in range(3)] \
        == [r1.outputs[f"a{i}"] for i in range(3)]
