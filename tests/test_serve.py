"""The serving subsystem (repro.serve): scheduler contract, KV-cache
lanes and the tiers a cold cache moves through, durable sessions, and
end-to-end engine properties.

Layer by layer:

* scheduler  — pure state machine: admission never exceeds the slot
  count, finished sequences free their slot within one step, FIFO
  fairness under oversubscription;
* kvcache    — slot surgery is exact; a smoke cache round-trips
  BIT-identically through the host, peer-staging and pool tiers;
* sessions   — the FliT session commit pairs table + block objects
  atomically, and recovery falls back past a torn block;
  async schedules pair the manifest with the meta captured at flush
  LAUNCH (regression: a later table must never describe older caches);
* engine     — continuous batching emits tokens identical to the static
  baseline; in-process kill + resume is bit-identical from both the
  committed-cache and replay paths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dsm.pool import DSMPool, manifest_entry, partition_leaves
from repro.dsm.tiers import TierManager
from repro.serve.kvcache import TieredKVCache
from repro.serve.paging import STATE_BLOCK, BlockPager, BlockRef, BlockTable
from repro.serve.scheduler import Request, SlotScheduler
from repro.serve.sessions import Session, SessionStore
from repro.serve.trace import synthetic_trace, trace_t_max


# ---------------------------------------------------------------------------
# scheduler (no jax)
# ---------------------------------------------------------------------------

def _reqs(n, max_new=4):
    return [Request(f"r{i}", (1, 2, 3), max_new) for i in range(n)]


def test_admission_never_exceeds_slots():
    s = SlotScheduler(3)
    s.submit(_reqs(10))
    placed = s.admit()
    assert len(placed) == 3
    assert s.n_running == 3
    assert s.admit() == []                    # no free slot, no admission
    assert s.n_running == 3


def test_finished_sequence_frees_slot_within_one_step():
    s = SlotScheduler(2)
    s.submit(_reqs(5))
    s.admit()
    slot = s.release("r0")
    assert s.slots[slot] is None
    placed = s.admit()                        # SAME tick refills the lane
    assert [(sl, r.rid) for sl, r in placed] == [(slot, "r2")]


def test_fifo_fairness_under_oversubscription():
    s = SlotScheduler(2)
    s.submit(_reqs(7))
    order = []
    s.admit()
    while not s.done:
        running = list(s.running)
        for rid in running:
            order.append(rid)
            s.release(rid)
        s.admit()
    assert s.admission_order == [f"r{i}" for i in range(7)]
    assert order == [f"r{i}" for i in range(7)]


def test_duplicate_rid_rejected():
    s = SlotScheduler(2)
    s.submit(_reqs(2))
    with pytest.raises(AssertionError):
        s.submit(_reqs(1))


# ---------------------------------------------------------------------------
# shared smoke model
# ---------------------------------------------------------------------------

TRACE_KW = dict(prompt_lens=(16,), new_tokens=(3, 5, 9, 13))


@pytest.fixture(scope="module")
def smoke():
    from repro.configs import get_smoke_config
    from repro.models.registry import build
    cfg = get_smoke_config("olmo-1b")
    trace = synthetic_trace(10, vocab_size=cfg.vocab_size, **TRACE_KW)
    t_max = trace_t_max(trace)
    bundle = build(cfg, dec_pos_len=t_max)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return cfg, bundle, params, trace, t_max


def _engine(smoke, **kw):
    from repro.serve.engine import ServeEngine
    _, bundle, params, _, t_max = smoke
    return ServeEngine(bundle, params, n_slots=4, t_max=t_max, **kw)


@pytest.fixture(scope="module")
def reference_outputs(smoke):
    """Uninterrupted continuous run — the bit-identity oracle."""
    _, _, _, trace, _ = smoke
    return _engine(smoke).run(trace).outputs


def _tree_eq(a, b):
    fa, ta = jax.tree_util.tree_flatten(a)
    fb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# tiered KV cache
# ---------------------------------------------------------------------------

def _filled_cache1(smoke, seed=1):
    """A single-sequence cache with non-trivial contents (via prefill)."""
    _, bundle, params, _, t_max = smoke
    toks = jax.random.randint(jax.random.PRNGKey(seed), (1, 16), 0,
                              smoke[0].vocab_size)
    _, st = bundle.prefill(params, {"tokens": toks},
                           bundle.init_caches(jax.random.PRNGKey(0), 1,
                                              t_max))
    return st.caches


def test_kv_slot_write_read_roundtrip(smoke, tmp_path):
    _, bundle, _, _, t_max = smoke
    kv = TieredKVCache(bundle, 4, BlockPager(bundle, t_max))
    c1 = _filled_cache1(smoke)
    kv.write_slot(2, c1)
    _tree_eq(kv.read_slot(2), c1)
    # other lanes untouched (still zeros)
    z = jax.tree_util.tree_leaves(kv.read_slot(0))
    assert all(float(jnp.max(jnp.abs(l.astype(jnp.float32)))) == 0.0
               for l in z)


def test_kv_spill_restore_bit_identical_host_tier(smoke, tmp_path):
    """LStore into the host object tier and read it back unchanged."""
    tiers = TierManager(DSMPool(str(tmp_path / "pool")), worker_id=0)
    c1 = _filled_cache1(smoke)
    tiers.lstore("kv/s0", c1)
    _tree_eq(tiers.hbm["kv/s0"], c1)


def test_kv_spill_restore_bit_identical_peer_staging(smoke, tmp_path):
    """RStore into a peer's host buffer, then OUR crash: the peer reads
    the exact bytes back from its staging tier."""
    ours = TierManager(DSMPool(str(tmp_path / "a")), worker_id=0)
    peer = TierManager(DSMPool(str(tmp_path / "b")), worker_id=1)
    c1 = _filled_cache1(smoke)
    ours.lstore("kv/s0", c1)
    ours.rstore("kv/s0", peer, tag=ours.versions["kv/s0"])
    ours.crash()                              # our volatile tiers vanish
    restored = peer.rload("kv/s0")
    assert restored is not None
    _tree_eq(restored, c1)


def test_kv_spill_durable_pool_roundtrip(smoke, tmp_path):
    """Sharded RFlush to the pool (byte-balanced blocks) and back:
    bit-identical including non-native dtypes (bf16 raw-view storage)."""
    _, bundle, _, _, t_max = smoke
    tiers = TierManager(DSMPool(str(tmp_path / "pool")), worker_id=0)
    c1 = _filled_cache1(smoke)
    assert any(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(c1))
    tiers.lstore("kv/s0", c1)
    entry = manifest_entry(tiers.rflush_sharded("kv/s0", 2))
    tiers.crash()                             # host tier gone: pool only
    restored = tiers.pool.read_entry("kv/s0", entry,
                                     bundle.abstract_caches(1, t_max))
    _tree_eq(restored, c1)


def test_kv_block_layout_covers_all_leaves_byte_balanced(smoke):
    _, bundle, _, _, t_max = smoke
    leaves = jax.tree_util.tree_leaves(bundle.abstract_caches(1, t_max))
    layout = partition_leaves(
        [int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves], 2)
    flat = sorted(i for g in layout for i in g)
    assert flat == list(range(len(leaves)))   # exact cover, no dupes
    assert all(g for g in layout)             # no empty block


# ---------------------------------------------------------------------------
# durable sessions
# ---------------------------------------------------------------------------

def _stage_session(store, pager, s, cache1, table):
    """Stage session ``s``'s dirty blocks of ``cache1`` for the next
    commit, as the engine does from a lane."""
    for blk, leaves in pager.slice_dirty(cache1, s.pos, table).items():
        ref = table.refs.setdefault(blk, BlockRef(
            blk=blk, bid=len(table.refs), tokens=0,
            name=store.block_name(s.rid, blk)))
        if blk != STATE_BLOCK:
            ref.tokens = pager.tokens_in_block(blk, s.pos)
        store.stage_block(s, ref, leaves)


def test_session_commit_and_recover(smoke, tmp_path):
    _, bundle, _, _, t_max = smoke
    pager = BlockPager(bundle, t_max)
    store = SessionStore(DSMPool(str(tmp_path / "pool")))
    c1 = _filled_cache1(smoke)                # 16 prefilled positions
    s = Session("r0", tuple(range(16)), 8, emitted=[7, 9])
    table = BlockTable()
    _stage_session(store, pager, s, c1, table)
    store.commit_paged({"r0": s}, {"r0": table}, step=4,
                       block_tokens=pager.block_tokens)
    store.close()

    store2 = SessionStore(DSMPool(str(tmp_path / "pool")))
    rec = store2.recover(pager=pager)
    assert rec is not None and rec.step == 4
    assert rec.sessions["r0"].emitted == [7, 9]
    assert rec.sessions["r0"].pos == 16 + 2 - 1
    _tree_eq(rec.caches["r0"], c1)


def test_session_recover_falls_back_on_torn_commit(smoke, tmp_path):
    """Corrupting the newest commit's block object must push recovery to
    the previous manifest — a session table can never pair with torn
    bytes."""
    import os
    _, bundle, _, _, t_max = smoke
    pager = BlockPager(bundle, t_max)
    store = SessionStore(DSMPool(str(tmp_path / "pool")))
    s = Session("r0", (1, 2, 3), 8, emitted=[7])
    table = BlockTable()
    _stage_session(store, pager, s, _filled_cache1(smoke, seed=1), table)
    store.commit_paged({"r0": s}, {"r0": table}, step=2,
                       block_tokens=pager.block_tokens)
    s.emitted.append(8)                       # block 0 grows: dirty again
    _stage_session(store, pager, s, _filled_cache1(smoke, seed=2), table)
    store.commit_paged({"r0": s}, {"r0": table}, step=4,
                       block_tokens=pager.block_tokens)
    store.close()
    # tear the newest commit: clobber its block object payload
    obj_dir = os.path.join(str(tmp_path / "pool"), "objects",
                           store.block_name("r0", 0))
    newest = sorted(f for f in os.listdir(obj_dir)
                    if f.endswith((".npz", ".cxl0")))[-1]
    with open(os.path.join(obj_dir, newest), "wb") as f:
        f.write(b"torn")
    rec = SessionStore(DSMPool(str(tmp_path / "pool"))).recover(
        pager=pager)
    assert rec is not None and rec.step == 2
    assert rec.sessions["r0"].emitted == [7]


def test_async_commit_meta_captured_at_launch(tmp_path):
    """Regression: in async schedules the manifest for step s must carry
    the meta passed WITH step s's commit call (captured at flush launch),
    not whatever meta a later commit happens to pass at join time."""
    from repro.dsm.flit_runtime import DurableCommitter
    tiers = TierManager(DSMPool(str(tmp_path / "pool")), worker_id=0)
    c = DurableCommitter(tiers, mode="async")
    c.update({"x": {"a": np.arange(4)}}, step=0)
    assert c.commit(0, meta={"tag": "step0"}) is None   # launched, no join
    c.update({"x": {"a": np.arange(4) + 1}}, step=1)
    st = c.commit(1, meta={"tag": "step1"})             # joins step 0
    assert st is not None and st.step == 0
    c.drain()
    manifests = {m["step"]: m for m in tiers.pool.manifests_desc()}
    assert manifests[0]["meta"] == {"tag": "step0"}
    assert manifests[1]["meta"] == {"tag": "step1"}


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

def test_continuous_matches_static_bitwise(smoke, reference_outputs):
    _, _, _, trace, _ = smoke
    res_s = _engine(smoke).run_static(trace)
    assert res_s.outputs == reference_outputs
    # and the occupancy win is real: strictly fewer decode ticks
    res_c = _engine(smoke).run(trace)
    assert res_c.decode_ticks < res_s.decode_ticks


def test_engine_reuses_freed_slots(smoke):
    _, _, _, trace, _ = smoke
    eng = _engine(smoke)
    res = eng.run(trace)
    # 10 requests through 4 slots: every request got a lane eventually
    assert len(res.outputs) == len(trace)
    assert eng.sched.done
    assert res.prefills == len(trace)
    for r in trace:
        assert len(res.outputs[r.rid]) == r.max_new_tokens


class _Kill(Exception):
    pass


@pytest.mark.parametrize("point,restore_mode", [
    ("pre_flush", "cache"), ("mid_flush", "cache"),
    ("post_completeOp", "cache"), ("mid_flush", "replay"),
])
def test_engine_kill_resume_bit_identical(smoke, reference_outputs,
                                          tmp_path, point, restore_mode):
    """In-process kill inside the session-commit window, then a fresh
    engine resumes from the pool: every session's final tokens equal the
    uninterrupted run exactly — via committed-cache restore AND replay."""
    _, _, _, trace, _ = smoke

    def hook(p, step):
        if p == point and step >= 6:
            raise _Kill()

    store = SessionStore(DSMPool(str(tmp_path / "pool")), fault_hook=hook)
    eng = _engine(smoke, store=store, commit_every=3)
    with pytest.raises(_Kill):
        eng.run(trace)

    store2 = SessionStore(DSMPool(str(tmp_path / "pool")))
    eng2 = _engine(smoke, store=store2, commit_every=3,
                   restore_mode=restore_mode)
    resumed = eng2.resume()
    assert resumed is not None
    done_at_resume = len(eng2.results)
    res = eng2.run(trace)
    assert res.outputs == reference_outputs
    if restore_mode == "cache":
        # fast-forward really happened: recovered-done sessions came back
        # as results and resumed sessions re-entered WITHOUT a prefill
        assert res.prefills == (len(trace) - done_at_resume
                                - res.resumed_sessions)


def test_engine_retire_done_bounds_committed_table(smoke,
                                                   reference_outputs,
                                                   tmp_path):
    """With retire_done, finished sessions leave the committed table one
    commit after completion: the final manifest stays O(live sessions)
    while the caller still gets every output."""
    pool_dir = str(tmp_path / "pool")
    store = SessionStore(DSMPool(pool_dir))
    eng = _engine(smoke, store=store, commit_every=3, retire_done=True)
    _, _, _, trace, _ = smoke
    res = eng.run(trace)
    eng.close()
    assert res.outputs == reference_outputs       # delivery unaffected
    final = DSMPool(pool_dir).latest_manifest()
    assert len(final["meta"]["sessions"]) < len(trace)
    # a restart serves the trace as NEW work for retired sessions only —
    # nothing unfinished was lost
    store2 = SessionStore(DSMPool(pool_dir))
    eng2 = _engine(smoke, store=store2)
    eng2.resume()
    assert all(not s.done or rid in eng2.results
               for rid, s in eng2.sessions.items())


def test_engine_rejects_encoder_decoder(smoke):
    """Encoder-decoder archs fail fast with a clear error, not deep in
    the slot-decode assert (and the CLIs exclude them via
    servable_archs)."""
    from repro.configs import get_smoke_config
    from repro.models.registry import build
    from repro.serve.engine import ServeEngine, servable_archs
    assert "whisper-small" not in servable_archs()
    assert "olmo-1b" in servable_archs()
    cfg = get_smoke_config("whisper-small")
    bundle = build(cfg, dec_pos_len=8)
    with pytest.raises(ValueError, match="decoder-only"):
        ServeEngine(bundle, params=None, n_slots=2, t_max=8)


def test_engine_full_recovery_no_recompute(smoke, reference_outputs,
                                           tmp_path):
    """Restarting over a COMPLETED run's pool returns every output from
    the session table without a single prefill or decode tick."""
    _, _, _, trace, _ = smoke
    store = SessionStore(DSMPool(str(tmp_path / "pool")))
    eng = _engine(smoke, store=store, commit_every=3)
    eng.run(trace)
    eng.close()
    store2 = SessionStore(DSMPool(str(tmp_path / "pool")))
    eng2 = _engine(smoke, store=store2)
    assert eng2.resume() is not None
    res = eng2.run(trace)
    assert res.outputs == reference_outputs
    assert res.prefills == 0 and res.decode_ticks == 0
