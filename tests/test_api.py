"""The unified programming-model API (repro.dsm.api): config round-trip,
export surface, and EQUIVALENCE — a run wired through `open_cxl0` /
commit regions must be bit-identical (pool manifests + recovered state)
to the legacy hand-wired five-object stack, including one crash/recovery
cell per subsystem (train / serve / cluster)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import repro.dsm as dsm
from repro.dsm import (CXL0Config, CXL0Context, DSMPool, DurableCommitter,
                       RecoveryManager, TierManager, open_cxl0)
from repro.dsm.cluster import ClusterProtocol, FileStagingArea, rank_ns
from repro.dsm.recovery import ColdStartError, CrashError

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(REPO, "src")


# ---------------------------------------------------------------------------
# deterministic toy state (pure numpy — no jit, fast)
# ---------------------------------------------------------------------------

def init_objects():
    return {
        "params": {"w0": np.arange(12, dtype=np.float32).reshape(3, 4),
                   "w1": np.linspace(-1, 1, 8).astype(np.float32)},
        "opt": {"mu": np.zeros(6, np.float32),
                "nu": np.full(6, 0.5, np.float32)},
    }


def step_objects(objs, i):
    """Pure function of (state, step): both wirings replay identically."""
    import jax
    return jax.tree_util.tree_map(
        lambda a: a * np.float32(0.9) + np.float32(i + 1) / 16, objs)


def templates():
    import jax
    return jax.tree_util.tree_map(np.zeros_like, init_objects())


def manifest_docs(pool_dir):
    return DSMPool(pool_dir).manifests_desc()


def tree_equal(a, b):
    import jax
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# config + exports
# ---------------------------------------------------------------------------

def test_config_round_trip():
    cfg = CXL0Config(path="/x/pool", worker_id=3, topology="cxl30-fabric",
                     schedule="sharded", n_shards=4, retention=7)
    d = cfg.to_dict()
    assert json.loads(json.dumps(d)) == d       # JSON-serializable
    back = CXL0Config.from_dict(d)
    assert back.to_dict() == d
    assert (back.path, back.worker_id, back.topology, back.schedule,
            back.n_shards, back.retention) == \
        ("/x/pool", 3, "cxl30-fabric", "sharded", 4, 7)


def test_config_schedule_resolution():
    assert CXL0Config(path="p").resolved_schedule() == "sharded-async"
    assert CXL0Config(path="p", schedule="sync").resolved_schedule() == "sync"
    assert CXL0Config(path="p", topology="cxl11-direct") \
        .resolved_schedule() == "auto"
    with pytest.raises(ValueError):
        CXL0Config(path="p", schedule="bogus")


def test_config_open_wires_the_stack(tmp_path):
    ctx = CXL0Config(path=str(tmp_path / "p"), worker_id=2,
                     topology="cxl20-switched-pool", schedule="sync",
                     retention=3).open()
    assert isinstance(ctx, CXL0Context)
    assert ctx.committer.mode == "sync"
    assert ctx.committer.retention == 3
    assert ctx.tiers.worker_id == 2
    assert ctx.placement is not None
    assert ctx.placement.topology.name == "cxl20-switched-pool"
    assert ctx.committer.placement is ctx.placement
    ctx.close()


def test_all_exports():
    expected = {"open_cxl0", "CXL0Context", "CXL0Config", "CommitRegion",
                "DurableHandle", "TransformedObject", "DSMPool",
                "TierManager", "DurableCommitter", "RecoveryManager",
                "CrashError", "ColdStartError"}
    assert expected <= set(dsm.__all__)
    ns = {}
    exec("from repro.dsm import *", ns)
    assert expected <= set(ns)


def test_import_clean_under_deprecation_errors():
    """`import repro.dsm` must not trip -W error::DeprecationWarning."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-W", "error::DeprecationWarning", "-c",
         "import repro.dsm"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_py_typed_marker_ships():
    assert os.path.exists(os.path.join(SRC, "repro", "py.typed"))


def test_no_tiermanager_constructed_outside_dsm():
    """The acceptance grep as a test: every subsystem builds its stack via
    open_cxl0/CXL0Config — TierManager is constructed only inside
    repro/dsm (and tests)."""
    offenders = []
    for root in ("src", "examples", "benchmarks"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            if os.path.join("repro", "dsm") in dirpath:
                continue
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                p = os.path.join(dirpath, fn)
                with open(p) as f:
                    if "TierManager(" in f.read():
                        offenders.append(os.path.relpath(p, REPO))
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# TRAIN: ctx-wired run == legacy hand-wired run, bit for bit
# ---------------------------------------------------------------------------

N_STEPS, CADENCE = 6, 2


def legacy_train(pool_dir, mode="sync", n_shards=None):
    """The pre-API wiring, verbatim: five objects assembled by hand."""
    pool = DSMPool(pool_dir)
    tiers = TierManager(pool, 0)
    committer = DurableCommitter(tiers, mode=mode, n_shards=n_shards)
    objs = init_objects()
    committer.update(objs, step=-1)
    committer.commit(-1)
    committer.drain()
    for i in range(N_STEPS):
        objs = step_objects(objs, i)
        committer.update(objs, step=i)
        if (i + 1) % CADENCE == 0:
            committer.commit(i)
    committer.drain()
    tiers.close()
    rec = RecoveryManager(pool).recover(templates(), ())
    return objs, rec


def ctx_train(pool_dir, mode="sync", n_shards=None):
    """The same program through the unified API."""
    ctx = open_cxl0(pool_dir, schedule=mode, n_shards=n_shards)
    objs = init_objects()
    ctx.put(objs, step=-1)
    with ctx.commit(-1):
        pass
    ctx.drain()
    for i in range(N_STEPS):
        objs = step_objects(objs, i)
        ctx.put(objs, step=i)
        if (i + 1) % CADENCE == 0:
            with ctx.commit(i):
                pass
    ctx.drain()
    ctx.close()
    rec = ctx.recover(templates())
    return objs, rec


@pytest.mark.parametrize("mode,n_shards", [("sync", None),
                                           ("sharded-async", 2)])
def test_train_equivalence_bit_identical(tmp_path, mode, n_shards):
    objs_l, rec_l = legacy_train(str(tmp_path / "legacy"), mode, n_shards)
    objs_c, rec_c = ctx_train(str(tmp_path / "ctx"), mode, n_shards)
    # identical manifest DOCUMENTS (seq, step, per-object version/crc/
    # nbytes, meta) — the durable history is bit-identical
    docs_l = manifest_docs(str(tmp_path / "legacy"))
    docs_c = manifest_docs(str(tmp_path / "ctx"))
    assert docs_l == docs_c
    assert len(docs_l) == 1 + N_STEPS // CADENCE
    # identical live and recovered state
    assert tree_equal(objs_l, objs_c)
    assert rec_l[1:] == rec_c[1:]               # (step, source)
    assert tree_equal(rec_l[0], rec_c[0])


@pytest.mark.parametrize("point", ["pre_flush", "post_completeOp"])
def test_train_crash_cell(tmp_path, point):
    """One crash/recovery cell through the migrated training entry point:
    a CrashError fired INSIDE the commit window at `point`; the loop must
    recover to a completed commit and end bit-identical to a clean run."""
    import jax
    from repro.data.pipeline import DataPipeline, SyntheticLMSource
    from repro.scenarios.worker import make_toy_state, make_toy_step
    from repro.train.loop import run_durable_loop

    def run(pool_dir, hook=None):
        pipe = DataPipeline(SyntheticLMSource(64), 2, 8)
        return run_durable_loop(
            make_toy_step(), make_toy_state(dim=8, n_tensors=2, seed=0),
            pipe, DSMPool(pool_dir), n_steps=6, commit_every=2,
            commit_mode="sync", fault_hook=hook)

    fired = []

    def hook(p, step):
        if not fired and p == point and step >= 3:
            fired.append(step)
            raise CrashError(f"injected at {p}")

    r = run(str(tmp_path / "crash"), hook)
    clean = run(str(tmp_path / "clean"))
    assert fired and r.crashes == 1
    assert r.recoveries == ["pool"]
    assert tree_equal(r.state.params, clean.state.params)
    # both pools end with the same durable history
    assert (manifest_docs(str(tmp_path / "crash"))[0]["step"]
            == manifest_docs(str(tmp_path / "clean"))[0]["step"])


# ---------------------------------------------------------------------------
# SERVE: a SessionStore's context == legacy hand-wired commit, bit for bit
# ---------------------------------------------------------------------------

def serve_caches(tick):
    return {
        "s1": {"k": np.arange(8, dtype=np.float32) + tick,
               "v": np.full(4, 2.0 + tick, np.float32)},
        "s2": {"k": np.arange(8, dtype=np.float32) * 2 + tick,
               "v": np.full(4, 7.0 + tick, np.float32)},
    }


#: recovery templates of the two sessions' ``kv/<rid>`` cache objects
SERVE_TEMPLATES = {f"kv/{rid}": {"k": np.zeros(8, np.float32),
                                 "v": np.zeros(4, np.float32)}
                   for rid in ("s1", "s2")}


def serve_table(store_like_versions, tick):
    from repro.serve.sessions import Session
    table = {}
    for rid in ("s1", "s2"):
        s = Session(rid, prompt=(1, 2, 3), max_new_tokens=4,
                    emitted=[9, 8][: 1 + tick % 2])
        s.cache_version = store_like_versions[rid]
        table[rid] = s
    return table


def serve_meta(table):
    return {"kind": "serve",
            "sessions": {rid: s.to_meta() for rid, s in table.items()}}


def test_serve_equivalence_bit_identical(tmp_path):
    from repro.serve.sessions import SessionStore

    # -- legacy: hand-wired tiers + committer, meta assembled by hand ----
    pool_l = DSMPool(str(tmp_path / "legacy"))
    tiers = TierManager(pool_l, 0)
    committer = DurableCommitter(tiers, mode="sync", retention=2)
    for tick in (3, 7):
        caches = serve_caches(tick)
        versions = {}
        for rid, c in caches.items():
            tiers.lstore(f"kv/{rid}", c)
            versions[rid] = tiers.versions[f"kv/{rid}"]
        committer.commit(tick, meta=serve_meta(serve_table(versions, tick)))
    tiers.close()

    # -- new API: a SessionStore's open_cxl0 context, one commit region --
    store = SessionStore(DSMPool(str(tmp_path / "ctx")), mode="sync",
                         retention=2)
    for tick in (3, 7):
        caches = serve_caches(tick)
        versions = {}
        for rid, c in caches.items():
            store.tiers.lstore(f"kv/{rid}", c)
            versions[rid] = store.tiers.versions[f"kv/{rid}"]
        meta = serve_meta(serve_table(versions, tick))
        with store.ctx.commit(tick, meta=meta):
            pass
    store.close()

    docs_l = manifest_docs(str(tmp_path / "legacy"))
    docs_c = manifest_docs(str(tmp_path / "ctx"))
    assert docs_l == docs_c and len(docs_l) == 2

    # recovered state identical through the context's recovery path
    objs, step, _ = RecoveryManager(DSMPool(str(tmp_path / "ctx"))).recover(
        SERVE_TEMPLATES)
    assert step == 7
    assert tree_equal(objs["kv/s1"], serve_caches(7)["s1"])


def test_serve_crash_cell(tmp_path):
    """Crash inside the session-commit window (pre_flush): no completeOp,
    a restarted store recovers the PREVIOUS committed tick."""
    from repro.serve.sessions import SessionStore

    def hook(point, step):
        if point == "pre_flush" and step >= 7:
            raise CrashError("die in the commit window")

    store = SessionStore(DSMPool(str(tmp_path)), mode="sync",
                         fault_hook=hook)
    committed = {}
    for tick in (3, 7):
        caches = serve_caches(tick)
        versions = {}
        for rid, c in caches.items():
            store.tiers.lstore(f"kv/{rid}", c)
            versions[rid] = store.tiers.versions[f"kv/{rid}"]
        meta = serve_meta(serve_table(versions, tick))
        if tick == 3:
            with store.ctx.commit(tick, meta=meta):
                pass
            committed = caches
        else:
            with pytest.raises(CrashError):
                with store.ctx.commit(tick, meta=meta):
                    pass
    store.ctx.crash()

    objs, step, _ = RecoveryManager(DSMPool(str(tmp_path))).recover(
        SERVE_TEMPLATES)
    assert step == 3                                # previous commit
    assert tree_equal(objs["kv/s1"], committed["s1"])


# ---------------------------------------------------------------------------
# CLUSTER: delegated completeOp + the one recovery path
# ---------------------------------------------------------------------------

def cluster_objects(step):
    return {rank_ns(0, "params"): {"t": np.arange(6, dtype=np.float32)
                                   + step},
            rank_ns(0, "opt"): {"t": np.full(6, 0.25 + step, np.float32)}}


def test_cluster_equivalence_bit_identical(tmp_path):
    """A rank committing through the elected cluster protocol: legacy
    hand-wired committer(complete_fn=...) vs open_cxl0(complete_fn=...)
    produce bit-identical cluster manifests."""
    def run(pool_dir, use_ctx):
        pool = DSMPool(pool_dir)
        proto = ClusterProtocol(pool, 0, [0])
        if use_ctx:
            ctx = open_cxl0(pool, 0, schedule="sharded", n_shards=2,
                            complete_fn=proto.cluster_complete)
            for step in range(4):
                ctx.put(cluster_objects(step), step=step)
                if step % 2 == 1:
                    with ctx.commit(step, meta={"live": [0]}):
                        pass
            ctx.close()
        else:
            tiers = TierManager(pool, 0)
            committer = DurableCommitter(
                tiers, mode="sharded", n_shards=2,
                complete_fn=proto.cluster_complete)
            for step in range(4):
                committer.update(cluster_objects(step), step=step)
                if step % 2 == 1:
                    committer.commit(step, meta={"live": [0]})
            tiers.close()

    run(str(tmp_path / "legacy"), use_ctx=False)
    run(str(tmp_path / "ctx"), use_ctx=True)
    docs_l = manifest_docs(str(tmp_path / "legacy"))
    docs_c = manifest_docs(str(tmp_path / "ctx"))
    assert docs_l == docs_c and len(docs_l) == 2
    assert set(docs_l[0]["objects"]) == set(cluster_objects(0))
    assert docs_l[0]["meta"] == {"live": [0]}   # the elected commit's meta


def test_cluster_crash_cell_staging_precedence(tmp_path):
    """The crash/recovery cell of the cluster subsystem: a victim's
    partition recovered by its sibling — ctx.recover must adopt the
    cross-process RStore-staged copy when its tag beats the newest
    cluster manifest and fall back to the pool when it doesn't,
    bit-identical to the legacy RecoveryManager path."""
    pool_dir = str(tmp_path / "pool")
    area = FileStagingArea(str(tmp_path / "staging"))
    name = rank_ns(0, "params")
    old = {"t": np.zeros(4, np.float32)}
    new = {"t": np.full(4, 2.5, np.float32)}

    victim = open_cxl0(pool_dir, 0)
    h = victim.durable(name, init=old)
    victim.pool.commit_manifest(3, {name: h.rflush()})   # pool at step 3
    h.lstore(new)
    h.rstore(area.proxy(1), tag=5)                       # staged at step 5
    victim.crash()

    # sibling adopts: fresh handles, as a separate process would have
    sibling = open_cxl0(pool_dir, 1)
    view = FileStagingArea(str(tmp_path / "staging")).view(1, {name: old})
    objs, step, source = sibling.recover({name: old}, peers=(view,),
                                         exact=False)
    legacy = RecoveryManager(DSMPool(pool_dir)).recover(
        {name: old}, peers=(view,), exact=False)
    assert (step, source) == (5, "peer-staging") == legacy[1:]
    assert tree_equal(objs, legacy[0])
    assert np.array_equal(np.asarray(objs[name]["t"]), new["t"])

    # stale staging (tag <= newest manifest step) loses to the pool
    h2 = open_cxl0(pool_dir, 0).durable(name, init=old)
    area.proxy(1).staging[name] = (3, {"t": np.asarray(old["t"])})
    view = area.view(1, {name: old})
    objs, step, source = sibling.recover({name: old}, peers=(view,),
                                         exact=False)
    assert (step, source) == (3, "pool")


# ---------------------------------------------------------------------------
# commit regions, handles, §6 transform
# ---------------------------------------------------------------------------

def test_commit_region_crash_inside_emits_no_completeop(tmp_path):
    ctx = open_cxl0(str(tmp_path), schedule="sync")
    with ctx.commit(0) as txn:
        txn.store("x", {"a": np.arange(3, dtype=np.float32)})
    with pytest.raises(RuntimeError):
        with ctx.commit(1) as txn:
            txn.store("x", {"a": np.full(3, 9.0, np.float32)})
            raise RuntimeError("crash inside the region")
    docs = manifest_docs(str(tmp_path))
    assert [d["step"] for d in docs] == [0]     # step 1 never completed
    objs, step, source = ctx.recover({"x": {"a": np.zeros(3, np.float32)}})
    assert step == 0 and source == "pool"
    assert np.array_equal(np.asarray(objs["x"]["a"]),
                          np.arange(3, dtype=np.float32))


def test_commit_region_rollback_keeps_later_commits_clean(tmp_path):
    """A caller that CATCHES the exception in-process and keeps committing
    must not have the torn batch published by a later commit: the region
    rolls its own stores back out of the volatile tier."""
    ctx = open_cxl0(str(tmp_path), schedule="sync")
    with ctx.commit(0) as txn:
        txn.store("a", {"v": np.full(2, 1.0, np.float32)})
    with pytest.raises(RuntimeError):
        with ctx.commit(1) as txn:
            txn.store("a", {"v": np.full(2, 9.0, np.float32)})
            txn.store("b", {"v": np.full(2, 5.0, np.float32)})   # brand new
            raise RuntimeError("crash inside the region")
    with ctx.commit(2):                         # commits whatever is live
        pass
    doc = manifest_docs(str(tmp_path))[0]
    assert doc["step"] == 2
    assert set(doc["objects"]) == {"a"}         # "b" never leaked
    objs, _, _ = ctx.recover({"a": {"v": np.zeros(2, np.float32)}})
    assert np.array_equal(np.asarray(objs["a"]["v"]), np.full(2, 1.0))


def test_commit_region_reports_stats(tmp_path):
    ctx = open_cxl0(str(tmp_path), schedule="sync")
    with ctx.commit(4, meta={"tag": "t"}) as txn:
        txn.store("x", {"a": np.ones(2, np.float32)})
    assert txn.stats is not None
    assert txn.stats.step == 4 and txn.stats.n_objects == 1
    assert manifest_docs(str(tmp_path))[0]["meta"] == {"tag": "t"}


def test_durable_handle_primitives(tmp_path):
    ctx = open_cxl0(str(tmp_path / "a"), schedule="sync")
    peer = open_cxl0(str(tmp_path / "b"), 1)
    h = ctx.durable("obj", init={"v": np.zeros(2, np.float32)})
    assert h.version == 1
    obj = h.mstore({"v": np.full(2, 3.0, np.float32)})
    assert (obj.version, h.version) == (2, 2)
    assert np.array_equal(np.asarray(h.value["v"]), np.full(2, 3.0))
    h.rstore(peer, tag=7)                       # a context IS a peer
    assert "obj" in peer.staging
    with pytest.raises(ValueError):
        ctx.durable("other", init={"v": np.zeros(1, np.float32)}).rstore()


def test_transform_survives_crash(tmp_path):
    from repro.core.objects import CounterSpec
    ctx = open_cxl0(str(tmp_path), schedule="sync")
    ctr = ctx.transform(CounterSpec(), name="ctr")
    assert [ctr.op("inc") for _ in range(5)] == [0, 1, 2, 3, 4]
    ctx.crash()
    revived = open_cxl0(str(tmp_path)).transform(CounterSpec(), name="ctr")
    assert revived.state == 5 and revived.ops_done == 4
    assert revived.recovered_from == (4, "pool")
    assert revived.op("inc") == 5               # history continues


def test_transform_tuple_states_round_trip(tmp_path):
    from repro.core.objects import StackSpec
    ctx = open_cxl0(str(tmp_path), schedule="sync")
    st = ctx.transform(StackSpec(), name="stack")
    st.op("push", 7)
    st.op("push", 9)
    ctx.crash()
    revived = open_cxl0(str(tmp_path)).transform(StackSpec(), name="stack")
    assert revived.state == (7, 9)              # tuples, not JSON lists
    assert revived.op("pop") == 9


def test_try_recover_cold_pool(tmp_path):
    ctx = open_cxl0(str(tmp_path))
    assert ctx.try_recover({"x": np.zeros(1, np.float32)}) is None
    with pytest.raises(ColdStartError):
        ctx.recover({"x": np.zeros(1, np.float32)})
