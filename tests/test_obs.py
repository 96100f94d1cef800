"""Program spans (``repro.obs``): off costs nothing and records nothing;
on, the spans of the serving and training paths nest as the commit,
crash and tick paths run them, carry their attributes, and match the
events the profiler wrote on its host plane."""
import glob
import threading
import time
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.data.pipeline import DataPipeline, SyntheticLMSource
from repro.dsm.pool import DSMPool
from repro.models.registry import build
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Request
from repro.serve.sessions import SessionStore
from repro.train.loop import run_durable_loop
from repro.train.state import init_train_state
from repro.train.step import make_train_step


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; returns (its spans, the xplane)."""
    t0 = time.perf_counter()
    with jax.profiler.trace(str(tmp_path / "trace")):
        fn()
    t1 = time.perf_counter()
    got = obs.spans(t0, t1)
    assert got is not None
    xplane = sorted(glob.glob(str(tmp_path / "trace" / "**" /
                                  "*.xplane.pb"), recursive=True))[-1]
    return got, xplane


def _by_name(spans):
    out = defaultdict(list)
    for s in spans:
        out[s.name].append(s)
    return out


def _ancestors(s, by_id):
    out = []
    while s.parent in by_id:
        s = by_id[s.parent]
        out.append(s.name)
    return out


def test_off_records_nothing(tmp_path):
    t0 = time.perf_counter()
    sp = obs.span("dsm.commit", step=1)
    assert not sp
    with sp as inner:
        inner.set(bytes=3)
    assert obs.carry(len) is len
    pool = DSMPool(str(tmp_path / "pool"))
    obj = pool.write_object("x", 1, {"a": np.arange(4)})
    pool.commit_manifest(0, {"x": obj})
    pool.gc(keep=1)
    assert obs.spans(t0, time.perf_counter()) == []


def test_ring_overflow_counted(monkeypatch, tmp_path):
    log = obs.SpanLog(maxlen=4)
    for i in range(6):
        log.append(obs.Span(i, "s", float(i), i + 0.5, None, "t", {}))
    assert log.dropped == 2
    assert log.spans(0.0, 10.0) is None          # records 0 and 1 are gone
    assert log.spans(1.5, 10.0) is None          # record 1 ended at 1.5
    assert [s.id for s in log.spans(2.0, 10.0)] == [2, 3, 4, 5]

    small = obs.SpanLog(maxlen=3)
    monkeypatch.setattr(obs, "_log", small)
    t0 = time.perf_counter()
    with jax.profiler.trace(str(tmp_path / "trace")):
        for _ in range(5):
            with obs.span("tick"):
                pass
    assert small.dropped == 2
    assert obs.spans(t0, time.perf_counter()) is None


def test_carry_parents_and_ids_across_threads(tmp_path):
    def work():
        with obs.span("dsm.flush.write", name="a", bytes=None):
            pass
        with obs.span("dsm.flush.fsync", step=9):
            pass

    def run():
        with obs.span("dsm.commit", step=4, mode="sharded-async"):
            th = threading.Thread(target=obs.carry(work))
            th.start()
            th.join(timeout=30)
            assert not th.is_alive()

    got, _ = _traced(tmp_path, run)
    by = _by_name(got)
    commit, = by["dsm.commit"]
    write, = by["dsm.flush.write"]
    fsync, = by["dsm.flush.fsync"]
    assert write.parent == commit.id and fsync.parent == commit.id
    assert write.thread != commit.thread
    assert write.attrs == {"name": "a", "step": 4}     # None left out
    assert fsync.attrs["step"] == 9                    # own id wins


@pytest.fixture(scope="module")
def serve_smoke():
    cfg = get_smoke_config("olmo-1b")
    bundle = build(cfg, dec_pos_len=32)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return cfg, bundle, params


def test_serve_spans_nest_and_match_the_xplane(serve_smoke, tmp_path):
    """A durable engine's ticks, admissions and a paged commit, traced:
    the spans nest as the engine runs them, carry their attributes, and
    the profiler's host plane holds the same spans with the same
    durations."""
    from jax.profiler import ProfileData
    cfg, bundle, params = serve_smoke
    store = SessionStore(DSMPool(str(tmp_path / "pool")))
    eng = ServeEngine(bundle, params, n_slots=2, t_max=32, store=store,
                      commit_every=2)
    eng.warmup([8])
    rng = np.random.default_rng(0)
    eng.submit([Request(f"r{i}", tuple(int(t) for t in rng.integers(
        0, cfg.vocab_size, 8)), 5) for i in range(3)])

    def run():
        for _ in range(6):
            eng.tick()

    got, xplane = _traced(tmp_path, run)
    eng.close()
    by, by_id = _by_name(got), {s.id: s for s in got}
    assert len(by["serve.tick"]) == 6
    assert [s.attrs["tick"] for s in by["serve.tick"]] == list(range(6))
    for s in by["serve.admit"]:
        assert _ancestors(s, by_id) == ["serve.tick"]
        assert s.attrs["prompt"] == 8 and s.attrs["queued_ms"] >= 0
    assert len(by["serve.admit"]) == 3
    assert all(_ancestors(s, by_id) == ["serve.admit", "serve.tick"]
               for s in by["serve.prefill.wait"])
    assert all(_ancestors(s, by_id) == ["serve.tick"]
               for s in by["serve.decode.wait"])
    commits = by["serve.commit"]
    assert len(commits) == 3
    for s in commits:
        assert _ancestors(s, by_id) == ["serve.tick"]
        assert s.attrs["sessions"] >= 1
    for s in by["serve.commit.d2h"]:
        assert _ancestors(s, by_id)[:1] == ["serve.commit"]
        assert s.attrs["bytes"] > 0 and s.attrs["rid"].startswith("r")
    for name in ("serve.commit.table", "dsm.commit", "dsm.lstore.seed"):
        assert by[name] and all(
            _ancestors(s, by_id)[0] == "serve.commit" for s in by[name])
    for name in ("dsm.commit.snapshot", "dsm.flush.write",
                 "dsm.flush.fsync", "dsm.commit.manifest"):
        assert by[name] and all(
            _ancestors(s, by_id)[:2] == ["dsm.commit", "serve.commit"]
            or _ancestors(s, by_id)[:3] == ["dsm.commit.snapshot",
                                            "dsm.commit", "serve.commit"]
            for s in by[name]), name
    assert all(s.attrs["bytes"] > 0 for s in by["dsm.flush.write"])
    assert all(s.attrs["bytes"] > 0 for s in by["dsm.commit.manifest"])
    assert max(s.attrs["objects"] for s in by["dsm.commit.manifest"]) > 0
    assert all(s.attrs["entries"] >= 0 for s in by["dsm.lstore.seed"])

    # the same spans on the profiler's host plane, in the same order
    plane = defaultdict(list)
    for p in ProfileData.from_file(xplane).planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                for ev in line.events:
                    if ev.name in by:
                        plane[ev.name].append(ev)
    for name, spans in by.items():
        evs = sorted(plane[name], key=lambda e: e.start_ns)
        assert len(evs) == len(spans), name
        for s, ev in zip(sorted(spans, key=lambda s: s.t0), evs):
            d_log, d_ev = s.t1 - s.t0, ev.duration_ns * 1e-9
            assert abs(d_ev - d_log) <= max(0.05 * d_log, 50e-6), \
                (name, d_log, d_ev)


def test_durable_loop_crash_and_commit_spans(tmp_path):
    """Sharded-async training crashed after a commit: the crash joins the
    aborted flush inside ``train.crash``; a commit joins the previous
    flush and snapshots the state inside ``dsm.commit``; the flush
    pipelines' writes belong to the commit that started them."""
    cfg = get_smoke_config("olmo-1b")
    bundle = build(cfg)
    key = jax.random.PRNGKey(0)
    state = init_train_state(bundle.init_params(key), key)
    step = jax.jit(make_train_step(bundle))

    def run():
        run_durable_loop(
            step, state, DataPipeline(SyntheticLMSource(cfg.vocab_size), 2,
                                      32),
            DSMPool(str(tmp_path / "pool")), n_steps=6, commit_every=2,
            commit_mode="sharded-async", n_shards=2, retention=2,
            crash_at={3: "after_commit"})

    got, _ = _traced(tmp_path, run)
    by, by_id = _by_name(got), {s.id: s for s in got}
    crash, = by["train.crash"]
    assert crash.attrs["step"] == 3
    joins = [s for s in by["dsm.crash.join"]
             if _ancestors(s, by_id)[-1] == "train.crash"]
    assert joins and max(s.attrs["writes"] for s in joins) > 0
    assert all(s.attrs["step"] == 3 for s in joins)
    commits = {s.id: s for s in by["dsm.commit"]}
    assert all(s.attrs["mode"] == "sharded-async" for s in commits.values())
    for name in ("dsm.commit.snapshot", "dsm.commit.join"):
        inside = [s for s in by[name] if s.parent in commits]
        assert inside, name
    assert all(s.attrs["bytes"] > 0 for s in by["dsm.commit.snapshot"])
    join = next(s for s in by["dsm.commit.join"] if s.parent in commits)
    assert join.attrs["joined"] < commits[join.parent].attrs["step"]
    assert by["dsm.commit.manifest"] and by["dsm.commit.gc"]
    writes = by["dsm.flush.write"] + by["dsm.flush.fsync"]
    assert writes and all("dsm.commit" in _ancestors(s, by_id)
                          for s in writes)
    assert any(s.thread != crash.thread for s in writes)
    for s in writes:
        commit = by_id[s.parent]
        while commit.name != "dsm.commit":
            commit = by_id[commit.parent]
        assert s.attrs["step"] == commit.attrs["step"]
    assert by["train.compute"] and by["train.place"]
    assert all("dsm.recover" in _ancestors(s, by_id)
               for s in by["dsm.recover.read"])
