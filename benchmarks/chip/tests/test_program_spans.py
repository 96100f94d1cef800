"""The readers of the program's spans on hand-built span logs: each of the
eight metrics reads what its spans say, and every reader returns None on
an empty log, on a log that dropped records inside the window, and for a
program without ``repro.obs``."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

pytest.importorskip("jax")
import chiplib  # noqa: E402
from repro import obs  # noqa: E402

#: each cell's window in the hand-built log
WINDOWS = {"durable": (0.0, 39.5), "backlog": (39.5, 49.0),
           "chat": (49.0, 100.0)}
METRICS = {
    "commit_snapshot_ms.durable": 1500.0,
    "commit_join_ms.durable": 250.0,
    "crash_join_s.durable": 6.5,
    "commit_d2h_ms.backlog": 450.0,
    "commit_write_ms.backlog": 500.0,
    "commit_meta_ms.backlog": 150.0,
    "tick_host_ms.chat": 5.0,
    "queue_p95_ms.chat": 95.25,      # 5, 10, ..., 100: 95 + 0.05 x 5
}


class Log:
    """Spans written as (name, t0, t1, parent name or None, attrs); the
    parent is the newest span of that name already written."""

    def __init__(self):
        self.spans, self.last = [], {}

    def add(self, name, t0, t1, parent=None, **attrs):
        sid = len(self.spans) + 1
        self.spans.append(obs.Span(sid, name, t0, t1, self.last.get(parent),
                                   "main", attrs))
        self.last[name] = sid
        return self


def durable_log(log):
    # two commits: 1.4 + 0.1 s and 1.5 s of snapshot, one 0.5 s join
    for t, snap, join in ((10.0, (1.4, 0.1), 0.5), (20.0, (1.5,), 0.0)):
        log.add("dsm.commit", t, t + 3.0, step=int(t))
        u = t
        for s in snap:
            log.add("dsm.commit.snapshot", u, u + s, "dsm.commit")
            u += s
        if join:
            log.add("dsm.commit.join", u, u + join, "dsm.commit")
        # a pool thread's write, parented to the commit, ending later
        log.add("dsm.flush.write", t + 1.0, t + 2.5, "dsm.commit")
    log.add("train.crash", 30.0, 37.0, step=30)
    log.add("dsm.crash.join", 30.0, 36.0, "train.crash", writes=10)
    log.add("dsm.crash.join", 36.0, 36.5, "train.crash", writes=0)
    log.add("dsm.crash.join", 38.0, 39.0)          # outside any crash


def backlog_log(log):
    # two commits: d2h 0.4 / 0.5 s, writes + fsyncs 0.3 + 0.2 / 0.5 s,
    # metadata 0.1 / 0.2 s, a nested seed counted once
    for t, d2h, wr, meta in ((40.0, (0.2, 0.2), (0.3, 0.2), (0.05, 0.05)),
                             (45.0, (0.5,), (0.4, 0.1), (0.2,))):
        log.add("serve.commit", t, t + 2.0, tick=int(t))
        u = t
        for d in d2h:
            log.add("serve.commit.d2h", u, u + d, "serve.commit")
            u += d
        log.add("dsm.commit", u, u + 1.5, "serve.commit")
        log.add("dsm.flush.write", u, u + wr[0], "dsm.commit")
        u += wr[0]
        log.add("dsm.flush.fsync", u, u + wr[1], "dsm.commit")
        u += wr[1]
        names = ("dsm.lstore.seed", "dsm.commit.manifest")
        for name, m in zip(names, meta):
            log.add(name, u, u + m, "dsm.commit")
            u += m
        log.add("dsm.lstore.seed", u - 0.01, u, "dsm.lstore.seed")


def chat_log(log):
    # 20 ticks of 10 ms, each waiting 2 + 3 ms on the device; admissions
    # queued 5, 10, ..., 100 ms
    for k in range(20):
        t = 50.0 + 0.01 * k
        log.add("serve.tick", t, t + 0.010, tick=k)
        log.add("serve.admit", t, t + 0.004, "serve.tick", rid=f"r{k}",
                prompt=128, queued_ms=float(k + 1) * 5)
        log.add("serve.prefill.wait", t + 0.001, t + 0.003, "serve.admit")
        log.add("serve.decode.wait", t + 0.005, t + 0.008, "serve.tick")


def run_of(name):
    window = WINDOWS[name.rsplit(".", 1)[1]]
    return SimpleNamespace(record=SimpleNamespace(host={"window": window}))


def build_log():
    log = Log()
    durable_log(log)
    backlog_log(log)
    chat_log(log)
    return log.spans


def use_ring(monkeypatch, spans, maxlen=obs.LOG_RECORDS):
    ring = obs.SpanLog(maxlen)
    for s in spans:
        ring.append(s)
    monkeypatch.setattr(obs, "_log", ring)
    return ring


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_reads_its_spans(monkeypatch, name):
    use_ring(monkeypatch, build_log())
    got = chiplib.metric_reader(name)(run_of(name))
    assert got == pytest.approx(METRICS[name], rel=1e-6)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_none_on_empty_log(monkeypatch, name):
    use_ring(monkeypatch, [])
    assert chiplib.metric_reader(name)(run_of(name)) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_none_when_records_dropped_inside_window(monkeypatch, name):
    """The ring drops the log's first record (a train-durable commit,
    ending at 13 s): the durable window reads None, later windows read."""
    spans = build_log()
    ring = use_ring(monkeypatch, spans, maxlen=len(spans) - 1)
    assert ring.dropped == 1
    got = chiplib.metric_reader(name)(run_of(name))
    if name.endswith(".durable"):
        assert got is None
    else:
        assert got == pytest.approx(METRICS[name], rel=1e-6)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_none_without_program_spans(monkeypatch, name):
    """A program that has no ``repro.obs`` (the parent of this change)."""
    import repro
    use_ring(monkeypatch, build_log())
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert chiplib.metric_reader(name)(run_of(name)) is None
