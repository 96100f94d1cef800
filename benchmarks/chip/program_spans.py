"""Per-layer metrics read from the program's own spans (``repro.obs``):
host-clock regions that the program records inside its commit, crash
and tick paths while the profiler runs.

Every reader takes the spans that ran wholly inside the measured window
(``run.record.host["window"]``, ``time.perf_counter``) and returns None
when there is nothing to read: a ``--trace 0`` run, a program without
``repro.obs``, a window with no matching span, or a span log that
dropped records inside the window.  The metric is then left out of the
result line rather than reported wrong.

A span counts toward an outer span when the outer span is its nearest
ancestor among the names asked for, so spans nested in one another are
counted once.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

import chiplib
from readers import mean


def window_spans(run) -> Optional[list]:
    """The program's spans inside the window, or None (see above)."""
    try:
        from repro import obs
    except ImportError:
        return None
    window = run.record.host.get("window")
    return None if window is None else obs.spans(*window)


def seconds_under(spans: list, outer: str, inner: Iterable[str]
                  ) -> Optional[List[float]]:
    """For each span named ``outer``, in order, the seconds of the spans
    named in ``inner`` that ran inside it; None when there is none."""
    inner = set(inner)
    by_id = {s.id: s for s in spans}
    totals: Dict[int, float] = {s.id: 0.0 for s in spans if s.name == outer}
    if not totals:
        return None
    for s in spans:
        if s.name not in inner:
            continue
        up = by_id.get(s.parent)
        while up is not None and up.name not in inner and up.name != outer:
            up = by_id.get(up.parent)
        if up is not None and up.name == outer:
            totals[up.id] += s.t1 - s.t0
    return [totals[s.id] for s in sorted(
        (s for s in spans if s.name == outer), key=lambda s: s.t0)]


def mean_ms_under(run, outer: str, inner: Iterable[str]) -> Optional[float]:
    """Mean over the ``outer`` spans of the window of the milliseconds
    spent in ``inner`` spans inside each."""
    spans = window_spans(run)
    per = None if spans is None else seconds_under(spans, outer, inner)
    return None if per is None else 1e3 * mean(per)


# -- train-durable --------------------------------------------------------

def commit_snapshot_ms(run) -> Optional[float]:
    return mean_ms_under(run, "dsm.commit", ["dsm.commit.snapshot"])


def commit_join_ms(run) -> Optional[float]:
    return mean_ms_under(run, "dsm.commit", ["dsm.commit.join"])


def crash_join_s(run) -> Optional[float]:
    spans = window_spans(run)
    per = (None if spans is None
           else seconds_under(spans, "train.crash", ["dsm.crash.join"]))
    return None if per is None else sum(per)


# -- serve-backlog-durable ------------------------------------------------

def commit_d2h_ms(run) -> Optional[float]:
    return mean_ms_under(run, "serve.commit", ["serve.commit.d2h"])


def commit_write_ms(run) -> Optional[float]:
    return mean_ms_under(run, "serve.commit",
                         ["dsm.flush.write", "dsm.flush.fsync"])


COMMIT_META = ("dsm.lstore.seed", "serve.commit.table",
               "dsm.commit.manifest", "dsm.commit.gc")


def commit_meta_ms(run) -> Optional[float]:
    return mean_ms_under(run, "serve.commit", COMMIT_META)


# -- serve-chat -----------------------------------------------------------

DEVICE_WAITS = ("serve.prefill.wait", "serve.decode.wait")


def tick_host_ms(run) -> Optional[float]:
    spans = window_spans(run)
    waits = (None if spans is None
             else seconds_under(spans, "serve.tick", DEVICE_WAITS))
    if waits is None:
        return None
    ticks = sorted((s for s in spans if s.name == "serve.tick"),
                   key=lambda s: s.t0)
    return 1e3 * mean(s.t1 - s.t0 - w for s, w in zip(ticks, waits))


def queue_p95_ms(run) -> Optional[float]:
    spans = window_spans(run)
    queued = [] if spans is None else [
        s.attrs["queued_ms"] for s in spans
        if s.name == "serve.admit" and "queued_ms" in s.attrs]
    return chiplib.percentile(queued, 95) if queued else None
