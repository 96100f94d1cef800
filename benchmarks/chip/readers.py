"""What the per-layer metric files share.  Each metric is a file of its
own under ``metrics/`` with a ``read(run)`` that returns the number, or
None when the run has nothing to read it from (then the metric is left
out of the result line, never reported as 0).

``run`` carries the cell's files (``config``, ``traffic``), the driver's
``record`` (host-clock series), the trace ``reduction`` of a traced run
(None otherwise) and the chip's ``peak`` row of ``peaks.json``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flops
import trace_reduce


@dataclasses.dataclass
class Run:
    cell: str
    config: dict
    traffic: dict
    record: Any
    reduction: Optional[trace_reduce.Reduction]
    peak: dict


def mean(xs) -> Optional[float]:
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def idle_share_pct(run: Run) -> Optional[float]:
    r = run.reduction
    return None if r is None else 100.0 * r.idle_share


# -- training -----------------------------------------------------------------

def step_ms(run: Run) -> Optional[float]:
    m = mean(s for _, s, inside in run.record.host.get("compute", [])
             if inside)
    return None if m is None else 1e3 * m


def train_mfu_pct(run: Run) -> Optional[float]:
    h, mix = run.record.host, run.traffic
    if "tokens" not in h:
        return None
    per_token = (flops.train_flops_per_sequence(run.config, mix["seq_len"])
                 / mix["seq_len"])
    rate = h["tokens"] / run.record.window_s
    return 100.0 * rate * per_token / run.peak["bf16_flops_per_s"]


def commit_stall_ms(run: Run) -> Optional[float]:
    m = mean(s for _, s, inside in run.record.host.get("commits", [])
             if inside)
    return None if m is None else 1e3 * m


def recover_s(run: Run) -> Optional[float]:
    return mean(s for s, inside in run.record.host.get("recovers", [])
                if inside)


# -- serving ------------------------------------------------------------------

def window_ticks(run: Run):
    h = run.record.host
    if "ticks" not in h:
        return []
    t0, t1 = h["window"]
    return [t for t in h["ticks"] if t["t0"] >= t0 and t["t1"] <= t1]


def tick_ms(run: Run) -> Optional[float]:
    m = mean(t["t1"] - t["t0"] for t in window_ticks(run) if not t["commit"])
    return None if m is None else 1e3 * m


def commit_ms(run: Run) -> Optional[float]:
    plain = tick_ms(run)
    m = mean(t["t1"] - t["t0"] for t in window_ticks(run) if t["commit"])
    return None if m is None or plain is None else 1e3 * m - plain


def serve_mfu_pct(run: Run) -> Optional[float]:
    ticks = window_ticks(run)
    if not ticks:
        return None
    work = sum(t["prefill_flops"] + t["decode_flops"] for t in ticks)
    return (100.0 * work / run.record.window_s
            / run.peak["bf16_flops_per_s"])


def program_ms(run: Run, programs) -> Optional[float]:
    m = mean(trace_reduce.program_seconds(run.reduction, programs))
    return None if m is None else 1e3 * m


def decode_roofline_pct(run: Run, programs) -> Optional[float]:
    """The least bytes of every traced decode step over the chip's HBM
    bandwidth, against those steps' device time."""
    device = trace_reduce.program_seconds(run.reduction, programs)
    ticks = [t for t in window_ticks(run) if t["decode_keys"]]
    if not device or not ticks:
        return None
    least = sum(flops.decode_min_bytes(run.config, t["decode_keys"],
                                       decode_slots=t["decode_slots"])
                for t in ticks) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least / sum(device)
