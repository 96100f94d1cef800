"""From a profiler trace (``.xplane.pb``) to the numbers per-layer metrics
read, with nothing but ``jax.profiler.ProfileData``:

* the union of the intervals in which an operation ran on each device,
  and the idle share of the traced window;
* device time per program (the ``XLA Modules`` line, by jit name);
* device time per operation, largest first;
* idle gaps on the device, each labelled by the innermost host span the
  benchmark had open in it.

The window is the ``bench.window`` host span when the trace has one,
else the stretch from the first to the last device event.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "serve.", "train.")

Interval = Tuple[float, float]


def _merge(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def program_name(event_name: str) -> str:
    """``jit_slot_decode(1234)`` -> ``slot_decode``."""
    name = re.sub(r"\(.*\)$", "", event_name).strip()
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """An HLO instruction's name and result shape, without its layout and
    operands: ``%copy.93 = bf16[16,32,1024,16,128]``."""
    return event_name.split("{")[0].strip()[:120]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                      # averaged over the devices seen
    n_devices: int
    programs: Dict[str, List[float]]   # program -> device seconds per run
    ops: List[Tuple[str, float]]       # (op, device seconds), largest first
    gaps: List[Tuple[str, float]]      # (host span, seconds), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(path: str, top: int = 10) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/device:TPU"):
            lines = {line.name: list(line.events) for line in plane.lines}
            if lines.get(OPS_LINE) or lines.get(MODULES_LINE):
                devices.append(lines)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane with operations")

    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        evs = [ev for d in devices for ev in d.get(OPS_LINE) or
               d[MODULES_LINE]]
        lo = min(ev.start_ns for ev in evs)
        hi = max(ev.end_ns for ev in evs)

    busy_total = 0.0
    programs: Dict[str, List[float]] = defaultdict(list)
    ops: Dict[str, float] = defaultdict(float)
    busy0: List[Interval] = []
    for i, d in enumerate(devices):
        busy_evs = d.get(OPS_LINE) or d[MODULES_LINE]
        busy = _merge(_clip([(ev.start_ns, ev.end_ns) for ev in busy_evs],
                            lo, hi))
        busy_total += sum(e - s for s, e in busy)
        if i == 0:
            busy0 = busy
        for ev in d.get(MODULES_LINE, []):
            if lo <= ev.start_ns and ev.end_ns <= hi:
                programs[program_name(ev.name)].append(ev.duration_ns * 1e-9)
        for ev in d.get(OPS_LINE, []):
            if lo <= ev.start_ns and ev.end_ns <= hi:
                ops[op_name(ev.name)] += ev.duration_ns * 1e-9

    gaps = []
    prev = lo
    for s, e in busy0 + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        inside = [sp for sp in spans
                  if sp[0] <= mid <= sp[1] and sp[2] != WINDOW_SPAN]
        label = max(inside, key=lambda sp: sp[0])[2] if inside else "none"
        labelled.append((label, (e - s) * 1e-9))
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / len(devices),
        n_devices=len(devices), programs=dict(programs),
        ops=sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        gaps=labelled)


def breakdown(r: Reduction) -> dict:
    return {"device_ops": [[n, s] for n, s in r.ops],
            "idle_gaps": [[n, s] for n, s in r.gaps]}


def program_seconds(r: Optional[Reduction], names) -> List[float]:
    """Device seconds of every run of the programs named (jit names)."""
    if r is None:
        return []
    return [t for n in names for t in r.programs.get(n, [])]
