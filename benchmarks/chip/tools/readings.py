"""Readings that the limits of ``correct`` are set from (see PERF.md),
several seeds in one process, on the chip at the cell's own size.

    python benchmarks/chip/tools/readings.py --workload CELL \
        --seeds 11,22,33 [--seconds 20]

Training cells: per seed, the plain float32 reference against the
control (the same reference with int8 products) and against the planted
fault "half of the batch left out"; the fault "state unchanged" reads 1
on the weight-change number by construction and is printed from the
reference's own change.  No
program run is needed for these (a benchmark run prints the program's).

Serving cells: per seed, a short window at the cell's own load, then the
widest gap of the served tokens (the program's reading) and of the int8
reference's own picks at the same positions (the control's reading).

Each reading stands in the program's place: it is compared with the
cell's limits as a run's numbers are, and ``correct`` is printed beside
it.  Each seed prints one JSON line; nothing here is part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import chiplib                                             # noqa: E402


def judged(numbers: dict, limits: dict) -> dict:
    """Numbers as a run compares them: each with its limit, and whether
    all of them pass."""
    checks = [chiplib.Check(k, v, limits.get(k, float("nan")))
              for k, v in numbers.items()]
    return {"correct": all(c.ok for c in checks), **numbers}


def train_readings(a: chiplib.RunArgs) -> dict:
    drv = chiplib.driver_module("train")
    ref = drv.reference_numbers(a)
    ctrl = drv.reference_numbers(a, quant="int8")
    half = drv.reference_numbers(a, drop_half=True)
    still = dict(ref, change=[0.0] * len(ref["change"]))
    lim = a.limits
    return {"control": judged(drv.gaps(ctrl, ref), lim),
            "half_batch": judged(drv.gaps(half, ref), lim),
            "unchanged": judged(drv.gaps(still, ref), lim),
            "ref_loss": ref["loss"]}


def serve_readings(a: chiplib.RunArgs) -> dict:
    drv = chiplib.driver_module("serve")
    srv = drv.Server(a)
    srv.warm()
    window = chiplib.Window(a.seconds)
    drv.drive(srv, window, chiplib.Spans(False), a.counter)
    picks = [(srv.requests[r].prompt, list(srv.engine.results[r]))
             for r in drv.sample_finished(srv, a.seed, drv.CHECK_REQUESTS)]
    srv.close()
    del srv
    gc.collect()
    gap, ctrl, n = drv.reference_gaps(a, picks, quant="int8")
    return {"program": judged({"served_logit_gap": gap}, a.limits),
            "control": judged({"served_logit_gap": ctrl}, a.limits),
            "requests": len(picks), "tokens": n}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    spec = chiplib.benchmark_spec()
    cell = chiplib.find_cell(spec, args.workload)
    config = chiplib.config_file(spec, cell["config"])
    traffic = chiplib.traffic_file(cell["traffic"])
    sys.path.insert(0, str(chiplib.ROOT / "src"))
    import jax
    chiplib.check_device(jax.devices(), cell["chips"], chiplib.peaks_table())
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    counter = chiplib.CompileCounter()
    read = train_readings if traffic["driver"] == "train" else \
        serve_readings
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        a = chiplib.RunArgs(cell=cell["name"], config=config,
                            traffic=traffic, seed=seed,
                            seconds=args.seconds, trace=False,
                            limits=chiplib.limits_file(cell["name"]),
                            counter=counter)
        out = read(a)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t,
                          **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
