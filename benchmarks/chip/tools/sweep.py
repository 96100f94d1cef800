"""Find the knee of an open-loop serving cell: the highest arrival rate
the engine sustains without a growing queue.  One engine, one process;
each rate gets a window of its own after the previous one has drained.

    python benchmarks/chip/tools/sweep.py --workload CELL --seed N \
        --rates 8,12,16,20 [--seconds 20]

Per rate it prints one JSON line: offered and completed requests per
second, the queue left at the close, time to first token (p50, p95) and
the gap between tokens (p95).  The cell's rate is then written into its
traffic file by hand, at about four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import chiplib                                             # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
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
    drv = chiplib.driver_module("serve")
    counter = chiplib.CompileCounter()
    a = chiplib.RunArgs(cell=cell["name"], config=config, traffic=traffic,
                        seed=args.seed, seconds=args.seconds, trace=False,
                        counter=counter)
    srv = drv.Server(a)
    srv.warm()
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        # ids of their own: the engine keeps every finished request's
        # result by id, so a repeated id would read as already answered
        srv.rid_prefix = f"q{k}_"
        srv.mix = dict(traffic, arrival={"kind": "poisson",
                                         "rate_per_s": rate})
        srv.requests, srv.due, srv.tokens, srv.live = {}, {}, {}, {}
        srv.ticks, srv.lateness = [], []
        srv.a = chiplib.RunArgs(**{**a.__dict__, "seed": args.seed + int(
            rate * 1000)})
        w = chiplib.Window(args.seconds)
        compiles = drv.drive(srv, w, chiplib.Spans(False), counter)
        e2e = drv.end_to_end(srv, w)
        due = [r for r in srv.tokens if srv.due[r] <= w.t1]
        done_in = [r for r in due if r in srv.engine.results
                   and srv.tokens[r][-1] <= w.t1]
        first = [srv.tokens[r][0] - srv.due[r] for r in due if srv.tokens[r]]
        out = {"rate": rate, "offered_per_s": len(due) / w.length,
               "completed_per_s": len(done_in) / w.length,
               "queued_at_close": len(srv.engine.sched.pending),
               "ttft_p50_ms": 1e3 * chiplib.percentile(first, 50),
               "compiles": compiles, **e2e}
        print(json.dumps(out), flush=True)
        print(drv.occupancy(srv, w), flush=True)
        while not srv.engine.sched.done:
            srv.engine.tick()
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
