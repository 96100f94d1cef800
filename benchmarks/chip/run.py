"""One run of one cell of the chip benchmark.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix, limits and per-layer metrics are files of their own under
``benchmarks/chip/``.  The run refuses (nonzero exit, no result) unless
JAX's first device is a TPU listed in ``peaks.json`` and it sees the
chips the cell asks for.  It sets up (weights, warm-up of every shape
the traffic uses), measures for ``--seconds``, checks what the window
produced against the plain float32 reference, and prints as the last
line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                            # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402
from pathlib import Path                                   # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import chiplib                                             # noqa: E402


def say(msg: str):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = chiplib.benchmark_spec()
    cell = chiplib.find_cell(spec, args.workload)
    config = chiplib.config_file(spec, cell["config"])
    traffic = chiplib.traffic_file(cell["traffic"])
    limits = chiplib.limits_file(cell["name"])
    sys.path.insert(0, str(chiplib.ROOT / "src"))

    import jax
    devices = jax.devices()
    try:
        peak = chiplib.check_device(devices, cell["chips"],
                                    chiplib.peaks_table())
    except chiplib.NoChip as e:
        say(f"run: {e}; nothing was measured")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    say(f"run: {cell['name']} on {devices[0].device_kind}, seed "
        f"{args.seed}, {args.seconds} s, trace {args.trace}; compile "
        f"cache {enable_compile_cache()}")
    counter = chiplib.CompileCounter()
    driver = chiplib.driver_module(traffic["driver"])
    rec = driver.run(chiplib.RunArgs(
        cell=cell["name"], config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), limits=limits, counter=counter, t_start=T_START, log=say))
    say(f"compilations inside the window: {rec.compiles_in_window}")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": rec.memory_peak_bytes}
    metrics, breakdown = {}, None
    if args.trace:
        import readers
        import trace_reduce
        red = trace_reduce.reduce(rec.trace_path)
        shutil.rmtree(Path(rec.trace_path).parents[3], ignore_errors=True)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        say(f"trace: {red.n_devices} device(s), window {red.window_s:.3f} "
            f"s, busy {red.busy_s:.3f} s; programs "
            f"{ {n: len(v) for n, v in red.programs.items()} }")
        breakdown = trace_reduce.breakdown(red)
        run = readers.Run(cell["name"], config, traffic, rec, red, peak)
        for m in chiplib.cell_metrics(spec, cell["name"], trace=True):
            value = chiplib.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in chiplib.cell_metrics(spec, cell["name"], trace=False):
            metrics[m["name"]] = {"value": rec.end_to_end[m["name"]],
                                  "unit": m["unit"]}

    correct = bool(rec.checks) and all(c.ok for c in rec.checks)
    for c in rec.checks:
        say(f"check {c.name}: {c.value!r} limit {c.limit!r}"
            f"{'' if c.ok else '  FAILED'}")
    print(chiplib.result_line(
        correct=correct, attempted=rec.attempted, failed=rec.failed,
        metrics=metrics, device=device, checks=rec.checks,
        breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
