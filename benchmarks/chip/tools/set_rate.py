"""Write an open-loop cell's arrival rate from a sweep's output: four
fifths of the knee, the highest swept rate whose time to first token
stayed under ``--ttft-ms`` at its 95th percentile.

    python benchmarks/chip/tools/set_rate.py SWEEP_OUT TRAFFIC_NAME
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("sweep_out")
    ap.add_argument("traffic")
    ap.add_argument("--ttft-ms", type=float, default=1000.0)
    args = ap.parse_args(argv)
    rows = [json.loads(l) for l in open(args.sweep_out) if l.startswith("{")]
    ok = [r["rate"] for r in rows if r["ttft_p95_ms"] < args.ttft_ms]
    if not ok:
        print("set_rate: no swept rate was sustained", file=sys.stderr)
        return 1
    knee = max(ok)
    path = BENCH / "traffic" / f"{args.traffic}.json"
    mix = json.loads(path.read_text())
    mix["arrival"]["rate_per_s"] = round(0.8 * knee, 2)
    path.write_text(json.dumps(mix, indent=2) + "\n")
    print(f"knee {knee} req/s -> rate {mix['arrival']['rate_per_s']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
