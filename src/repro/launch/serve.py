"""Serving launcher: continuous batching over the durable tier stack.

Thin front-end over ``repro.serve`` — the slot scheduler, tiered KV-cache
manager and durable session store live there; this file only parses
flags, builds the (data, model) mesh and reports throughput.

    # stateless continuous batching, mixed-length synthetic trace
    python -m repro.launch.serve --arch olmo-1b --smoke --requests 16

    # durable serving: sessions commit through the FliT path; re-running
    # the same command after a kill resumes every committed session
    python -m repro.launch.serve --smoke --pool /tmp/serve_pool \
        --commit-every 4

    # the static-batch baseline the benchmark compares against
    python -m repro.launch.serve --smoke --mode static

    # a 2-engine fleet over one pool: cost-routed admission, automatic
    # rebalancing migrations, cross-engine prefix reuse
    python -m repro.launch.serve --smoke --pool /tmp/fleet_pool \
        --engines 2 --topology cxl20-switched-pool
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.dsm.api import CXL0Config
from repro.dsm.emu import PRESETS
from repro.dsm.flit_runtime import AUTO_MODE, COMMIT_MODES
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.parallel.sharding import ctx_for_mesh
from repro.serve.engine import build_serve_engine, servable_archs
from repro.serve.trace import synthetic_trace, trace_t_max


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=servable_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", default="continuous",
                    choices=["continuous", "static"])
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (= static batch size)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", default="4,8,16,32,48",
                    help="cycled per-request decode budgets (the mixed-"
                         "length workload)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--pool", default=None,
                    help="DSM pool dir: enables durable sessions + resume")
    ap.add_argument("--commit-every", type=int, default=4,
                    help="session-commit cadence in decode ticks")
    ap.add_argument("--commit-mode", default="sync",
                    choices=COMMIT_MODES + (AUTO_MODE,),
                    help="flush schedule; 'auto' defers to the placement "
                         "policy (requires --topology)")
    ap.add_argument("--topology", default=None, choices=sorted(PRESETS),
                    help="emulated CXL topology: cost-driven commit shard "
                         "count (and schedule, with --commit-mode auto)")
    ap.add_argument("--retire-done", action="store_true",
                    help="drop finished sessions from the committed table "
                         "(bounds commit cost for long-lived serving; "
                         "restarts then replay only unfinished sessions)")
    ap.add_argument("--restore-mode", default="cache",
                    choices=["cache", "replay"])
    ap.add_argument("--engines", type=int, default=1,
                    help=">= 2 serves the trace with a FLEET of engines "
                         "over one pool: cost-routed admission, "
                         "rebalancing live migrations, prefix reuse")
    ap.add_argument("--block-tokens", type=int, default=16,
                    help="paged KV layout: tokens per pool block")
    ap.add_argument("--no-prefix-reuse", action="store_true",
                    help="fleet: disable content-addressed cross-engine "
                         "prefix blocks")
    args = ap.parse_args()
    enable_compile_cache()
    if args.commit_mode == AUTO_MODE and args.topology is None:
        ap.error("--commit-mode auto requires --topology")
    if args.topology is not None and args.pool is None:
        ap.error("--topology drives durable-commit placement: it needs "
                 "--pool (stateless serving has nothing to place)")
    if args.engines >= 2:
        if args.pool is None:
            ap.error("--engines >= 2 is fleet serving over a SHARED "
                     "pool: it needs --pool")
        if args.mode != "continuous":
            ap.error("fleet serving is continuous-batching only")
        return _fleet_main(args)

    mesh = make_debug_mesh(jax.device_count(), model=args.mesh_model)
    ctx = ctx_for_mesh(mesh)

    new_tokens = tuple(int(t) for t in args.new_tokens.split(","))
    trace = synthetic_trace(args.requests, seed=args.seed,
                            prompt_lens=(args.prompt_len,),
                            new_tokens=new_tokens, vocab_size=1)
    # one wiring path: the pool/schedule/topology knobs land in the
    # unified config; stateless serving passes no config at all
    dsm = (CXL0Config(path=args.pool, schedule=args.commit_mode,
                      topology=args.topology, retention=2)
           if args.pool else None)
    engine, cfg = build_serve_engine(
        args.arch, smoke=args.smoke, n_slots=args.slots,
        t_max=trace_t_max(trace), ctx=ctx, dsm=dsm,
        commit_every=args.commit_every if args.pool else 0,
        restore_mode=args.restore_mode, retire_done=args.retire_done,
        seed=args.seed)
    # regenerate with the real vocab now the config is known
    trace = synthetic_trace(args.requests, seed=args.seed,
                            prompt_lens=(args.prompt_len,),
                            new_tokens=new_tokens,
                            vocab_size=cfg.vocab_size)

    resumed = engine.resume() if args.pool else None
    if resumed is not None:
        print(f"resumed from committed tick {resumed}")
    t0 = time.perf_counter()
    res = (engine.run(trace) if args.mode == "continuous"
           else engine.run_static(trace))
    dt = time.perf_counter() - t0
    engine.close()
    print(f"{res.mode}: {len(res.outputs)} requests, "
          f"{res.emitted_tokens} tokens in {dt:.2f}s "
          f"({res.emitted_tokens / dt:.0f} tok/s incl. compile), "
          f"{res.decode_ticks} decode ticks, {res.prefills} prefills"
          + (f", {res.commits} session commits" if res.commits else "")
          + (f", {res.resumed_sessions} sessions resumed"
             if res.resumed_sessions else ""))


def _fleet_main(args):
    from repro.configs import get_config, get_smoke_config
    from repro.serve.fleet import FleetController

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    new_tokens = tuple(int(t) for t in args.new_tokens.split(","))
    trace = synthetic_trace(args.requests, seed=args.seed,
                            prompt_lens=(args.prompt_len,),
                            new_tokens=new_tokens,
                            vocab_size=cfg.vocab_size)
    fl = FleetController(
        args.arch, pool_path=args.pool, n_engines=args.engines,
        smoke=args.smoke, n_slots=args.slots, t_max=trace_t_max(trace),
        commit_every=args.commit_every, commit_mode=args.commit_mode,
        topology=args.topology, seed=args.seed,
        block_tokens=args.block_tokens,
        prefix_reuse=not args.no_prefix_reuse,
        restore_mode=args.restore_mode, retire_done=args.retire_done)
    steps = fl.resume()
    resumed = [f"e{i}@{s}" for i, s in steps.items() if s is not None]
    if resumed:
        print(f"resumed: {', '.join(resumed)}")
    t0 = time.perf_counter()
    res = fl.run(trace)
    dt = time.perf_counter() - t0
    fl.close()
    per = ", ".join(
        f"e{i}: {len(r.outputs)} req / {r.prefills} prefills / "
        f"{r.prefix_hits} prefix hits"
        for i, r in sorted(res.per_engine.items()))
    print(f"fleet[{args.engines}]: {len(res.outputs)} requests, "
          f"{res.emitted_tokens} tokens in {dt:.2f}s "
          f"({res.emitted_tokens / dt:.0f} tok/s incl. compile), "
          f"{res.migrations} migrations, {res.prefix_hits} prefix hits "
          f"({per})")


if __name__ == "__main__":
    main()
