"""Production training launcher (one process per worker/host).

On a real cluster every host runs this with the usual JAX distributed
env (``jax.distributed.initialize`` picks up coordinator/rank from the
scheduler); on a dev box it runs single-process.  Wires together:

  mesh -> sharded state -> train_step -> durable FliT-commit loop
  (pool on shared storage; peer staging optional; elastic restart).

    python -m repro.launch.train --arch olmo-1b --steps 100 \
        --global-batch 8 --seq 512 --pool /tmp/pool [--mesh-data 4] \
        [--commit-every 10] [--mode sharded-async] [--shards 8] \
        [--retention 5] [--compress int8]

The default commit schedule is ``sharded-async``: per-device state shards
are flushed on parallel pipelines, double-buffered behind the next step's
compute, with manifest retention GC (see repro.dsm.flit_runtime).
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.data.pipeline import DataPipeline, SyntheticLMSource
from repro.dsm.api import CXL0Config
from repro.dsm.emu import PRESETS
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.registry import build
from repro.parallel.sharding import ctx_for_mesh
from repro.parallel.compression import make_int8_transform
from repro.train.elastic import shardings_for
from repro.train.loop import run_durable_loop
from repro.train.state import init_train_state
from repro.train.step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU dev loop)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--pool", default="/tmp/repro_pool")
    ap.add_argument("--commit-every", type=int, default=10)
    ap.add_argument("--mode", default="sharded-async",
                    choices=["sync", "async", "sharded", "sharded-async",
                             "auto"],
                    help="flush schedule; 'auto' defers to the placement "
                         "policy (requires --topology)")
    ap.add_argument("--topology", default=None, choices=sorted(PRESETS),
                    help="emulated CXL topology: cost-driven commit shard "
                         "count (and schedule, with --mode auto)")
    ap.add_argument("--shards", type=int, default=0,
                    help="shard pipelines per object (0 = auto: one per "
                         "local device, capped by state size)")
    ap.add_argument("--retention", type=int, default=5,
                    help="manifests kept by GC after each commit "
                         "(0 = unbounded)")
    ap.add_argument("--resume", action="store_true",
                    help="recover from the pool before training "
                         "(restart of a crashed/preempted worker)")
    ap.add_argument("--mesh-data", type=int, default=0,
                    help="data axis size (0 = all devices)")
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--compress", default="none", choices=["none", "int8"])
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() (multi-host)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.distributed:
        jax.distributed.initialize()

    n_dev = jax.device_count()
    data = args.mesh_data or max(n_dev // args.mesh_model, 1)
    mesh = make_mesh((data, args.mesh_model), ("data", "model"))
    ctx = ctx_for_mesh(mesh)
    print(f"mesh: data={data} model={args.mesh_model} "
          f"({n_dev} devices, process {jax.process_index()})")

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    bundle = build(cfg)
    key = jax.random.PRNGKey(0)
    params = bundle.init_params(key)
    params = jax.tree_util.tree_map(jax.device_put, params,
                                    shardings_for(ctx, bundle.descs))
    state = init_train_state(params, key, cfg.moment_dtype)

    grad_transform = None
    if args.compress == "int8":
        transform, _ = make_int8_transform(with_error_feedback=False)
        grad_transform = lambda g, ctx: transform(g, None)[0]

    step = jax.jit(make_train_step(bundle, ctx, microbatch=args.microbatch,
                                   total_steps=args.steps,
                                   grad_transform=grad_transform))
    pipe = DataPipeline(SyntheticLMSource(cfg.vocab_size),
                        args.global_batch, args.seq)
    if args.mode == "auto" and args.topology is None:
        ap.error("--mode auto requires --topology")
    # one wiring path: every DSM knob lands in the unified config.
    # --shards 0 -> None: the committer auto-sizes from the actual HBM
    # state volume at the first sharded flush (one heuristic, one place)
    ctx = CXL0Config(path=args.pool,
                     worker_id=jax.process_index(),
                     schedule=args.mode,
                     topology=args.topology,
                     n_shards=args.shards or None,
                     retention=args.retention or None).open()
    pool = ctx.pool
    r = run_durable_loop(step, state, pipe, ctx, n_steps=args.steps,
                         commit_every=args.commit_every,
                         resume=args.resume)
    if r.resumed_from is not None:
        print(f"resumed from step {r.resumed_from} "
              f"(source: {r.recoveries[0]})")
    if not r.losses:        # resume found every step already committed
        print(f"done: nothing to do; commits in pool up to step "
              f"{pool.latest_manifest()['step']}")
        return
    print(f"done: {len(r.losses)} steps, loss {r.losses[0]:.3f} -> "
          f"{r.losses[-1]:.3f}; commits in pool: "
          f"{pool.latest_manifest()['step'] + 1}")
    comp = np.mean([t.compute_s for t in r.timings if t.compute_s])
    print(f"mean step {comp*1e3:.1f} ms")


if __name__ == "__main__":
    main()
