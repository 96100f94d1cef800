"""The durable training loop: train steps + FliT-protocol commits + crash
recovery, with fault-injection hooks and straggler statistics.

This is the single-process integration of the whole stack (model, optimizer,
data pipeline, DSM runtime); the multi-pod launch wraps exactly this loop
per worker (launch/train.py).  The loop guarantees:

* any step whose commit completed survives a crash (durable linearizability
  of the step history — the paper's §6 transformation at system scale);
* recovery resumes from the newest recoverable state — a peer's RStore-staged
  copy if fresher than the pool (CXL0 cache-to-cache propagation), else the
  newest CRC-valid manifest;
* the data pipeline resumes exactly where the recovered step left off
  (PipelineState is one of the committed objects) — no data loss or dupes.

The default commit schedule is ``sharded-async``: per-device byte-balanced
state shards flushed on parallel pipelines, double-buffered one commit
behind compute (see repro.dsm.flit_runtime for all four schedules).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro import obs
from repro.data.pipeline import DataPipeline, PipelineState
from repro.dsm.api import CXL0Context, open_cxl0
from repro.dsm.pool import DSMPool
from repro.dsm.recovery import CrashError, ColdStartError
from repro.train.state import TrainState


@dataclasses.dataclass
class StepTiming:
    """Per-step wall times — the straggler-mitigation signal: the launcher
    feeds these into ``data.shard_plan`` weights to shrink a slow worker's
    shard."""
    step: int
    compute_s: float
    commit_s: float


@dataclasses.dataclass
class LoopResult:
    state: TrainState
    pipeline_state: PipelineState
    losses: List[float]
    timings: List[StepTiming]
    recoveries: List[str]       # recovery sources used ("pool"/"peer-staging")
    crashes: int
    resumed_from: Optional[int] = None    # step recovered at startup
    #                                       (resume=True), None if cold


def _state_objects(state: TrainState, pipe_state: PipelineState):
    return {
        "params": state.params,
        "opt_mu": state.opt.mu,
        "opt_nu": state.opt.nu,
        "counters": {"opt_step": state.opt.step, "rng": state.rng},
        "pipeline": {"seed": np.int64(pipe_state.seed),
                     "step": np.int64(pipe_state.step)},
    }


def _restore_placement(objs, templates):
    """Put recovered (host-resident) leaves back onto the device layout the
    templates carry: a template leaf placed on the mesh donates its
    ``NamedSharding``, so a mesh run resumes device-sharded and the NEXT
    commit can run device-local again.  Every other leaf (host templates,
    and unplaced scalars such as the optimizer step) stays on the host:
    pinning it to one device would clash with the mesh-placed arguments
    of the next step."""
    def place(r, t):
        sh = getattr(t, "sharding", None)
        if isinstance(t, jax.Array) and isinstance(sh, NamedSharding):
            return jax.device_put(r, sh)
        return r
    return {name: jax.tree_util.tree_map(place, objs[name], templates[name])
            for name in objs}


def _recovered_state(objs, templates, state: TrainState, mesh, step: int):
    """The train state and pipeline state of recovered objects, leaves
    back on the mesh when there is one."""
    with obs.span("train.place", step=step):
        if mesh is not None:
            objs = _restore_placement(objs, templates)
        return _objects_to_state(objs, state)


def _objects_to_state(objs, template: TrainState):
    st = TrainState(
        params=objs["params"],
        opt=template.opt._replace(
            mu=objs["opt_mu"], nu=objs["opt_nu"],
            step=jnp.asarray(objs["counters"]["opt_step"])),
        rng=jnp.asarray(objs["counters"]["rng"]))
    ps = PipelineState(seed=int(objs["pipeline"]["seed"]),
                       step=int(objs["pipeline"]["step"]))
    return st, ps


def run_durable_loop(
    step_fn: Callable,
    init_state: TrainState,
    pipeline: DataPipeline,
    pool: DSMPool,
    *,
    n_steps: int,
    commit_every: int = 5,
    commit_mode: str = "sharded-async",   # the production default schedule
    n_shards: Optional[int] = None,      # sharded modes; None = per-device
    placement=None,         # PlacementPolicy: cost-driven shard count (and,
    #                         with commit_mode="auto", the schedule) under
    #                         an emulated topology — see repro.dsm.placement
    retention: Optional[int] = None,     # keep newest k manifests (GC)
    worker_id: int = 0,
    peer_tiers=None,            # one peer, or a sequence of peers: anything
    #                             with a .staging mapping (TierManager, or a
    #                             cross-process staging view).  Replication
    #                             targets the FIRST peer; recovery consults
    #                             them all.
    replicate: bool = False,
    crash_at: Optional[Dict[int, str]] = None,   # step -> "before_commit" |
    #                                              "after_commit" | "mid_write"
    fault_hook: Optional[Callable] = None,  # (point, step) inside the commit
    #                                         window — see flit_runtime
    resume: bool = False,   # recover from the pool before training (process
    #                         restart); skips the initial step -1 commit
    mesh=None,              # jax Mesh: device-sharded commits (each shard
    #                         pipeline drains its devices' buffers — no host
    #                         gather) and recovered leaves are put back onto
    #                         the template leaf's NamedSharding
    to_device: Callable = jnp.asarray,
) -> LoopResult:
    """Run ``n_steps`` with durable commits every ``commit_every`` steps.

    ``crash_at`` injects worker crashes at precise points (tests use this to
    prove prefix-consistency); after a crash the loop RECOVERS and continues
    — emulating the scheduler restarting the worker.  ``fault_hook`` is the
    harder variant: it fires INSIDE the commit window (pre-flush, mid-flush,
    post-completeOp) so the scenario runner can kill the whole process
    there; the restarted process passes ``resume=True`` to recover from the
    pool instead of re-committing a fresh step -1 (which would shadow newer
    manifests).

    ``pool`` may be a ``DSMPool`` (or pool path) — the loop then opens a
    ``CXL0Context`` from the wiring kwargs — or an already-open
    ``CXL0Context`` (e.g. from a launcher's ``CXL0Config``), in which case
    the context's own wiring wins and the kwargs above only drive the loop
    (cadence, crash injection, resume).
    """
    if isinstance(pool, CXL0Context):
        ctx = pool
    else:
        peers = (tuple(peer_tiers) if isinstance(peer_tiers, (tuple, list))
                 else (peer_tiers,) if peer_tiers is not None else ())
        ctx = open_cxl0(
            pool, worker_id, schedule=commit_mode, n_shards=n_shards,
            retention=retention, placement=placement, peers=peers,
            replicate_to=peers[0] if (replicate and peers) else None,
            mesh=mesh, fault_hook=fault_hook)
    mesh = mesh if mesh is not None else getattr(ctx.config, "mesh", None)
    templates = _state_objects(init_state, pipeline.state)

    state = init_state
    losses: List[float] = []
    timings: List[StepTiming] = []
    recoveries: List[str] = []
    crashes = 0
    resumed_from: Optional[int] = None
    crash_at = dict(crash_at or {})

    i = 0
    if resume:
        try:
            objs, rec_step, source = ctx.recover(templates)
            state, pipe_state = _recovered_state(objs, templates, state,
                                                 mesh, rec_step)
            pipeline.state = pipe_state
            recoveries.append(source)
            resumed_from = rec_step
            i = rec_step + 1
        except ColdStartError:
            pass                # cold pool: fall through to the fresh path
            # (any OTHER failure propagates — committing a fresh step -1
            #  over an existing history would shadow every newer manifest)
    if resumed_from is None:
        # initial durable state (step -1): a cold restart is always possible
        ctx.put(_state_objects(state, pipeline.state), step=-1)
        with ctx.commit(-1):
            pass
        ctx.drain()
    while i < n_steps:
        plan = crash_at.get(i)
        try:
            with obs.span("train.compute", step=i):
                t0 = time.perf_counter()
                batch_np = pipeline.next_global()
                batch = {k: to_device(v) for k, v in batch_np.items()}
                new_state, metrics = step_fn(state, batch)
                state = new_state
                losses.append(float(metrics["loss"]))
                t1 = time.perf_counter()

            ctx.put(_state_objects(state, pipeline.state), step=i)

            if plan == "before_commit":
                raise CrashError(f"injected before commit of step {i}")

            commit_s = 0.0
            if (i + 1) % commit_every == 0:
                if plan == "mid_write":
                    # simulate dying midway through the durable write: some
                    # objects reach the pool, the manifest does NOT
                    for name in list(ctx.tiers.hbm)[:2]:
                        ctx.tiers.rflush(name)
                    raise CrashError(f"injected mid-write at step {i}")
                tc = time.perf_counter()
                with ctx.commit(i):
                    pass
                commit_s = time.perf_counter() - tc
                if plan == "after_commit":
                    raise CrashError(f"injected after commit of step {i}")

            timings.append(StepTiming(i, t1 - t0, commit_s))
            i += 1
        except CrashError:
            crashes += 1
            crash_at.pop(i, None)
            with obs.span("train.crash", step=i):
                ctx.crash()   # f_i: abort in-flight flushes, volatile tiers
                #               vanish
            # --- recovery (new worker incarnation) -------------------------
            objs, rec_step, source = ctx.recover(templates)
            state, pipe_state = _recovered_state(objs, templates, state,
                                                 mesh, rec_step)
            pipeline.state = pipe_state
            recoveries.append(source)
            i = rec_step + 1

    td = time.perf_counter()
    drained = ctx.drain()
    if drained is not None:
        # the tail flush join is real blocking commit time (it overlaps no
        # compute) — charge it so schedule comparisons stay honest
        timings.append(StepTiming(n_steps, 0.0, time.perf_counter() - td))
    ctx.close()
    return LoopResult(state, pipeline.state, losses, timings, recoveries,
                      crashes, resumed_from)
