"""Driver for training cells: ``run_durable_loop`` driving the jitted
``make_train_step`` of the configuration, committing through a
``CXL0Context`` that the benchmark opens itself, as the training launcher
does.

One call of ``run_durable_loop`` spans set-up and window.  Set-up: the
benchmark's weights from the seed, the step -1 commit of the initial
state, and the first ``WINDOW_STEP`` steps, whose first three the plain
reference follows (their losses, the first gradient as the optimizer
holds it, the weights' change after three steps).  The window
opens when the feed is asked for step ``WINDOW_STEP`` and closes when it
is asked for a batch after ``--seconds``; the loop is stopped there.

``crash_after_commit`` k injects a worker crash (``crash_at``
``after_commit``) right after the k-th commit in the window: the pending
flush is aborted and the job recovers from the pool and replays.  Such a
mix also crashes once in set-up (``SETUP_CRASH_STEP``, before its
commit), so the window's recovery finds the step compiled for
recovered arguments.  The rate counts the steps by which the job
advanced, so lost work and recovery lower it.

Host timings copy ``train.loop.StepTiming``: a step's compute time runs
from the feed's call to the state's hand-off to the context (after
``float(loss)``, so the device has finished), and commit time is the
blocking commit region.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import chiplib                                            # noqa: E402
from traffic.generator import TrainRows                   # noqa: E402

#: AdamW as ``repro.train.step.make_train_step`` runs it (the reference
#: is given the same settings; the benchmark reads none of them from the
#: program)
OPT = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "grad_clip": 1.0, "warmup": 100, "floor": 0.1}
#: the loop step at which the window opens
WINDOW_STEP = 3
#: the loop step of the crash in set-up of a mix that crashes
SETUP_CRASH_STEP = 1


class WindowClosed(Exception):
    """The feed was asked for a batch after the window's close."""


def opt_settings(mix: dict) -> dict:
    return dict(OPT, peak_lr=mix["peak_lr"], total_steps=mix["total_steps"])


def crash_step(mix: dict) -> int:
    """The step whose commit is the k-th inside the window."""
    k, ce, ws = mix.get("crash_after_commit"), mix["commit_every"], \
        WINDOW_STEP
    first = ws + (-(ws + 1)) % ce
    return first + (k - 1) * ce


def norms_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def leaf_norms(tree):
        return [jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                for l in jax.tree_util.tree_leaves(tree)]

    @jax.jit
    def change_norms(a, b):
        return leaf_norms(jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b))
    return leaf_norms, change_norms


class Job:
    """The training job under test, with the benchmark's hooks on the
    feed, the step and the context."""

    def __init__(self, a: chiplib.RunArgs):
        import jax
        from repro.data.pipeline import DataPipeline, PipelineState
        from repro.dsm.api import CXL0Config
        from repro.launch.mesh import make_debug_mesh
        from repro.models.registry import build
        from repro.parallel.sharding import ctx_for_mesh
        from repro.train.elastic import shardings_for
        from repro.train.state import init_train_state
        from repro.train.step import make_train_step

        self.a, self.mix = a, a.traffic
        mix = self.mix
        self.cfg = chiplib.program_config(a.config)
        bundle = build(self.cfg)
        pctx = ctx_for_mesh(make_debug_mesh(jax.device_count()))
        self.step_jit = jax.jit(make_train_step(
            bundle, pctx, peak_lr=mix["peak_lr"],
            total_steps=mix["total_steps"]))
        params = chiplib.arch(a.config).make_params(
            bundle.abstract_params(), a.seed, a.config)
        params = jax.tree_util.tree_map(jax.device_put, params,
                                        shardings_for(pctx, bundle.descs))
        self.init_state = init_train_state(
            params, chiplib.prng_key(a.seed, stream=2),
            self.cfg.moment_dtype)
        del params
        self.pool = tempfile.mkdtemp(prefix="chipbench_train_")
        self.ctx = CXL0Config(path=self.pool, schedule=mix["schedule"],
                              retention=mix["retention"]).open()
        job = self

        class Feed(DataPipeline):
            def next_global(self):
                job.before_batch(self.state.step)
                with job.spans("train.feed"):
                    return super().next_global()

        self.feed = Feed(TrainRows(self.cfg.vocab_size), mix["global_batch"],
                         mix["seq_len"],
                         state=PipelineState(seed=int(a.seed), step=0))
        self.leaf_norms, self.change_norms = norms_fn()
        self.window = chiplib.Window(a.seconds)
        self.spans = chiplib.Spans(a.trace)
        self.profile = None
        self.compiles0 = self.compiles = 0
        self.t_iter = None
        self.cur = None
        self.compute: List[tuple] = []      # (step, seconds, in window)
        self.commits: List[tuple] = []      # (step, seconds, in window)
        self.recovers: List[tuple] = []     # (seconds, in window)
        self.losses: Dict[int, list] = {}   # step -> [loss arrays]
        self.last_done = None               # newest step whose state exists
        self.first: Dict[str, object] = {}
        self._wrap_ctx()

    # -- hooks ------------------------------------------------------------
    def before_batch(self, i: int):
        now = time.perf_counter()
        w = self.window
        if i == WINDOW_STEP and w.t0 is None:
            if self.a.trace:
                self.profile = chiplib.Profile(tempfile.mkdtemp(
                    prefix="chipbench_trace_"))
                self.profile.__enter__()
            self.compiles0 = self.a.counter.n
            self.wspan = self.spans("bench.window")
            self.wspan.__enter__()
            w.open()
            now = w.t0
        elif w.is_open and w.due():
            w.close()
            self.wspan.__exit__(None, None, None)
            self.compiles = self.a.counter.n - self.compiles0
            raise WindowClosed
        self.t_iter, self.cur = now, i

    def step(self, state, batch):
        with self.spans("train.step"):
            new_state, metrics = self.step_jit(state, batch)
        i = self.cur
        self.losses.setdefault(i, []).append(metrics["loss"])
        if i == 0 and "grad" not in self.first:
            # after one step mu = (1 - b1) * the clipped gradient
            self.first["grad"] = [x / (1 - OPT["b1"]) for x in
                                  self.leaf_norms(new_state.opt.mu)]
        if i == 2 and "change" not in self.first:
            self.first["change"] = self.change_norms(
                new_state.params, self.init_state.params)
        return new_state, metrics

    def _wrap_ctx(self):
        ctx, job, spans = self.ctx, self, self.spans
        put, commit, recover = ctx.put, ctx.commit, ctx.recover

        def timed_put(objects, step=None):
            if job.t_iter is not None:
                job.compute.append((job.cur, time.perf_counter()
                                    - job.t_iter, job.window.is_open))
                job.last_done = job.cur
            with spans("train.put"):
                return put(objects, step=step)

        class TimedCommit:
            def __init__(self, step, meta=None):
                self.region = commit(step, meta)
                self.step = step

            def __enter__(self):
                self.t = time.perf_counter()
                self.span = spans("train.commit")
                self.span.__enter__()
                return self.region.__enter__()

            def __exit__(self, *exc):
                try:
                    return self.region.__exit__(*exc)
                finally:
                    self.span.__exit__(None, None, None)
                    job.commits.append((self.step,
                                        time.perf_counter() - self.t,
                                        job.window.is_open))

        def timed_recover(*args, **kw):
            t = time.perf_counter()
            with spans("train.recover"):
                out = recover(*args, **kw)
            job.recovers.append((time.perf_counter() - t,
                                 job.window.is_open))
            job.last_done = out[1]
            return out

        ctx.put, ctx.commit, ctx.recover = timed_put, TimedCommit, \
            timed_recover

    # -- the run ------------------------------------------------------------
    def run(self):
        from repro.train.loop import run_durable_loop
        mix = self.mix
        crash = {}
        if mix.get("crash_after_commit"):
            crash[crash_step(mix)] = "after_commit"
            # the same crash once in set-up, before any commit: the step
            # then compiles for the recovered state's arguments here and
            # not after the crash in the window
            crash[SETUP_CRASH_STEP] = "before_commit"
        try:
            run_durable_loop(self.step, self.init_state, self.feed,
                             self.ctx, n_steps=10 ** 9,
                             commit_every=mix["commit_every"],
                             crash_at=crash)
        except WindowClosed:
            pass
        if self.profile is not None:
            self.profile.__exit__(None, None, None)
        self.ctx.crash()           # abort the flush in flight at the close
        self.ctx.close()
        shutil.rmtree(self.pool, ignore_errors=True)


def program_numbers(job: Job) -> dict:
    """What the program computed for the reference to compare: the first
    three steps' losses, the first gradient's and the three steps'
    weight-change leaf norms; and each step run twice (a replay after
    recovery) with its two losses."""
    losses = {i: [float(x) for x in v] for i, v in job.losses.items()}
    return {"loss": [losses[i][0] for i in range(3)],
            "grad": [float(x) for x in job.first["grad"]],
            "change": [float(x) for x in job.first["change"]],
            "replayed": {i: v for i, v in losses.items() if len(v) > 1}}


def reference_numbers(a: chiplib.RunArgs, quant=None,
                      drop_half: bool = False) -> dict:
    """The plain float32 reference over the same weights and rows."""
    from repro.data.pipeline import DataPipeline, PipelineState
    from repro.models.registry import build
    mix = a.traffic
    ref = chiplib.reference(a.config)
    cfg = chiplib.program_config(a.config)
    params = ref.to_f32(chiplib.arch(a.config).make_params(
        build(cfg).abstract_params(), a.seed, a.config))
    feed = DataPipeline(TrainRows(cfg.vocab_size), mix["global_batch"],
                        mix["seq_len"],
                        state=PipelineState(seed=int(a.seed), step=0))
    batches = []
    for _ in range(3):
        b = feed.next_global()
        batches.append((b["tokens"], b["targets"]))
    losses, grad, change = ref.train(
        a.config, params, batches, opt_settings(mix), quant=quant,
        store_dtype=a.config["torch_dtype"],
        drop_half=drop_half)
    del params
    return {"loss": losses, "grad": grad, "change": change}


def gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers compared: the widest loss gap over the three steps, and
    by the worst leaf the gap between the program's and the reference's
    norm of the first gradient and of the weights' change, each over the
    larger of the reference leaf's norm and the median leaf's.  Leaves
    whose reference gradient is under a thousandth of the median leaf's
    are left out of the change."""
    out = {"loss_gap": max(abs(p - r) for p, r in zip(prog["loss"],
                                                         ref["loss"]))}
    g_ref = np.asarray(ref["grad"])
    g_med = float(np.median(g_ref))
    for key in ("grad", "change"):
        p, r = np.asarray(prog[key]), np.asarray(ref[key])
        keep = g_ref >= 1e-3 * g_med if key == "change" else \
            np.ones(len(r), bool)
        denom = np.maximum(r, np.median(r[keep]))
        out[f"{key}_norm_gap"] = float(np.max((np.abs(p - r)
                                               / denom)[keep]))
    return out


def run(a: chiplib.RunArgs) -> chiplib.RunRecord:
    import jax
    log, mix = a.log, a.traffic
    job = Job(a)
    job.run()
    w = job.window
    advance = job.last_done + 1 - WINDOW_STEP
    tokens = advance * mix["global_batch"] * mix["seq_len"]
    e2e = {mix["rate_metric"]: tokens / w.length,
           "setup_s": w.t0 - a.t_start}
    mem = chiplib.memory_peak_bytes(jax.devices())
    in_commits = [c for c in job.commits if c[2]]
    log(f"window {w.length:.3f} s: steps {WINDOW_STEP}.."
        f"{job.last_done} ({advance} advanced, "
        f"{sum(1 for c in job.compute if c[2])} run), "
        f"{len(in_commits)} commits at steps "
        f"{[c[0] for c in in_commits]} taking "
        f"{[round(c[1], 3) for c in in_commits]} s, recoveries taking "
        f"{[round(r[0], 3) for r in job.recovers if r[1]]} s")
    prog = program_numbers(job)
    host = {"compute": job.compute, "commits": job.commits,
            "recovers": job.recovers, "tokens": tokens,
            "window": (w.t0, w.t1)}
    replay_bad = sum(1 for v in prog["replayed"].values()
                     if any(x != v[0] for x in v))
    log(f"replayed steps {sorted(prog['replayed'])}: {replay_bad} with a "
        f"loss other than the first run's")
    compiles, trace_path = job.compiles, (job.profile.xplane()
                                          if job.profile else None)
    del job
    gc.collect()
    ref = reference_numbers(a)
    log(f"losses program {prog['loss']} reference {ref['loss']}")
    lim = a.limits
    nan = float("nan")
    checks = [chiplib.Check(k, v, lim.get(k, nan))
              for k, v in gaps(prog, ref).items()]
    if mix.get("crash_after_commit"):
        checks.append(chiplib.Check("replayed_loss_mismatch",
                                    float(replay_bad if prog["replayed"]
                                          else 1), 0.0))
    return chiplib.RunRecord(
        end_to_end=e2e, checks=checks,
        attempted=advance, failed=0, memory_peak_bytes=mem, host=host,
        window_s=w.length, trace_path=trace_path,
        compiles_in_window=compiles)
