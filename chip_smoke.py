"""Chip smoke test: olmo-1b serving and durable training on the TPU,
through the entry points a user calls.

    python chip_smoke.py            # one chip: serve phase, then train phase
    python chip_smoke.py --chips 4  # four chips: the mesh-commit phase only

Serve phase: ``olmo-1b`` at published widths (16 layers, d_model 2048,
bf16), built the way ``repro.launch.serve`` builds it (mesh ->
``ctx_for_mesh`` -> ``build_serve_engine``).  A trace of 16 requests with
512-token prompts is served once with no pool: the reference.  It is then
served with durable sessions; after two commits the engine is stopped
without ``finish()`` and its volatile state wiped (``ctx.crash()``), and a
second engine on the same pool resumes and must emit the reference's
tokens exactly.

Train phase: ``olmo-1b`` widths with depth cut to 4 of 16 layers, so the
params, the fp32 Adam moments and each commit fit one chip.
``run_durable_loop`` with ``sharded-async`` commits every 2 steps runs once
clean and once with a crash injected after a commit; final state digests
and losses must be equal.

Mesh-commit phase (``--chips 4``): the train phase on a data=2 x model=2
mesh with device-local commits (``CXL0Config.mesh``), compared with the
same run committed through the host gather: the manifests must be
bit-identical, the device-local side must gather no bytes, recovery must
put the leaves back on their NamedShardings, and the state must be spread
over all four devices.

Weights are random, made from a seed.  Wall times printed are smoke
timings, not a benchmark.  Any failed check exits nonzero; on success the
last line of stdout is one JSON object naming the device.  Nothing runs
unless JAX's first device is a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Optional

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                               # noqa: E402
import numpy as np                                       # noqa: E402

from repro.configs import get_config                     # noqa: E402
from repro.data.pipeline import DataPipeline, SyntheticLMSource  # noqa: E402
from repro.dsm.api import CXL0Config                     # noqa: E402
from repro.dsm.meshio import per_device_nbytes           # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_debug_mesh, make_mesh  # noqa: E402
from repro.models.registry import build                  # noqa: E402
from repro.parallel.sharding import ctx_for_mesh         # noqa: E402
from repro.serve.engine import build_serve_engine        # noqa: E402
from repro.serve.trace import synthetic_trace            # noqa: E402
from repro.train.elastic import shardings_for            # noqa: E402
from repro.train.loop import run_durable_loop            # noqa: E402
from repro.train.state import init_train_state           # noqa: E402
from repro.train.step import make_train_step             # noqa: E402

ARCH = "olmo-1b"
#: depth of the train phase: 4 of olmo-1b's 16 layers keep params, fp32
#: Adam moments, a step's temporaries and the commit in flight on one chip
TRAIN_LAYERS = 4


def say(msg: str):
    print(msg, flush=True)


def _peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


def _tree_nbytes(tree) -> int:
    return sum(int(l.nbytes) for l in jax.tree_util.tree_leaves(tree))


# -- serve ------------------------------------------------------------------

def serve_phase(mesh, *, smoke: bool = False, n_slots: int = 8,
                t_max: int = 1024, n_requests: int = 16,
                prompt_len: int = 512, budgets=(32, 64, 128),
                commit_every: int = 4, seed: int = 0) -> dict:
    """Reference run, then a durable run crashed after two commits and
    resumed by a second engine; ``ok`` iff the resumed outputs equal the
    reference token for token."""
    ctx = ctx_for_mesh(mesh)
    kw = dict(smoke=smoke, n_slots=n_slots, t_max=t_max, ctx=ctx, seed=seed)
    ref_engine, cfg = build_serve_engine(ARCH, **kw)
    bundle, params = ref_engine.bundle, ref_engine.params
    say(f"serve: {ARCH} {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.param_dtype}; {bundle.n_params()} params, "
        f"{_tree_nbytes(params)} param bytes")
    trace = synthetic_trace(n_requests, seed=seed,
                            prompt_lens=(prompt_len,), new_tokens=budgets,
                            vocab_size=cfg.vocab_size)
    t0 = time.perf_counter()
    ref = ref_engine.run(trace)
    t_ref = time.perf_counter() - t0
    del ref_engine

    pool = tempfile.mkdtemp(prefix="chip_smoke_serve_")
    try:
        def durable_engine():
            dsm = CXL0Config(path=pool, schedule="sync", retention=2)
            return build_serve_engine(ARCH, dsm=dsm,
                                      commit_every=commit_every,
                                      bundle=bundle, params=params, **kw)[0]

        first = durable_engine()
        first.resume()                       # cold pool: nothing to resume
        first.submit(trace)
        for _ in range(2 * commit_every):    # commits at ticks k and 2k
            first.tick()
        first.store.ctx.crash()              # killed without finish()
        first.close()
        del first                            # its KV lanes leave the chip

        t0 = time.perf_counter()
        second = durable_engine()
        resumed_tick = second.resume()
        res = second.run(trace)
        second.close()
        t_res = time.perf_counter() - t0
    finally:
        shutil.rmtree(pool, ignore_errors=True)

    want = {r.rid: r.max_new_tokens for r in trace}
    complete = ({rid: len(t) for rid, t in ref.outputs.items()} == want)
    match = res.outputs == ref.outputs
    facts = {"match": match, "resumed_tick": resumed_tick,
             "resumed_sessions": res.resumed_sessions,
             "ok": (match and complete and resumed_tick == 2 * commit_every
                    and res.resumed_sessions > 0)}
    say(f"serve: reference {len(ref.outputs)} requests, {ref.emitted_tokens}"
        f" tokens, {ref.prefills} prefills, {ref.decode_ticks} decode ticks")
    say(f"serve: crashed after tick {2 * commit_every}; resumed from "
        f"committed tick {resumed_tick}, {res.resumed_sessions} resumed "
        f"sessions, {res.prefills} prefills, {res.decode_ticks} decode "
        f"ticks, {res.commits} commits after resume")
    say(f"serve: smoke timing, not a benchmark: reference {t_ref:.1f} s, "
        f"resume to end {t_res:.1f} s (compiles included)")
    say(f"serve: match {str(match).lower()}; peak_bytes_in_use "
        f"{_peak_bytes()}")
    return facts


# -- train ------------------------------------------------------------------

def train_config():
    """olmo-1b at published widths, depth cut to ``TRAIN_LAYERS``."""
    return get_config(ARCH).with_(n_layers=TRAIN_LAYERS)


@dataclasses.dataclass
class TrainRun:
    """What one durable training run leaves behind, read before its
    device state is released."""
    losses: list
    crashes: int
    recoveries: list
    resumed_from: Optional[int]
    digest: str
    state_bytes: int
    manifest: dict
    d2h_gather_bytes: int
    d2h_shard_bytes: int
    state: Any = None              # the final TrainState, when asked for


class _Trainer:
    """One compiled train step over ``mesh`` and the runs made with it;
    every run starts from the same seeded state in a pool of its own."""

    def __init__(self, cfg, mesh, root: str, *, global_batch: int,
                 seq: int, n_steps: int, commit_every: int, seed: int):
        self.cfg, self.mesh, self.root = cfg, mesh, root
        self.bundle = build(cfg)
        self.ctx = ctx_for_mesh(mesh)
        self.step = jax.jit(make_train_step(self.bundle, self.ctx,
                                            total_steps=n_steps))
        self.global_batch, self.seq = global_batch, seq
        self.n_steps, self.commit_every, self.seed = (n_steps, commit_every,
                                                      seed)

    def fresh_state(self):
        key = jax.random.PRNGKey(self.seed)
        params = jax.tree_util.tree_map(
            jax.device_put, self.bundle.init_params(key),
            shardings_for(self.ctx, self.bundle.descs))
        return init_train_state(params, key, self.cfg.moment_dtype)

    def run(self, name: str, *, device_local: bool, crash_at=None,
            resume: bool = False, keep_state: bool = False,
            keep_pool: bool = False) -> TrainRun:
        """``run_durable_loop`` over pool ``name``, committing as
        ``repro.launch.train`` does (``device_local`` adds the mesh).  The
        pool is removed afterwards unless ``keep_pool``: each holds
        several commits of the whole state."""
        gc.collect()            # the previous run's state leaves the chip
        path = os.path.join(self.root, name)
        dsm = CXL0Config(path=path,
                         schedule="sharded-async", retention=2,
                         mesh=self.mesh if device_local else None).open()
        pipe = DataPipeline(SyntheticLMSource(self.cfg.vocab_size),
                            self.global_batch, self.seq)
        r = run_durable_loop(self.step, self.fresh_state(), pipe, dsm,
                             n_steps=self.n_steps,
                             commit_every=self.commit_every,
                             crash_at=crash_at, resume=resume)
        run = TrainRun(
            losses=r.losses, crashes=r.crashes, recoveries=r.recoveries,
            resumed_from=r.resumed_from, digest=state_digest(r.state),
            state_bytes=_tree_nbytes((r.state.params, r.state.opt)),
            manifest=dsm.pool.latest_manifest(),
            d2h_gather_bytes=dsm.tiers.d2h_gather_bytes,
            d2h_shard_bytes=dsm.tiers.d2h_shard_bytes,
            state=r.state if keep_state else None)
        if not keep_pool:
            shutil.rmtree(path, ignore_errors=True)
        return run


def state_digest(state) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves((state.params, state.opt,
                                           state.rng)):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()[:16]


def _losses_match(clean, crashed, crash_step: int) -> bool:
    """The crashed run logs steps 0..crash_step, then replays from the
    recovered commit to the end: its head and tail must be the clean
    run's, bit for bit."""
    head = crash_step + 1
    tail = len(crashed) - head
    return (len(crashed) > len(clean) and crashed[:head] == clean[:head]
            and crashed[head:] == clean[len(clean) - tail:])


def _clean_and_crashed(tr: _Trainer, *, device_local: bool,
                       crash_step: int, tag: str):
    t0 = time.perf_counter()
    clean = tr.run("clean", device_local=device_local)
    crashed = tr.run("crashed", device_local=device_local,
                     crash_at={crash_step: "after_commit"})
    dt = time.perf_counter() - t0
    match = (clean.digest == crashed.digest and crashed.crashes == 1
             and _losses_match(clean.losses, crashed.losses, crash_step))
    say(f"{tag}: clean losses {clean.losses}")
    say(f"{tag}: crash after the commit of step {crash_step}, recovered "
        f"from {crashed.recoveries}; losses {crashed.losses}")
    say(f"{tag}: final state digest clean {clean.digest} crashed "
        f"{crashed.digest}; match {str(match).lower()}")
    say(f"{tag}: smoke timing, not a benchmark: two runs {dt:.1f} s "
        f"(compile included)")
    return clean, match


def train_phase(cfg, mesh, *, global_batch: int = 8, seq: int = 512,
                n_steps: int = 6, commit_every: int = 2, crash_step: int = 3,
                seed: int = 0) -> dict:
    """Clean vs crash-injected durable training, committed the way
    ``repro.launch.train`` commits; ``ok`` iff digests and losses agree."""
    root = tempfile.mkdtemp(prefix="chip_smoke_train_")
    try:
        tr = _Trainer(cfg, mesh, root, global_batch=global_batch, seq=seq,
                      n_steps=n_steps, commit_every=commit_every, seed=seed)
        say(f"train: {ARCH} widths cut to {cfg.n_layers} of "
            f"{get_config(ARCH).n_layers} layers (d_model {cfg.d_model}, "
            f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); "
            f"{tr.bundle.n_params()} params; batch {global_batch}x{seq}, "
            f"{n_steps} steps, sharded-async commit every {commit_every}, "
            f"retention 2")
        clean, match = _clean_and_crashed(
            tr, device_local=False, crash_step=crash_step, tag="train")
        say(f"train: state (params + Adam moments) {clean.state_bytes} "
            f"bytes per commit; peak_bytes_in_use {_peak_bytes()}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return {"match": match, "ok": match}


def mesh_commit_phase(cfg, mesh, *, global_batch: int = 8, seq: int = 512,
                      n_steps: int = 6, commit_every: int = 2,
                      crash_step: int = 3, seed: int = 0) -> dict:
    """The train phase on ``mesh`` with device-local commits, against the
    same run committed through the host gather."""
    n_dev = mesh.devices.size
    root = tempfile.mkdtemp(prefix="chip_smoke_mesh_")
    try:
        tr = _Trainer(cfg, mesh, root, global_batch=global_batch, seq=seq,
                      n_steps=n_steps, commit_every=commit_every, seed=seed)
        say(f"mesh: {dict(mesh.shape)} over {n_dev} devices; {ARCH} widths "
            f"cut to {cfg.n_layers} of {get_config(ARCH).n_layers} layers, "
            f"{tr.bundle.n_params()} params")
        dev, match = _clean_and_crashed(
            tr, device_local=True, crash_step=crash_step, tag="mesh")
        hg = tr.run("gathered", device_local=False, keep_pool=True)
        manifests_equal = dev.manifest == hg.manifest
        say(f"mesh: latest manifest device-local == host-gather: "
            f"{str(manifests_equal).lower()} (step {dev.manifest['step']}); "
            f"final digests {dev.digest} {hg.digest}")
        say(f"mesh: d2h_gather_bytes device-local {dev.d2h_gather_bytes} "
            f"(d2h_shard_bytes {dev.d2h_shard_bytes}), host-gather "
            f"{hg.d2h_gather_bytes}")

        # the host-gather pool, recovered by a device-local stack
        rec = tr.run("gathered", device_local=True, resume=True,
                     keep_state=True)
        template = tr.fresh_state()
        moved = (rec.state.params, rec.state.opt.mu, rec.state.opt.nu)
        pairs = list(zip(jax.tree_util.tree_leaves(moved),
                         jax.tree_util.tree_leaves((template.params,
                                                    template.opt.mu,
                                                    template.opt.nu))))
        placed = all(a.sharding == t.sharding for a, t in pairs)
        say(f"mesh: resumed from step {rec.resumed_from}; leaves back on "
            f"their NamedShardings: {str(placed).lower()} ({len(pairs)} "
            f"leaves); digest {rec.digest}")
        per_dev = per_device_nbytes(moved)
        spread = (len(per_dev) == n_dev
                  and min(per_dev) * 2 * n_dev >= sum(per_dev))
        say(f"mesh: state bytes per device {per_dev}; spread over all "
            f"{n_dev}: {str(spread).lower()}")
        in_use = [d.memory_stats()["bytes_in_use"] if d.memory_stats()
                  else "not reported" for d in mesh.devices.flat]
        say(f"mesh: bytes_in_use per device {in_use}")
        ok = (match and manifests_equal and placed and spread
              and dev.digest == hg.digest == rec.digest
              and rec.resumed_from == n_steps - 1
              and dev.d2h_gather_bytes == 0 and dev.d2h_shard_bytes > 0
              and hg.d2h_gather_bytes > 0)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return {"match": match, "manifests_equal": manifests_equal,
            "placed": placed, "spread": spread, "ok": ok}


# -- entry ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh-commit phase, on a 2x2 mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"platform {dev.platform!r}; nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    say(f"device: {dev.device_kind} ({dev.platform}), {len(devices)} "
        f"visible; jax {jax.__version__}; compile cache "
        f"{enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.chips == 4:
        results = [mesh_commit_phase(train_config(),
                                     make_mesh((2, 2), ("data", "model")))]
    else:
        results = [serve_phase(make_debug_mesh(len(devices))),
                   train_phase(train_config(), make_debug_mesh(len(devices)))]
    say(f"total: smoke timing, not a benchmark: {time.perf_counter() - t0:.1f}"
        f" s")
    if not all(r["ok"] for r in results):
        say(f"chip_smoke: FAILED {results}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
