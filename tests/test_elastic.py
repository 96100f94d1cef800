"""Elastic scaling: recover state from the pool and re-shard onto a
different mesh — shrink (8 -> 4 devices) AND grow (4 -> 8).  The mesh
tests run in subprocesses; the 8-device host force is inherited from
the environment (set once in conftest.py).  Plan symmetry (grow then
shrink returns the original partition) is pure and runs in-process."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.train.elastic import grow_plan, partition_plan, plan_delta

SCRIPT = textwrap.dedent("""
    import os
    import json
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.data.pipeline import DataPipeline, SyntheticLMSource, shard_plan
    from repro.dsm.pool import DSMPool
    from repro.dsm.recovery import RecoveryManager
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build
    from repro.parallel.sharding import ctx_for_mesh
    from repro.train.elastic import remesh, shardings_for, shrink_plan
    from repro.train.loop import run_durable_loop, _state_objects
    from repro.train.state import init_train_state
    from repro.train.step import make_train_step

    cfg = get_smoke_config("olmo-1b")
    bundle = build(cfg)
    key = jax.random.PRNGKey(0)

    # --- run on the 8-device mesh, committing durably -------------------
    mesh8 = make_mesh((4, 2), ("data", "model"))
    ctx8 = ctx_for_mesh(mesh8)
    params = bundle.init_params(key)
    sh8 = shardings_for(ctx8, bundle.descs)
    params = jax.tree_util.tree_map(jax.device_put, params, sh8)
    state = init_train_state(params, key)
    step8 = jax.jit(make_train_step(bundle, ctx8))
    pool = DSMPool(os.environ["POOL_DIR"])
    pipe = DataPipeline(SyntheticLMSource(cfg.vocab_size), 8, 32)
    r = run_durable_loop(step8, state, pipe, pool, n_steps=4, commit_every=2)

    # --- "cluster shrinks": rebuild on a 4-device mesh ------------------
    mesh4 = make_mesh((2, 2), ("data", "model"))
    templates = _state_objects(r.state, r.pipeline_state)
    objs, rec_step, src = RecoveryManager(pool).recover(templates)
    assert rec_step == 3, rec_step

    new_params, ctx4 = remesh(objs["params"], bundle.descs, mesh4)
    # every leaf is now addressable on the 4-device mesh
    for leaf in jax.tree_util.tree_leaves(new_params):
        assert len(leaf.sharding.device_set) <= 4

    # training continues on the shrunk mesh from the recovered state
    state4 = init_train_state(new_params, key)
    state4 = state4._replace(opt=state4.opt._replace(
        step=jnp.asarray(objs["counters"]["opt_step"])))
    step4 = jax.jit(make_train_step(bundle, ctx4))
    batch = {k: jnp.asarray(v) for k, v in pipe.next_global().items()}
    state4, m = step4(state4, batch)
    assert bool(jnp.isfinite(m["loss"]))

    # data shard plan reassigns the lost ranks
    plan = shrink_plan(8, 4)
    assert all(0 <= v < 4 for v in plan.values())
    print(json.dumps({"ok": True, "rec_step": rec_step,
                      "loss": float(m["loss"]), "source": src}))
""")


GROW_SCRIPT = textwrap.dedent("""
    import os
    import json
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.data.pipeline import DataPipeline, SyntheticLMSource
    from repro.dsm.pool import DSMPool
    from repro.dsm.recovery import RecoveryManager
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build
    from repro.parallel.sharding import ctx_for_mesh
    from repro.train.elastic import grow_plan, remesh, shardings_for
    from repro.train.loop import run_durable_loop, _state_objects
    from repro.train.state import init_train_state
    from repro.train.step import make_train_step

    cfg = get_smoke_config("olmo-1b")
    bundle = build(cfg)
    key = jax.random.PRNGKey(0)

    # --- run on a 4-device mesh, committing durably ---------------------
    mesh4 = make_mesh((2, 2), ("data", "model"))
    ctx4 = ctx_for_mesh(mesh4)
    params = bundle.init_params(key)
    sh4 = shardings_for(ctx4, bundle.descs)
    params = jax.tree_util.tree_map(jax.device_put, params, sh4)
    state = init_train_state(params, key)
    step4 = jax.jit(make_train_step(bundle, ctx4))
    pool = DSMPool(os.environ["POOL_DIR"])
    pipe = DataPipeline(SyntheticLMSource(cfg.vocab_size), 8, 32)
    r = run_durable_loop(step4, state, pipe, pool, n_steps=4, commit_every=2)

    # --- "cluster grows": rebuild on the full 8-device mesh -------------
    mesh8 = make_mesh((4, 2), ("data", "model"))
    templates = _state_objects(r.state, r.pipeline_state)
    objs, rec_step, src = RecoveryManager(pool).recover(templates)
    assert rec_step == 3, rec_step

    new_params, ctx8 = remesh(objs["params"], bundle.descs, mesh8)
    # every leaf is now spread over the grown device set
    for leaf in jax.tree_util.tree_leaves(new_params):
        assert len(leaf.sharding.device_set) <= 8

    # training continues on the grown mesh from the recovered state
    state8 = init_train_state(new_params, key)
    state8 = state8._replace(opt=state8.opt._replace(
        step=jnp.asarray(objs["counters"]["opt_step"])))
    step8 = jax.jit(make_train_step(bundle, ctx8))
    batch = {k: jnp.asarray(v) for k, v in pipe.next_global().items()}
    state8, m = step8(state8, batch)
    assert bool(jnp.isfinite(m["loss"]))

    # data shard plan: old ranks keep their identity, joiners start fresh
    plan = grow_plan(4, 8)
    assert plan == {r: r for r in range(4)}
    print(json.dumps({"ok": True, "rec_step": rec_step,
                      "loss": float(m["loss"]), "source": src}))
""")


def _run_script(script, tmp_path):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"),
               POOL_DIR=str(tmp_path / "pool"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_elastic_shrink_8_to_4(tmp_path):
    out = _run_script(SCRIPT, tmp_path)
    assert out["ok"] and out["rec_step"] == 3


def test_elastic_grow_4_to_8(tmp_path):
    out = _run_script(GROW_SCRIPT, tmp_path)
    assert out["ok"] and out["rec_step"] == 3


def test_partition_plan_grow_then_shrink_is_identity():
    """Membership round-trips: growing to 4 ranks and shrinking back to
    3 derives the ORIGINAL partition — the plan is a pure function of
    the live set, so a failed grow leaves nothing to repair."""
    names = [f"t{i}" for i in range(9)]
    old = partition_plan(names, [0, 1, 2])
    grown = partition_plan(names, [0, 1, 2, 3])
    assert partition_plan(names, [0, 1, 2]) == old
    fwd = plan_delta(old, grown)
    back = plan_delta(grown, old)
    assert set(fwd) == set(back)
    assert all(back[n] == (fwd[n][1], fwd[n][0]) for n in fwd)


def test_grow_plan_keeps_old_rank_identity():
    assert grow_plan(4, 8) == {0: 0, 1: 1, 2: 2, 3: 3}
    with pytest.raises(AssertionError):
        grow_plan(8, 4)                       # that's shrink_plan's job
