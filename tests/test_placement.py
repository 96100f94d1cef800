"""Cost-driven placement (repro.dsm.placement): decisions must FLIP when
the emulated topology changes, be logged with their priced alternatives,
never lose to a fixed strategy, and actually steer the wired layers
(DurableCommitter shard count/schedule, the spill route of a smoke KV
cache through the tiers, cluster rank staging)."""
import numpy as np
import pytest

from repro.dsm.emu import PRESETS
from repro.dsm.flit_runtime import DurableCommitter
from repro.dsm.placement import PlacementPolicy, plan_rank_staging
from repro.dsm.pool import DSMPool
from repro.dsm.tiers import TierManager

MB = 1 << 20


# ---------------------------------------------------------------------------
# decisions flip with the topology preset
# ---------------------------------------------------------------------------

def test_shard_count_flips_with_topology():
    """Direct-attach has one link (sharding is overhead); the switched
    pool and fabric fan out — for the same 64 MiB state the chosen shard
    count must strictly grow with the topology's link count."""
    ks = {name: PlacementPolicy(name).choose_shards(64 * MB)
          for name in PRESETS}
    assert ks["cxl11-direct"] == 1
    assert (ks["cxl11-direct"] < ks["cxl20-switched-pool"]
            < ks["cxl30-fabric"])
    assert ks["cxl30-fabric"] <= PRESETS["cxl30-fabric"].n_links


def test_shard_count_scales_with_size():
    p = PlacementPolicy("cxl30-fabric")
    assert p.choose_shards(4 << 10) == 1         # latency-dominated
    assert p.choose_shards(64 * MB) > 1          # bandwidth-dominated


def test_spill_tier_flips_with_topology():
    """A 1 MiB object: the direct-attach staging path (fast cache-to-cache,
    slow single pool link) prefers staging; the fabric (slow multi-hop
    staging, wide pool fan-out) prefers the pool."""
    assert PlacementPolicy("cxl11-direct").choose_spill("kv", MB) == "staging"
    assert PlacementPolicy("cxl30-fabric").choose_spill("kv", MB) == "pool"


def test_spill_tier_flips_with_size():
    p = PlacementPolicy("cxl30-fabric")
    assert p.choose_spill("small", 4 << 10) == "staging"
    assert p.choose_spill("large", 64 * MB) == "pool"


def test_schedule_flips_with_size():
    p = PlacementPolicy("cxl11-direct")
    assert p.choose_schedule(64 << 10) == "sync"
    assert p.choose_schedule(64 * MB) == "sharded-async"


def test_decisions_are_logged_with_costs():
    p = PlacementPolicy("cxl20-switched-pool")
    p.choose_spill("kv/r1", 2 * MB)
    p.choose_shards(2 * MB, "kv/r1")
    p.choose_schedule(2 * MB, "state")
    kinds = [d.kind for d in p.decisions]
    assert kinds == ["spill", "shards", "schedule"]
    spill = p.decisions_for("spill")[0]
    assert spill.name == "kv/r1" and spill.nbytes == 2 * MB
    assert set(spill.costs) == {"staging", "pool"}
    assert spill.costs[spill.choice] == min(spill.costs.values())
    assert spill.topology == "cxl20-switched-pool"
    sched = p.decisions_for("schedule")[0]
    assert sched.choice in ("sync", "sharded-async")
    assert "flush_ns" in sched.costs


def test_policy_never_loses_to_fixed_strategies():
    """The bench invariant at test scale: per-object argmin of the same
    cost model can never exceed either fixed strategy, on any preset."""
    rng = np.random.default_rng(42)
    sizes = [int(x) for x in np.exp(rng.uniform(np.log(4 << 10),
                                                np.log(64 * MB), 16))]
    mixed = 0
    for name in PRESETS:
        p = PlacementPolicy(name)
        staging = pool = policy = 0.0
        choices = set()
        for nb in sizes:
            c = p.spill_costs(nb)
            staging += c["staging"]
            pool += c["pool"]
            ch = p.choose_spill("o", nb)
            choices.add(ch)
            policy += c[ch]
        assert policy <= staging + 1e-9
        assert policy <= pool + 1e-9
        mixed += len(choices) == 2
    assert mixed >= 1       # somewhere the decisions mix -> strict win


# ---------------------------------------------------------------------------
# wiring: committer
# ---------------------------------------------------------------------------

def _state(nbytes):
    return {"params": {"w": np.zeros(nbytes // 4, np.float32)}}


def test_committer_resolves_shards_from_policy(tmp_path):
    p = PlacementPolicy("cxl30-fabric")
    tiers = TierManager(DSMPool(str(tmp_path / "pool")), 0)
    c = DurableCommitter(tiers, mode="sharded", placement=p)
    c.update(_state(8 * MB))
    st = c.commit(0)
    assert st.n_shards == p.choose_shards(8 * MB, log=False)
    assert st.n_shards > 1
    assert p.decisions_for("shards")          # the decision was logged
    assert tiers.pool.latest_manifest()["step"] == 0
    tiers.close()


def test_committer_auto_mode_resolves_schedule(tmp_path):
    p = PlacementPolicy("cxl11-direct")
    tiers = TierManager(DSMPool(str(tmp_path / "pool")), 0)
    c = DurableCommitter(tiers, mode="auto", placement=p)
    c.update(_state(64 << 10))               # small: policy says sync
    st = c.commit(0)
    assert c.mode == "sync"
    assert st is not None and st.step == 0
    assert p.decisions_for("schedule")[0].choice == "sync"
    tiers.close()

    p2 = PlacementPolicy("cxl11-direct")
    tiers2 = TierManager(DSMPool(str(tmp_path / "pool2")), 0)
    c2 = DurableCommitter(tiers2, mode="auto", placement=p2)
    c2.update(_state(64 * MB))               # large: overlap pays
    c2.commit(0)
    assert c2.mode == "sharded-async"
    c2.drain()
    tiers2.close()


def test_auto_mode_requires_policy(tmp_path):
    tiers = TierManager(DSMPool(str(tmp_path / "pool")), 0)
    with pytest.raises(AssertionError):
        DurableCommitter(tiers, mode="auto")
    tiers.close()


def test_durable_loop_with_placement_auto(tmp_path):
    """End to end through the training loop: commit_mode='auto' + a policy
    resolves to a real schedule, the run commits durably, and the final
    state matches the fixed-schedule reference bit for bit (placement
    trades latency, never correctness)."""
    from repro.data.pipeline import DataPipeline, SyntheticLMSource
    from repro.scenarios.worker import (make_toy_state, make_toy_step,
                                        state_digest)
    from repro.train.loop import run_durable_loop

    def pipe():
        return DataPipeline(SyntheticLMSource(1024), 4, 32)

    p = PlacementPolicy("cxl20-switched-pool")
    pool = DSMPool(str(tmp_path / "auto"))
    r = run_durable_loop(make_toy_step(), make_toy_state(), pipe(), pool,
                         n_steps=6, commit_every=2, commit_mode="auto",
                         placement=p)
    assert pool.latest_manifest()["step"] == 5
    assert p.decisions_for("schedule")           # the choice was priced
    r_ref = run_durable_loop(make_toy_step(), make_toy_state(), pipe(),
                             DSMPool(str(tmp_path / "ref")), n_steps=6,
                             commit_every=2, commit_mode="sync")
    assert state_digest(r.state) == state_digest(r_ref.state)


# ---------------------------------------------------------------------------
# wiring: cluster rank staging
# ---------------------------------------------------------------------------

def test_plan_rank_staging_flips_with_topology():
    """A 1 MiB rank partition: ring RStore-staging is worth it on the
    direct pair, dead weight on the fabric (pool fan-out + slow staging
    path) — and either way the decision lands in the log."""
    p_direct = PlacementPolicy("cxl11-direct")
    p_fabric = PlacementPolicy("cxl30-fabric")
    assert plan_rank_staging(p_direct, MB) is True
    assert plan_rank_staging(p_fabric, MB) is False
    assert p_direct.decisions_for("staging")[0].choice is True
    assert p_fabric.decisions_for("staging")[0].nbytes == MB


# ---------------------------------------------------------------------------
# wiring: a KV cache spilled by policy (real bundle, both routes restorable)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_bundle():
    import jax
    from repro.configs import get_smoke_config
    from repro.models.registry import build
    cfg = get_smoke_config("olmo-1b")
    bundle = build(cfg, dec_pos_len=32)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return bundle, params


def _filled_cache1(bundle, params):
    import jax
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 64)
    _, st = bundle.prefill(params, {"tokens": toks},
                           bundle.init_caches(jax.random.PRNGKey(0), 1, 32))
    return st.caches


def _tree_eq(a, b):
    import jax
    fa, ta = jax.tree_util.tree_flatten(a)
    fb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_spill_auto_routes_by_policy_and_restores(smoke_bundle, tmp_path):
    from repro.dsm.emu import tree_nbytes
    from repro.dsm.pool import manifest_entry
    bundle, params = smoke_bundle
    c1 = _filled_cache1(bundle, params)
    nbytes = tree_nbytes(c1)

    # direct-attach: small caches go to staging (host tier + peer buffer)
    tiers = TierManager(DSMPool(str(tmp_path / "a")), 0)
    peer = TierManager(DSMPool(str(tmp_path / "peer")), 1)
    assert PlacementPolicy("cxl11-direct").choose_spill("kv/s0",
                                                        nbytes) == "staging"
    tiers.lstore("kv/s0", c1)
    tiers.rstore("kv/s0", peer, tag=tiers.versions["kv/s0"])
    _tree_eq(tiers.hbm["kv/s0"], c1)
    # ...and the copy really reached the peer's buffer (survives our loss)
    assert "kv/s0" in peer.staging

    # fabric at the same size: forced pool preference via a policy whose
    # staging path is hopeless (replay dominates), exercising the durable
    # route end to end
    tiers2 = TierManager(DSMPool(str(tmp_path / "b")), 0)
    pol = PlacementPolicy("cxl30-fabric", p_peer_loss=1.0,
                          replay_ns_per_byte=1e3)
    assert pol.choose_spill("kv/s0", nbytes) == "pool"
    tiers2.lstore("kv/s0", c1)
    entry = manifest_entry(tiers2.rflush_sharded(
        "kv/s0", pol.choose_shards(nbytes, "kv/s0")))
    tiers2.ldiscard("kv/s0")             # force the pool read path
    _tree_eq(tiers2.pool.read_entry("kv/s0", entry,
                                    bundle.abstract_caches(1, 32)), c1)
    decisions = pol.decisions_for("spill")
    assert decisions and decisions[0].choice == "pool"
    tiers.close()
    tiers2.close()
