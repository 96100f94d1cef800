"""Device-local sharded commit vs host-gather commit: bit-identical pool
state, opposite D2H traffic shape, format-compatible recovery.

The committer's device-sharded mode (``CXL0Config.mesh``) must be a pure
TRANSPORT change: each shard pipeline drains its devices' buffers
directly instead of a full-tree host gather, but the bytes that land in
the pool — shard files, CRCs, manifests — are identical to the classic
path at the same shard count.  That makes recovery trivially
cross-format, which is asserted in BOTH directions here.

Runs on the 8 host devices forced by conftest.py (``host_devices_8``
skips when a backend initialised first).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.dsm.api import CXL0Config
from repro.launch.mesh import make_mesh


def _mesh(shape=(2, 4)):
    return make_mesh(shape, ("data", "model")[:len(shape)])


def _tree(n_leaves=6, dim=64, seed=0):
    key = jax.random.PRNGKey(seed)
    tree = {}
    for t in range(n_leaves):
        key, k = jax.random.split(key)
        tree[f"w{t}"] = jax.random.normal(k, (dim, dim), jnp.float32)
    return tree


def _shard(tree, mesh):
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", "model"))
    return jax.tree_util.tree_map(lambda l: jax.device_put(l, sh), tree)


def _np_tree(tree):
    return {k: np.asarray(v) for k, v in tree.items()}


def _commit(path, tree, *, mesh=None, n_shards=4, topology=None):
    ctx = CXL0Config(path=str(path), schedule="sharded", n_shards=n_shards,
                     topology=topology, mesh=mesh).open()
    ctx.put({"params": tree}, step=1)
    with ctx.commit(1):
        pass
    return ctx


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_device_local_commit_bit_identical(host_devices_8, tmp_path):
    mesh = _mesh()
    tree = _shard(_tree(), mesh)
    expect = _np_tree(tree)

    ctx_dev = _commit(tmp_path / "dev", tree, mesh=mesh)
    ctx_hg = _commit(tmp_path / "hg", tree, mesh=None)

    # the D2H accounting proves the transport really differed: the device
    # path never gathered the full tree, the classic path ONLY did
    assert ctx_dev.tiers.d2h_gather_bytes == 0
    assert ctx_dev.tiers.d2h_shard_bytes > 0
    assert ctx_hg.tiers.d2h_gather_bytes > 0
    assert ctx_hg.tiers.d2h_shard_bytes == 0

    # ...while the durable state is indistinguishable
    assert ctx_dev.pool.latest_manifest() == ctx_hg.pool.latest_manifest()


def test_cross_format_recovery_both_directions(host_devices_8, tmp_path):
    mesh = _mesh()
    tree = _shard(_tree(seed=3), mesh)
    expect = _np_tree(tree)
    templates = {"params": _np_tree(tree)}

    _commit(tmp_path / "dev", tree, mesh=mesh)
    _commit(tmp_path / "hg", tree, mesh=None)

    # device-written pool read back by a mesh-less stack
    objs, step, src = CXL0Config(path=str(tmp_path / "dev")).open() \
        .recover(templates)
    assert (step, src) == (1, "pool")
    _assert_trees_equal(objs["params"], expect)

    # host-gather-written pool read back by a mesh-configured stack
    objs, step, src = CXL0Config(path=str(tmp_path / "hg"),
                                 mesh=mesh).open().recover(templates)
    assert (step, src) == (1, "pool")
    _assert_trees_equal(objs["params"], expect)


def test_shard_count_derived_from_mesh(host_devices_8, tmp_path):
    # 8 x 1 MiB leaves: the byte term allows 8 pipelines, so the device
    # term decides — a 2x2 sub-mesh must size to ITS 4 devices, not the
    # process's 8
    mesh = _mesh((2, 2))
    tree = _shard(_tree(n_leaves=8, dim=512, seed=1), mesh)
    ctx = _commit(tmp_path / "m22", tree, mesh=mesh, n_shards=None)
    assert ctx.committer.n_shards == 4

    ctx_hg = _commit(tmp_path / "flat", _np_tree(tree), n_shards=None)
    assert ctx_hg.committer.n_shards == 8  # local-device heuristic


def test_per_device_pricing_logged(host_devices_8, tmp_path):
    mesh = _mesh()
    tree = _shard(_tree(seed=2), mesh)
    ctx = _commit(tmp_path / "priced", tree, mesh=mesh, n_shards=None,
                  topology="cxl20-switched-pool")
    decisions = ctx.placement.decisions_for("shards")
    assert decisions, "sharded commit under a topology must price shards"
    d = decisions[-1]
    assert ctx.committer.n_shards == d.choice
    assert d.costs[f"k{d.choice}"] == min(d.costs.values())
    # priced from real per-device loads, committed device-local
    assert ctx.tiers.d2h_gather_bytes == 0
