"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  The single-pod mesh
is (data=16, model=16) = 256 chips (one TPU v5e pod); the multi-pod mesh
adds a leading pod axis: (pod=2, data=16, model=16) = 512 chips.  Data
parallelism (and FSDP weight sharding) runs over ('pod', 'data'); tensor/
expert/sequence parallelism over 'model'.

``make_mesh`` is the one place a mesh is built from a shape: every axis is
``AxisType.Auto``, the type ``with_sharding_constraint`` (used throughout
the model) accepts.  ``jax.make_mesh`` alone defaults to ``Explicit`` axes.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A mesh of ``shape`` named ``axes`` over the process's devices,
    every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int = 1, model: int = 1):
    """Tiny mesh over however many local devices exist (tests/examples)."""
    data = max(n_devices // model, 1)
    return make_mesh((data, model), ("data", "model"))


def parse_mesh(spec: str):
    """``"2x4"`` → a live (data=2, model=4) Mesh; ``"2x2x2"`` adds the
    leading pod axis.  The CLI surface of the mesh lane (scenario runner
    ``--mesh``, worker ``--mesh``): one parser, so every front-end names
    the axes the same way."""
    dims = tuple(int(d) for d in spec.lower().split("x"))
    if len(dims) == 2:
        return make_mesh(dims, ("data", "model"))
    if len(dims) == 3:
        return make_mesh(dims, ("pod", "data", "model"))
    raise ValueError(f"mesh spec {spec!r}: want DxM or PxDxM")


def mesh_device_sets(live):
    """Per-rank mesh-slice weights for ``train.elastic.partition_plan``:
    how many devices each live rank's ``rank_submesh`` slice owns.  Pure
    function of (device count, live set) — every process derives the same
    map, so partition plans stay coordination-free."""
    order = sorted(live)
    per = max(1, len(jax.devices()) // max(1, len(order)))
    return {r: per for r in order}


def rank_submesh(rank: int, live, *, axes=("data", "model")):
    """The mesh SLICE a cluster rank owns: the process's devices are split
    into contiguous equal runs over the sorted live ranks and this rank's
    run becomes its own (n, 1) Mesh.  Every rank derives the same layout
    from the same ``live`` set (pure function of public state — no
    coordination), and after a shrink the survivors re-derive slices over
    the REMAINING ranks, so the dead rank's devices are re-adopted rather
    than idled.  With fewer devices than ranks, slices degrade to single
    (possibly shared) devices — the 1-device CI fallback."""
    devs = jax.devices()
    order = sorted(live)
    if rank not in order:
        raise ValueError(f"rank {rank} not in live set {order}")
    per = max(1, len(devs) // max(1, len(order)))
    pos = order.index(rank)
    mine = devs[pos * per:(pos + 1) * per] or [devs[pos % len(devs)]]
    arr = np.array(mine).reshape(len(mine), 1)
    return jax.sharding.Mesh(arr, axes)      # Mesh axes default to Auto
