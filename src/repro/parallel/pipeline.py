"""GPipe-style pipeline parallelism via shard_map + collective_permute.

An optional stage axis for depth-dominated models (jamba 72L, deepseek 60L)
when TP×FSDP alone leaves the mesh under-utilized.  The schedule is the
classic GPipe fill-drain: M microbatches stream through P stages; stage p
computes microbatch m at tick t = p + m, activations hop stages via
``jax.lax.ppermute``.  Bubble fraction = (P-1)/(M+P-1).

This is a self-contained reference implementation operating on a
per-stage ``apply_fn(stage_params, x) -> x`` — the launcher lowers it on a
('stage', 'data') mesh.  Tested at small scale (tests/test_pipeline.py);
it is NOT part of the 40-cell baseline (DESIGN.md §5).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe_forward(apply_fn: Callable, mesh, stage_axis: str = "stage",
                  n_microbatches: int = None):
    """Build a pipelined forward: (stage_params, x) -> y.

    ``stage_params``: pytree with leading stage dim sharded over the stage
    axis; ``x``: (M, mb, ...) microbatched input, replicated over stages.
    """
    P_stages = mesh.shape[stage_axis]

    def local_fn(stage_params, x_mb):
        # stage_params leaves: (1, ...) local slice -> squeeze
        params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        stage = jax.lax.axis_index(stage_axis)
        M = x_mb.shape[0]
        n_ticks = M + P_stages - 1

        def tick(carry, t):
            buf, out = carry          # buf: activation entering this stage
            m = t - stage             # microbatch this stage works on
            active = (m >= 0) & (m < M)
            x_in = jnp.where(active, buf, jnp.zeros_like(buf))
            y = apply_fn(params, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage collects finished microbatches
            out = jax.lax.cond(
                active & (stage == P_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(m, 0), 0),
                lambda o: o, out)
            # hop activations to the next stage
            y_next = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % P_stages) for i in range(P_stages)])
            # stage 0 ingests the next microbatch from x_mb
            nxt = t + 1 - 0
            feed = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t + 1, 0, M - 1), 0, keepdims=False)
            buf_new = jnp.where(stage == 0, feed, y_next)
            return (buf_new, out), None

        buf0 = jnp.where(stage == 0,
                         x_mb[0], jnp.zeros_like(x_mb[0]))
        out0 = jnp.zeros_like(x_mb)
        (buf, out), _ = jax.lax.scan(tick, (buf0, out0),
                                     jnp.arange(n_ticks))
        # only the last stage holds real outputs (every other stage's
        # ``out`` is still zeros), so a psum over the stage axis IS the
        # broadcast — ppermute can't fan one source out to all
        return jax.lax.psum(out, stage_axis)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False)


def pipeline_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
