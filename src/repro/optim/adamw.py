"""Sharded AdamW with dtype-configurable moments.

Moments inherit each parameter's sharding (same tree structure, same logical
axes), so optimizer state is fully FSDP/TP-sharded for free.  ≥100 B-param
configs keep moments in bf16 to fit 16 GB/chip (``cfg.moment_dtype`` —
DESIGN.md §5); the update math runs in fp32 regardless.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array            # () int32
    mu: Any                    # first moment, tree like params
    nu: Any                    # second moment, tree like params


def adamw_init(params, moment_dtype: str = "float32") -> AdamWState:
    dt = jnp.dtype(moment_dtype)
    # zeros_like keeps each param's sharding: the moments start spread
    # over the mesh like the params, not gathered on one device
    zeros = lambda p: jnp.zeros_like(p, dtype=dt)
    return AdamWState(step=jnp.zeros((), jnp.int32),
                      mu=jax.tree_util.tree_map(zeros, params),
                      nu=jax.tree_util.tree_map(zeros, params))


def adamw_abstract(params_abstract, moment_dtype: str = "float32"):
    """ShapeDtypeStruct twin of adamw_init (dry-run; no allocation)."""
    dt = jnp.dtype(moment_dtype)
    z = lambda p: jax.ShapeDtypeStruct(p.shape, dt)
    return AdamWState(step=jax.ShapeDtypeStruct((), jnp.int32),
                      mu=jax.tree_util.tree_map(z, params_abstract),
                      nu=jax.tree_util.tree_map(z, params_abstract))


def adamw_update(params, grads, state: AdamWState, lr,
                 *, b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.1,
                 grad_clip: Optional[float] = 1.0):
    """One AdamW step. ``lr`` may be a scalar array (from a schedule)."""
    step = state.step + 1
    if grad_clip is not None:
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-9))
    else:
        gnorm = jnp.zeros((), jnp.float32)
        scale = jnp.ones((), jnp.float32)

    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32) * scale
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * gf
        v_new = b2 * v.astype(jnp.float32) + (1 - b2) * gf * gf
        mhat = m_new / c1
        vhat = v_new / c2
        delta = mhat / (jnp.sqrt(vhat) + eps)
        if p.ndim >= 2:                      # no decay on norms/biases
            delta = delta + weight_decay * p.astype(jnp.float32)
        p_new = p.astype(jnp.float32) - lr * delta
        return (p_new.astype(p.dtype), m_new.astype(m.dtype),
                v_new.astype(v.dtype))

    out = jax.tree_util.tree_map(upd, params, grads, state.mu, state.nu)
    p_new = jax.tree_util.tree_map(lambda t: t[0], out,
                                   is_leaf=lambda t: isinstance(t, tuple))
    m_new = jax.tree_util.tree_map(lambda t: t[1], out,
                                   is_leaf=lambda t: isinstance(t, tuple))
    v_new = jax.tree_util.tree_map(lambda t: t[2], out,
                                   is_leaf=lambda t: isinstance(t, tuple))
    return p_new, AdamWState(step=step, mu=m_new, nu=v_new), gnorm
