"""RWKV-6 WKV recurrence as a Pallas TPU kernel (chunked closed form).

Grid = (B, H, T/block_t); the time axis is the innermost ``arbitrary``
(sequential) dimension, carrying the (n × n) per-head state in VMEM
scratch across chunks.  Within a chunk of Q tokens the recurrence is
evaluated in closed form (FLA-style):

    y_t = (r_t · decay_to_t) Sᵀ + Σ_{s<t} (r_t · k_s · exp(logP_{t-1} −
          logP_s)) v_s + (r_t · u · k_t) v_t
    S' = exp(logP_Q) ⊙ S + Σ_s (k_s · exp(logP_Q − logP_s)) vᵀ_s

All cross-token terms are matmuls/reductions over (Q, Q, n) tensors with
exponents ≤ 0 (numerically stable: we always exponentiate *differences*
clamped by causality, never exp(+cumsum)).  For block_t = 64 and head_dim
n = 64 the (Q, Q, n) intermediate is 1 MB fp32 — well inside VMEM; r/k/v/w
chunks are 4·Q·n fp32 = 64 KB.

VMEM working set ≈ 1.3 MB per (batch, head) program: fits with double
buffering.  The MXU sees the (Q,n)@(n,n) and (Q,Q)@(Q,n) contractions;
the (Q,Q,n) mask-exp is VPU work — this kernel is the fusion the pure-JAX
path cannot express without materializing (B,T,H,n,n) HBM traffic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                 y_ref, sout_ref, S_scr, *, block_t: int, seq_t: int):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        S_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)            # (Q, n)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)          # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)               # (n,)
    Q, n = r.shape

    # zero padded positions (identity decay, zero kv contribution)
    t_pos = ti * block_t + jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    valid = t_pos < seq_t                           # (Q, 1)
    lw = jnp.where(valid, lw, 0.0)
    k = jnp.where(valid, k, 0.0)

    logP = jnp.cumsum(lw, axis=0)                   # inclusive  (Q, n)
    logPm1 = logP - lw                              # exclusive

    S = S_scr[...]                                  # (n, n) key x value
    # inter-chunk: r decayed against the carried state
    y_inter = jax.lax.dot_general(r * jnp.exp(logPm1), S,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # intra-chunk: A[t, s] = sum_i r[t,i] k[s,i] exp(logPm1[t,i] - logP[s,i])
    expo = logPm1[:, None, :] - logP[None, :, :]    # (Q, Q, n), <= 0 for s<t
    causal_lt = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
                 > jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    expo = jnp.where(causal_lt[:, :, None], expo, -jnp.inf)
    A = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(expo), axis=2)
    diag = jnp.sum(r * (u[None, :] * k), axis=1)    # bonus term
    y = y_inter + jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32) \
        + diag[:, None] * v
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state to chunk end: S' = exp(logP_Q) * S + (k * exp(logP_Q - logP))^T v
    logP_last = logP[-1]                            # (n,)
    k_tilde = k * jnp.exp(logP_last[None, :] - logP)
    S_new = jnp.exp(logP_last)[:, None] * S + jax.lax.dot_general(
        k_tilde, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    S_scr[...] = S_new

    @pl.when(ti == nt - 1)
    def _fin():
        sout_ref[0, 0] = S_new


def wkv6_kernel(r, k, v, logw, u, S0, *, block_t: int = 64,
                interpret: bool = False):
    """r/k/v/logw: (B, T, H, n); u: (H, n); S0: (B, H, n, n).
    Returns y (B, T, H, n) in r.dtype and final state (B, H, n, n) fp32."""
    B, T, H, n = r.shape
    block_t = min(block_t, T)
    T_p = math.ceil(T / block_t) * block_t
    if T_p != T:
        pad = ((0, 0), (0, T_p - T), (0, 0), (0, 0))
        r, k, v, logw = (jnp.pad(a, pad) for a in (r, k, v, logw))

    # layout: (B, H, T, n) blocks
    rt, kt, vt, lwt = (jnp.transpose(a, (0, 2, 1, 3))
                       for a in (r, k, v, logw))

    grid = (B, H, T_p // block_t)
    kern = functools.partial(_wkv6_kernel, block_t=block_t, seq_t=T)
    y, s_out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_t, n), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, block_t, n), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, block_t, n), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, block_t, n), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, n), lambda b, h, t: (h, 0)),
            pl.BlockSpec((1, 1, n, n), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_t, n), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, n, n), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T_p, n), r.dtype),
            jax.ShapeDtypeStruct((B, H, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rt, kt, vt, lwt, u, S0)
    return jnp.transpose(y, (0, 2, 1, 3))[:, :T], s_out
