"""Mamba (S6) selective scan as a Pallas TPU kernel.

Grid = (B, I/block_i, S/block_s); time is the innermost ``arbitrary``
dimension carrying h (block_i, N) in VMEM scratch.  Inside a time chunk
the affine recurrence h_t = dA_t h + dBu_t is evaluated by an in-kernel
``fori_loop`` over the chunk — each step is a fused (block_i, N) VPU
multiply-add plus a readout contraction against C_t, with zero HBM traffic
between steps (h never leaves VMEM).  This is the TPU adaptation of the
paper('s class of) GPU scan kernels: instead of warp-level prefix scans we
exploit the VPU's (8, 128) lanes across the state dimensions and keep the
sequential dependency in the grid's innermost loop.

Numerical notes: the log-cumsum closed form used by the pure-JAX path is
avoided here because exp(+cumsum) overflows for long chunks; the direct
recurrence is unconditionally stable (dA ∈ (0, 1)).

VMEM budget per program: dA/dBu chunks 2·block_s·block_i·N fp32
(= 4 MB at block_s=64, block_i=128, N=64), h (block_i, N), C (block_s, N).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(dA_ref, dBu_ref, C_ref, h0_ref, y_ref, hout_ref, h_scr, *,
                 block_s: int, seq_s: int):
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    dA = dA_ref[0].astype(jnp.float32)      # (bs, bi, N)
    dBu = dBu_ref[0].astype(jnp.float32)    # (bs, bi, N)
    Cc = C_ref[0].astype(jnp.float32)       # (bs, N)
    bs = dA.shape[0]

    # padded positions: identity transition (dA=1, dBu=0) keeps h exact
    t_pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, (bs, 1, 1), 0)
    valid = t_pos < seq_s
    dA = jnp.where(valid, dA, 1.0)
    dBu = jnp.where(valid, dBu, 0.0)

    def step(t, carry):
        h, ys = carry
        h = dA[t] * h + dBu[t]                          # (bi, N)
        y_t = jnp.sum(h * Cc[t][None, :], axis=1)       # (bi,)
        ys = jax.lax.dynamic_update_index_in_dim(ys, y_t, t, 0)
        return h, ys

    ys0 = jnp.zeros((bs, dA.shape[1]), jnp.float32)
    h, ys = jax.lax.fori_loop(0, bs, step, (h_scr[...], ys0))
    h_scr[...] = h
    y_ref[0] = ys.astype(y_ref.dtype)

    @pl.when(si == ns - 1)
    def _fin():
        hout_ref[0] = h


def selective_scan_kernel(dA, dBu, C, h0, *, block_s: int = 64,
                          block_i: int = 128, interpret: bool = False):
    """dA/dBu: (B, S, I, N); C: (B, S, N); h0: (B, I, N).
    Returns y (B, S, I) fp32 and final h (B, I, N) fp32."""
    B, S, I, N = dA.shape
    block_s = min(block_s, S)
    block_i = min(block_i, I)
    S_p = math.ceil(S / block_s) * block_s
    if S_p != S:
        pad4 = ((0, 0), (0, S_p - S), (0, 0), (0, 0))
        dA = jnp.pad(dA, pad4)
        dBu = jnp.pad(dBu, pad4)
        C = jnp.pad(C, ((0, 0), (0, S_p - S), (0, 0)))
    assert I % block_i == 0, (I, block_i)

    grid = (B, I // block_i, S_p // block_s)
    kern = functools.partial(_scan_kernel, block_s=block_s, seq_s=S)
    y, h_out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_i, N),
                         lambda b, i, s: (b, s, i, 0)),
            pl.BlockSpec((1, block_s, block_i, N),
                         lambda b, i, s: (b, s, i, 0)),
            pl.BlockSpec((1, block_s, N), lambda b, i, s: (b, s, 0)),
            pl.BlockSpec((1, block_i, N), lambda b, i, s: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_i), lambda b, i, s: (b, s, i)),
            pl.BlockSpec((1, block_i, N), lambda b, i, s: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S_p, I), jnp.float32),
            jax.ShapeDtypeStruct((B, I, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_i, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dA, dBu, C, h0)
    return y[:, :S], h_out
