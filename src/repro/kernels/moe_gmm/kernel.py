"""Grouped (per-expert) matmul as a Pallas TPU kernel.

The MoE hot loop after dispatch: every expert e multiplies its capacity
buffer (C, D) by its weights (D, F).  Grid = (E, C/bc, F/bf, D/bd) with the
contraction axis innermost (``arbitrary``) accumulating into fp32 VMEM
scratch — the classic MXU-tiled matmul, batched over experts by the grid's
leading (parallel) dimension.

Block defaults (bc, bf, bd) = (128, 128, 512): MXU-aligned (multiples of
128 on both matmul dims), working set bc·bd + bd·bf + bc·bf fp32 ≈ 640 KB —
small enough that Mosaic can double-buffer the weight stream.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_scr):
    di = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]                    # (bc, bd)
    w = w_ref[0]                    # (bd, bf)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _fin():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def grouped_matmul_kernel(x, w, *, block_c: int = 128, block_f: int = 128,
                          block_d: int = 512, interpret: bool = False):
    """x: (E, C, D) @ w: (E, D, F) -> (E, C, F)."""
    E, C, D = x.shape
    _, _, F = w.shape
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    block_d = min(block_d, D)

    C_p = math.ceil(C / block_c) * block_c
    F_p = math.ceil(F / block_f) * block_f
    D_p = math.ceil(D / block_d) * block_d
    if C_p != C or D_p != D:
        x = jnp.pad(x, ((0, 0), (0, C_p - C), (0, D_p - D)))
    if D_p != D or F_p != F:
        w = jnp.pad(w, ((0, 0), (0, D_p - D), (0, F_p - F)))

    grid = (E, C_p // block_c, F_p // block_f, D_p // block_d)
    out = pl.pallas_call(
        _gmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda e, c, f, d: (e, c, d)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda e, c, f, d: (e, d, f)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, c, f, d: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, C_p, F_p), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
    return out[:, :C, :F]
