"""Flash attention (forward) as a Pallas TPU kernel.

Flash-attention-2-style online softmax with GQA support:

* grid = (B, H, S/block_q, T/block_k); the kv-block axis is the innermost,
  ``arbitrary`` (sequential) dimension — running max / denominator / output
  accumulator live in VMEM scratch and persist across kv blocks;
* BlockSpecs tile q/o to (1, 1, block_q, hd) and k/v to (1, 1, block_k, hd)
  VMEM windows; the kv index_map folds the GQA head mapping (kv head =
  q head // group) so no repeated/broadcast KV is ever materialized;
* causal masking compares absolute positions; fully-masked kv blocks are
  skipped with ``pl.when`` (≈2× for causal — only the lower triangle runs);
* block sizes default to (128, 128): 128 lanes match the MXU/VREG tiling,
  and (128 q × 128 kv × hd≤256) keeps the working set ≤ ~1.5 MB of VMEM,
  far under the ~16 MB/core budget, leaving room for double buffering.

The MXU contractions (q·kᵀ and p·v) run in fp32 accumulation via
``preferred_element_type``; softmax statistics are fp32 throughout.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, block_q: int, block_k: int,
               seq_q: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # causal: skip kv blocks entirely above the diagonal
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        mask = k_pos < seq_k                                  # kv padding
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                   # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        m_scr[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)                   # (bk, hd)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-20)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,                 # (B, H, Sq, hd)
    k: jax.Array,                 # (B, K, Sk, hd)  — K divides H (GQA)
    v: jax.Array,                 # (B, K, Sk, hd_v)
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:                   # (B, H, Sq, hd_v)
    B, H, Sq, hd = q.shape
    _, K, Sk, hd_v = v.shape
    assert H % K == 0, (H, K)
    group = H // K
    scale = hd ** -0.5 if scale is None else scale

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    # pad sequence dims to block multiples (masked out in-kernel)
    Sq_p = math.ceil(Sq / block_q) * block_q
    Sk_p = math.ceil(Sk / block_k) * block_k
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sq_p - Sq), (0, 0)))
    if Sk_p != Sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))

    grid = (B, H, Sq_p // block_q, Sk_p // block_k)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=Sq, seq_k=Sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd_v),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd_v),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max
            pltpu.VMEM((block_q,), jnp.float32),      # running denom
            pltpu.VMEM((block_q, hd_v), jnp.float32),  # output acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
