"""Attention: grouped-query attention + MLA, chunked online-softmax.

Layout choices (see DESIGN.md §5):
* q is produced natively grouped as (B, S, K, G, hd) with K = kv heads
  (sharded on the model axis) and G = q-heads-per-kv-head (unsharded), so
  GQA needs no repeat/reshape of a sharded head axis.
* ``chunked_attention`` streams KV in chunks with an online softmax
  (the pure-JAX twin of the Pallas flash kernel in ``repro.kernels.attention``)
  so prefill_32k / decode_500k never materialize (S, T) score matrices.
* MLA decode reuses the same routine in "absorbed" form: a single shared
  latent KV head of width kv_lora(+rope) — K=1, G=H.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamDesc
from repro.models.common import rope, rms_head_norm

NEG_INF = -1e9


# ---------------------------------------------------------------------------
# Core: chunked online-softmax attention
# ---------------------------------------------------------------------------

def chunked_attention(
    q: jax.Array,                     # (B, S, K, G, hd_k) float
    kv,                               # pytree; each leaf (B, T, ...) on axis 1
    expand_fn: Callable,              # kv_chunk -> (k (B,Tc,K,hd_k), v (B,Tc,K,hd_v))
    q_positions: jax.Array,           # (B, S) int32
    kv_base: int,                     # kv chunk c covers positions [kv_base + c*chunk, ...)
    *,
    causal: bool,
    chunk: int,
    unroll: bool = False,
    softmax_scale: Optional[float] = None,
    layer: Optional[jax.Array] = None,
) -> jax.Array:                       # (B, S, K, G, hd_v)
    """``layer``: the kv leaves are a group's layer stack (R, B, T, ...) and
    attention reads layer ``layer`` of it chunk by chunk, straight from the
    stack, with no copy of the layer's lanes."""
    B, S, K, G, hd_k = q.shape
    T = jax.tree_util.tree_leaves(kv)[0].shape[1 if layer is None else 2]
    chunk = min(chunk, T)
    T_valid = T
    if T % chunk and layer is not None:    # the padding copies the lanes
        kv = jax.tree_util.tree_map(lambda a: a[layer], kv)
        layer = None
    if T % chunk:                      # pad KV to a chunk multiple; padded
        pad = chunk - T % chunk        # positions are masked out below
        kv = jax.tree_util.tree_map(
            lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)),
            kv)
        T += pad
    n_chunks = T // chunk
    scale = softmax_scale if softmax_scale is not None else hd_k ** -0.5

    def read(a, start):                # chunk [start, start + chunk) of a
        if layer is None:
            return jax.lax.dynamic_slice_in_dim(a, start, chunk, 1)
        zeros = (0,) * (a.ndim - 3)
        return jax.lax.dynamic_slice(
            a, (layer, 0, start) + zeros,
            (1, a.shape[1], chunk) + a.shape[3:])[0]

    # probe hd_v
    k0, v0 = expand_fn(jax.tree_util.tree_map(lambda a: read(a, 0), kv))
    hd_v = v0.shape[-1]

    qf = q.astype(jnp.float32) * scale

    def body(carry, c):
        m, l, acc = carry
        kv_c = jax.tree_util.tree_map(lambda a: read(a, c * chunk), kv)
        k_c, v_c = expand_fn(kv_c)
        # scores: (B, K, G, S, Tc)
        s = jnp.einsum("bskgh,btkh->bkgst", qf, k_c.astype(jnp.float32))
        kv_pos = kv_base + c * chunk + jnp.arange(chunk, dtype=jnp.int32)
        if causal:
            mask = q_positions[:, None, :] >= kv_pos[None, :, None]  # (B,Tc,S)
            mask = jnp.transpose(mask, (0, 2, 1))[:, None, None]     # (B,1,1,S,Tc)
            s = jnp.where(mask, s, NEG_INF)
        if T_valid != T:               # mask the chunk-padding positions
            valid = (kv_pos < kv_base + T_valid)[None, None, None, None, :]
            s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkh->bskgh", p.astype(v_c.dtype), v_c)
        acc_new = acc * jnp.transpose(corr, (0, 3, 1, 2))[..., None] + \
            pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, S), jnp.float32)
    acc0 = jnp.zeros((B, S, K, G, hd_v), jnp.float32)
    # checkpoint the chunk body: without it the scan's BACKWARD stacks the
    # per-chunk (B,K,G,S,Tc) score tensors across all chunks (flash-attention
    # forward, dense-attention backward). With it the bwd recomputes each
    # chunk's scores — O(S·Tc) live, not O(S·T).
    body_ck = jax.checkpoint(body,
                             policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(
        body_ck, (m0, l0, acc0), jnp.arange(n_chunks, dtype=jnp.int32),
        unroll=n_chunks if unroll else 1)
    denom = jnp.maximum(jnp.transpose(l, (0, 3, 1, 2)), 1e-20)[..., None]
    return (acc / denom).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def gqa_descs(cfg: ModelConfig):
    d, K, hd = cfg.d_model, cfg.n_kv_heads, cfg.head_dim
    G = cfg.n_heads // cfg.n_kv_heads
    out = {
        "wq": ParamDesc((d, K, G, hd), ("embed", "kv_heads", "q_per_kv", "head_dim")),
        "wk": ParamDesc((d, K, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDesc((d, K, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDesc((K, G, hd, d), ("kv_heads", "q_per_kv", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        out["q_norm"] = ParamDesc((hd,), ("head_dim",), init="ones")
        out["k_norm"] = ParamDesc((hd,), ("head_dim",), init="ones")
    return out


class KVCache(NamedTuple):
    """Decode-time cache for one attention layer (possibly layer-stacked)."""
    k: jax.Array          # (B, T_max, K, hd)  |  MLA: ckv (B, T_max, kv_lora)
    v: jax.Array          # (B, T_max, K, hd)  |  MLA: k_rope (B, T_max, qk_rope)


def write_rows(buf: jax.Array, rows: jax.Array, pos: jax.Array,
               layer: Optional[jax.Array] = None) -> jax.Array:
    """Write each row's new token ``rows`` (B, 1, ...) into its own lane of
    ``buf`` (B, T, ...) at its own position ``pos`` (B,), in place.  With
    ``layer``, ``buf`` is a group's layer stack (R, B, T, ...) and only that
    layer's rows are written.  A position past the lane writes the lane's
    last row, as ``dynamic_update_slice`` clamps its start."""
    rows = rows[:, 0].astype(buf.dtype)
    lanes = jnp.arange(rows.shape[0])
    idx = (lanes, pos) if layer is None else (layer, lanes, pos)
    return buf.at[idx].set(rows, mode="clip", indices_are_sorted=True,
                           unique_indices=True)


def gqa_cache_desc(cfg: ModelConfig, batch: int, t_max: int):
    shape = (batch, t_max, cfg.n_kv_heads, cfg.head_dim)
    dt = cfg.cache_dtype or cfg.compute_dtype
    return KVCache(
        k=ParamDesc(shape, ("batch", "seq_kv", "kv_heads", "head_dim"), dtype=dt, init="zeros"),
        v=ParamDesc(shape, ("batch", "seq_kv", "kv_heads", "head_dim"), dtype=dt, init="zeros"))


def _project_qkv(cfg: ModelConfig, p, x, positions):
    q = jnp.einsum("bsd,dkgh->bskgh", x, p["wq"])
    k = jnp.einsum("bsd,dkh->bskh", x, p["wk"])
    v = jnp.einsum("bsd,dkh->bskh", x, p["wv"])
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"])
        k = rms_head_norm(k, p["k_norm"])
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array,
                *, causal: bool = True, unroll: bool = False,
                kv_override=None) -> jax.Array:
    """Full-sequence attention (train / prefill). x: (B, S, D).

    ``cfg.use_pallas`` routes the inner attention through the Pallas flash
    kernel (TPU; validated on CPU via interpret mode in tests/benchmarks) —
    the jnp ``chunked_attention`` path is the oracle twin and the default
    under pjit (where XLA's fused attention is used)."""
    q, k, v = _project_qkv(cfg, p, x, positions)
    if kv_override is not None:       # cross-attention (whisper decoder)
        k, v = kv_override
    if cfg.use_pallas:
        from repro.kernels.attention.ops import flash_attention
        out = flash_attention(q, k, v, causal=causal)
    else:
        out = chunked_attention(
            q, (k, v), lambda kv: kv, positions, 0,
            causal=causal, chunk=cfg.attn_chunk, unroll=unroll)
    return jnp.einsum("bskgh,kghd->bsd", out, p["wo"])


def _row_positions(x: jax.Array, pos: jax.Array) -> jax.Array:
    """(B,) int32 decode position of each row; a scalar is broadcast."""
    return jnp.broadcast_to(pos, (x.shape[0],)).astype(jnp.int32)


def gqa_decode(cfg: ModelConfig, p, x: jax.Array, cache: KVCache,
               pos: jax.Array, *, unroll: bool = False,
               layer: Optional[jax.Array] = None):
    """One-token decode. x: (B, 1, D); pos: (B,) int32 position of each row
    (a scalar is broadcast).  ``cache`` is this layer's (B, T, ...) KV or,
    with ``layer``, its group's stacked (R, B, T, ...) KV; either way only
    the new row of each lane is written (``write_rows``)."""
    pos = _row_positions(x, pos)
    positions = pos[:, None]
    q, k, v = _project_qkv(cfg, p, x, positions)
    new_cache = KVCache(k=write_rows(cache.k, k, pos, layer),
                        v=write_rows(cache.v, v, pos, layer))
    out = chunked_attention(
        q, (new_cache.k, new_cache.v), lambda kv: kv, positions, 0,
        causal=True, chunk=cfg.attn_chunk, unroll=unroll, layer=layer)
    return jnp.einsum("bskgh,kghd->bsd", out, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# MLA (deepseek-v2)
# ---------------------------------------------------------------------------

def mla_descs(cfg: ModelConfig):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "w_dq": ParamDesc((d, m.q_lora_rank), ("embed", "lora")),
        "q_norm": ParamDesc((m.q_lora_rank,), ("lora",), init="ones"),
        "w_uq": ParamDesc((m.q_lora_rank, H, qk), ("lora", "heads", "head_dim")),
        "w_dkv": ParamDesc((d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "lora")),
        "kv_norm": ParamDesc((m.kv_lora_rank,), ("lora",), init="ones"),
        "w_uk": ParamDesc((m.kv_lora_rank, H, m.qk_nope_head_dim),
                          ("lora", "heads", "head_dim")),
        "w_uv": ParamDesc((m.kv_lora_rank, H, m.v_head_dim),
                          ("lora", "heads", "head_dim")),
        "wo": ParamDesc((H, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


def mla_cache_desc(cfg: ModelConfig, batch: int, t_max: int):
    # the latent (lora) dim is TP-sharded: scores contract over it (psum)
    # and it is the only >1 dim besides batch/seq — see sharding."mla_lora"
    m = cfg.mla
    dt = cfg.cache_dtype or cfg.compute_dtype
    return KVCache(
        k=ParamDesc((batch, t_max, m.kv_lora_rank),
                    ("batch", "seq_kv", "mla_lora"),
                    dtype=dt, init="zeros"),
        v=ParamDesc((batch, t_max, m.qk_rope_head_dim),
                    ("batch", "seq_kv", "mla_lora"),
                    dtype=dt, init="zeros"))


def _mla_q(cfg, p, x, positions):
    m = cfg.mla
    cq = jnp.einsum("bsd,dr->bsr", x, p["w_dq"])
    cq = rms_head_norm(cq, p["q_norm"])
    q = jnp.einsum("bsr,rhq->bshq", cq, p["w_uq"])
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(cfg, p, x, positions):
    m = cfg.mla
    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    ckv = rms_head_norm(ckv_full[..., :m.kv_lora_rank], p["kv_norm"])
    k_rope = rope(ckv_full[..., None, m.kv_lora_rank:], positions, cfg.rope_theta)
    return ckv, k_rope[..., 0, :]


def mla_forward(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array,
                *, causal: bool = True, unroll: bool = False) -> jax.Array:
    """Expanded MLA (train / prefill): KV up-projected chunk-by-chunk."""
    m = cfg.mla
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    q = jnp.concatenate([q_nope, q_rope], -1)[:, :, :, None, :]  # (B,S,K=H,G=1,qk)
    ckv, k_rope = _mla_ckv(cfg, p, x, positions)

    def expand(kv_c):
        ckv_c, kr_c = kv_c
        k_nope = jnp.einsum("btr,rhq->bthq", ckv_c, p["w_uk"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_c[:, :, None, :], k_nope.shape[:3] + (m.qk_rope_head_dim,))], -1)
        v = jnp.einsum("btr,rhv->bthv", ckv_c, p["w_uv"])
        return k, v

    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    out = chunked_attention(
        q, (ckv, k_rope), expand, positions, 0,
        causal=causal, chunk=cfg.attn_chunk, unroll=unroll,
        softmax_scale=qk ** -0.5)                       # (B,S,H,1,v)
    return jnp.einsum("bshgv,hvd->bsd", out, p["wo"])


def mla_decode(cfg: ModelConfig, p, x: jax.Array, cache: KVCache,
               pos: jax.Array, *, unroll: bool = False,
               layer: Optional[jax.Array] = None):
    """Absorbed MLA decode: attention in latent space; K=1 shared head.
    ``pos`` and ``layer`` as in ``gqa_decode``."""
    m = cfg.mla
    pos = _row_positions(x, pos)
    positions = pos[:, None]
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    # absorb w_uk: q' = q_nope @ w_uk^T -> latent width
    q_lat = jnp.einsum("bshq,rhq->bshr", q_nope, p["w_uk"])
    q_cat = jnp.concatenate([q_lat, q_rope], -1)          # (B,1,H,r+rope)
    q_cat = q_cat[:, :, None, :, :]                        # (B,1,K=1,G=H,·)

    ckv, k_rope = _mla_ckv(cfg, p, x, positions)
    new_cache = KVCache(k=write_rows(cache.k, ckv, pos, layer),
                        v=write_rows(cache.v, k_rope, pos, layer))

    def expand(kv_c):
        ckv_c, kr_c = kv_c
        k = jnp.concatenate([ckv_c, kr_c], -1)[:, :, None, :]  # (B,Tc,1,r+rope)
        v = ckv_c[:, :, None, :]                               # (B,Tc,1,r)
        return k, v

    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    out_lat = chunked_attention(
        q_cat, (new_cache.k, new_cache.v), expand, positions, 0,
        causal=True, chunk=cfg.attn_chunk, unroll=unroll,
        softmax_scale=qk ** -0.5, layer=layer)             # (B,1,1,H,r)
    out = jnp.einsum("bskhr,rhv->bshv", out_lat, p["w_uv"])
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"]), new_cache
