"""Mixture-of-Experts with shard_map expert parallelism.

Token-choice top-k routing with capacity-factor dropping (GShard-style),
implemented scatter-based (no (T, E, C) one-hot tensors):

* ``mode="a2a"`` (train / prefill): tokens are split over the model axis
  inside ``shard_map``; each device routes its token slice locally, packs a
  per-expert capacity buffer (E, C, D) via local scatter, exchanges it with
  ``all_to_all`` over the model axis (real EP dispatch), runs its local
  experts as one batched matmul, and reverses the exchange.
* ``mode="psum"`` (decode): routing is computed redundantly on every model
  shard (seq_len is tiny), each shard computes only its local experts'
  contribution and the combine is a single ``psum`` — no all_to_all on the
  latency-critical decode path.
* ``mode="dense"``: pure-jnp fallback (no mesh needed) — the oracle used by
  tests and the smoke configs.

Shared experts (deepseek-v2) are folded into one wider dense MLP, which is
mathematically identical (hidden-dim concatenation commutes with the
per-channel activation).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.params import ParamDesc
from repro.models.common import mlp_descs, apply_mlp


def moe_descs(cfg: ModelConfig):
    m = cfg.moe
    d, E, ff = cfg.d_model, m.n_experts, m.d_ff_expert
    out = {
        "router": ParamDesc((d, E), ("embed_nofsdp", None), dtype="float32",
                            init_scale=0.02),
        "w_up": ParamDesc((E, d, ff), ("expert", "embed", "mlp_e")),
        "w_down": ParamDesc((E, ff, d), ("expert", "mlp_e", "embed")),
    }
    if cfg.glu:
        out["w_gate"] = ParamDesc((E, d, ff), ("expert", "embed", "mlp_e"))
    if m.n_shared:
        out["shared"] = mlp_descs(cfg, d_ff=m.n_shared * ff)
    return out


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = math.ceil(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, ((c + 7) // 8) * 8)


def _route(cfg: ModelConfig, router_w, x_flat):
    """x_flat: (T, D) -> (weights (T,k), idx (T,k) int32, aux_loss scalar)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    # switch-style load balance loss
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=0)
    aux = m.n_experts * jnp.sum(frac_tokens * frac_probs) * m.top_k
    return w.astype(x_flat.dtype), idx.astype(jnp.int32), aux


def _pack(cfg: ModelConfig, x_flat, idx, capacity):
    """Scatter tokens into (E, C, D) capacity buffers. Returns (buf, dest)."""
    m = cfg.moe
    T, D = x_flat.shape
    flat_e = idx.reshape(-1)                                     # (T*k,)
    onehot = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                          # pos within expert
    pos = jnp.take_along_axis(pos, flat_e[:, None], 1)[:, 0]      # (T*k,)
    keep = pos < capacity
    dest = jnp.where(keep, flat_e * capacity + pos, m.n_experts * capacity)
    src = jnp.repeat(jnp.arange(T, dtype=jnp.int32), m.top_k)
    buf = jnp.zeros((m.n_experts * capacity, D), x_flat.dtype)
    buf = buf.at[dest].add(x_flat[src], mode="drop")
    return buf.reshape(m.n_experts, capacity, D), dest.reshape(T, m.top_k)


def _expert_mlp(cfg: ModelConfig, p_up, p_gate, p_down, buf):
    """buf: (E?, C, D) batched expert matmuls."""
    h = jnp.einsum("ecd,edf->ecf", buf, p_up)
    if p_gate is not None:
        g = jnp.einsum("ecd,edf->ecf", buf, p_gate)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    return jnp.einsum("ecf,efd->ecd", h, p_down)


def _combine(out_buf_flat, dest, weights):
    """Gather per-token expert outputs. out_buf_flat: (E*C(+1), D)."""
    picked = out_buf_flat[dest]                                  # (T, k, D)
    return jnp.einsum("tkd,tk->td", picked, weights.astype(picked.dtype))


# ---------------------------------------------------------------------------
# dense (oracle) path
# ---------------------------------------------------------------------------

def _moe_dense(cfg: ModelConfig, p, x_flat):
    cap = _capacity(x_flat.shape[0], cfg)
    w, idx, aux = _route(cfg, p["router"], x_flat)
    buf, dest = _pack(cfg, x_flat, idx, cap)
    out_buf = _expert_mlp(cfg, p["w_up"], p.get("w_gate"), p["w_down"], buf)
    out_flat = jnp.concatenate(
        [out_buf.reshape(-1, x_flat.shape[1]),
         jnp.zeros((1, x_flat.shape[1]), out_buf.dtype)], 0)
    return _combine(out_flat, dest, w), aux


# ---------------------------------------------------------------------------
# shard_map EP paths
# ---------------------------------------------------------------------------

def _gather_fsdp(ws, fsdp_axes, D):
    """All-gather FSDP-sharded expert weights over the data axes."""
    if ws["w_up"].shape[1] != D:
        ws = dict(ws)
        ws["w_up"] = jax.lax.all_gather(ws["w_up"], fsdp_axes, axis=1, tiled=True)
        if "w_gate" in ws:
            ws["w_gate"] = jax.lax.all_gather(ws["w_gate"], fsdp_axes, axis=1,
                                              tiled=True)
        ws["w_down"] = jax.lax.all_gather(ws["w_down"], fsdp_axes, axis=2,
                                          tiled=True)
    return ws


def _moe_local_a2a(cfg, tp_axis, dp_axes, fsdp_axes, x_loc, router_w, ws):
    """Local body under shard_map: x_loc (B_l, S_l, D) token slice."""
    B_l, S_l, D = x_loc.shape
    x_flat = x_loc.reshape(-1, D)
    cap = _capacity(x_flat.shape[0], cfg)
    w, idx, aux = _route(cfg, router_w, x_flat)
    buf, dest = _pack(cfg, x_flat, idx, cap)                     # (E, C, D)
    # dispatch: every device sends expert-group j to device j
    buf = jax.lax.all_to_all(buf, tp_axis, split_axis=0, concat_axis=1,
                             tiled=True)                          # (E_l, tp*C, D)
    ws = _gather_fsdp(ws, fsdp_axes, D)
    out = _expert_mlp(cfg, ws["w_up"], ws.get("w_gate"), ws["w_down"], buf)
    out = jax.lax.all_to_all(out, tp_axis, split_axis=1, concat_axis=0,
                             tiled=True)                          # (E, C, D)
    out_flat = jnp.concatenate([out.reshape(-1, D),
                                jnp.zeros((1, D), out.dtype)], 0)
    y = _combine(out_flat, dest, w).reshape(B_l, S_l, D)
    aux = jax.lax.pmean(aux, (*dp_axes, tp_axis))
    return y, aux


def _moe_local_psum(cfg, tp_axis, dp_axes, fsdp_axes, x_loc, router_w, ws):
    """Decode path: replicated routing, local experts only, psum combine."""
    m = cfg.moe
    B_l, S_l, D = x_loc.shape
    tp = jax.lax.axis_size(tp_axis)
    e_loc = m.n_experts // tp
    my = jax.lax.axis_index(tp_axis)
    x_flat = x_loc.reshape(-1, D)
    cap = _capacity(x_flat.shape[0], cfg)
    w, idx, aux = _route(cfg, router_w, x_flat)
    buf, dest = _pack(cfg, x_flat, idx, cap)                      # (E, C, D)
    buf_loc = jax.lax.dynamic_slice_in_dim(buf, my * e_loc, e_loc, 0)
    ws = _gather_fsdp(ws, fsdp_axes, D)
    out_loc = _expert_mlp(cfg, ws["w_up"], ws.get("w_gate"), ws["w_down"],
                          buf_loc)                                # (E_l, C, D)
    # place local outputs into the global (E*C+1, D) flat buffer, rest zero
    out_flat = jnp.zeros((m.n_experts * cap + 1, D), out_loc.dtype)
    out_flat = jax.lax.dynamic_update_slice_in_dim(
        out_flat, out_loc.reshape(-1, D), my * e_loc * cap, 0)
    y = _combine(out_flat, dest, w)
    y = jax.lax.psum(y, tp_axis)
    aux = jax.lax.pmean(aux, (*dp_axes, tp_axis))
    return y.reshape(B_l, S_l, D), aux


def moe_forward(cfg: ModelConfig, p, x: jax.Array, *, parallel=None,
                mode: str = "a2a"):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar).

    ``parallel``: a ``repro.parallel.sharding.ParallelCtx`` or None (dense).
    """
    m = cfg.moe
    if m.n_shared:
        shared = apply_mlp(cfg, p["shared"], x)
    else:
        shared = 0.0

    use_ep = (parallel is not None and parallel.ep
              and m.n_experts % parallel.tp_size == 0
              and x.shape[0] % parallel.dp_size == 0
              and (mode == "psum" or x.shape[1] % parallel.tp_size == 0))
    if not use_ep:
        B, S, D = x.shape
        y, aux = _moe_dense(cfg, p, x.reshape(-1, D))
        return y.reshape(B, S, D) + shared, aux

    dp, tp, fsdp = parallel.dp_axes, parallel.tp_axis, parallel.fsdp_axes
    ws = {k: p[k] for k in ("w_up", "w_gate", "w_down") if k in p}
    D = x.shape[-1]
    fs = fsdp if (fsdp and D % parallel.fsdp_size == 0) else ()
    f = (fs if len(fs) > 1 else fs[0]) if fs else None
    w_spec = {k: (P(tp, f, None) if k != "w_down" else P(tp, None, f))
              for k in ws}
    body = _moe_local_a2a if mode == "a2a" else _moe_local_psum
    x_spec = P(dp, tp, None) if mode == "a2a" else P(dp, None, None)
    fn = jax.shard_map(
        partial(body, cfg, tp, dp, fsdp),
        mesh=parallel.mesh,
        in_specs=(x_spec, P(None, None), w_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    y, aux = fn(x, p["router"], ws)
    return y + shared, aux
