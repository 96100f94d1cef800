"""Decoder-only LM assembly: params, forward, loss, prefill/decode.

Layer stacking
--------------
Architectures interleave heterogeneous blocks (jamba: 1 attention per 8
layers, MoE on odd layers; deepseek-v2: first layer dense).  We decompose
the layer sequence into a *prefix* of singleton groups plus one *periodic*
group: within a group of period P repeated R times, params of each of the P
positions are stacked with a leading (R,) dim and the group runs as a
``jax.lax.scan`` over R super-blocks (small HLO, fast 512-device compiles).
Remat (``cfg.remat``) wraps the super-block body.

Sharding (DESIGN.md §5)
-----------------------
Residual activations between blocks are constrained to
``P(dp, tp, None)`` — batch over the data axes, *sequence over the model
axis* (Megatron-style sequence parallelism) so that per-device saved
activations under full remat stay ~B·S·D/(dp·tp).  Inside a block GSPMD
re-shards to head-/ff-parallel layouts driven by the parameter shardings.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.params import ParamDesc, tree_map_descs
from repro.models import common, attention, moe as moe_mod, mamba as mamba_mod
from repro.models import rwkv as rwkv_mod
from repro.models.attention import KVCache
from repro.models.mamba import MambaCache
from repro.models.rwkv import RWKVCache


# ---------------------------------------------------------------------------
# Layer grouping
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerGroup:
    kinds: Tuple[Tuple[str, str], ...]    # per position: (mixer, mlp)
    n_repeats: int


def layer_kinds(cfg: ModelConfig) -> List[Tuple[str, str]]:
    return [(cfg.layer_kind(l), cfg.mlp_kind(l)) for l in range(cfg.n_layers)]


def layer_groups(cfg: ModelConfig) -> List[LayerGroup]:
    """(prefix of singletons) + one periodic group covering the rest."""
    kinds = layer_kinds(cfg)
    L = len(kinds)
    for prefix in range(0, L):
        rest = kinds[prefix:]
        n = len(rest)
        for p in range(1, min(16, n) + 1):
            if n % p:
                continue
            if all(rest[i] == rest[i % p] for i in range(n)):
                groups = [LayerGroup((kinds[i],), 1) for i in range(prefix)]
                groups.append(LayerGroup(tuple(rest[:p]), n // p))
                return groups
    return [LayerGroup((k,), 1) for k in kinds]


# ---------------------------------------------------------------------------
# Param descriptors
# ---------------------------------------------------------------------------

def _mixer_descs(cfg: ModelConfig, mixer: str):
    if mixer == "attn":
        att = attention.mla_descs(cfg) if cfg.mla else attention.gqa_descs(cfg)
        return {"norm1": common.norm_descs(cfg), "attn": att}
    if mixer == "mamba":
        return {"norm1": common.norm_descs(cfg),
                "mamba": mamba_mod.mamba_descs(cfg)}
    if mixer == "rwkv":
        # rwkv_descs includes both time-mix and channel-mix params
        return {"norm1": common.norm_descs(cfg),
                "norm2": common.norm_descs(cfg),
                "rwkv": rwkv_mod.rwkv_descs(cfg)}
    raise ValueError(mixer)


def _mlp_descs(cfg: ModelConfig, mlp: str):
    if mlp == "dense":
        return {"norm2": common.norm_descs(cfg),
                "mlp": common.mlp_descs(cfg)}
    if mlp == "moe":
        return {"norm2": common.norm_descs(cfg),
                "moe": moe_mod.moe_descs(cfg)}
    raise ValueError(mlp)


def block_descs(cfg: ModelConfig, kind: Tuple[str, str]):
    mixer, mlp = kind
    d = dict(_mixer_descs(cfg, mixer))
    if mixer != "rwkv":                   # rwkv has its own channel mix
        d.update(_mlp_descs(cfg, mlp))
    return d


def _stack(descs, n: int):
    if n == 1:
        return descs
    return tree_map_descs(
        lambda p: ParamDesc((n,) + p.shape, ("layers",) + p.logical,
                            dtype=p.dtype, init=p.init,
                            init_scale=p.init_scale), descs)


def model_descs(cfg: ModelConfig) -> Dict[str, Any]:
    out: Dict[str, Any] = {"embed": common.embed_descs(cfg)}
    out["groups"] = [
        {"blocks": [_stack(block_descs(cfg, kind), g.n_repeats)
                    for kind in g.kinds]}
        for g in layer_groups(cfg)]
    out["final_norm"] = common.norm_descs(cfg)
    return out


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _block_cache_desc(cfg: ModelConfig, kind: Tuple[str, str], batch: int,
                      t_max: int):
    mixer, _ = kind
    if mixer == "attn":
        return (attention.mla_cache_desc(cfg, batch, t_max) if cfg.mla
                else attention.gqa_cache_desc(cfg, batch, t_max))
    if mixer == "mamba":
        return mamba_mod.mamba_cache_desc(cfg, batch)
    if mixer == "rwkv":
        return rwkv_mod.rwkv_cache_desc(cfg, batch)
    raise ValueError(mixer)


def cache_descs(cfg: ModelConfig, batch: int, t_max: int):
    return [
        {"blocks": [_stack(_block_cache_desc(cfg, kind, batch, t_max),
                           g.n_repeats)
                    for kind in g.kinds]}
        for g in layer_groups(cfg)]


# ---------------------------------------------------------------------------
# Block forward
# ---------------------------------------------------------------------------

def _resid_spec(ctx, seq_shardable: bool) -> Optional[P]:
    if ctx is None or ctx.mesh is None:
        return None
    dp = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    if ctx.strategy == "dp_only":          # no TP -> no sequence sharding
        return P(dp, None, None)
    return P(dp, ctx.tp_axis if seq_shardable else None, None)


def _constrain(x, spec: Optional[P], ctx):
    if spec is None or ctx is None or ctx.mesh is None:
        return x
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def _take_layer(cache, layer):
    """One layer's cache out of its group's stack (``layer`` None: the
    cache is not stacked)."""
    if layer is None:
        return cache
    return jax.tree_util.tree_map(lambda a: a[layer], cache)


def _put_layer(stack, layer, new):
    """Rewrite one layer of the stack whole, in place: recurrent state has
    no token axis, and the token rewrites all of it."""
    if layer is None:
        return new
    return jax.tree_util.tree_map(lambda s, n: s.at[layer].set(n), stack, new)


def block_forward(cfg: ModelConfig, kind: Tuple[str, str], p, x, positions,
                  *, ctx=None, cache=None, pos=None, decode: bool = False,
                  moe_mode: str = "a2a", unroll: bool = False, layer=None):
    """One transformer block. Returns (x, new_cache, aux_loss).

    At decode ``pos`` is (B,), each row's position, and with ``layer`` the
    cache is the group's layer stack, carried through the layer loop and
    written in place: attention K/V (leaves with a ``seq_kv`` axis) get
    each row's new token at its position, recurrent state is rewritten
    whole for this layer."""
    mixer, mlp = kind
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache

    if mixer == "rwkv":
        h = common.apply_norm(cfg, p["norm1"], x)
        tm_cache = _take_layer(cache, layer)
        y, (last_tm, S_last) = rwkv_mod.rwkv_time_mix(
            cfg, p["rwkv"], h, tm_cache, unroll=unroll)
        x = x + y
        h2 = common.apply_norm(cfg, p["norm2"], x)
        y2, last_cm = rwkv_mod.rwkv_channel_mix(
            cfg, p["rwkv"], h2, tm_cache)
        x = x + y2
        if cache is not None:
            new_cache = _put_layer(cache, layer, RWKVCache(
                last_tm=last_tm.astype(tm_cache.last_tm.dtype),
                last_cm=last_cm.astype(tm_cache.last_cm.dtype),
                S=S_last))
        return x, new_cache, aux

    # -- sequence mixer -----------------------------------------------------
    h = common.apply_norm(cfg, p["norm1"], x)
    # Megatron-SP: un-shard the SEQUENCE here, at residual width — before
    # the q/kv (MLA: 4.8x wider) or mamba in_proj (4x wider) projections.
    # Left to GSPMD, the gather lands on the post-projection tensors in
    # fp32 (~20x the bytes on deepseek-v2; see EXPERIMENTS §Perf H3).
    # CONDITION (H3-i1 refinement): only when attention is genuinely
    # head-sharded. For kv<tp archs the fallback shards head_dim, scores
    # need a psum over tp, and gathering the sequence first makes each
    # psum tp-times larger (internlm2: 7x worse collectives — measured).
    heads_shardable = (
        mixer == "mamba"
        or (cfg.mla is not None and cfg.n_heads % max(ctx.tp_size, 1) == 0
            if ctx is not None else False)
        or (cfg.mla is None and ctx is not None
            and cfg.n_kv_heads % max(ctx.tp_size, 1) == 0))
    if (ctx is not None and ctx.mesh is not None and heads_shardable
            and getattr(ctx, "strategy", "tp") == "tp" and not decode
            and x.shape[1] % max(ctx.tp_size, 1) == 0):
        dp = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
        h = _constrain(h, P(dp, None, None), ctx)
    if mixer == "attn":
        if decode:
            dec = attention.mla_decode if cfg.mla else attention.gqa_decode
            y, new_cache = dec(cfg, p["attn"], h, cache, pos, unroll=unroll,
                               layer=layer)
        else:
            if cfg.mla:
                y = attention.mla_forward(cfg, p["attn"], h, positions,
                                          unroll=unroll)
            else:
                y = attention.gqa_forward(cfg, p["attn"], h, positions,
                                          unroll=unroll)
            if cache is not None:       # prefill: write the cache
                q, k, v = (None, None, None)
                if cfg.mla:
                    ckv, k_rope = attention._mla_ckv(cfg, p["attn"], h,
                                                     positions)
                    new_cache = KVCache(
                        k=_update_prefix(cache.k, ckv),
                        v=_update_prefix(cache.v, k_rope))
                else:
                    _, k, v = attention._project_qkv(cfg, p["attn"], h,
                                                     positions)
                    new_cache = KVCache(k=_update_prefix(cache.k, k),
                                        v=_update_prefix(cache.v, v))
    elif mixer == "mamba":
        initial = cache if cache is not None else None
        if decode:
            y, nc = mamba_mod.mamba_decode(cfg, p["mamba"], h,
                                           _take_layer(cache, layer))
            new_cache = _put_layer(cache, layer, nc)
        else:
            y, new_cache_full = mamba_mod.mamba_forward(
                cfg, p["mamba"], h, unroll=unroll, initial=initial)
            if cache is not None:
                new_cache = new_cache_full
    else:
        raise ValueError(mixer)
    x = x + y

    # -- channel mixer ------------------------------------------------------
    h2 = common.apply_norm(cfg, p["norm2"], x)
    if mlp == "dense":
        y2 = common.apply_mlp(cfg, p["mlp"], h2)
    elif decode:
        # each row's token is routed alone: a capacity shared over the
        # batch would let one slot's routing drop another slot's token
        y2, aux = jax.vmap(lambda r: moe_mod.moe_forward(
            cfg, p["moe"], r[None], parallel=ctx, mode=moe_mode))(h2)
        y2, aux = y2[:, 0], jnp.mean(aux)
    else:
        y2, aux = moe_mod.moe_forward(cfg, p["moe"], h2, parallel=ctx,
                                      mode=moe_mode)
    x = x + y2
    return x, new_cache, aux


def _update_prefix(cache_arr, new_vals):
    """Write new_vals (B, S, ...) into cache_arr (B, T_max, ...) at t=0."""
    new_vals = new_vals.astype(cache_arr.dtype)
    idx = (0,) * cache_arr.ndim
    return jax.lax.dynamic_update_slice(cache_arr, new_vals, idx)


# ---------------------------------------------------------------------------
# Full model forward
# ---------------------------------------------------------------------------

def _remat_wrap(cfg: ModelConfig, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


def _run_groups(cfg: ModelConfig, params, x, positions, *, ctx, caches=None,
                pos=None, decode=False, moe_mode="a2a", with_remat=False,
                unroll=False, unroll_layers=False):
    """Apply all layer groups. caches: matching structure or None.

    ``unroll_layers=True`` replaces the layer scan with a Python loop
    (used by the dry-run cost probes: 1-2 periods, no while in the HLO)."""
    groups = layer_groups(cfg)
    spec = _resid_spec(ctx, seq_shardable=(x.shape[1] % max(
        ctx.tp_size, 1) == 0) if ctx and ctx.mesh else False)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = [] if caches is not None else None

    for gi, g in enumerate(groups):
        gp = params["groups"][gi]["blocks"]
        gc = caches[gi]["blocks"] if caches is not None else None

        def superblock(x, blk_params, blk_caches, layer=None):
            aux_sb = jnp.zeros((), jnp.float32)
            out_caches = []
            for pi, kind in enumerate(g.kinds):
                c = blk_caches[pi] if blk_caches is not None else None
                x, nc, aux = block_forward(
                    cfg, kind, blk_params[pi], x, positions, ctx=ctx,
                    cache=c, pos=pos, decode=decode, moe_mode=moe_mode,
                    unroll=unroll, layer=layer)
                x = _constrain(x, spec, ctx)
                out_caches.append(nc)
                aux_sb = aux_sb + aux
            return x, out_caches, aux_sb

        if g.n_repeats == 1:
            x, ncs, aux = superblock(x, gp, gc)
            aux_total = aux_total + aux
            if new_caches is not None:
                new_caches.append({"blocks": ncs})
        elif decode:
            # the layer stack rides in the loop carry and each layer writes
            # its rows in place: no per-layer slice, no rebuilt stack
            if unroll_layers:
                for r in range(g.n_repeats):
                    blk_params = jax.tree_util.tree_map(lambda a: a[r], gp)
                    x, gc, aux = superblock(x, blk_params, gc, layer=r)
                    aux_total = aux_total + aux
            else:
                def decode_body(carry, blk_params):
                    x, aux_acc, stacks, r = carry
                    x, stacks, aux = superblock(x, blk_params, stacks,
                                                layer=r)
                    return (x, aux_acc + aux, stacks, r + 1), None

                (x, aux_total, gc, _), _ = jax.lax.scan(
                    decode_body, (x, aux_total, gc, jnp.int32(0)), gp)
            new_caches.append({"blocks": gc})
        elif unroll_layers:
            body_fn = superblock
            if with_remat:
                body_fn = _remat_wrap(cfg, superblock)
            ncs_list = []
            for r in range(g.n_repeats):
                blk_params = jax.tree_util.tree_map(lambda a: a[r], gp)
                blk_caches = (jax.tree_util.tree_map(lambda a: a[r], gc)
                              if gc is not None else None)
                x, ncs, aux = body_fn(x, blk_params, blk_caches)
                aux_total = aux_total + aux
                ncs_list.append(ncs)
            if new_caches is not None:
                stacked = jax.tree_util.tree_map(
                    lambda *a: jnp.stack(a, 0), *ncs_list)
                new_caches.append({"blocks": stacked})
        else:
            body_fn = superblock
            if with_remat:
                body_fn = _remat_wrap(cfg, superblock)

            def scan_body(carry, xs):
                x, aux_acc = carry
                blk_params, blk_caches = xs
                x, ncs, aux = body_fn(x, blk_params, blk_caches)
                return (x, aux_acc + aux), ncs

            xs = (gp, gc if gc is not None
                  else [None] * len(g.kinds))
            # scan needs a pytree with uniform leading dim; None caches are
            # replaced by a dummy zero array
            if gc is None:
                xs = (gp, jnp.zeros((g.n_repeats,), jnp.float32))

                def scan_body(carry, xs):      # noqa: F811
                    x, aux_acc = carry
                    blk_params, _ = xs
                    x, _, aux = body_fn(x, blk_params, None)
                    return (x, aux_acc + aux), None

            (x, aux_total), ncs = jax.lax.scan(scan_body,
                                               (x, aux_total), xs)
            if new_caches is not None:
                new_caches.append({"blocks": ncs})

    return x, new_caches, aux_total


def embed_inputs(cfg: ModelConfig, params, tokens_or_embeds, ctx=None):
    dtype = jnp.dtype(cfg.compute_dtype)
    if tokens_or_embeds.dtype in (jnp.int32, jnp.int64):
        return common.embed_tokens(params["embed"], tokens_or_embeds, dtype,
                                   ctx=ctx)
    return tokens_or_embeds.astype(dtype)     # stubbed modality frontend


def forward(cfg: ModelConfig, params, tokens, positions=None, *, ctx=None,
            moe_mode: str = "a2a", with_remat: bool = False,
            unroll: bool = False, unroll_layers: bool = False):
    """Full forward (train / prefill without cache). Returns (B, S, V) logits
    in ``cfg.logit_dtype`` and the MoE aux loss."""
    B, S = tokens.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
    x = embed_inputs(cfg, params, tokens, ctx)
    x, _, aux = _run_groups(cfg, params, x, positions, ctx=ctx,
                            moe_mode=moe_mode, with_remat=with_remat,
                            unroll=unroll, unroll_layers=unroll_layers)
    x = common.apply_norm(cfg, params["final_norm"], x)
    logits = common.unembed(cfg, params["embed"], x, ctx=ctx)
    return logits.astype(jnp.dtype(cfg.logit_dtype)), aux


def loss_fn(cfg: ModelConfig, params, batch, *, ctx=None,
            moe_mode: str = "a2a", aux_weight: float = 0.01,
            with_remat: bool = True, unroll: bool = False,
            unroll_layers: bool = False):
    """Next-token cross entropy + MoE aux. batch: {tokens, (targets)}."""
    tokens = batch["tokens"]
    targets = batch.get("targets")
    if targets is None:
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        mask = jnp.concatenate(
            [jnp.ones_like(tokens[:, 1:], jnp.float32),
             jnp.zeros_like(tokens[:, :1], jnp.float32)], axis=1)
    else:
        mask = batch.get("mask", jnp.ones_like(targets, jnp.float32))
    logits, aux = forward(cfg, params, tokens, ctx=ctx, moe_mode=moe_mode,
                          with_remat=with_remat, unroll=unroll,
                          unroll_layers=unroll_layers)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32),
                              axis=-1)[..., 0]
    nll = (logz - tgt) * mask
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + aux_weight * aux, {"nll": loss, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode
# ---------------------------------------------------------------------------

class ServeState(NamedTuple):
    caches: Any            # list of group cache dicts
    pos: jax.Array         # int32 next position to write: scalar or (B,)


def prefill(cfg: ModelConfig, params, tokens, caches, *, ctx=None,
            moe_mode: str = "a2a", unroll: bool = False,
            unroll_layers: bool = False):
    """Run the prompt through the model, filling caches.

    Returns (last-token logits (B, V), ServeState)."""
    B, S = tokens.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = embed_inputs(cfg, params, tokens, ctx)
    x, new_caches, _ = _run_groups(cfg, params, x, positions, ctx=ctx,
                                   caches=caches, moe_mode=moe_mode,
                                   unroll=unroll, unroll_layers=unroll_layers)
    x = common.apply_norm(cfg, params["final_norm"], x)
    logits = common.unembed(cfg, params["embed"], x[:, -1:], ctx=ctx)
    return (logits[:, 0].astype(jnp.dtype(cfg.logit_dtype)),
            ServeState(new_caches, jnp.asarray(S, jnp.int32)))


def decode_step(cfg: ModelConfig, params, tokens, state: ServeState, *,
                ctx=None, moe_mode: str = "psum", unroll: bool = False,
                unroll_layers: bool = False):
    """One decode step. tokens: (B, 1) int32; ``state.pos`` is a scalar
    shared by every row or (B,), each row's own position.  Every row writes
    its token's K/V at its position and attends up to it, independently of
    the other rows.  Returns (logits (B, V), state)."""
    B = tokens.shape[0]
    pos = state.pos
    row_pos = jnp.broadcast_to(pos, (B,)).astype(jnp.int32)
    x = embed_inputs(cfg, params, tokens, ctx)
    x, new_caches, _ = _run_groups(cfg, params, x, row_pos[:, None], ctx=ctx,
                                   caches=state.caches, pos=row_pos,
                                   decode=True,
                                   moe_mode=moe_mode, unroll=unroll,
                                   unroll_layers=unroll_layers)
    x = common.apply_norm(cfg, params["final_norm"], x)
    logits = common.unembed(cfg, params["embed"], x, ctx=ctx)
    return (logits[:, 0].astype(jnp.dtype(cfg.logit_dtype)),
            ServeState(new_caches, pos + 1))
