"""Step factories: jit-able train / prefill / decode steps with shardings.

``make_train_step(bundle, ctx)`` returns ``step(state, batch) -> (state,
metrics)`` with:

* loss + grad under remat (``cfg.remat``),
* optional microbatching (gradient accumulation via ``lax.scan`` over
  microbatch slices — hillclimb lever for activation memory),
* optional gradient compression hook (``repro.parallel.compression``),
* AdamW update with cosine schedule.

``make_serve_steps`` returns (prefill_step, decode_step) — the static
serving pair.  ``make_slot_decode_step`` is the continuous-batching
variant: a slot-masked decode where every batch slot advances at its own
position (see repro.serve for the scheduler/KV-manager that drives it).

Checkpoint-commit planning (how many per-device shard pipelines flush the
state this step produces) lives with the commit scheduler:
``repro.dsm.flit_runtime.auto_shard_count`` sizes pipelines from the
actual HBM state volume; callers pass ``n_shards=None`` to get it.

All functions are pure; shardings are applied by the caller via
``jax.jit(..., in_shardings=..., out_shardings=...)`` (see launch/dryrun).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.registry import ModelBundle
from repro.optim.adamw import adamw_update
from repro.optim.schedule import cosine_schedule
from repro.train.state import TrainState


def make_train_step(bundle: ModelBundle, ctx=None, *,
                    microbatch: int = 1,
                    peak_lr: float = 3e-4,
                    total_steps: int = 10_000,
                    grad_transform: Optional[Callable] = None,
                    moe_mode: str = "a2a",
                    donate: bool = True) -> Callable:
    """Build the train step. ``grad_transform(grads, ctx) -> grads`` is the
    gradient-compression hook (identity if None)."""
    cfg = bundle.cfg

    def loss_of(params, batch):
        loss, metrics = bundle.loss(params, batch, ctx=ctx,
                                    moe_mode=moe_mode, with_remat=True)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def accumulate(params, batch):
        if microbatch <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads
        B = batch["tokens"].shape[0]
        assert B % microbatch == 0, (B, microbatch)
        mb = B // microbatch

        def slice_mb(x, i):
            return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, 0)

        def body(carry, i):
            loss_acc, grads_acc = carry
            mb_batch = jax.tree_util.tree_map(partial(slice_mb, i=i), batch)
            (loss, metrics), grads = grad_fn(params, mb_batch)
            grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
            return (loss_acc + loss, grads_acc), metrics

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, grads), metrics = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros),
            jnp.arange(microbatch))
        inv = 1.0 / microbatch
        grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        return loss_sum * inv, metrics, grads

    # grads must land on the PARAM shardings before the optimizer update:
    # without the constraint GSPMD is free to all-reduce FSDP gradients to
    # full (replicated) size and run the fp32 moment math unsharded —
    # ~100 GB/device at jamba scale. The constraint forces reduce-scatter
    # + fully sharded optimizer math (ZeRO).
    grad_specs = None
    if ctx is not None and ctx.mesh is not None:
        from jax.sharding import NamedSharding
        from repro.parallel.sharding import param_specs
        grad_specs = jax.tree_util.tree_map(
            lambda s: NamedSharding(ctx.mesh, s),
            param_specs(ctx, bundle.descs))

    def step(state: TrainState, batch) -> Tuple[TrainState, Dict[str, Any]]:
        loss, metrics, grads = accumulate(state.params, batch)
        if grad_specs is not None:
            grads = jax.lax.with_sharding_constraint(grads, grad_specs)
        if grad_transform is not None:
            grads = grad_transform(grads, ctx)
        lr = cosine_schedule(state.opt.step, peak_lr=peak_lr,
                             total=total_steps)
        params, opt, gnorm = adamw_update(
            state.params, grads, state.opt, lr,
            weight_decay=0.1, grad_clip=1.0)
        new_state = TrainState(params=params, opt=opt, rng=state.rng)
        out = {"loss": loss, "lr": lr, "grad_norm": gnorm,
               "step": opt.step, **metrics}
        return new_state, out

    return step


def make_serve_steps(bundle: ModelBundle, ctx=None, *,
                     moe_mode_prefill: str = "a2a",
                     moe_mode_decode: str = "psum"):
    cfg = bundle.cfg

    def prefill_step(params, batch, caches):
        return bundle.prefill(params, batch, caches, ctx=ctx,
                              moe_mode=moe_mode_prefill)

    def decode_step(params, tokens, state):
        return bundle.decode(params, tokens, state, ctx=ctx,
                             moe_mode=moe_mode_decode)

    return prefill_step, decode_step


def cache_batch_axes(bundle: ModelBundle):
    """Per-leaf index of the BATCH axis in the decode-cache pytree.

    Layer-stacked groups prepend a ``(repeats,)`` dim to their cache
    leaves, so batch is axis 1 there and axis 0 on singleton groups — any
    slot-wise cache surgery (per-slot insert/extract) must be driven
    by the cache descriptors' logical axis names, not a fixed axis."""
    from repro.models.params import tree_map_descs
    return tree_map_descs(lambda d: d.logical.index("batch"),
                          bundle.cache_descs(1, 2))


def make_slot_decode_step(bundle: ModelBundle, ctx=None, *,
                          moe_mode: str = "psum"):
    """The continuous-batching decode step: every slot advances by one
    token at its OWN position.

    ``slot_decode(params, tokens, caches, pos, active)`` with

    * ``tokens`` (B, 1) int32 — last sampled token per slot,
    * ``caches`` — batched cache pytree (B on the per-leaf batch axis),
    * ``pos``    (B,) int32 — per-slot decode position,
    * ``active`` (B,) bool — slot occupancy mask,

    returns ``(next_tokens (B,), logits (B, V), caches, pos)``; greedy
    argmax is baked into the graph (the repo's only sampler).  One batched
    decode with a position per row: each layer writes only the new token's
    K/V row of each slot, at that slot's position, in place in the donated
    cache (``models.lm.block_forward``).  Every row's computation is
    INDEPENDENT of what the other slots hold — outputs do not depend on
    slot assignment or batch composition, which is what makes crash-replay
    of a session bit-identical under a different interleaving.  Inactive
    slots still compute (masked lanes are the price of a fixed batch shape)
    but their position does not advance and their garbage is overwritten
    wholesale at the next admission.
    """
    assert not bundle.cfg.is_encdec, "slot decode is decoder-only"
    from repro.models.lm import ServeState

    def slot_decode(params, tokens, caches, pos, active):
        logits, st = bundle.decode(params, tokens, ServeState(caches, pos),
                                   ctx=ctx, moe_mode=moe_mode)
        new_pos = jnp.where(active, st.pos, pos)
        next_tokens = jnp.argmax(logits, -1).astype(jnp.int32)
        return next_tokens, logits, st.caches, new_pos

    return slot_decode
