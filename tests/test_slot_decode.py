"""The continuous-batching decode step (``train.step.make_slot_decode_step``).

It runs one batched decode with a position per slot and writes each new
K/V row in place in the layer-stacked cache.  These tests hold it to:

* the per-slot formulation, bit for bit: a ``vmap`` over slots of the
  single-sequence decode at a scalar position (the test-local oracle) —
  next tokens, logits and every cache leaf, at mixed positions, with
  inactive slots and a slot at the lane's last row, for attention-only,
  MoE, MLA and recurrent (Mamba, RWKV) architectures, stacked or not;
* slot independence: a slot's logits and cache bytes do not depend on the
  other slots' tokens, positions or contents, nor on which slot it holds;
* the lane's end: a row written past the lane lands on its last row, as
  ``dynamic_update_slice`` clamps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.attention import write_rows
from repro.models.lm import ServeState
from repro.models.registry import build
from repro.train.step import cache_batch_axes, make_slot_decode_step

B = 4
tree_map = jax.tree_util.tree_map

# (arch, layer count): the counts put MLA and Mamba layers into a stacked
# group as well as the smoke size's singletons
ARCHS = [("olmo-1b", None), ("olmoe-1b-7b", None), ("rwkv6-7b", None),
         ("deepseek-v2-236b", None), ("deepseek-v2-236b", 3),
         ("jamba-1.5-large-398b", None), ("jamba-1.5-large-398b", 16)]


def _oracle(bundle):
    """The slot decode as a per-slot ``vmap`` of the single-sequence decode
    at a scalar position: each slot is a batch of one."""
    axes = cache_batch_axes(bundle)

    def slot_decode(params, tokens, caches, pos, active):
        def one(tok, cache, p):
            cache1 = tree_map(lambda x, a: jnp.expand_dims(x, a), cache, axes)
            logits, st = bundle.decode(params, tok[None],
                                       ServeState(cache1, p), moe_mode="psum")
            nc = tree_map(lambda x, a: jnp.squeeze(x, a), st.caches, axes)
            return logits[0], nc, st.pos

        logits, new_caches, new_pos = jax.vmap(
            one, in_axes=(0, axes, 0), out_axes=(0, axes, 0))(
            tokens, caches, pos)
        new_pos = jnp.where(active, new_pos, pos)
        next_tokens = jnp.argmax(logits, -1).astype(jnp.int32)
        return next_tokens, logits, new_caches, new_pos

    return slot_decode


def _model(arch, n_layers):
    cfg = get_smoke_config(arch)
    if n_layers is not None:
        cfg = cfg.with_(n_layers=n_layers)
    bundle = build(cfg)
    return bundle, bundle.init_params(jax.random.PRNGKey(0))


def _random_caches(bundle, t_max, seed):
    """A cache pytree of B slots with every leaf filled at random."""
    tmpl = bundle.abstract_caches(B, t_max)
    leaves, treedef = jax.tree_util.tree_flatten(tmpl)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
        for k, s in zip(keys, leaves)])


def _inputs(bundle, t_max, seed):
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 100), (B, 1), 0,
                                bundle.cfg.vocab_size, jnp.int32)
    pos = jnp.asarray([3, t_max - 1, 0, t_max // 2 + 1], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    return tokens, _random_caches(bundle, t_max, seed), pos, active


def _equal(a, b):
    fa, ta = jax.tree_util.tree_flatten(a)
    fb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(fa, fb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("t_max", [64, 40])
@pytest.mark.parametrize("arch,n_layers", ARCHS)
def test_slot_decode_matches_per_slot_oracle_bitwise(arch, n_layers, t_max):
    bundle, params = _model(arch, n_layers)
    tokens, caches, pos, active = _inputs(bundle, t_max, seed=1)
    want = jax.jit(_oracle(bundle))(params, tokens, caches, pos, active)
    got = jax.jit(make_slot_decode_step(bundle))(params, tokens, caches, pos,
                                                 active)
    _equal(got, want)
    np.testing.assert_array_equal(np.asarray(got[3]), [4, t_max, 0,
                                                       t_max // 2 + 2])


def _slot(tree, axes, i):
    return tree_map(lambda x, a: np.take(np.asarray(x), i, axis=a), tree, axes)


@pytest.mark.parametrize("arch,n_layers", [("olmo-1b", None),
                                           ("olmoe-1b-7b", None),
                                           ("jamba-1.5-large-398b", 16)])
def test_slot_outputs_independent_of_other_slots(arch, n_layers):
    t_max = 64
    bundle, params = _model(arch, n_layers)
    axes = cache_batch_axes(bundle)
    step = jax.jit(make_slot_decode_step(bundle))
    tokens, caches, pos, active = _inputs(bundle, t_max, seed=1)
    ref = step(params, tokens, caches, pos, active)

    # slot 0 kept; every other slot's token, position and contents changed
    o_tokens, o_caches, _, _ = _inputs(bundle, t_max, seed=7)
    o_tokens = o_tokens.at[0].set(tokens[0])
    o_caches = tree_map(
        lambda o, c, a: jnp.where(
            (jnp.arange(B) == 0).reshape([-1 if d == a else 1
                                          for d in range(c.ndim)]), c, o),
        o_caches, caches, axes)
    o_pos = jnp.asarray([pos[0], 11, t_max - 1, 2], jnp.int32)
    other = step(params, o_tokens, o_caches, o_pos,
                 jnp.asarray([True, False, True, True]))
    for a, b in zip(ref[:2], other[:2]):
        np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(b)[0])
    _equal(_slot(ref[2], axes, 0), _slot(other[2], axes, 0))

    # the slots permuted: each slot's outputs follow it to its new lane
    perm = np.asarray([2, 0, 3, 1])
    p_caches = tree_map(lambda c, a: jnp.take(c, perm, axis=a), caches, axes)
    moved = step(params, tokens[perm], p_caches, pos[perm], active[perm])
    for a, b in zip(ref[:2], moved[:2]):
        np.testing.assert_array_equal(np.asarray(a)[perm], np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ref[3])[perm],
                                  np.asarray(moved[3]))
    for new_slot, old_slot in enumerate(perm):
        _equal(_slot(ref[2], axes, old_slot), _slot(moved[2], axes, new_slot))


@pytest.mark.parametrize("layer", [None, 1])
def test_write_rows_clamps_like_dynamic_update_slice(layer):
    T = 8
    shape = (B, T, 3, 2) if layer is None else (3, B, T, 3, 2)
    buf = jax.random.normal(jax.random.PRNGKey(0), shape).astype(jnp.bfloat16)
    rows = jax.random.normal(jax.random.PRNGKey(1),
                             (B, 1, 3, 2)).astype(jnp.float32)
    pos = jnp.asarray([0, T - 1, T, T + 5], jnp.int32)
    got = write_rows(buf, rows, pos, layer)
    want = buf
    for b in range(B):
        lane = buf[b] if layer is None else buf[layer, b]
        lane = jax.lax.dynamic_update_slice_in_dim(
            lane, rows[b].astype(buf.dtype), pos[b], 0)
        want = (want.at[b].set(lane) if layer is None
                else want.at[layer, b].set(lane))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
