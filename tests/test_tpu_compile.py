"""Compiles for a TPU v5e that is described, not attached.

The kernels and steps of the served path at ``olmo-1b``'s published widths
go through the TPU compiler here, on the CPU: what it refuses (a tiling it
cannot lower, VMEM over budget, a program larger than the chip's 16 GB)
fails here at no chip time.  Nothing runs, so nothing here is a result or
a time.  The topology is described inside a fixture, never at import: one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.attention.kernel import flash_attention_kernel
from repro.models.registry import build
from repro.train.step import make_serve_steps, make_slot_decode_step

HBM_BYTES = 16e9                     # one v5e chip
N_SLOTS, T_MAX, PROMPT = 8, 1024, 512
CHAT_SLOTS = 32                      # the serve-chat cell's batch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def olmo():
    cfg = get_config("olmo-1b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads) == (16, 2048, 16)
    return build(cfg, dec_pos_len=T_MAX)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("B,S", [(1, PROMPT), (4, 2048), (1, 1)])
def test_flash_kernel_compiles(one_chip, B, S):
    hd = get_config("olmo-1b").head_dim
    x = jax.ShapeDtypeStruct((B, 16, S, hd), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(flash_attention_kernel).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _slot_decode(one_chip, olmo, n_slots):
    args = _on(one_chip, (
        olmo.abstract_params(),
        jax.ShapeDtypeStruct((n_slots, 1), jnp.int32),
        olmo.abstract_caches(n_slots, T_MAX),
        jax.ShapeDtypeStruct((n_slots,), jnp.int32),
        jax.ShapeDtypeStruct((n_slots,), jnp.bool_)))
    step = jax.jit(make_slot_decode_step(olmo), donate_argnums=(2,))
    return step.lower(*args).compile()


def test_slot_decode_step_fits(one_chip, olmo):
    assert _device_bytes(_slot_decode(one_chip, olmo, N_SLOTS)) < HBM_BYTES


def test_slot_decode_writes_rows_in_place(one_chip, olmo):
    """At the chat cell's shape the decode writes each new K/V row into the
    donated cache: no temporary copy of the cache, and no copy or transpose
    of a whole cache leaf (each 2.1 GB here)."""
    compiled = _slot_decode(one_chip, olmo, CHAT_SLOTS)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
    leaf_shapes = {",".join(map(str, s.shape)) for s in
                   jax.tree_util.tree_leaves(
                       olmo.abstract_caches(CHAT_SLOTS, T_MAX))}
    moves = re.findall(r"= \w+\[([\d,]+)\]\S* (?:copy|transpose)\(",
                       compiled.as_text())
    assert moves and not leaf_shapes & set(moves), leaf_shapes & set(moves)


def test_prefill_fits(one_chip, olmo):
    prefill, _ = make_serve_steps(olmo)
    args = _on(one_chip, (
        olmo.abstract_params(),
        {"tokens": jax.ShapeDtypeStruct((1, PROMPT), jnp.int32)},
        olmo.abstract_caches(1, T_MAX)))
    assert _device_bytes(jax.jit(prefill).lower(*args).compile()) < HBM_BYTES
