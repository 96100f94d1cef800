"""Training state: params + optimizer + step counter + RNG.

The state tree is what the DSM runtime checkpoints: each top-level entry
(params / mu / nu / counters) is registered as a durable object with the
FliT-protocol commit (see ``repro.dsm``).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.optim.adamw import AdamWState, adamw_init, adamw_abstract


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState
    rng: jax.Array            # (2,) uint32


def init_train_state(params, key, moment_dtype: str = "float32") -> TrainState:
    return TrainState(params=params,
                      opt=adamw_init(params, moment_dtype),
                      rng=jax.random.key_data(key))


def abstract_train_state(params_abstract,
                         moment_dtype: str = "float32") -> TrainState:
    return TrainState(
        params=params_abstract,
        opt=adamw_abstract(params_abstract, moment_dtype),
        rng=jax.ShapeDtypeStruct((2,), jnp.uint32))
