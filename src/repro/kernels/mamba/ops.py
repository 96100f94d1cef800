"""jit'd wrapper for the selective-scan kernel with CPU fallback."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.mamba.kernel import selective_scan_kernel
from repro.kernels.mamba.ref import selective_scan_ref


def _pick_backend(backend: Optional[str]) -> str:
    if backend is not None:
        return backend
    return "pallas" if jax.devices()[0].platform == "tpu" else "ref"


@partial(jax.jit, static_argnames=("block_s", "block_i", "backend"))
def selective_scan(dA, dBu, C, h0=None, *, block_s: int = 64,
                   block_i: int = 128, backend: Optional[str] = None):
    B, S, I, N = dA.shape
    if h0 is None:
        h0 = jnp.zeros((B, I, N), jnp.float32)
    be = _pick_backend(backend)
    if be == "ref":
        return selective_scan_ref(dA, dBu, C, h0)
    return selective_scan_kernel(dA, dBu, C, h0, block_s=block_s,
                                 block_i=min(block_i, I),
                                 interpret=(be == "interpret"))
