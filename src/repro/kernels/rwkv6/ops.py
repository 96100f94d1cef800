"""jit'd wrapper for the WKV6 kernel with CPU fallback to the oracle."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6.kernel import wkv6_kernel
from repro.kernels.rwkv6.ref import wkv6_ref


def _pick_backend(backend: Optional[str]) -> str:
    if backend is not None:
        return backend
    return "pallas" if jax.devices()[0].platform == "tpu" else "ref"


@partial(jax.jit, static_argnames=("block_t", "backend"))
def wkv6(r, k, v, logw, u, S0=None, *, block_t: int = 64,
         backend: Optional[str] = None):
    """RWKV-6 WKV. r/k/v/logw: (B, T, H, n); u: (H, n).
    Returns (y (B,T,H,n) fp32-accurate in r.dtype, final state fp32)."""
    B, T, H, n = r.shape
    if S0 is None:
        S0 = jnp.zeros((B, H, n, n), jnp.float32)
    be = _pick_backend(backend)
    if be == "ref":
        y, S = wkv6_ref(r, k, v, logw, u, S0)
        return y.astype(r.dtype), S
    return wkv6_kernel(r, k, v, logw, u, S0, block_t=block_t,
                       interpret=(be == "interpret"))
