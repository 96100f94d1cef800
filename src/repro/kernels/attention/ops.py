"""jit'd public wrapper: model layout <-> kernel layout + CPU fallback.

Models use q (B, S, K, G, hd); the kernel wants (B, H, S, hd).  On TPU the
Pallas kernel runs natively; on CPU ``interpret=True`` executes the same
kernel body (used by the allclose sweeps); ``backend="ref"`` uses the
pure-jnp oracle (the default inside traced/sharded model code, where XLA's
fused attention is already near-roofline on CPU).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.attention.kernel import flash_attention_kernel
from repro.kernels.attention.ref import attention_ref


def _pick_backend(backend: Optional[str]) -> str:
    if backend is not None:
        return backend
    return "pallas" if jax.devices()[0].platform == "tpu" else "ref"


@partial(jax.jit, static_argnames=("causal", "scale", "block_q", "block_k",
                                   "backend"))
def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    backend: Optional[str] = None):
    """q: (B, S, K, G, hd); k/v: (B, T, K, hd[/v]) -> (B, S, K, G, hd_v)."""
    B, S, K, G, hd = q.shape
    T = k.shape[1]
    qh = jnp.transpose(q, (0, 2, 3, 1, 4)).reshape(B, K * G, S, hd)
    kh = jnp.transpose(k, (0, 2, 1, 3))                   # (B, K, T, hd)
    vh = jnp.transpose(v, (0, 2, 1, 3))
    be = _pick_backend(backend)
    if be == "ref":
        oh = attention_ref(qh, kh, vh, causal=causal, scale=scale)
    else:
        oh = flash_attention_kernel(
            qh, kh, vh, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, interpret=(be == "interpret"))
    hd_v = vh.shape[-1]
    return jnp.transpose(oh.reshape(B, K, G, S, hd_v), (0, 3, 1, 2, 4))
