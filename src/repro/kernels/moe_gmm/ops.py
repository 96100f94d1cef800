"""jit'd wrapper for the grouped matmul with CPU fallback."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels.moe_gmm.kernel import grouped_matmul_kernel
from repro.kernels.moe_gmm.ref import grouped_matmul_ref


def _pick_backend(backend: Optional[str]) -> str:
    if backend is not None:
        return backend
    return "pallas" if jax.devices()[0].platform == "tpu" else "ref"


@partial(jax.jit, static_argnames=("block_c", "block_f", "block_d",
                                   "backend"))
def grouped_matmul(x, w, *, block_c: int = 128, block_f: int = 128,
                   block_d: int = 512, backend: Optional[str] = None):
    be = _pick_backend(backend)
    if be == "ref":
        return grouped_matmul_ref(x, w)
    return grouped_matmul_kernel(x, w, block_c=block_c, block_f=block_f,
                                 block_d=block_d,
                                 interpret=(be == "interpret"))
