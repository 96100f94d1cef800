"""Operations and bytes the algorithm needs, counted from a configuration
file's shapes (the published key names) and kept with the benchmark, so
that no change to the program can move them.

The formulas of each architecture are in ``archs/<model_type>.py``; each
function here forwards to the module of ``cfg["model_type"]``
(``chiplib.arch``).  Every such module counts by the same conventions:

- Model FLOPs count each multiply-add as two operations: every matrix
  product of the forward pass, the unembedding included (an embedding
  lookup is no product), plus the products of the token mixer, such as
  attention's scores and weighted values over the keys each query may
  see.
- Training is three forward passes' worth (forward, and twice that
  backward); recomputation under remat is not counted.
- Bytes are the least HBM traffic the step needs: every parameter read
  once, plus the cached state the step has to read.
"""
from __future__ import annotations

import chiplib


def dims(cfg: dict) -> dict:
    return chiplib.arch(cfg).dims(cfg)


def layer_matmul_params(cfg: dict) -> int:
    return chiplib.arch(cfg).layer_matmul_params(cfg)


def param_count(cfg: dict) -> int:
    """Every parameter of the model."""
    return chiplib.arch(cfg).param_count(cfg)


def forward_flops(cfg: dict, n_tokens: int, keys_seen: int) -> float:
    """Forward FLOPs of ``n_tokens`` query tokens that attend to
    ``keys_seen`` keys in all (summed over the tokens)."""
    return chiplib.arch(cfg).forward_flops(cfg, n_tokens, keys_seen)


def causal_keys(n: int, start: int = 0) -> int:
    """Keys seen by queries at positions start..start+n-1 under a causal
    mask: position p sees p + 1 keys."""
    return n * start + n * (n + 1) // 2


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    return forward_flops(cfg, prompt_len, causal_keys(prompt_len))


def decode_flops(cfg: dict, pos: int) -> float:
    """One token at position ``pos`` (it sees pos + 1 keys)."""
    return forward_flops(cfg, 1, pos + 1)


def train_flops_per_sequence(cfg: dict, seq_len: int) -> float:
    return 3.0 * forward_flops(cfg, seq_len, causal_keys(seq_len))


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """K and V of one position over all layers."""
    return chiplib.arch(cfg).kv_bytes_per_token(cfg, bytes_per_value)


def decode_min_bytes(cfg: dict, live_positions: int,
                     bytes_per_value: int = 2, *,
                     decode_slots: int = 0) -> float:
    """The least HBM traffic of one decode step in which ``decode_slots``
    slots decoded a token over ``live_positions`` live positions in all."""
    return chiplib.arch(cfg).decode_min_bytes(cfg, live_positions,
                                              decode_slots, bytes_per_value)
