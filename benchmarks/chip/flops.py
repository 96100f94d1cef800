"""Operations and bytes the algorithm needs, counted from a configuration
file's shapes (the published key names) and kept with the benchmark, so
that no change to the program can move them.

Model FLOPs count each multiply-add as two operations: every matrix
product of the forward pass, the tied unembedding included, plus the
two attention products (scores and weighted values) over the keys each
query may see.  Training is three forward passes' worth (forward, and
twice that backward); recomputation under remat is not counted.
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "L": cfg["num_hidden_layers"], "H": h,
            "K": cfg["num_key_value_heads"], "hd": cfg.get("head_dim", d // h),
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def layer_matmul_params(cfg: dict) -> int:
    """Weights one layer multiplies each token by: q, k, v, o and the
    three SwiGLU matrices."""
    m = dims(cfg)
    attn = m["d"] * m["hd"] * (2 * m["H"] + 2 * m["K"])
    return attn + 3 * m["d"] * m["ff"]


def param_count(cfg: dict) -> int:
    """Every parameter: the layers and one tied embedding table (the
    non-parametric LayerNorm has none)."""
    m = dims(cfg)
    return m["L"] * layer_matmul_params(cfg) + m["V"] * m["d"]


def forward_flops(cfg: dict, n_tokens: int, keys_seen: int) -> float:
    """Forward FLOPs of ``n_tokens`` query tokens that attend to
    ``keys_seen`` keys in all (summed over the tokens)."""
    m = dims(cfg)
    dense = 2 * (m["L"] * layer_matmul_params(cfg) + m["V"] * m["d"])
    attn = 2 * 2 * m["L"] * m["H"] * m["hd"]
    return float(dense) * n_tokens + float(attn) * keys_seen


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
    m = dims(cfg)
    return 2 * m["L"] * m["K"] * m["hd"] * bytes_per_value


def decode_min_bytes(cfg: dict, live_positions: int,
                     bytes_per_value: int = 2) -> float:
    """The least HBM traffic of one decode step: every parameter once,
    plus K and V of the live positions of the active slots."""
    return (float(param_count(cfg)) * bytes_per_value
            + float(kv_bytes_per_token(cfg, bytes_per_value))
            * live_positions)
