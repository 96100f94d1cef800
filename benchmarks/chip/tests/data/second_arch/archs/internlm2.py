"""InternLM2 (``model_type`` ``internlm2``; arXiv:2403.17297) for the
harness's own test that a second architecture needs new files only:
RMSNorm with a scale, grouped-query attention and untied embeddings."""
from __future__ import annotations

import chiplib

_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
         "vocab_size": "vocab_size", "rope_theta": "rope_theta",
         "tie_word_embeddings": "tied_embeddings",
         "torch_dtype": "param_dtype"}


def program_config(cfg_file: dict):
    from repro.configs import get_config, get_smoke_config
    base = (get_smoke_config if cfg_file.get("smoke") else get_config)(
        cfg_file["arch"])
    cfg = base.with_(n_layers=cfg_file["num_hidden_layers"])
    bad = {k: (cfg_file[k], getattr(cfg, f)) for k, f in _KEYS.items()
           if cfg_file[k] != getattr(cfg, f)}
    if bad or cfg.norm != "rmsnorm" or not cfg.glu:
        raise ValueError(f"{cfg_file['name']}: the program's "
                         f"{cfg_file['arch']} differs from the file: {bad}")
    return cfg


def _norm_scale(path, leaf, key):
    """RMSNorm scales start at 1, as published."""
    import jax.numpy as jnp
    return jnp.ones(leaf.shape, jnp.float32) if "norm" in path else None


def make_params(abstract_tree, seed: int, cfg_file: dict):
    return chiplib.make_params(abstract_tree, seed,
                               cfg_file["initializer_range"],
                               init=_norm_scale)


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return {"d": d, "L": cfg["num_hidden_layers"],
            "H": cfg["num_attention_heads"],
            "K": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def layer_matmul_params(cfg: dict) -> int:
    m = dims(cfg)
    return m["d"] * m["hd"] * (2 * m["H"] + 2 * m["K"]) + 3 * m["d"] * m["ff"]


def param_count(cfg: dict) -> int:
    """The layers with their two norm scales, the final norm, and the
    embedding and unembedding tables."""
    m = dims(cfg)
    return (m["L"] * (layer_matmul_params(cfg) + 2 * m["d"]) + m["d"]
            + 2 * m["V"] * m["d"])


def forward_flops(cfg: dict, n_tokens: int, keys_seen: int) -> float:
    m = dims(cfg)
    dense = 2 * (m["L"] * layer_matmul_params(cfg) + m["V"] * m["d"])
    attn = 2 * 2 * m["L"] * m["H"] * m["hd"]
    return float(dense) * n_tokens + float(attn) * keys_seen


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    m = dims(cfg)
    return 2 * m["L"] * m["K"] * m["hd"] * bytes_per_value


def decode_min_bytes(cfg: dict, live_positions: int, decode_slots: int,
                     bytes_per_value: int = 2) -> float:
    return (float(param_count(cfg)) * bytes_per_value
            + float(kv_bytes_per_token(cfg, bytes_per_value))
            * live_positions)
