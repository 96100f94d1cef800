"""OLMo (``model_type`` ``olmo``; arXiv:2402.00838): the program's
configuration for such a file, the benchmark's weights from the seed, and
the operations and bytes the algorithm needs.

Every ``archs/<model_type>.py`` gives the harness these names, which
``chiplib.arch`` finds by the ``model_type`` of a configuration file
(the harness's directory is on ``sys.path``, so it may ``import
chiplib``):

- ``program_config(cfg_file)``: the program's ``ModelConfig``, with every
  shape key checked against the file; a disagreement is a ValueError.
- ``make_params(abstract_tree, seed, cfg_file)``: the weights of every
  leaf of the program's abstract tree, from the seed, on the device in
  one jitted call (``chiplib.make_params``: N(0, scale), with a hook for
  leaves that have other published initializers).
- ``param_count(cfg)``, ``forward_flops(cfg, n_tokens, keys_seen)``,
  ``kv_bytes_per_token(cfg, bytes_per_value)`` and
  ``decode_min_bytes(cfg, live_positions, decode_slots,
  bytes_per_value)``: the counts, by the conventions of ``flops.py``,
  which forwards to them.  ``decode_slots`` is the number of slots that
  decoded a token, so that a model with recurrent state can count the
  state each reads and writes.

Every ``reference/<model_type>.py``, found by ``chiplib.reference``, is
a plain float32 implementation that imports nothing of the program and
gives:

- ``to_f32(params)``: the tree in float32;
- ``logits(cfg, params, tokens, quant=None)``: (B, S, V) logits;
- ``loss_and_grad(cfg, params, tokens, targets, quant=None)``;
- ``served_gaps(cfg, params, prompt, served, quant=None)``: per served
  position, the gap of the served token's logit below the best, and with
  ``quant`` the same for the control's own pick;
- ``train(cfg, params, batches, opt, quant=None, store_dtype=None,
  drop_half=False)``: the losses, the first clipped gradient's leaf norms
  and the weights' change over the steps.

``quant`` names the control's lower precision (``"int8"`` here).
"""
from __future__ import annotations

import chiplib

#: published key -> the program's ModelConfig field
_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab_size",
         "rope_theta": "rope_theta", "tie_word_embeddings":
         "tied_embeddings", "torch_dtype": "param_dtype"}


def program_config(cfg_file: dict):
    """The program's ModelConfig for a configuration file: its registry
    entry for ``arch`` (the smoke-size entry for the self-tests' files) at
    the file's depth.  Every other shape key has to agree already; a
    disagreement is an error, not a silent change."""
    from repro.configs import get_config, get_smoke_config
    base = (get_smoke_config if cfg_file.get("smoke") else get_config)(
        cfg_file["arch"])
    cfg = base.with_(
        n_layers=cfg_file["num_hidden_layers"])
    bad = {k: (cfg_file[k], getattr(cfg, f)) for k, f in _KEYS.items()
           if cfg_file[k] != getattr(cfg, f)}
    if bad or cfg.norm != "nonparametric_ln" or not cfg.glu:
        raise ValueError(f"{cfg_file['name']}: the program's "
                         f"{cfg_file['arch']} differs from the file: {bad}")
    return cfg


def make_params(abstract_tree, seed: int, cfg_file: dict):
    """Every leaf N(0, ``initializer_range``): OLMo's non-parametric
    LayerNorm has no weights of its own."""
    return chiplib.make_params(abstract_tree, seed,
                               cfg_file["initializer_range"])


# -- counts -------------------------------------------------------------------

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
    """Every matrix product of the layers and the tied unembedding, and
    the two attention products (scores and weighted values) over the
    keys seen."""
    m = dims(cfg)
    dense = 2 * (m["L"] * layer_matmul_params(cfg) + m["V"] * m["d"])
    attn = 2 * 2 * m["L"] * m["H"] * m["hd"]
    return float(dense) * n_tokens + float(attn) * keys_seen


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """K and V of one position over all layers."""
    m = dims(cfg)
    return 2 * m["L"] * m["K"] * m["hd"] * bytes_per_value


def decode_min_bytes(cfg: dict, live_positions: int, decode_slots: int,
                     bytes_per_value: int = 2) -> float:
    """Every parameter once, plus K and V of the live positions of the
    active slots.  OLMo keeps no state per slot beside its K and V, so
    ``decode_slots`` adds nothing."""
    return (float(param_count(cfg)) * bytes_per_value
            + float(kv_bytes_per_token(cfg, bytes_per_value))
            * live_positions)
