"""Plain float32 OLMo, written from the OLMo description (arXiv:2402.00838,
and the HF ``OlmoForCausalLM`` it was released as): token embedding,
then per layer a non-parametric LayerNorm, multi-head attention with
rotary position embedding (rotate-half form, theta from the config), a
second non-parametric LayerNorm and a SwiGLU MLP, each added to the
residual; a final non-parametric LayerNorm and the tied embedding as
the output head.  It imports nothing of the program under test.

Every matrix product runs in float32 under
``jax.default_matmul_precision("highest")``.  ``quant="int8"`` is the
control: each product's operands are rounded to int8 first (weights per
tensor, activations per row, symmetric absmax scales; the backward pass
treats the rounding as the identity), the cheaper precision a later
change would be tempted by.

Weights arrive as the tree the serving and training steps take
(``embed.tok``, and per layer group ``attn.wq/wk/wv/wo``,
``mlp.w_gate/w_up/w_down``, layer-stacked or not); only their values and
shapes are read.  Departure from the description: with ``store_dtype``
the optimizer writes weights back rounded to that dtype after each
update, as the configuration that states bfloat16 weights keeps them.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5


# -- weights ------------------------------------------------------------------

def _layer_trees(params) -> List[Tuple[Dict[str, Any], int]]:
    """(block dict, stacked layer count or 0) per block, in layer order."""
    out = []
    for g in params["groups"]:
        for blk in g["blocks"]:
            wq = blk["attn"]["wq"]
            out.append((blk, wq.shape[0] if wq.ndim == 5 else 0))
    return out


def unpack(params, cfg: dict):
    """The tree as plain per-layer matrices, stacked over layers:
    wq (L, d, H*hd), wk/wv (L, d, K*hd), wo (L, H*hd, d), w_gate/w_up
    (L, d, ff), w_down (L, ff, d); and the embedding (V, d)."""
    d = cfg["hidden_size"]
    cols = {"wq": lambda a: a.reshape(a.shape[:-3] + (-1,)),
            "wk": lambda a: a.reshape(a.shape[:-2] + (-1,)),
            "wv": lambda a: a.reshape(a.shape[:-2] + (-1,)),
            "wo": lambda a: a.reshape(a.shape[:-4] + (-1, d))}
    stacks: Dict[str, list] = {k: [] for k in
                               ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                                "w_down")}
    for blk, n in _layer_trees(params):
        mats = {k: f(blk["attn"][k]) for k, f in cols.items()}
        mats.update({k: blk["mlp"][k] for k in ("w_gate", "w_up",
                                                 "w_down")})
        for k, a in mats.items():
            a = a.astype(jnp.float32)
            stacks[k].append(a if n else a[None])
    layers = {k: jnp.concatenate(v, 0) for k, v in stacks.items()}
    return params["embed"]["tok"].astype(jnp.float32), layers


# -- numerics -----------------------------------------------------------------

def _int8(x, per_row: bool):
    axis = -1 if per_row else None
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=per_row) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.round(x / s) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant: Optional[str]):
    if quant == "int8":
        x, w = _int8(x, True), _int8(w, False)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return x @ w


def layer_norm(x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS)


def rope(x, theta: float):
    """x: (..., S, heads, hd); rotate-half RoPE at positions 0..S-1."""
    S, hd = x.shape[-3], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1))[:, None, :]
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1))[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


def _block(cfg: dict, quant, x, w):
    """One layer on x (B, S, d)."""
    B, S, d = x.shape
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // H)
    h = layer_norm(x)
    q = _mm(h, w["wq"], quant).reshape(B, S, H, hd)
    k = _mm(h, w["wk"], quant).reshape(B, S, K, hd)
    v = _mm(h, w["wv"], quant).reshape(B, S, K, hd)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    rep = H // K
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    if quant == "int8":
        q, k, v = _int8(q, True), _int8(k, True), _int8(v, True)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(hd)
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant == "int8":
        p = _int8(p, True)
    o = jnp.einsum("bhst,bthd->bshd", p, v).reshape(B, S, H * hd)
    x = x + _mm(o, w["wo"], quant)
    h = layer_norm(x)
    g = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(g, w["w_down"], quant)


def hidden(cfg: dict, params, tokens, quant=None, remat: bool = False):
    """Final normed hidden states (B, S, d) of token ids (B, S)."""
    tok, layers = unpack(params, cfg)
    x = tok[tokens]
    body = partial(_block, cfg, quant)
    if remat:
        body = jax.checkpoint(body)

    def step(x, w):
        return body(x, w), None

    x, _ = jax.lax.scan(step, x, layers)
    return layer_norm(x), tok


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _logits(cfg_items, params, tokens, quant):
    cfg = dict(cfg_items)
    h, tok = hidden(cfg, params, tokens, quant)
    return _mm(h, tok.T, quant)


def _items(cfg: dict):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "rope_theta")
    return tuple((k, cfg[k]) for k in keys) + (
        ("head_dim", cfg.get("head_dim",
                             cfg["hidden_size"] // cfg["num_attention_heads"])),)


def logits(cfg: dict, params, tokens, quant=None):
    """Logits (B, S, V) of token ids (B, S), float32."""
    with jax.default_matmul_precision("highest"):
        return _logits(_items(cfg), params, jnp.asarray(tokens), quant)


@jax.jit
def to_f32(params):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


# -- serving check ------------------------------------------------------------

def served_gaps(cfg: dict, params, prompt, served, quant=None):
    """For one request: at each served position, how far the served
    token's reference logit lies below the reference's best, and (with
    ``quant``) the same gap for the token the quantized reference puts
    first.  Returns (gap of served tokens, gap of quantized argmax or
    None)."""
    seq = np.asarray(list(prompt) + list(served)[:-1], np.int32)[None]
    first = len(prompt) - 1
    ref = logits(cfg, params, seq)[0, first:]
    best = jnp.max(ref, -1)
    served_gap = best - ref[jnp.arange(len(served)),
                            jnp.asarray(served, jnp.int32)]
    ctrl_gap = None
    if quant is not None:
        q = logits(cfg, params, seq, quant)[0, first:]
        pick = jnp.argmax(q, -1)
        ctrl_gap = best - ref[jnp.arange(len(served)), pick]
    return np.asarray(served_gap), (None if ctrl_gap is None
                                    else np.asarray(ctrl_gap))


# -- training check -----------------------------------------------------------

def loss(cfg: dict, params, tokens, targets, quant=None):
    """Mean next-token cross entropy over every position of the batch."""
    h, tok = hidden(cfg, params, tokens, quant, remat=True)
    lg = _mm(h, tok.T, quant)
    logz = jax.nn.logsumexp(lg, -1)
    tgt = jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - tgt)


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _loss_grad(cfg_items, params, tokens, targets, quant):
    return jax.value_and_grad(
        lambda p: loss(dict(cfg_items), p, tokens, targets, quant))(params)


def loss_and_grad(cfg: dict, params, tokens, targets, quant=None):
    with jax.default_matmul_precision("highest"):
        return _loss_grad(_items(cfg), params, jnp.asarray(tokens),
                          jnp.asarray(targets), quant)


def lr_at(step: int, opt: dict) -> float:
    """Linear warm-up to the peak, then cosine to ``floor`` x peak."""
    peak, warm = opt["peak_lr"], opt["warmup"]
    if step < warm:
        return peak * step / max(warm, 1)
    t = min(max((step - warm) / max(opt["total_steps"] - warm, 1), 0.0), 1.0)
    return peak * (opt["floor"] + (1 - opt["floor"]) * 0.5
                   * (1 + np.cos(np.pi * t)))


@partial(jax.jit, static_argnames=("store_dtype", "b1", "b2", "eps", "wd",
                                   "clip"))
def _adamw(params, grads, mu, nu, step, lr, *, store_dtype, b1, b2, eps,
           wd, clip):
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
    t = step + 1.0

    def upd(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        delta = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if p.ndim >= 2:
            delta = delta + wd * p
        new = p - lr * delta
        if store_dtype is not None:
            # an output in the stored dtype: a round trip inside one
            # program may be folded away as excess precision
            new = new.astype(store_dtype)
        return new, m, v, g

    out = jax.tree_util.tree_map(upd, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), pick(3)


def train(cfg: dict, params, batches, opt: dict, quant=None,
          store_dtype: Optional[str] = None, drop_half: bool = False):
    """``len(batches)`` AdamW steps from ``params`` (float32 tree).
    Returns the losses, the per-leaf norms of the first step's clipped
    gradient, and the per-leaf norms of the weights' change over all the
    steps, with the reference gradient's per-leaf norms for the filter.
    ``drop_half`` plants a fault: the loss is the mean over the first
    half of the batch only."""
    p0 = to_f32(params)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, g_first = [], None
    for step, (tokens, targets) in enumerate(batches):
        if drop_half:
            half = tokens.shape[0] // 2
            tokens, targets = tokens[:half], targets[:half]
        lval, grads = loss_and_grad(cfg, params, tokens, targets, quant)
        params, mu, nu, clipped = _adamw(
            params, grads, mu, nu, jnp.float32(step),
            jnp.float32(lr_at(step, opt)), store_dtype=store_dtype,
            b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            wd=opt["weight_decay"], clip=opt["grad_clip"])
        params = to_f32(params)
        losses.append(float(lval))
        if g_first is None:
            g_first = leaf_norms(clipped)
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, p0))
    return losses, g_first, change


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
            for l in jax.tree_util.tree_leaves(tree)]


def leaf_norms(tree) -> List[float]:
    return [float(x) for x in _norms(tree)]
