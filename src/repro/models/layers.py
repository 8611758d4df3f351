"""Basic functional layers.

Parameters are plain pytrees (nested dicts of jnp arrays).  Every ``init_*``
returns ``(params, axes)`` where ``axes`` mirrors ``params`` with tuples of
*logical axis names* per dimension — the sharding layer maps logical axes to
mesh axes (MaxText-style), see ``repro.distributed.sharding``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import scopes

Params = dict
Axes = dict

INIT_SCALE = 0.02


def _dtype(name: str):
    return jnp.dtype(name)


def dense_init(key, in_dim: int, out_dim: int, dtype, in_axis: str, out_axis: str,
               bias: bool = False):
    w = jax.random.normal(key, (in_dim, out_dim), dtype=jnp.float32) * INIT_SCALE
    p = {"w": w.astype(dtype)}
    a = {"w": (in_axis, out_axis)}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype=dtype)
        a["b"] = (out_axis,)
    return p, a


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def rmsnorm_init(dim: int, dtype):
    return {"scale": jnp.ones((dim,), dtype=dtype)}, {"scale": ("embed",)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# SwiGLU FFN
# ---------------------------------------------------------------------------


def ffn_init(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    p, a = {}, {}
    p["gate"], a["gate"] = dense_init(k1, d_model, d_ff, dtype, "embed", "ff")
    p["up"], a["up"] = dense_init(k2, d_model, d_ff, dtype, "embed", "ff")
    p["down"], a["down"] = dense_init(k3, d_ff, d_model, dtype, "ff", "embed")
    return p, a


@scopes.scoped(scopes.MLP)
def ffn(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = dense(p["gate"], x)
    u = dense(p["up"], x)
    return dense(p["down"], jax.nn.silu(g) * u)


# ---------------------------------------------------------------------------
# Embedding / heads
# ---------------------------------------------------------------------------


def embedding_init(key, vocab: int, d_model: int, dtype, n_codebooks: int = 1):
    shape = (n_codebooks, vocab, d_model) if n_codebooks > 1 else (vocab, d_model)
    w = jax.random.normal(key, shape, dtype=jnp.float32) * INIT_SCALE
    axes = ("codebook", "vocab", "embed") if n_codebooks > 1 else ("vocab", "embed")
    return {"w": w.astype(dtype)}, {"w": axes}


def embed(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    """tokens (..., [n_codebooks]) int32 -> (..., d_model)."""
    w = p["w"]
    if w.ndim == 3:  # multi-codebook (MusicGen): sum codebook embeddings
        # tokens: (B, S, n_codebooks)
        outs = [jnp.take(w[c], tokens[..., c], axis=0) for c in range(w.shape[0])]
        return sum(outs)
    return jnp.take(w, tokens, axis=0)


def lm_head_init(key, d_model: int, vocab: int, dtype, n_heads: int = 1):
    shape = (n_heads, d_model, vocab) if n_heads > 1 else (d_model, vocab)
    w = jax.random.normal(key, shape, dtype=jnp.float32) * INIT_SCALE
    axes = ("head_idx", "embed", "vocab") if n_heads > 1 else ("embed", "vocab")
    return {"w": w.astype(dtype)}, {"w": axes}


def lm_head(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    w = p["w"]
    if w.ndim == 3:  # (n_heads, d, V) -> (..., n_heads, V)
        return jnp.einsum("bsd,hdv->bshv", x, w)
    return x @ w


def tied_lm_head(embed_p: Params, x: jnp.ndarray) -> jnp.ndarray:
    w = embed_p["w"]
    assert w.ndim == 2
    return x @ w.T


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


@scopes.scoped(scopes.LM_HEAD_LOSS)
def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Mean cross-entropy; logits (..., V) in any float dtype (f32 math)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
