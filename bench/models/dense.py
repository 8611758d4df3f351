"""Plain float32 reference of a Llama-architecture decoder (SmolLM):
token embedding, pre-norm blocks of grouped-query attention with rotary
positions and a SwiGLU MLP, a final RMSNorm, the tied LM head and masked
cross-entropy.

Parameters use the layout the trainer keeps (layers stacked on a leading
axis), so the weights this file makes from a seed can be handed to the
trainer as its starting state; nothing else is taken from the program.
The one departure from the published model is the program's: documents
packed into a row attend to the documents before them in the row, and
positions count from the start of the row.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.models import common

F32 = jnp.float32
INIT_SCALE = 0.02


def param_shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    d, ff, V, L = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                   c["num_hidden_layers"])
    H, KV, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    mat = lambda *s: ("normal", s)
    one = lambda *s: ("ones", s)
    return {
        "embed": {"w": mat(V, d)},
        "blocks": {
            "ln1": {"scale": one(L, d)},
            "attn": {"wq": {"w": mat(L, d, H * dh)},
                     "wk": {"w": mat(L, d, KV * dh)},
                     "wv": {"w": mat(L, d, KV * dh)},
                     "wo": {"w": mat(L, H * dh, d)}},
            "ln2": {"scale": one(L, d)},
            "ffn": {"gate": {"w": mat(L, d, ff)},
                    "up": {"w": mat(L, d, ff)},
                    "down": {"w": mat(L, ff, d)}},
        },
        "final_ln": {"scale": one(d)},
    }


def init_params(c: Dict[str, Any], key):
    """Weights from one key: N(0, 0.02²) matrices, unit norm scales, in the
    configuration's parameter dtype."""
    dtype = jnp.dtype(c["param_dtype"])
    shapes = param_shapes(c)
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and x[0] in
        ("normal", "ones"))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (kind, shape) in zip(keys, leaves):
        if kind == "ones":
            out.append(jnp.ones(shape, dtype))
        else:
            out.append((jax.random.normal(k, shape, F32) * INIT_SCALE
                        ).astype(dtype))
    return jax.tree.unflatten(tree, out)


def rope(x, theta):
    """Rotary positions, rotate-half convention; x (b, S, heads, dh)."""
    S, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs  # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block_loss(p, c, tokens, labels, mask, ops: common.Ops):
    """Sum of the masked next-token loss over rows (b, S); ``p`` float32."""
    H, KV, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    b, S = tokens.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        h = common.rmsnorm(x, lp["ln1"]["scale"], eps)
        a = lp["attn"]
        q = rope(ops.mm(h, a["wq"]["w"]).reshape(b, S, H, dh), theta)
        k = rope(ops.mm(h, a["wk"]["w"]).reshape(b, S, KV, dh), theta)
        v = ops.mm(h, a["wv"]["w"]).reshape(b, S, KV, dh)
        # query head j reads key/value head j // (H / KV)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = ops.ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        s = jnp.where(causal, s, -jnp.inf)
        o = ops.ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + ops.mm(o.reshape(b, S, H * dh), a["wo"]["w"])
        h = common.rmsnorm(x, lp["ln2"]["scale"], eps)
        f = lp["ffn"]
        g = jax.nn.silu(ops.mm(h, f["gate"]["w"])) * ops.mm(h, f["up"]["w"])
        return x + ops.mm(g, f["down"]["w"]), None

    x = common.scan_layers(layer, p["embed"]["w"][tokens], p["blocks"])
    x = common.rmsnorm(x, p["final_ln"]["scale"], eps)
    return common.head_xent_sum(x, p["embed"]["w"], labels, mask, ops)


def attention_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs of one trained token's causal attention, forward and
    backward: q·k and p·v over an average of ``seq_len / 2`` keys in every
    layer, whatever implements them."""
    L, H, dh = (c["num_hidden_layers"], c["num_attention_heads"],
                c["head_dim"])
    return 6.0 * L * 2 * H * dh * (seq_len / 2)


def flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward (3 × the
    forward's 2 × multiply-adds): every projection, the MLP, the tied LM
    head, and causal attention (``attention_flops_per_token``).
    Recomputation does not count; the embedding lookup is a gather."""
    d, ff, V, L = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                   c["num_hidden_layers"])
    H, KV, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    proj = d * H * dh + 2 * d * KV * dh + H * dh * d
    mlp = 3 * d * ff
    macs = L * (proj + mlp) + d * V
    return 6.0 * macs + attention_flops_per_token(c, seq_len)
