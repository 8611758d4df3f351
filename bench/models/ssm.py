"""Plain float32 reference of Mamba-2 (arXiv:2405.21060): token embedding,
pre-norm blocks of the Mamba-2 mixer, a final RMSNorm, the tied LM head and
masked cross-entropy.

The mixer: one input projection to (z, x, B, C, dt); a causal depthwise
convolution with SiLU over (x, B, C); dt = softplus(dt + dt_bias);
A = -exp(A_log); the selective state-space recurrence run step by step,

    h_t = exp(dt_t · A) · h_{t-1} + dt_t · x_t ⊗ B_t,
    y_t = h_t · C_t + D · x_t,

then RMSNorm of y · SiLU(z) and the output projection.  This is the
recurrence itself, not the chunked (SSD) algorithm the program runs.

Parameters use the layout the trainer keeps (layers stacked on a leading
axis); nothing else is taken from the program.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.models import common

F32 = jnp.float32
INIT_SCALE = 0.02
#: the recurrence is checkpointed every this many steps (memory ~ S / SPAN)
SPAN = 64


def _sizes(c):
    d = c["d_model"]
    din = c["expand"] * d
    H = din // c["headdim"]
    GN = c["ngroups"] * c["d_state"]
    return d, din, H, GN


def param_shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    d, din, H, GN = _sizes(c)
    L, V, k = c["n_layer"], c["vocab_size"], c["d_conv"]
    conv = din + 2 * GN
    return {
        "embed": {"w": ("normal", (V, d))},
        "blocks": {
            "ln": {"scale": ("ones", (L, d))},
            "mixer": {
                "in_proj": {"w": ("normal", (L, d, 2 * din + 2 * GN + H))},
                "conv_w": ("normal", (L, k, conv)),
                "conv_b": ("zeros", (L, conv)),
                "A_log": ("A_log", (L, H)),
                "D": ("ones32", (L, H)),
                "dt_bias": ("dt_bias", (L, H)),
                "norm": ("ones", (L, din)),
                "out_proj": {"w": ("normal", (L, din, d))},
            },
        },
        "final_ln": {"scale": ("ones", (d,))},
    }


def init_params(c: Dict[str, Any], key):
    """Weights from one key.  Matrices and the convolution N(0, 0.02²) in
    the parameter dtype; A_log, D and dt_bias in float32 as Mamba-2
    initialises them: A uniform in [1, 16], dt log-uniform in
    [dt_min, dt_max] (floored at dt_init_floor) through softplus⁻¹."""
    dtype = jnp.dtype(c["param_dtype"])
    shapes = param_shapes(c)
    leaves, tree = jax.tree.flatten(
        shapes,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], str))
    keys = jax.random.split(key, len(leaves))
    lo, hi = c["A_init_range"]
    out = []
    for k, (kind, shape) in zip(keys, leaves):
        if kind == "normal":
            x = (jax.random.normal(k, shape, F32) * INIT_SCALE).astype(dtype)
        elif kind == "ones":
            x = jnp.ones(shape, dtype)
        elif kind == "zeros":
            x = jnp.zeros(shape, dtype)
        elif kind == "ones32":
            x = jnp.ones(shape, F32)
        elif kind == "A_log":
            x = jnp.log(jax.random.uniform(k, shape, F32, lo, hi))
        else:  # dt_bias
            u = jax.random.uniform(k, shape, F32)
            dt = jnp.exp(u * (math.log(c["dt_max"]) - math.log(c["dt_min"]))
                         + math.log(c["dt_min"]))
            dt = jnp.maximum(dt, c["dt_init_floor"])
            x = dt + jnp.log(-jnp.expm1(-dt))  # softplus⁻¹
        out.append(x)
    return jax.tree.unflatten(tree, out)


def recurrence(x, dt, A, Bm, Cm):
    """Step-by-step selective scan from a zero state.

    x (b, S, H, P), dt (b, S, H), A (H,), Bm/Cm (b, S, G, N) -> y (b, S, H, P).
    Head j reads group j // (H / G).  Checkpointed every ``SPAN`` steps.
    """
    b, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    R = H // G
    span = math.gcd(S, SPAN)
    a = jnp.exp(dt * A).reshape(b, S, G, R)
    u = (dt[..., None] * x).reshape(b, S, G, R, P)

    def step(h, inp):  # h (b, G, R, P, N)
        at, ut, Bt, Ct = inp
        h = (at[..., None, None] * h
             + ut[..., None] * Bt[:, :, None, None, :])
        return h, jnp.sum(h * Ct[:, :, None, None, :], axis=-1)

    @jax.checkpoint
    def run_span(h, inp):
        return jax.lax.scan(step, h, inp, unroll=8)

    def t_major(v):  # (b, S, ...) -> (S/span, span, b, ...)
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((S // span, span) + v.shape[1:])

    h0 = jnp.zeros((b, G, R, P, N), F32)
    _, ys = jax.lax.scan(run_span, h0,
                         tuple(t_major(v) for v in (a, u, Bm, Cm)))
    return jnp.moveaxis(ys.reshape((S, b, H, P)), 0, 1)


def mixer(mp, x, c, ops: common.Ops):
    d, din, H, GN = _sizes(c)
    b, S, _ = x.shape
    P, G, N = c["headdim"], c["ngroups"], c["d_state"]
    proj = ops.mm(x, mp["in_proj"]["w"])
    z, xbc, dt = (proj[..., :din], proj[..., din:2 * din + 2 * GN],
                  proj[..., 2 * din + 2 * GN:])
    k = mp["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, j:j + S] * mp["conv_w"][j] for j in range(k))
    xbc = jax.nn.silu(conv + mp["conv_b"])
    xs = xbc[..., :din].reshape(b, S, H, P)
    Bm = xbc[..., din:din + GN].reshape(b, S, G, N)
    Cm = xbc[..., din + GN:].reshape(b, S, G, N)
    dt = jax.nn.softplus(dt + mp["dt_bias"])
    y = recurrence(xs, dt, -jnp.exp(mp["A_log"]), Bm, Cm)
    y = (y + xs * mp["D"][:, None]).reshape(b, S, din)
    y = common.rmsnorm(y * jax.nn.silu(z), mp["norm"], c["norm_epsilon"])
    return ops.mm(y, mp["out_proj"]["w"])


def block_loss(p, c, tokens, labels, mask, ops: common.Ops):
    """Sum of the masked next-token loss over rows (b, S); ``p`` float32."""
    eps = c["norm_epsilon"]

    def layer(x, lp):
        h = common.rmsnorm(x, lp["ln"]["scale"], eps)
        return x + mixer(lp["mixer"], h, c, ops), None

    x = common.scan_layers(layer, p["embed"]["w"][tokens], p["blocks"])
    x = common.rmsnorm(x, p["final_ln"]["scale"], eps)
    return common.head_xent_sum(x, p["embed"]["w"], labels, mask, ops)


def flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward (3 × the
    forward's 2 × multiply-adds): the input and output projections, the
    depthwise convolution, the SSD terms of the chunked algorithm at chunk
    length Q (C·B over Q positions, the Q-long weighted sum of x, and the
    state's read and write), and the tied LM head."""
    d, din, H, GN = _sizes(c)
    P, N, G, Q = c["headdim"], c["d_state"], c["ngroups"], c["chunk_size"]
    proj = d * (2 * din + 2 * GN + H) + din * d
    conv = (din + 2 * GN) * c["d_conv"]
    ssd = H * (Q * N + Q * P + 2 * P * N)
    macs = c["n_layer"] * (proj + conv + ssd) + d * c["vocab_size"]
    return 6.0 * macs
