"""Pieces the plain references share: precision, norms, the loss, and the
training recipe (clipping, AdamW, the learning-rate schedule) written out
in float32 from the configuration file's ``training`` block.

Nothing here imports the program.  Every matrix product goes through an
``Ops`` object, so the same reference runs at float32 (``HIGHEST``) and, as
the lower-precision control, with its matrix operands in fp8.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


class Ops:
    """Matrix products in float32 at full precision."""

    def q(self, x):
        return x

    def mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b), precision=HIGHEST,
                          preferred_element_type=F32)

    def ein(self, spec: str, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b), precision=HIGHEST,
                          preferred_element_type=F32)


def _scaled_cast(x, dtype):
    """Round ``x`` to ``dtype`` under one per-tensor scale (amax to the
    format's largest finite value), as fp8 training recipes do."""
    top = float(jnp.finfo(dtype).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(F32) * s


@jax.custom_vjp
def fp8(x):
    return _scaled_cast(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, ct):
    return (_scaled_cast(ct, jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


class Fp8Ops(Ops):
    """The control: operands of every matrix product in fp8 (e4m3 forward,
    e5m2 cotangents, per-tensor scales), accumulated in float32."""

    def q(self, x):
        return fp8(x.astype(F32))


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def scan_layers(layer, x, blocks):
    """Run ``layer`` over the stacked layers, keeping only the inputs of
    about sqrt(L) groups of layers and, while a group is recomputed for the
    backward pass, those of its layers."""
    L = jax.tree.leaves(blocks)[0].shape[0]
    g = min((d for d in range(1, L + 1) if L % d == 0),
            key=lambda d: abs(d - math.sqrt(L)))
    groups = jax.tree.map(lambda w: w.reshape((L // g, g) + w.shape[1:]),
                          blocks)

    @jax.checkpoint
    def group(x, gp):
        return jax.lax.scan(jax.checkpoint(layer), x, gp)[0], None

    return jax.lax.scan(group, x, groups)[0]


def head_xent_sum(h, w_vocab, labels, mask, ops: Ops, tokens: int = 2048):
    """Sum over positions of mask · (logsumexp − logit of the label), with
    ``h @ w_vocab.T`` formed for about ``tokens`` tokens at a time."""
    b, S, d = h.shape
    chunk = math.gcd(S, max(1, tokens // b))
    n = S // chunk
    hs = h.reshape(b, n, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(b, n, chunk).swapaxes(0, 1)
    ms = mask.reshape(b, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def one(hc, lc, mc):
        logits = ops.mm(hc, w_vocab.T)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - ll) * mc)

    def body(acc, xs):
        return acc + one(*xs), None

    total, _ = jax.lax.scan(body, jnp.zeros((), F32), (hs, ls, ms))
    return total


# ---------------------------------------------------------------------------
# Training recipe
# ---------------------------------------------------------------------------


def learning_rate(tr: Dict[str, float], step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup`` steps, then a cosine to
    ``lr_floor`` × ``lr`` at ``total_steps``."""
    peak, warmup, total = tr["lr"], tr["warmup"], tr["total_steps"]
    if step < warmup:
        return peak * min(1.0, step / max(warmup, 1))
    t = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    floor = tr["lr_floor"]
    return peak * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * t)))


def _clip(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def _adamw(params, grads, m, v, count, lr, tr):
    """One AdamW update in float32; parameters are stored back in their
    own dtype, as the configuration keeps them."""
    b1, b2, eps, wd = tr["b1"], tr["b2"], tr["eps"], tr["weight_decay"]
    bc1 = 1.0 - b1 ** count
    bc2 = 1.0 - b2 ** count

    def one(p, g, mm, vv):
        mm = b1 * mm + (1 - b1) * g
        vv = b2 * vv + (1 - b2) * g * g
        pf = p.astype(F32)
        upd = (mm / bc1) / (jnp.sqrt(vv / bc2) + eps) + wd * pf
        return (pf - lr * upd).astype(p.dtype), mm, vv

    out = jax.tree.map(one, params, grads, m, v)
    is3 = lambda x: isinstance(x, tuple)
    pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=is3)
    return pick(0), pick(1), pick(2)


def leaf_norms(tree) -> List[float]:
    """Float32 norm of every leaf, in ``jax.tree.leaves`` order."""
    return [float(x) for x in jax.device_get(jax.jit(
        lambda t: [jnp.sqrt(jnp.sum(jnp.square(l.astype(F32))))
                   for l in jax.tree.leaves(t)])(tree))]


def _block(batch, start: int, n: int):
    """Rows ``start`` to ``start + n`` of a batch; past its end, copies of
    its first row with no loss, so that every block has one shape."""
    out = []
    for k in ("tokens", "labels", "loss_mask"):
        x = batch[k][start:start + n]
        pad = n - x.shape[0]
        if pad:
            filler = np.zeros_like(x[:1]) if k == "loss_mask" else x[:1]
            x = np.concatenate([x] + [filler] * pad)
        out.append(jnp.asarray(x))
    return out


def train_steps(block_loss: Callable, config: Dict[str, Any], params0,
                batches: Sequence[Dict[str, np.ndarray]], ops: Ops
                ) -> Dict[str, Any]:
    """Follow the program's first steps: loss, clipped gradient and AdamW
    update of each batch, the loss summed over blocks of rows so that it
    fits.

    Returns the losses, the per-leaf norms of the first clipped gradient,
    and the per-leaf norms of the parameters' change over all the steps.
    """
    tr = config["training"]
    seq = batches[0]["tokens"].shape[1]
    per_block = max(1, config["reference_block_tokens"] // seq)
    n_rows = batches[0]["tokens"].shape[0]

    def summed(pf, tokens, labels, mask):
        return block_loss(pf, config, tokens, labels, mask, ops)

    grad_block = jax.jit(jax.value_and_grad(summed))
    upcast = jax.jit(lambda p: jax.tree.map(lambda x: x.astype(F32), p))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    finish = jax.jit(lambda g, n: _clip(jax.tree.map(lambda x: x / n, g),
                                        tr["clip_norm"]))
    update = jax.jit(
        lambda p, g, mm, vv, c, lr: _adamw(p, g, mm, vv, c, lr, tr),
        donate_argnums=(0, 2, 3))

    params = jax.tree.map(jnp.array, params0)
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    losses, first_grad = [], None
    for k, batch in enumerate(batches):
        pf = upcast(params)
        total, grads = 0.0, None
        n_tok = float(np.sum(batch["loss_mask"]))
        for i in range(0, n_rows, per_block):
            s, g = grad_block(pf, *_block(batch, i, per_block))
            total += float(s)
            grads = g if grads is None else add(grads, g)
        del pf
        n = max(n_tok, 1.0)
        losses.append(total / n)
        grads = finish(grads, n)
        if k == 0:
            first_grad = leaf_norms(grads)
        params, m, v = update(params, grads, m, v, float(k + 1),
                              learning_rate(tr, k))
    change = leaf_norms(jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b))(params, params0))
    return {"losses": losses, "first_grad": first_grad, "change": change}
