"""Mixture-of-Experts layer: top-k token-choice routing with GShard-style
dense dispatch (capacity-bounded, einsum dispatch/combine tensors).

TP mode shards expert FFN dims over the model axis; EP mode additionally
shards the expert dim (applied when it divides the axis — see Plan.moe_mode).
The dispatch einsum over (tokens × experts × capacity) is grouped to bound
the dispatch-tensor size.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import logical
from repro.models import layers
from repro.obs import scopes

GROUP_TOKENS = 2048  # dispatch group size (tokens)


def moe_init(key, cfg, dtype):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["router"] = (jax.random.normal(ks[0], (d, E), jnp.float32) * 0.02).astype(dtype)
    a["router"] = ("embed", "expert")

    def ew(k, shape, axes):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dtype), axes

    p["gate"], a["gate"] = ew(ks[1], (E, d, ff), ("expert", "embed", "ff"))
    p["up"], a["up"] = ew(ks[2], (E, d, ff), ("expert", "embed", "ff"))
    p["down"], a["down"] = ew(ks[3], (E, ff, d), ("expert", "ff", "embed"))
    return p, a


def _capacity(tokens_per_group: int, E: int, top_k: int, factor: float) -> int:
    c = int(math.ceil(top_k * tokens_per_group * factor / E))
    return max(c, 4)


@scopes.scoped(scopes.MOE)
def moe_apply(p, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    mcfg = cfg.moe
    B, S, d = x.shape
    E, K = mcfg.n_experts, mcfg.top_k
    T = B * S
    tg = min(GROUP_TOKENS, T)
    assert T % tg == 0, (T, tg)
    G = T // tg
    C = _capacity(tg, E, K, mcfg.capacity_factor)

    xg = x.reshape(G, tg, d)
    logits = (xg.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # (G,t,E)
    probs = jax.nn.softmax(logits, axis=-1)

    # --- token-choice top-K with capacity (GShard 'tokens choose') ---
    combine = jnp.zeros((G, tg, E, C), jnp.float32)
    expert_usage = jnp.zeros((G, E), jnp.float32)  # tokens already assigned
    remaining = probs
    gates_sum = jnp.zeros((G, tg), jnp.float32)
    picked_masks = []
    for _ in range(K):
        idx = jnp.argmax(remaining, axis=-1)  # (G,t)
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (G,t,E)
        gate = jnp.sum(probs * mask, axis=-1)  # (G,t)
        # position within expert buffer (0-indexed)
        pos = jnp.cumsum(mask, axis=1) - 1.0 + expert_usage[:, None, :]
        pos = jnp.sum(pos * mask, axis=-1)  # (G,t)
        keep = pos < C
        gate = gate * keep
        onehot_c = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
        combine = combine + (gate[..., None] * mask)[..., None] * onehot_c[:, :, None, :]
        expert_usage = expert_usage + jnp.sum(mask * keep[..., None], axis=1)
        gates_sum = gates_sum + gate
        picked_masks.append(mask)
        remaining = remaining * (1.0 - mask)  # exclude chosen expert

    # normalize combine weights over the K picks (Mixtral renormalizes top-k)
    combine = combine / jnp.maximum(gates_sum, 1e-9)[..., None, None]
    dispatch = (combine > 0.0).astype(x.dtype)

    # --- aux load-balancing loss (Switch/GShard style, over first choice) ---
    frac_tokens = jnp.mean(picked_masks[0], axis=1)  # (G,E)
    frac_probs = jnp.mean(probs, axis=1)  # (G,E)
    aux = E * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))

    # --- dispatch -> expert FFN -> combine ---
    xe = jnp.einsum("gtec,gtd->egcd", dispatch, xg)  # (E,G,C,d)
    xe = logical(xe, ("act_expert", "act_batch", None, "act_embed"))
    h_g = jnp.einsum("egcd,edf->egcf", xe, p["gate"])
    h_u = jnp.einsum("egcd,edf->egcf", xe, p["up"])
    h = jax.nn.silu(h_g) * h_u
    h = logical(h, ("act_expert", "act_batch", None, "act_ff"))
    ye = jnp.einsum("egcf,efd->egcd", h, p["down"])  # (E,G,C,d)
    y = jnp.einsum("egcd,gtec->gtd", ye, combine.astype(x.dtype))
    return y.reshape(B, S, d), aux.astype(jnp.float32)
