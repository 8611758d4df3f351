"""Model assembly for every assigned architecture family.

All families share one parameter/layout convention: layer parameters are
*stacked* along a leading ``layers`` axis and iterated with ``jax.lax.scan``
(keeping HLO compact — essential for the 80 dry-run compiles), with
``jax.checkpoint`` remat per block.

Families:
  dense / moe / vlm / audio : pre-norm attention + (FFN | MoE) blocks
  ssm                       : Mamba2 (SSD) blocks
  hybrid                    : Zamba2 — SSD blocks + one *shared* attention+MLP
                              block applied after every k-th SSD layer
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import logical
from repro.models import attention as attn
from repro.models import layers, moe, ssm
from repro.obs import scopes

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _stacked_init(init_fn, key, n: int):
    """vmap an init over n layer keys -> stacked params (+ axes w/ 'layers')."""
    keys = jax.random.split(key, n)
    p0, a0 = init_fn(keys[0])
    p = jax.vmap(lambda k: init_fn(k)[0])(keys)
    a = jax.tree.map(lambda ax: ("layers",) + ax, a0,
                     is_leaf=lambda x: isinstance(x, tuple))
    return p, a


def _dense_block_init(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    p, a = {}, {}
    p["ln1"], a["ln1"] = layers.rmsnorm_init(cfg.d_model, dtype)
    p["attn"], a["attn"] = attn.attn_init(k1, cfg, dtype)
    p["ln2"], a["ln2"] = layers.rmsnorm_init(cfg.d_model, dtype)
    if cfg.moe is not None:
        p["moe"], a["moe"] = moe.moe_init(k2, cfg, dtype)
    else:
        p["ffn"], a["ffn"] = layers.ffn_init(k2, cfg.d_model, cfg.d_ff, dtype)
    return p, a


def _ssm_block_init(key, cfg: ArchConfig, dtype):
    p, a = {}, {}
    p["ln"], a["ln"] = layers.rmsnorm_init(cfg.d_model, dtype)
    p["mixer"], a["mixer"] = ssm.ssm_init(key, cfg, dtype)
    return p, a


def init_params(cfg: ArchConfig, key) -> Tuple[Params, Params]:
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["embed"], a["embed"] = layers.embedding_init(
        ks[0], cfg.vocab_size, cfg.d_model, dtype, cfg.n_input_codebooks)

    if cfg.family == "ssm":
        p["blocks"], a["blocks"] = _stacked_init(
            lambda k: _ssm_block_init(k, cfg, dtype), ks[1], cfg.n_layers)
    elif cfg.family == "hybrid":
        k_every = cfg.hybrid.attn_every
        assert cfg.n_layers % k_every == 0
        p["blocks"], a["blocks"] = _stacked_init(
            lambda k: _ssm_block_init(k, cfg, dtype), ks[1], cfg.n_layers)
        p["shared"], a["shared"] = _dense_block_init(ks[3], cfg, dtype)
    else:
        p["blocks"], a["blocks"] = _stacked_init(
            lambda k: _dense_block_init(k, cfg, dtype), ks[1], cfg.n_layers)

    p["final_ln"], a["final_ln"] = layers.rmsnorm_init(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        p["head"], a["head"] = layers.lm_head_init(
            ks[2], cfg.d_model, cfg.vocab_size, dtype, cfg.n_output_heads)
    return p, a


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def param_axes(cfg: ArchConfig) -> Params:
    """Logical-axes tree for ``init_params(cfg, ·)[0]`` WITHOUT allocating
    the full model: axes depend only on the tree structure, which the
    reduced same-family config shares exactly."""
    _, axes = init_params(cfg.reduced(), jax.random.PRNGKey(0))
    return axes


def param_shapes(cfg: ArchConfig) -> Params:
    """ShapeDtypeStruct tree of the full parameter pytree (no allocation)."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k)[0],
        jax.ShapeDtypeStruct((2,), jnp.uint32))


def decode_state_axes(cfg: ArchConfig) -> Dict[str, Any]:
    """Logical-axes tree mirroring ``init_decode_state`` output."""
    from repro.models.attention import KVCache
    from repro.models.ssm import SSMState
    kv_axes = KVCache(
        k=("act_layers", "act_batch", "act_seq_dp", "act_kv_heads", None),
        v=("act_layers", "act_batch", "act_seq_dp", "act_kv_heads", None))
    ssm_axes = SSMState(
        conv=("act_layers", "act_batch", None, "act_ssm_inner"),
        h=("act_layers", "act_batch", "act_ssm_heads", None, None))
    axes: Dict[str, Any] = {"pos": ()}
    if cfg.family == "ssm":
        axes["ssm"] = ssm_axes
    elif cfg.family == "hybrid":
        axes["ssm"] = ssm_axes
        axes["kv"] = kv_axes
    else:
        axes["kv"] = kv_axes
    return axes


# ---------------------------------------------------------------------------
# Remat
# ---------------------------------------------------------------------------


def _remat(fn, policy: str):
    """Checkpoint a block.  Both policies also save the flash forward
    kernel's o and lse where attention takes the Pallas path, so the
    backward reads them instead of running that kernel again; on every
    other path nothing carries those names."""
    if policy in (None, "none"):
        return fn
    from repro.kernels.flash_attention import FLASH_RESIDUALS
    cp = jax.checkpoint_policies
    pol = cp.save_only_these_names(*FLASH_RESIDUALS)
    if policy == "dots":
        pol = cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable, pol)
    # "full" / "nothing": block boundaries and the named residuals
    return jax.checkpoint(fn, policy=pol)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _annotate_resid(h):
    return logical(h, ("act_batch", "act_seq", "act_embed"))


def _dense_block_apply(bp, h, cfg, positions):
    x = layers.rmsnorm(bp["ln1"], h, cfg.norm_eps)
    a_out, _ = attn.attn_apply(bp["attn"], x, cfg, positions=positions)
    h = _annotate_resid(h + a_out)
    x = layers.rmsnorm(bp["ln2"], h, cfg.norm_eps)
    if cfg.moe is not None:
        f_out, aux = moe.moe_apply(bp["moe"], x, cfg)
    else:
        f_out, aux = layers.ffn(bp["ffn"], x), jnp.float32(0.0)
    h = _annotate_resid(h + f_out)
    return h, aux


def _ssm_block_apply(bp, h, cfg):
    x = layers.rmsnorm(bp["ln"], h, cfg.norm_eps)
    m_out, _ = ssm.ssm_apply(bp["mixer"], x, cfg)
    return _annotate_resid(h + m_out)


def embed_inputs(params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray]):
    h = layers.embed(params["embed"], batch["tokens"])
    if cfg.vision_tokens:
        ve = batch["vision_embeds"].astype(h.dtype)  # (B, vt, d)
        h = jax.lax.dynamic_update_slice(h, ve, (0, 0, 0))
    return _annotate_resid(h)


@scopes.scoped(scopes.LM_HEAD_LOSS)
def logits_from_hidden(params, cfg: ArchConfig, h):
    h = layers.rmsnorm(params["final_ln"], h, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = layers.tied_lm_head(params["embed"], h)
        names = ("act_batch", "act_seq", "act_vocab")
    else:
        logits = layers.lm_head(params["head"], h)
        names = (("act_batch", "act_seq", "act_vocab")
                 if cfg.n_output_heads == 1
                 else ("act_batch", "act_seq", None, "act_vocab"))
    return logical(logits, names)


def forward(params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray],
            remat_policy: Optional[str] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (logits, aux_loss).  Train/prefill path (full sequence)."""
    policy = remat_policy or cfg.remat_policy
    h = embed_inputs(params, cfg, batch)
    B, S = h.shape[0], h.shape[1]
    positions = attn._positions_for(cfg, B, S)

    if cfg.family == "ssm":
        def body(hc, bp):
            return _ssm_block_apply(bp, hc, cfg), None
        h, _ = jax.lax.scan(_remat(body, policy), h, params["blocks"])
        aux = jnp.float32(0.0)
    elif cfg.family == "hybrid":
        k_every = cfg.hybrid.attn_every
        n_super = cfg.n_layers // k_every
        blocks = jax.tree.map(
            lambda x: x.reshape((n_super, k_every) + x.shape[1:]),
            params["blocks"])
        shared = params["shared"]

        def super_body(hc, bp_chunk):
            def inner(hh, bp):
                return _ssm_block_apply(bp, hh, cfg), None
            hc, _ = jax.lax.scan(inner, hc, bp_chunk)
            hc, _ = _dense_block_apply(shared, hc, cfg, positions)
            return hc, None

        h, _ = jax.lax.scan(_remat(super_body, policy), h, blocks)
        aux = jnp.float32(0.0)
    else:
        def body(carry, bp):
            hc, aux_acc = carry
            hc, aux = _dense_block_apply(bp, hc, cfg, positions)
            return (hc, aux_acc + aux), None
        (h, aux), _ = jax.lax.scan(_remat(body, policy),
                                   (h, jnp.float32(0.0)), params["blocks"])

    return logits_from_hidden(params, cfg, h), aux


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def loss_fn(params, cfg: ArchConfig, batch, remat_policy=None):
    logits, aux = forward(params, cfg, batch, remat_policy)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if cfg.n_output_heads > 1:  # (B,S,heads,V) vs (B,S,heads)
        ce = layers.softmax_xent(logits, labels,
                                 mask[..., None] if mask is not None else None)
    else:
        ce = layers.softmax_xent(logits, labels, mask)
    total = ce
    if cfg.moe is not None:
        total = total + cfg.moe.aux_loss_weight * aux
    return total, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------


def init_decode_state(cfg: ArchConfig, B: int, max_len: int,
                      dtype=None) -> Dict[str, Any]:
    """Stacked per-layer decode caches (KV and/or SSM state) + position."""
    dtype = dtype or jnp.dtype(cfg.compute_dtype)

    def stack(tree, n):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)

    state: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.family == "ssm":
        state["ssm"] = stack(ssm.init_ssm_state(cfg, B, dtype), cfg.n_layers)
    elif cfg.family == "hybrid":
        n_sites = cfg.n_layers // cfg.hybrid.attn_every
        state["ssm"] = stack(ssm.init_ssm_state(cfg, B, dtype), cfg.n_layers)
        state["kv"] = stack(attn.init_cache(cfg, B, max_len, dtype), n_sites)
    else:
        state["kv"] = stack(attn.init_cache(cfg, B, max_len, dtype),
                            cfg.n_layers)
    return state


def decode_step(params, cfg: ArchConfig, state: Dict[str, Any],
                tokens: jnp.ndarray, batch_extras: Optional[dict] = None
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One-token decode.  tokens (B, 1[, n_codebooks]) -> logits, new state."""
    pos = state["pos"]
    h = layers.embed(params["embed"], tokens)
    h = _annotate_resid(h)
    B = h.shape[0]
    positions = attn._positions_for(cfg, B, 1, offset=pos)
    new_state: Dict[str, Any] = {"pos": pos + 1}

    if cfg.family == "ssm":
        def body(hc, inp):
            bp, st = inp
            x = layers.rmsnorm(bp["ln"], hc, cfg.norm_eps)
            m, st_new = ssm.ssm_apply(bp["mixer"], x, cfg, state=st)
            return hc + m, st_new
        h, new_ssm = jax.lax.scan(body, h, (params["blocks"], state["ssm"]))
        new_state["ssm"] = new_ssm
    elif cfg.family == "hybrid":
        k_every = cfg.hybrid.attn_every
        n_super = cfg.n_layers // k_every
        blocks = jax.tree.map(
            lambda x: x.reshape((n_super, k_every) + x.shape[1:]),
            params["blocks"])
        ssm_states = jax.tree.map(
            lambda x: x.reshape((n_super, k_every) + x.shape[1:]),
            state["ssm"])
        shared = params["shared"]

        def super_body(hc, inp):
            bp_chunk, st_chunk, kv = inp

            def inner(hh, i2):
                bp, st = i2
                x = layers.rmsnorm(bp["ln"], hh, cfg.norm_eps)
                m, st_new = ssm.ssm_apply(bp["mixer"], x, cfg, state=st)
                return hh + m, st_new
            hc, st_new = jax.lax.scan(inner, hc, (bp_chunk, st_chunk))
            x = layers.rmsnorm(shared["ln1"], hc, cfg.norm_eps)
            a_out, kv_new = attn.attn_apply(shared["attn"], x, cfg,
                                            positions=positions,
                                            cache=kv, cache_pos=pos)
            hc = hc + a_out
            x = layers.rmsnorm(shared["ln2"], hc, cfg.norm_eps)
            hc = hc + layers.ffn(shared["ffn"], x)
            return hc, (st_new, kv_new)

        h, (new_ssm, new_kv) = jax.lax.scan(
            super_body, h, (blocks, ssm_states, state["kv"]))
        new_state["ssm"] = jax.tree.map(
            lambda x: x.reshape((cfg.n_layers,) + x.shape[2:]), new_ssm)
        new_state["kv"] = new_kv
    else:
        def body(hc, inp):
            bp, kv = inp
            x = layers.rmsnorm(bp["ln1"], hc, cfg.norm_eps)
            a_out, kv_new = attn.attn_apply(bp["attn"], x, cfg,
                                            positions=positions,
                                            cache=kv, cache_pos=pos)
            hc = _annotate_resid(hc + a_out)
            x = layers.rmsnorm(bp["ln2"], hc, cfg.norm_eps)
            if cfg.moe is not None:
                f_out, _ = moe.moe_apply(bp["moe"], x, cfg)
            else:
                f_out = layers.ffn(bp["ffn"], x)
            return _annotate_resid(hc + f_out), kv_new
        h, new_kv = jax.lax.scan(body, h, (params["blocks"], state["kv"]))
        new_state["kv"] = new_kv

    logits = logits_from_hidden(params, cfg, h)
    return logits, new_state
