"""Attention: GQA, RoPE / M-RoPE, sliding-window, memory-efficient chunked
softmax (pure-XLA flash-attention analog used by the distributed lowering),
and KV-cache decode.

The Pallas flash-attention kernel in ``repro.kernels.flash_attention`` is the
TPU hot-path implementation of the same contraction; ``attention_core`` here
is both the XLA production path (it lowers on any backend and keeps peak
memory to O(chunk²)) and the reference the kernel is validated against.
"""
from __future__ import annotations

import functools
import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import logical
from repro.models import layers
from repro.obs import scopes

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def _rope_angles(positions: jnp.ndarray, half: int, theta: float) -> jnp.ndarray:
    """positions (..., S) -> angles (..., S, half)  [f32]."""
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    return positions.astype(jnp.float32)[..., None] * freqs


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x (B, S, H, dh); positions (B, S) int32."""
    half = x.shape[-1] // 2
    ang = _rope_angles(positions, half, theta)  # (B, S, half)
    cos = jnp.cos(ang)[..., None, :]  # (B, S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
                sections: Tuple[int, ...]) -> jnp.ndarray:
    """Qwen2-VL M-RoPE.  positions (B, S, 3) = (t, h, w) ids.

    The head_dim//2 frequency slots are partitioned into ``sections`` (t,h,w);
    each slot uses the position component of its section.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    # per-frequency-slot section id: (half,)
    sec_id = jnp.repeat(jnp.arange(len(sections)), jnp.array(sections),
                        total_repeat_length=half)
    # (B, S, half): pick the position component per slot
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),
        jnp.broadcast_to(sec_id, positions.shape[:-1] + (half,)).astype(jnp.int32),
        axis=-1,
    )
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos * freqs  # (B, S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core
# ---------------------------------------------------------------------------


def _mask(q_pos, k_pos, causal: bool, window: Optional[int]):
    """q_pos (Sq,), k_pos (Sk,) -> bool (Sq, Sk), True = attend."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def _plain_attention(q, k, v, q_pos, k_pos, causal, window, scale):
    """Materialized-logits path (small Sq·Sk).  GQA via head grouping."""
    B, Sq, H, dh = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qg = q.reshape(B, Sq, KVH, G, dh)
    s = jnp.einsum("bqkgd,bskd->bqkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    m = _mask(q_pos, k_pos, causal, window)  # (Sq, Sk)
    s = jnp.where(m[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, dh).astype(q.dtype)


_BIAS_NEG = -1e9   # additive mask bias (finite: keeps exp() well-defined)
_M_INIT = -1e4     # running-max floor; masked rows renormalize to 0


def _chunked_attention(q, k, v, q_pos, k_pos, causal, window, scale,
                       chunk_q: int, chunk_kv: int):
    """Online-softmax double loop (scan over q chunks × scan over kv chunks).

    Peak live memory is O(B · chunk_q · chunk_kv) logits — this is what makes
    32k-token prefill lowerable.  Fully-masked chunk pairs are skipped with
    ``lax.cond`` (runtime savings on causal lower-triangle).

    Masking is ADDITIVE (a (cq, ck) f32 bias), not a ``where`` over the
    (B, cq, KVH, G, ck) score tensor: the where's pred operand becomes a
    per-kv-step scan residual in the backward pass — a hoisted
    (nk, B, cq, KVH, G, ck) stack that cost ~8 GB/layer before this change
    (EXPERIMENTS.md §Perf, iteration 1).  The bias adds with a trivial
    backward and leaves masked lanes at exp(-1e9 − m) ≡ 0, with the running
    max floored at ``_M_INIT`` so fully-masked rows stay exactly zero.
    """
    B, Sq, H, dh = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    assert Sq % chunk_q == 0 and Skv % chunk_kv == 0, (Sq, chunk_q, Skv, chunk_kv)
    nq, nk = Sq // chunk_q, Skv // chunk_kv

    # Shard the grouped layout on G (= H/KVH): H-sharding cannot survive
    # the (KVH, G) split when KVH < tp (GSPMD would replicate the whole
    # microbatch — a 12 GB/step involuntary-remat all-reduce on the 405B
    # lowering, §Perf iteration B); G is the tp-divisible factor.
    qc = q.reshape(B, nq, chunk_q, KVH, G, dh)
    qc = logical(qc, ("act_batch", None, None, "act_kv_heads",
                      "act_heads", None)).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, chunk_kv, KVH, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, chunk_kv, KVH, dh).transpose(1, 0, 2, 3, 4)
    qp = q_pos.reshape(nq, chunk_q)
    kp = k_pos.reshape(nk, chunk_kv)

    def q_chunk_body(qi, q_blk):
        q_blk = q_blk.astype(jnp.float32)
        qpos = qp[qi]

        def kv_step(carry, inp):
            m_run, l_run, acc = carry
            ki, k_blk, v_blk = inp
            kpos = kp[ki]

            def compute(args):
                m_run, l_run, acc = args
                s = jnp.einsum("bqkgd,bskd->bqkgs", q_blk,
                               k_blk.astype(jnp.float32)) * scale
                msk = _mask(qpos, kpos, causal, window)  # (cq, ck)
                bias = jnp.where(msk, 0.0, _BIAS_NEG).astype(jnp.float32)
                s = s + bias[None, :, None, None, :]
                m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
                m_new = jnp.maximum(m_new, _M_INIT)  # masked-row floor
                alpha = jnp.exp(m_run - m_new)
                p = jnp.exp(s - m_new[..., None])    # masked lanes -> 0
                l_new = l_run * alpha + jnp.sum(p, axis=-1)
                acc_new = acc * alpha[..., None] + jnp.einsum(
                    "bqkgs,bskd->bqkgd", p, v_blk.astype(jnp.float32))
                return m_new, l_new, acc_new

            # skip chunk pairs that are fully masked
            needed = jnp.logical_and(
                (kpos[0] <= qpos[-1]) if causal else True,
                (qpos[0] - kpos[-1] < window) if window is not None else True,
            )
            carry = jax.lax.cond(needed, compute, lambda a: a,
                                 (m_run, l_run, acc))
            return carry, None

        m0 = jnp.full((B, chunk_q, KVH, G), _M_INIT, jnp.float32)
        l0 = jnp.zeros((B, chunk_q, KVH, G), jnp.float32)
        a0 = jnp.zeros((B, chunk_q, KVH, G, dh), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l_f, 1e-20)[..., None]
        lse = m_f + jnp.log(jnp.maximum(l_f, 1e-20))  # (B, cq, KVH, G)
        return out.astype(q.dtype), lse

    outs, lses = jax.lax.map(lambda args: q_chunk_body(*args),
                             (jnp.arange(nq), qc))  # (nq, B, cq, KVH, G, dh)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, dh)
    lse = lses.transpose(1, 0, 2, 3, 4).reshape(B, Sq, KVH, G)
    return out, lse


# ---------------------------------------------------------------------------
# Flash-style custom VJP for the chunked path.
#
# The naive scan backward stacks per-kv-step residuals — the recomputed
# probability tensors p of every (q-chunk, kv-chunk) pair, a
# (nq·nk, B, cq, KVH, G, ck) monster that cost ~100s of GB/device on the
# 32k-prefill lowering (EXPERIMENTS.md §Perf iteration 1).  The flash
# backward saves only (o, lse) — O(B·S·H·dh) — and re-derives each p tile
# inside the gradient loops, exactly like the TPU kernel would in VMEM.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_xla(q, k, v, q_start, causal, window, scale, chunk_q, chunk_kv):
    """``q_start``: (traced) absolute position of q[0] — context-parallel
    slices pass their own offset."""
    out, _ = _chunked_attention(q, k, v,
                                q_start + jnp.arange(q.shape[1]),
                                jnp.arange(k.shape[1]),
                                causal, window, scale, chunk_q, chunk_kv)
    return out


def _flash_xla_fwd(q, k, v, q_start, causal, window, scale, chunk_q,
                   chunk_kv):
    out, lse = _chunked_attention(q, k, v,
                                  q_start + jnp.arange(q.shape[1]),
                                  jnp.arange(k.shape[1]),
                                  causal, window, scale, chunk_q, chunk_kv)
    return out, (q, k, v, q_start, out, lse)


def _flash_xla_bwd(causal, window, scale, chunk_q, chunk_kv, res, do):
    q, k, v, q_start, o, lse = res
    B, Sq, H, dh = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    nq, nk = Sq // chunk_q, Skv // chunk_kv

    grp = ("act_batch", None, None, "act_kv_heads", "act_heads", None)
    qf = logical(q.reshape(B, nq, chunk_q, KVH, G, dh), grp
                 ).astype(jnp.float32)
    dof = logical(do.reshape(B, nq, chunk_q, KVH, G, dh), grp
                  ).astype(jnp.float32)
    of = logical(o.reshape(B, nq, chunk_q, KVH, G, dh), grp
                 ).astype(jnp.float32)
    lsef = logical(lse.reshape(B, nq, chunk_q, KVH, G), grp[:-1])
    kf = k.reshape(B, nk, chunk_kv, KVH, dh).astype(jnp.float32)
    vf = v.reshape(B, nk, chunk_kv, KVH, dh).astype(jnp.float32)
    # D_i = rowsum(do ⊙ o)  (B, nq, cq, KVH, G)
    Dmat = jnp.sum(dof * of, axis=-1)
    qpos_all = q_start + jnp.arange(Sq).reshape(nq, chunk_q)
    kpos_all = jnp.arange(Skv).reshape(nk, chunk_kv)

    def kv_chunk_body(dq_acc, ki):
        k_blk = kf[:, ki]  # (B, ck, KVH, dh)
        v_blk = vf[:, ki]
        kpos = kpos_all[ki]

        def q_step(carry, qi):
            dq_acc, dk_blk, dv_blk = carry
            qpos = qpos_all[qi]

            def compute(args):
                dq_acc, dk_blk, dv_blk = args
                q_blk = qf[:, qi]      # (B, cq, KVH, G, dh)
                s = jnp.einsum("bqkgd,bskd->bqkgs", q_blk, k_blk) * scale
                msk = _mask(qpos, kpos, causal, window)
                bias = jnp.where(msk, 0.0, _BIAS_NEG).astype(jnp.float32)
                s = s + bias[None, :, None, None, :]
                p = jnp.exp(s - lsef[:, qi][..., None])  # re-derived tile
                do_blk = dof[:, qi]
                dv_new = dv_blk + jnp.einsum("bqkgs,bqkgd->bskd", p, do_blk)
                dp = jnp.einsum("bqkgd,bskd->bqkgs", do_blk, v_blk)
                ds = p * (dp - Dmat[:, qi][..., None])
                dq_new = dq_acc.at[:, qi].add(
                    jnp.einsum("bqkgs,bskd->bqkgd", ds, k_blk) * scale)
                dk_new = dk_blk + jnp.einsum(
                    "bqkgs,bqkgd->bskd", ds, q_blk) * scale
                return dq_new, dk_new, dv_new

            needed = jnp.logical_and(
                (kpos[0] <= qpos[-1]) if causal else True,
                (qpos[0] - kpos[-1] < window) if window is not None else True,
            )
            carry = jax.lax.cond(needed, compute, lambda a: a,
                                 (dq_acc, dk_blk, dv_blk))
            return carry, None

        dk0 = jnp.zeros((B, chunk_kv, KVH, dh), jnp.float32)
        dv0 = jnp.zeros((B, chunk_kv, KVH, dh), jnp.float32)
        (dq_acc, dk_blk, dv_blk), _ = jax.lax.scan(
            q_step, (dq_acc, dk0, dv0), jnp.arange(nq))
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, nq, chunk_q, KVH, G, dh), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(kv_chunk_body, dq0, jnp.arange(nk))
    dq = dq.reshape(B, Sq, H, dh).astype(q.dtype)
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, Skv, KVH, dh).astype(k.dtype)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, Skv, KVH, dh).astype(v.dtype)
    return dq, dk, dv, jnp.zeros_like(q_start)  # positions carry no grad


_flash_xla.defvjp(_flash_xla_fwd, _flash_xla_bwd)


@scopes.scoped(scopes.ATTENTION_CORE)
def attention_core(q, k, v, *, causal: bool = True,
                   window: Optional[int] = None,
                   q_offset: int = 0,
                   chunk_q: int = 1024, chunk_kv: int = 1024,
                   force_chunked: bool = False) -> jnp.ndarray:
    """q (B,Sq,H,dh) × k,v (B,Skv,KVH,dh) -> (B,Sq,H,dh).

    ``q_offset``: absolute position of q[0] (decode: cache length).
    Dispatches to the materialized path for small problems and the
    online-softmax chunked path for long sequences.
    """
    B, Sq, H, dh = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Skv)
    big = Sq * Skv > 2048 * 2048
    if (big or force_chunked) and Sq % 512 == 0 and Skv % 512 == 0 \
            and Sq > 1:
        cq = min(chunk_q, Sq)
        ck = min(chunk_kv, Skv)
        start = jnp.asarray(q_offset, jnp.float32) \
            if not isinstance(q_offset, jax.Array) else q_offset
        return _flash_xla(q, k, v, start, causal, window, scale, cq, ck)
    return _plain_attention(q, k, v, q_pos, k_pos, causal, window, scale)


# ---------------------------------------------------------------------------
# GQA attention block (param init + apply, with KV cache support)
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jnp.ndarray  # (B, Smax, KVH, dh)
    v: jnp.ndarray


def attn_init(key, cfg, dtype):
    d, H, KVH, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["wq"], a["wq"] = layers.dense_init(ks[0], d, H * dh, dtype, "embed", "heads",
                                         bias=cfg.use_qkv_bias)
    p["wk"], a["wk"] = layers.dense_init(ks[1], d, KVH * dh, dtype, "embed",
                                         "kv_heads", bias=cfg.use_qkv_bias)
    p["wv"], a["wv"] = layers.dense_init(ks[2], d, KVH * dh, dtype, "embed",
                                         "kv_heads", bias=cfg.use_qkv_bias)
    p["wo"], a["wo"] = layers.dense_init(ks[3], H * dh, d, dtype, "heads", "embed")
    return p, a


def _positions_for(cfg, B, S, offset=0):
    pos = offset + jnp.arange(S)
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.m_rope:
        return jnp.broadcast_to(pos[..., None], (B, S, 3))  # stub: t=h=w
    return pos


@scopes.scoped(scopes.ATTENTION)
def attn_apply(p, x, cfg, *, positions=None,
               cache: Optional[KVCache] = None,
               cache_pos: Optional[jnp.ndarray] = None):
    """x (B, S, d).  If ``cache`` is given, S is the decode step width (1),
    k/v are written at ``cache_pos`` and attention runs over the cache."""
    B, S, d = x.shape
    H, KVH, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    if positions is None:
        offset = 0 if cache is None else cache_pos
        positions = _positions_for(cfg, B, S, offset)

    q = layers.dense(p["wq"], x).reshape(B, S, H, dh)
    k = layers.dense(p["wk"], x).reshape(B, S, KVH, dh)
    v = layers.dense(p["wv"], x).reshape(B, S, KVH, dh)
    # Megatron SP: the residual stream is sequence-sharded, but attention
    # itself is HEAD-sharded over the full sequence — annotating q/k/v with
    # act_seq would hand the model axis to the seq dim and leave the head
    # dim replicated (≈tp× redundant attention compute; §Perf iteration 2)
    q = logical(q, ("act_batch", None, "act_heads", None))
    k = logical(k, ("act_batch", None, "act_kv_heads", None))
    v = logical(v, ("act_batch", None, "act_kv_heads", None))

    if cfg.m_rope:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is None:
        from repro.distributed.sharding import context_parallel_factor
        from repro.runtime import flags
        cp = context_parallel_factor(H, S)
        if flags.attention_stubbed():  # cost-attribution mode
            o = jnp.repeat(v, H // KVH, axis=2)
        elif flags.pallas_enabled():
            from repro.kernels import ops as kops
            with jax.named_scope(scopes.ATTENTION_CORE):
                o = kops.flash_attention(
                    q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), causal=True,
                    window=cfg.sliding_window,
                    block_sizes="auto",  # cost-model-chosen tiling
                ).transpose(0, 2, 1, 3)
        elif cp > 1:
            # context parallelism: n_heads % tp != 0, so attention divides
            # over the model axis by q-SLICE instead of by head; k/v stay
            # whole (they were replicated anyway) and each slice runs flash
            # with its own absolute offset
            Scp = S // cp
            qs = q.reshape(B, cp, Scp, H, dh)
            qs = logical(qs, ("act_batch", "act_cp", None, None, None))
            offs = jnp.arange(cp, dtype=jnp.float32) * Scp
            o = jax.vmap(
                lambda qq, off: attention_core(
                    qq, k, v, causal=True, window=cfg.sliding_window,
                    q_offset=off),
                in_axes=(1, 0), out_axes=1)(qs, offs)
            o = logical(o, ("act_batch", "act_cp", None, None, None))
            o = o.reshape(B, S, H, dh)
        else:
            o = attention_core(q, k, v, causal=True,
                               window=cfg.sliding_window)
    else:
        # decode: write into the cache ring/window and attend over it
        Smax = cache.k.shape[1]
        if cfg.sliding_window is not None and Smax <= cfg.sliding_window:
            slot = cache_pos % Smax  # ring buffer for SWA
        else:
            slot = cache_pos
        ck = jax.lax.dynamic_update_slice(cache.k, k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache.v, v, (0, slot, 0, 0))
        ck = logical(ck, ("act_batch", "act_seq_dp", "act_kv_heads", None))
        cv = logical(cv, ("act_batch", "act_seq_dp", "act_kv_heads", None))
        new_cache = KVCache(ck, cv)
        o = _decode_attention(q, ck, cv, cfg, cache_pos)

    o = logical(o, ("act_batch", "act_seq", "act_heads", None))
    out = layers.dense(p["wo"], o.reshape(B, S, H * dh))
    return out, new_cache


def _decode_attention(q, ck, cv, cfg, cache_pos):
    """Single-token decode over a (possibly seq-sharded) cache.

    Materializes (B, H, Smax) logits — O(S) per token, fine at 524k — and
    lets GSPMD turn the S-dim reductions into cheap scalar all-reduces when
    the cache is sequence-sharded.
    """
    B, S, H, dh = q.shape  # S == 1
    Smax, KVH = ck.shape[1], ck.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(B, S, KVH, G, dh).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bqkgs", qg, ck.astype(jnp.float32)) * scale
    k_pos = jnp.arange(Smax)
    if cfg.sliding_window is not None and Smax <= cfg.sliding_window:
        valid = jnp.ones((Smax,), bool)  # ring buffer: all slots valid
    else:
        valid = k_pos <= cache_pos
        if cfg.sliding_window is not None:
            valid &= cache_pos - k_pos < cfg.sliding_window
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgs,bskd->bqkgd", p, cv.astype(jnp.float32))
    return o.reshape(B, S, H, dh).astype(q.dtype)


def init_cache(cfg, B: int, max_len: int, dtype) -> KVCache:
    KVH, dh = cfg.n_kv_heads, cfg.head_dim_
    if cfg.sliding_window is not None:
        max_len = min(max_len, cfg.sliding_window)
    shape = (B, max_len, KVH, dh)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
