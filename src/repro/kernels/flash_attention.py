"""Flash attention (online-softmax) Pallas TPU kernel with GQA + causal +
sliding-window masking.

TPU adaptation of the memory-tiling insight: Q/K/V stream HBM→VMEM in
(block_q × head_dim) / (block_k × head_dim) tiles sized for VMEM; the
(block_q × block_k) logit tile lives only in VMEM/VREGs; the softmax
running max/sum and the output accumulator are VMEM scratch carried across
the *sequential* innermost grid dimension (the kv-block walk).  MXU does the
two matmuls per tile pair; block shapes are multiples of (8, 128) so the
MXU/VPU tiling is hardware-aligned.

Fully-masked (q-block, k-block) pairs in the causal/SWA lower triangle are
skipped with ``pl.when`` — on TPU the grid step still issues, but no
compute/copy runs (the paper's 'barrier'-style schedule effect; counted by
``schedule_props``).

Validated on CPU via ``interpret=True`` against ``ref.attention``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            block_q: int, block_k: int, n_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level skip predicates (compile-time structure, runtime ids)
    needed = jnp.bool_(True)
    if causal:
        needed &= k_start <= q_start + block_q - 1
    if window is not None:
        needed &= q_start - (k_start + block_k - 1) < window

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-20)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True) -> jnp.ndarray:
    """q (B,H,Sq,dh) × k,v (B,KVH,Skv,dh) → (B,H,Sq,dh).

    ``interpret=True`` executes the kernel body on CPU (validation mode);
    on a TPU runtime pass ``interpret=False``.
    """
    B, H, Sq, dh = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    assert H % KVH == 0, (H, KVH)
    G = H // KVH
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    n_q, n_k = Sq // block_q, Skv // block_k
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k)

    grid = (B, H, n_q, n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def schedule_props(B: int, H: int, KVH: int, Sq: int, Skv: int, dh: int,
                   *, causal: bool = True, window: Optional[int] = None,
                   block_q: int = 128, block_k: int = 128,
                   bits: int = 16) -> dict:
    """Schedule-derived property vector (paper §3.2: barriers/local loads
    need the *schedule*) for the fitted model: grid cells, VMEM block
    traffic, and the *executed* (non-skipped) tile-pair count."""
    from repro.core import properties as props
    n_q, n_k = Sq // block_q, Skv // block_k
    cells = B * H * n_q * n_k
    # executed pairs after causal/SWA skip
    exec_pairs = 0
    for qi in range(n_q):
        for ki in range(n_k):
            ok = True
            if causal and ki * block_k > qi * block_q + block_q - 1:
                ok = False
            if window is not None and \
                    qi * block_q - (ki * block_k + block_k - 1) >= window:
                ok = False
            exec_pairs += ok
    exec_cells = B * H * exec_pairs
    local = exec_cells * (block_q * dh + 2 * block_k * dh)
    return {
        props.local_key(bits): float(local),
        props.BARRIER: float(cells),
        props.GROUPS: float(cells),
        props.mxu_key(bits): 4.0 * exec_cells * block_q * block_k * dh,
    }
