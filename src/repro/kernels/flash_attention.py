"""Flash attention (online softmax) Pallas TPU kernels, forward and
backward, with GQA, causal and sliding-window masking.

``attention_core`` runs training and prefill attention through these
kernels on a TPU (``repro.models.attention``); the CPU keeps the XLA paths
and runs the kernels only in interpret mode, for validation.

Layout.  q (B, H, S, dh) is read as (B, KVH, G, S, dh), G = H / KVH: a q
block of ``block_q`` positions holds all G heads that share a kv head,
taken as one (G·block_q, dh) tile, row g·block_q + s.  One MXU product
then covers the whole group against a (block_k, dh) k tile, and the
backward's sum of dk and dv over the group is the product's own
contraction.  The (rows × block_k) score tile lives only in VMEM, in both
directions.

* Forward, grid (B, KVH, n_q, n_k), the kv walk sequential: running max,
  sum and accumulator in f32 VMEM scratch, the row statistics lane-dense
  (rows, 128); outputs o and the per-row logsumexp ``lse`` (B, KVH, G, S).
* dK/dV, grid (B, KVH, n_k, n_q), the q walk sequential: the scores
  transposed, (block_k, rows), so ``lse`` and D = rowsum(dO ⊙ O)
  broadcast as rows and dv += pᵀ·dO, dk += dsᵀ·q are plain products.
* dQ, grid (B, KVH, n_q, n_k), the kv walk sequential.

Products take the inputs' own dtype (bf16 in training) with f32
accumulation; P and dS are cast to that dtype before their products, as
XLA's default TPU precision does on the f32 path.  Block pairs that the
causal or window mask hides entirely are skipped with ``pl.when``, and
their index maps repeat the last needed block so no DMA is issued; only
pairs that the mask's edge crosses pay for the mask.  A row that sees no
key gives a zero output and ``lse = +inf``, so every probability
exp(s − lse) of the backward is 0.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NUM_LANES = 128
NEG_INF = -1e30                # running-max floor: finite, so exp(m − m) = 1
_MASKED = float("-inf")        # a hidden score: exp(−inf − m) = 0
_NT = (((1,), (1,)), ((), ()))  # contract both operands' last dims
_VMEM_LIMIT = 64 * 1024 * 1024


class _Static(NamedTuple):
    """The kernels' compile-time parameters (hashable, for custom_vjp)."""
    causal: bool
    window: Optional[int]
    G: int
    block_q: int
    block_k: int
    interpret: bool


# ---------------------------------------------------------------------------
# Masks and row statistics
# ---------------------------------------------------------------------------


def _needed(st: _Static, qi, ki, bq: int, bk: int):
    """Some entry of the (q block, k block) pair is visible."""
    ok = jnp.bool_(True)
    if st.causal:
        ok &= ki * bk <= qi * bq + bq - 1
    if st.window is not None:
        ok &= qi * bq - (ki * bk + bk - 1) < st.window
    return ok


def _unmasked(st: _Static, qi, ki, bq: int, bk: int):
    """Every entry of the pair is visible: no element mask is needed."""
    ok = jnp.bool_(True)
    if st.causal:
        ok &= ki * bk + bk - 1 <= qi * bq
    if st.window is not None:
        ok &= qi * bq + bq - 1 - ki * bk < st.window
    return ok


def _visible(st: _Static, qpos, kpos):
    ok = None
    if st.causal:
        ok = qpos >= kpos
    if st.window is not None:
        w = qpos - kpos < st.window
        ok = w if ok is None else ok & w
    return ok


def _masked_scores(st: _Static, s, q0, k0, bq: int, rows_first: bool):
    """Hide the invisible entries of a score tile: (G·bq, bk) with the
    group's rows first, or its transpose (bk, G·bq).  Positions are
    built on one head's (bq, bk) tile and repeated over the group."""
    G = st.G
    if rows_first:
        shape, ax = (bq, s.shape[1]), 0
    else:
        shape, ax = (s.shape[0], bq), 1
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, ax)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - ax)
    vis = _visible(st, qpos, kpos).astype(jnp.int32)
    vis = jnp.concatenate([vis] * G, axis=ax) if G > 1 else vis
    return jnp.where(vis != 0, s, _MASKED)


def _k_range(st: _Static, qi, bq: int, bk: int, n_k: int):
    """First and last k block that q block ``qi`` needs."""
    hi = (qi * bq + bq - 1) // bk if st.causal else n_k - 1
    hi = jnp.minimum(hi, n_k - 1)
    lo = 0
    if st.window is not None:
        lo = jnp.maximum((qi * bq - st.window + 1) // bk, 0)
    return jnp.minimum(lo, hi), hi


def _q_range(st: _Static, ki, bq: int, bk: int, n_q: int):
    """First and last q block that k block ``ki`` is needed by."""
    lo = (ki * bk) // bq if st.causal else 0
    lo = jnp.minimum(lo, n_q - 1)
    hi = n_q - 1
    if st.window is not None:
        hi = jnp.minimum((ki * bk + bk + st.window - 2) // bq, n_q - 1)
    return lo, jnp.maximum(lo, hi)


def _lanes(x, n: int):
    """A lane-replicated (rows, 128) statistic widened or cut to n lanes."""
    if n % NUM_LANES == 0:
        return jnp.tile(x, (1, n // NUM_LANES))
    return x[:, :n]


def _stat_row(blk):
    """A (G, bq) statistics block -> one (1, G·bq) row."""
    G = blk.shape[0]
    return jnp.concatenate([blk[g:g + 1] for g in range(G)], axis=1)


def _stat_column(blk):
    """A (G, bq) statistics block -> lane-replicated (G·bq, 128)."""
    G, bq = blk.shape
    return jnp.concatenate(
        [jnp.transpose(jnp.broadcast_to(blk[g:g + 1], (NUM_LANES, bq)))
         for g in range(G)], axis=0)


def _stat_block(col, G: int):
    """Lane-replicated (G·bq, 128) -> a (G, bq) statistics block."""
    row = jnp.transpose(col)[:1]                     # (1, G·bq)
    bq = row.shape[1] // G
    return jnp.concatenate([row[:, g * bq:(g + 1) * bq] for g in range(G)],
                           axis=0)


def _folds(scale: float) -> bool:
    """The scale is a power of two (dh = 64 gives 1/8), so q·scale is exact
    in q's dtype: the scale then rides on the q operand and the scores
    need no scaling pass."""
    return math.log2(scale).is_integer()


def _q_tile(q_ref, scale: float):
    """The (G·bq, dh) q tile of a (1, 1, G, bq, dh) block, scaled where
    that is exact."""
    G, bq, dh = q_ref.shape[2:]
    q = q_ref[0, 0].reshape(G * bq, dh)
    if _folds(scale):
        return (q.astype(jnp.float32) * scale).astype(q.dtype)
    return q


def _scores(a, b, scale: float):
    """a·bᵀ in f32, scaled (the scale is already in q where it folds)."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    return s if _folds(scale) else s * scale


def _branches(st: _Static, qi, ki, bq: int, bk: int, step) -> None:
    """Run ``step(masked)`` on a needed pair: unmasked where the whole
    pair is visible, masked where the mask's edge crosses it."""
    if not st.causal and st.window is None:
        step(False)
        return
    needed = _needed(st, qi, ki, bq, bk)
    plain = _unmasked(st, qi, ki, bq, bk)
    pl.when(needed & plain)(lambda: step(False))
    pl.when(needed & jnp.logical_not(plain))(lambda: step(True))


def _compiler_params(n_parallel: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",),
        vmem_limit_bytes=_VMEM_LIMIT)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, st: _Static, scale: float, n_k: int):
    qi, ki = pl.program_id(2), pl.program_id(3)
    bq, bk = st.block_q, st.block_k
    G, dh = st.G, q_ref.shape[4]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        s = _scores(_q_tile(q_ref, scale), k_ref[0, 0], scale)  # (G·bq, bk)
        if masked:
            s = _masked_scores(st, s, qi * bq, ki * bk, bq, True)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, bk))
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0]
        acc_scr[...] = acc_scr[...] * _lanes(alpha, dh) + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _branches(st, qi, ki, bq, bk, step)

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_scr[...]
        empty = l == 0.0
        o = acc_scr[...] / _lanes(jnp.where(empty, 1.0, l), dh)
        o_ref[0, 0] = o.reshape(G, bq, dh).astype(o_ref.dtype)
        lse = jnp.where(empty, jnp.inf, m_scr[...] + jnp.log(l))
        lse_ref[0, 0] = _stat_block(lse, G)


def _forward(qg, k, v, st: _Static):
    """qg (B, KVH, G, S, dh), k/v (B, KVH, Skv, dh) -> o like qg, and lse
    (B, KVH, G, S) f32."""
    B, KVH, G, S, dh = qg.shape
    bq, bk = st.block_q, st.block_k
    n_q, n_k = S // bq, k.shape[2] // bk

    def kv_map(b, h, qi, ki):
        lo, hi = _k_range(st, qi, bq, bk, n_k)
        return b, h, jnp.clip(ki, lo, hi), 0

    q_spec = pl.BlockSpec((1, 1, G, bq, dh),
                          lambda b, h, qi, ki: (b, h, 0, qi, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, st=st, scale=1.0 / math.sqrt(dh),
                          n_k=n_k),
        grid=(B, KVH, n_q, n_k),
        in_specs=[q_spec, pl.BlockSpec((1, 1, bk, dh), kv_map),
                  pl.BlockSpec((1, 1, bk, dh), kv_map)],
        out_specs=[q_spec,
                   pl.BlockSpec((1, 1, G, bq),
                                lambda b, h, qi, ki: (b, h, 0, qi))],
        out_shape=[jax.ShapeDtypeStruct(qg.shape, qg.dtype),
                   jax.ShapeDtypeStruct((B, KVH, G, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((G * bq, NUM_LANES), jnp.float32),
                        pltpu.VMEM((G * bq, NUM_LANES), jnp.float32),
                        pltpu.VMEM((G * bq, dh), jnp.float32)],
        compiler_params=_compiler_params(3),
        interpret=st.interpret,
    )(qg, k, v)


# ---------------------------------------------------------------------------
# Backward: dK/dV over the q walk, dQ over the kv walk
# ---------------------------------------------------------------------------


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, st: _Static, scale: float, n_q: int):
    ki, qi = pl.program_id(2), pl.program_id(3)
    bq, bk = st.block_q, st.block_k
    G, dh = st.G, q_ref.shape[4]

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked: bool):
        q = _q_tile(q_ref, scale)
        s = _scores(k_ref[0, 0], q, scale)                  # (bk, G·bq): sᵀ
        if masked:
            s = _masked_scores(st, s, qi * bq, ki * bk, bq, False)
        p = jnp.exp(s - _stat_row(lse_ref[0, 0]))
        do = do_ref[0, 0].reshape(G * bq, dh)
        dv_scr[...] += jax.lax.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[0, 0], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _stat_row(d_ref[0, 0]))
        # with the scale folded into q this is already scale · dsᵀ·q
        dk_scr[...] += jax.lax.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    _branches(st, qi, ki, bq, bk, step)

    @pl.when(qi == n_q - 1)
    def _finish():
        dk = dk_scr[...]
        dk_ref[0, 0] = (dk if _folds(scale) else dk * scale
                        ).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
               lse_scr, d_scr, dq_scr, *, st: _Static, scale: float,
               n_k: int):
    qi, ki = pl.program_id(2), pl.program_id(3)
    bq, bk = st.block_q, st.block_k
    G, dh = st.G, q_ref.shape[4]

    @pl.when(ki == 0)
    def _init():
        lse_scr[...] = _stat_column(lse_ref[0, 0])
        d_scr[...] = _stat_column(d_ref[0, 0])
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(masked: bool):
        s = _scores(_q_tile(q_ref, scale), k_ref[0, 0], scale)  # (G·bq, bk)
        if masked:
            s = _masked_scores(st, s, qi * bq, ki * bk, bq, True)
        p = jnp.exp(s - _lanes(lse_scr[...], bk))
        dp = jax.lax.dot_general(do_ref[0, 0].reshape(G * bq, dh),
                                 v_ref[0, 0], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(d_scr[...], bk))
        k = k_ref[0, 0]
        dq_scr[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    _branches(st, qi, ki, bq, bk, step)

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0, 0] = (dq_scr[...] * scale).reshape(G, bq, dh).astype(
            dq_ref.dtype)


def _backward(qg, k, v, o, lse, do, st: _Static):
    B, KVH, G, S, dh = qg.shape
    bq, bk = st.block_q, st.block_k
    n_q, n_k = S // bq, k.shape[2] // bk
    scale = 1.0 / math.sqrt(dh)
    # D = rowsum(dO ⊙ O), laid out like lse
    d = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    q_shape, stat_shape, kv_shape = (1, 1, G, bq, dh), (1, 1, G, bq), \
        (1, 1, bk, dh)

    # dK/dV: grid (b, h, ki, qi); q-side blocks clamped to those needed
    def q_side(ki, qi):
        lo, hi = _q_range(st, ki, bq, bk, n_q)
        return jnp.clip(qi, lo, hi)

    q_blk = pl.BlockSpec(q_shape,
                         lambda b, h, ki, qi: (b, h, 0, q_side(ki, qi), 0))
    stat_blk = pl.BlockSpec(stat_shape,
                            lambda b, h, ki, qi: (b, h, 0, q_side(ki, qi)))
    kv_blk = pl.BlockSpec(kv_shape, lambda b, h, ki, qi: (b, h, ki, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, st=st, scale=scale, n_q=n_q),
        grid=(B, KVH, n_k, n_q),
        in_specs=[q_blk, kv_blk, kv_blk, q_blk, stat_blk, stat_blk],
        out_specs=[kv_blk, kv_blk],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                        pltpu.VMEM((bk, dh), jnp.float32)],
        compiler_params=_compiler_params(3),
        interpret=st.interpret,
    )(qg, k, v, do, lse, d)

    # dQ: grid (b, h, qi, ki); kv blocks clamped to those needed
    def kv_side(qi, ki):
        lo, hi = _k_range(st, qi, bq, bk, n_k)
        return jnp.clip(ki, lo, hi)

    q_blk = pl.BlockSpec(q_shape, lambda b, h, qi, ki: (b, h, 0, qi, 0))
    stat_blk = pl.BlockSpec(stat_shape, lambda b, h, qi, ki: (b, h, 0, qi))
    kv_blk = pl.BlockSpec(kv_shape,
                          lambda b, h, qi, ki: (b, h, kv_side(qi, ki), 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, st=st, scale=scale, n_k=n_k),
        grid=(B, KVH, n_q, n_k),
        in_specs=[q_blk, kv_blk, kv_blk, q_blk, stat_blk, stat_blk],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        scratch_shapes=[pltpu.VMEM((G * bq, NUM_LANES), jnp.float32),
                        pltpu.VMEM((G * bq, NUM_LANES), jnp.float32),
                        pltpu.VMEM((G * bq, dh), jnp.float32)],
        compiler_params=_compiler_params(3),
        interpret=st.interpret,
    )(qg, k, v, do, lse, d)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Differentiable entry points
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(qg, k, v, st: _Static):
    return _forward(qg, k, v, st)[0]


def _attention_fwd(qg, k, v, st: _Static):
    o, lse = _forward(qg, k, v, st)
    return o, (qg, k, v, o, lse)


def _attention_bwd(st: _Static, res, do):
    return _backward(*res, do, st)


_attention.defvjp(_attention_fwd, _attention_bwd)


def _static(q, k, causal, window, block_q, block_k, interpret) -> _Static:
    B, H, Sq, dh = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    if H % KVH:
        raise ValueError(f"{H} query heads do not group over {KVH} kv heads")
    block_q, block_k = min(block_q, Sq), min(block_k, Skv)
    if Sq % block_q or Skv % block_k:
        raise ValueError(f"blocks {block_q} x {block_k} do not divide "
                         f"lengths {Sq} x {Skv}")
    return _Static(causal, window, H // KVH, block_q, block_k, interpret)


def _grouped(q, KVH: int):
    """(B, H, S, dh) -> (B, KVH, G, S, dh), a free reshape."""
    B, H, S, dh = q.shape
    return q.reshape(B, KVH, H // KVH, S, dh)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True) -> jnp.ndarray:
    """q (B,H,Sq,dh) × k,v (B,KVH,Skv,dh) → (B,H,Sq,dh), differentiable
    through the backward kernels, which take the forward's blocks
    (positions).  ``interpret=True`` runs the kernel bodies on the CPU
    (validation); on a TPU pass ``interpret=False``."""
    st = _static(q, k, causal, window, block_q, block_k, interpret)
    return _attention(_grouped(q, k.shape[1]), k, v, st).reshape(q.shape)


def flash_attention_lse(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = True):
    """The forward kernel alone: (o (B,H,Sq,dh), lse (B,H,Sq) f32), the
    logsumexp of each row's scaled scores (+inf where a row sees no
    key)."""
    st = _static(q, k, causal, window, block_q, block_k, interpret)
    o, lse = _forward(_grouped(q, k.shape[1]), k, v, st)
    return o.reshape(q.shape), lse.reshape(q.shape[:3])


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
