"""Mamba2 SSD (state-space duality) Pallas TPU kernel.

TPU adaptation of the SSD insight (arXiv:2405.21060): within a chunk of Q
timesteps the recurrence is a *masked matmul* (MXU work); across chunks only
the (P × N) state is carried.  The kernel walks chunks on the sequential
innermost grid dimension with the state in VMEM scratch — the carried state
never round-trips to HBM (the GPU version holds it in registers/SMEM; the
TPU analog is VMEM residency across grid steps).

Per (batch, head, chunk) grid cell:
    cum   = cumsum(dt·A)                       (Q,)
    Lmat  = tril(exp(cum_i − cum_j))           (Q, Q)   decay matrix
    W     = (C Bᵀ) ⊙ Lmat ⊙ dt_j               (Q, Q)   MXU + VPU
    y     = W x  +  (C ⊙ exp(cum)) h_prevᵀ     (Q, P)   MXU
    h_new = exp(cum_Q) h_prev + (B ⊙ dt ⊙ decay_to_end)ᵀ x    (P, N)

Validated on CPU via ``interpret=True`` against the naive O(L) recurrence
``ref.ssd`` and the chunked XLA path ``models.ssm._ssd_chunked``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, contract):
    """f32 MXU contraction at full precision.  The state carries f32 sums
    across every chunk, and Mosaic's default precision for f32 operands
    left the final state up to 9e-3 off the O(L) recurrence (TPU v5e,
    mamba2-370m widths, L=2048)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr, *,
            n_chunks: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    # Every per-step vector stays 2-D: Mosaic lays out (sublane, lane)
    # tiles, and a chunk's dt/cumsum is needed both as a row (1, Q) and as
    # a column (Q, 1).
    x = x_ref[0, 0].astype(jnp.float32)                  # (Q, P)
    dt = dt_ref[0, 0, pl.ds(ci, 1), :].astype(jnp.float32)  # (1, Q)
    A = a_ref[hi]                                        # SMEM scalar (< 0)
    Bm = b_ref[0, 0].astype(jnp.float32)                 # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)                 # (Q, N)

    Q = x.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    eye = ii == jj
    dA = dt * A                                          # (1, Q) ≤ 0
    # cumsum as a masked lane reduction (no cumsum lowering on Mosaic);
    # row <-> column moves go through the diagonal
    cum_c = jnp.sum(jnp.where(jj <= ii, dA, 0.0), axis=1,
                    keepdims=True)                       # (Q, 1)
    cum_r = jnp.sum(jnp.where(eye, cum_c, 0.0), axis=0,
                    keepdims=True)                       # (1, Q)
    dt_c = jnp.sum(jnp.where(eye, dt, 0.0), axis=1, keepdims=True)  # (Q, 1)
    total = jnp.sum(dA, axis=1, keepdims=True)           # (1, 1) = cum[-1]

    # mask the exponent, not the product (avoids inf·0 in the bwd pass)
    diff = jnp.where(ii >= jj, cum_c - cum_r, -1e30)     # (Q, Q)
    Lmat = jnp.exp(diff)

    CB = _dot(Cm, Bm, ((1,), (1,)))                      # (Q, Q)
    W = CB * Lmat * dt
    y_intra = _dot(W, x, ((1,), (0,)))                   # (Q, P)

    h_prev = h_scr[...]                                  # (P, N)
    y_inter = _dot(Cm * jnp.exp(cum_c), h_prev, ((1,), (1,)))  # (Q, P)

    decay_end = jnp.exp(total - cum_c)                   # (Q, 1)
    Bw = Bm * (dt_c * decay_end)                         # (Q, N)
    S_c = _dot(x, Bw, ((0,), (0,)))                      # (P, N)
    h_new = h_prev * jnp.exp(total) + S_c
    h_scr[...] = h_new

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _write_state():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = True):
    """x (Bz,H,L,P); dt (Bz,H,L); A (H,); B,C (Bz,G,L,N) with G | H.

    Returns (y (Bz,H,L,P), h_final (Bz,H,P,N) fp32).
    """
    Bz, H, L, P = x.shape
    G, N = B.shape[1], B.shape[3]
    assert H % G == 0
    rep = H // G
    chunk = min(chunk, L)
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk

    kernel = functools.partial(_kernel, n_chunks=nc)
    grid = (Bz, H, nc)
    y, h_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
            # a head's whole dt as one (nc, chunk) tile — a legal TPU block
            # for any chunk; the kernel reads its chunk's row
            pl.BlockSpec((1, 1, nc, chunk), lambda b, h, ci: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, ci, rep=rep: (b, h // rep, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, ci, rep=rep: (b, h // rep, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((Bz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt.reshape(Bz, H, nc, chunk), A.astype(jnp.float32), B, C)
    return y, h_fin


def schedule_props(Bz: int, H: int, L: int, P: int, N: int, *,
                   chunk: int = 128, bits: int = 16) -> dict:
    """Schedule-derived properties: per grid cell, x/B/C blocks move
    HBM→VMEM and the (P, N) state stays VMEM-resident."""
    from repro.core import properties as props
    nc = L // chunk
    cells = Bz * H * nc
    local = cells * (chunk * P + 2 * chunk * N + P * N)
    mxu = cells * 2.0 * (chunk * chunk * N      # CB
                         + chunk * chunk * P    # y_intra
                         + chunk * P * N * 2)   # y_inter + state update
    return {
        props.local_key(bits): float(local),
        props.BARRIER: float(cells),
        props.GROUPS: float(cells),
        props.mxu_key(bits): mxu,
    }
