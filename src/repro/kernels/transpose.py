"""Tiled transpose Pallas TPU kernel — the paper's *Transpose* measurement
class (prefetch variant).

On GPU the tile pass through shared memory converts uncoalesced reads into
coalesced ones; the TPU analog is a VMEM-tile relayout: blocks stream in
(bt × bt) tiles, transpose in-register, and stream out, so both HBM
directions stay contiguous ('stride-1') — exactly the access-class change
the fitted model prices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].T


def transpose(x, *, block: int = 256, interpret: bool = True) -> jnp.ndarray:
    """(M, N) -> (N, M) via VMEM tiles."""
    M, N = x.shape
    bm = min(block, M)
    bn = min(block, N)
    assert M % bm == 0 and N % bn == 0
    return pl.pallas_call(
        _kernel,
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((N, M), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x)


def schedule_props(M: int, N: int, *, block: int = 256, bits: int = 32) -> dict:
    from repro.core import properties as props
    cells = (M // block) * (N // block)
    return {
        props.local_key(bits): float(M * N),
        props.BARRIER: float(cells),
        props.GROUPS: float(cells),
    }
