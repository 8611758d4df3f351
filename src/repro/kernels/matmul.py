"""Tiled matmul Pallas TPU kernel — the paper's *Matrix Multiplication*
measurement-kernel class as a TPU-native kernel.

The paper's GPU version prefetches gsize×gsize tiles into shared memory;
the TPU analog streams (bm × bk) / (bk × bn) tiles HBM→VMEM via BlockSpec
and accumulates the (bm × bn) product in fp32 VMEM scratch across the
sequential k grid dimension, feeding the MXU with hardware-aligned
(multiples of 128) tile shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, acc_scr, *, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _store():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def matmul(a, b, *, block_m: int = 128, block_n: int = 128,
           block_k: int = 128, interpret: bool = True) -> jnp.ndarray:
    """(M, K) @ (K, N) with fp32 accumulation."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    n_k = K // block_k
    kernel = functools.partial(_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)


def schedule_props(M: int, N: int, K: int, *, block_m: int = 128,
                   block_n: int = 128, block_k: int = 128,
                   bits: int = 32) -> dict:
    from repro.core import properties as props
    cells = (M // block_m) * (N // block_n) * (K // block_k)
    local = cells * (block_m * block_k + block_k * block_n
                     + block_m * block_n)
    return {
        props.local_key(bits): float(local),
        props.BARRIER: float(cells),
        props.GROUPS: float((M // block_m) * (N // block_n)),
        props.mxu_key(bits): 2.0 * M * N * K,
    }
