"""Int8 error-feedback gradient compression for the DP all-reduce.

The wire format is per-chunk int8 + fp32 scale (≈4× fewer collective bytes
than fp32, 2× vs bf16).  Error feedback (Seide et al. / 1-bit Adam lineage)
accumulates the quantization residual locally and re-adds it before the
next step's compression, so the *long-run* gradient is unbiased and
convergence matches uncompressed SGD/Adam to first order.

Two layers:

  * pure quantizer (``quantize``/``dequantize``/``ef_compress``) — unit
    tested, usable anywhere;
  * ``compressed_psum`` — a shard_map collective: quantized
    reduce-scatter (all_to_all + local sum) followed by a quantized
    all_gather.  Per-device wire bytes ≈ 2·(n−1)/n·(size/4) vs
    2·(n−1)/n·size uncompressed — the 4× shows up directly in the dry-run
    HLO (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

CHUNK = 1024  # quantization granularity (one fp32 scale per CHUNK values)


# ---------------------------------------------------------------------------
# Quantizer
# ---------------------------------------------------------------------------


def _pad_to(x: jnp.ndarray, mult: int) -> Tuple[jnp.ndarray, int]:
    n = x.size
    pad = (-n) % mult
    return jnp.pad(x.reshape(-1), (0, pad)), n


def quantize(x: jnp.ndarray, chunk: int = CHUNK
             ) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """fp array -> (int8 codes, fp32 per-chunk scales, original size)."""
    flat, n = _pad_to(x.astype(jnp.float32), chunk)
    c = flat.reshape(-1, chunk)
    scale = jnp.max(jnp.abs(c), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    codes = jnp.clip(jnp.round(c / scale), -127, 127).astype(jnp.int8)
    return codes, scale[:, 0], n


def dequantize(codes: jnp.ndarray, scales: jnp.ndarray, n: int,
               shape, dtype=jnp.float32) -> jnp.ndarray:
    vals = codes.astype(jnp.float32) * scales[:, None]
    return vals.reshape(-1)[:n].reshape(shape).astype(dtype)


def ef_compress(x: jnp.ndarray, residual: jnp.ndarray, chunk: int = CHUNK):
    """Error-feedback compress: returns (codes, scales, new_residual)."""
    y = x.astype(jnp.float32) + residual
    codes, scales, n = quantize(y, chunk)
    deq = dequantize(codes, scales, n, x.shape)
    return codes, scales, y.reshape(x.shape) - deq


# ---------------------------------------------------------------------------
# Compressed all-reduce (shard_map collective)
# ---------------------------------------------------------------------------


def psum_compressed(x: jnp.ndarray, axis: str, chunk: int = CHUNK
                    ) -> jnp.ndarray:
    """``jax.lax.psum`` with int8 wire format — call INSIDE a shard_map body.

    Algorithm (ring-equivalent):
      1. split the local value into n destination shards, quantize, and
         ``all_to_all`` (the reduce-scatter wire move, int8);
      2. dequantize + sum the n received contributions (my reduced shard);
      3. re-quantize, ``all_gather`` (int8), dequantize.
    """
    n = jax.lax.axis_size(axis)
    flat, size = _pad_to(x.astype(jnp.float32), n * chunk)
    shards = flat.reshape(n, -1)  # row i -> destined for rank i

    codes, scales, _ = quantize(shards.reshape(-1), chunk)
    codes = codes.reshape(n, -1)
    scales = scales.reshape(n, -1)
    # all_to_all: exchange shard rows (the reduce-scatter wire move)
    codes_x = jax.lax.all_to_all(codes, axis, 0, 0)
    scales_x = jax.lax.all_to_all(scales, axis, 0, 0)
    # local dequant-sum of the n received contributions for my shard
    part = jnp.sum(codes_x.astype(jnp.float32)
                   * jnp.repeat(scales_x, chunk, axis=-1), axis=0)

    # quantize my reduced shard, all_gather to complete the all-reduce
    c2, s2, _ = quantize(part, chunk)          # (k, chunk) int8, (k,) f32
    c_all = jax.lax.all_gather(c2, axis)       # (n, k, chunk) on the wire
    s_all = jax.lax.all_gather(s2, axis)       # (n, k)
    full = (c_all.astype(jnp.float32) * s_all[..., None]).reshape(-1)
    return full[:size].reshape(x.shape).astype(x.dtype)


def psum_tree_compressed(tree: Any, axis: str, chunk: int = CHUNK) -> Any:
    return jax.tree.map(
        functools.partial(psum_compressed, axis=axis, chunk=chunk), tree)
