"""Jitted public wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU (kernel bodies execute in Python for
validation) and False on TPU (compiled for the MXU/VMEM target).  Model code
calls these wrappers; swapping the XLA production path for the Pallas hot
path is a Plan-level switch (``Plan.use_pallas`` in the runtime).

Every wrapper accepts ``block_sizes``:

  * ``None`` (default) — use the explicit ``block_*`` keyword arguments;
  * a mapping — override the block keywords wholesale;
  * ``"auto"`` — ask the cost-model-guided autotuner
    (``repro.kernels.autotune.best_block_sizes``) to pick them for this
    shape, scoring candidates through ``model`` (None → analytic v5e seed,
    a registry device name, or an in-memory ``LinearCostModel``).

``"auto"`` resolution happens in plain Python before the jitted inner call,
so it runs once per (shape, model) at trace time and is memoized.
"""
from __future__ import annotations

import functools
from typing import Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import matmul as _mm
from repro.kernels import ssd_scan as _ssd
from repro.kernels import transpose as _tr

BlockSizes = Union[None, str, Mapping[str, int]]


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dtype_bits(dtype) -> int:
    return jnp.dtype(dtype).itemsize * 8


def _resolve_blocks(kernel: str, shape: dict, block_sizes: BlockSizes,
                    explicit: dict, model) -> dict:
    """Merge the three block-size sources (explicit kwargs < mapping <
    autotuner) into concrete ints."""
    if block_sizes is None:
        return explicit
    if block_sizes == "auto":
        from repro.kernels import autotune
        return dict(autotune.best_block_sizes(kernel, shape, model=model))
    if isinstance(block_sizes, Mapping):
        out = dict(explicit)
        out.update(block_sizes)
        return out
    raise TypeError(f"block_sizes must be None, 'auto' or a mapping; "
                    f"got {block_sizes!r}")


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def _flash_attention_jit(q, k, v, *, causal, window, block_q, block_k,
                         interpret):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    block_sizes: BlockSizes = None, model=None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q (B,H,Sq,dh) × k,v (B,KVH,Skv,dh) → (B,H,Sq,dh)."""
    if interpret is None:
        interpret = _default_interpret()
    B, H, Sq, dh = q.shape
    shape = {"B": B, "H": H, "KVH": k.shape[1], "Sq": Sq, "Skv": k.shape[2],
             "dh": dh, "causal": causal, "window": window,
             "bits": _dtype_bits(q.dtype)}
    blocks = _resolve_blocks("flash_attention", shape, block_sizes,
                             {"block_q": block_q, "block_k": block_k}, model)
    return _flash_attention_jit(q, k, v, causal=causal, window=window,
                                block_q=blocks["block_q"],
                                block_k=blocks["block_k"],
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_scan_jit(x, dt, A, B, C, *, chunk, interpret):
    return _ssd.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128,
             block_sizes: BlockSizes = None, model=None,
             interpret: Optional[bool] = None) -> Tuple[jnp.ndarray,
                                                        jnp.ndarray]:
    """Chunked SSD: x (Bz,H,L,P), dt (Bz,H,L), A (H,), B/C (Bz,G,L,N)."""
    if interpret is None:
        interpret = _default_interpret()
    Bz, H, L, P = x.shape
    shape = {"Bz": Bz, "H": H, "L": L, "P": P, "N": B.shape[3],
             "bits": _dtype_bits(x.dtype)}
    blocks = _resolve_blocks("ssd_scan", shape, block_sizes,
                             {"chunk": chunk}, model)
    return _ssd_scan_jit(x, dt, A, B, C, chunk=blocks["chunk"],
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret"))
def _matmul_jit(a, b, *, block_m, block_n, block_k, interpret):
    return _mm.matmul(a, b, block_m=block_m, block_n=block_n,
                      block_k=block_k, interpret=interpret)


def matmul(a, b, *, block_m: int = 128, block_n: int = 128,
           block_k: int = 128, block_sizes: BlockSizes = None, model=None,
           interpret: Optional[bool] = None):
    if interpret is None:
        interpret = _default_interpret()
    shape = {"M": a.shape[0], "K": a.shape[1], "N": b.shape[1],
             "bits": _dtype_bits(a.dtype)}
    blocks = _resolve_blocks(
        "matmul", shape, block_sizes,
        {"block_m": block_m, "block_n": block_n, "block_k": block_k}, model)
    return _matmul_jit(a, b, block_m=blocks["block_m"],
                       block_n=blocks["block_n"], block_k=blocks["block_k"],
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _transpose_jit(x, *, block, interpret):
    return _tr.transpose(x, block=block, interpret=interpret)


def transpose(x, *, block: int = 256, block_sizes: BlockSizes = None,
              model=None, interpret: Optional[bool] = None):
    if interpret is None:
        interpret = _default_interpret()
    shape = {"M": x.shape[0], "N": x.shape[1],
             "bits": _dtype_bits(x.dtype)}
    blocks = _resolve_blocks("transpose", shape, block_sizes,
                             {"block": block}, model)
    return _transpose_jit(x, block=blocks["block"], interpret=interpret)
