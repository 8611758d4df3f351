"""Compile rehearsal: the main path's Pallas kernels at real widths, built by
the TPU compiler for a described (not attached) v5e chip.

Interpret mode cannot see what the chip's compiler refuses — block shapes
off the (8, 128) tiling, ops Mosaic cannot lower, VMEM overuse — so these
compiles guard every change to a kernel without a chip.  The topology is
described inside a module fixture, never at import: only one process may
load the TPU library, and under pytest-xdist every worker imports this
file.  Keep these tests in this one file.
"""
from __future__ import annotations

import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import compile_cache
from repro.configs.registry import get_arch
from repro.kernels import ops

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # the compiler logs nowhere
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _kernel_cases():
    """(kernel call, argument shapes) at the widths the models run them:
    smollm-360m attention and mamba2-370m SSD at 2048 tokens, batch 4;
    4096² matmul and transpose."""
    sm, mb = get_arch("smollm-360m"), get_arch("mamba2-370m")
    S, B, bf16, f32 = 2048, 4, jnp.bfloat16, jnp.float32
    kv = ((B, sm.n_kv_heads, S, sm.head_dim_), bf16)
    H, P, N, G = mb.ssm_heads, mb.ssm.head_dim, mb.ssm.d_state, \
        mb.ssm.n_groups
    auto = dict(block_sizes="auto", interpret=False)
    return {
        "flash_attention": (
            lambda q, k, v: ops.flash_attention(q, k, v, causal=True, **auto),
            [((B, sm.n_heads, S, sm.head_dim_), bf16), kv, kv]),
        "ssd_scan": (
            lambda *xs: ops.ssd_scan(*xs, chunk=mb.ssm.chunk, **auto),
            [((B, H, S, P), bf16), ((B, H, S), f32), ((H,), f32),
             ((B, G, S, N), bf16), ((B, G, S, N), bf16)]),
        "matmul": (lambda a, b: ops.matmul(a, b, **auto),
                   [((4096, 4096), bf16)] * 2),
        "transpose": (lambda x: ops.transpose(x, **auto),
                      [((4096, 4096), bf16)]),
    }


@pytest.mark.parametrize("kernel", ["flash_attention", "ssd_scan", "matmul",
                                    "transpose"])
def test_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    fn, shapes = _kernel_cases()[kernel]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_attention_core_grad_compiles_for_v5e(one_chip, no_persistent_cache,
                                              monkeypatch):
    """jax.grad through ``attention_core`` on the TPU path at smollm-360m's
    attention (1 × 15 (5 kv) × 2048 × 64, bf16): the forward kernel and
    both backward kernels are in the program, under ``attention_core``."""
    from repro.models import attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sm, S = get_arch("smollm-360m"), 2048
    q = jax.ShapeDtypeStruct((1, S, sm.n_heads, sm.head_dim_), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, sm.n_kv_heads, sm.head_dim_),
                              jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(attention.attention_core(q, k, v).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile(
        ).as_text()
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    fwd = [c for c in calls if "transpose(" not in c]
    bwd = [c for c in calls if "transpose(" in c]
    assert len(fwd) >= 1 and len(bwd) >= 2, (len(fwd), len(bwd))
    assert all("attention_core" in c for c in calls)


def test_compile_cache_dir_is_fixed_in_checkout(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_jax_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_jax_cache()
        assert path == str(ROOT / ".cache" / "jax")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_jax_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
