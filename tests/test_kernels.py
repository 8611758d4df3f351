"""Per-kernel validation: shape/dtype sweeps, interpret=True vs the pure-jnp
oracle in ``repro.kernels.ref`` (deliverable c)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.kernels import ref
from repro.kernels import ops
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_lse,
                                           schedule_props as fa_props)
from repro.kernels.ssd_scan import ssd_scan
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.obs import metrics as obs_metrics

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # B, H, KVH, Sq, Skv, dh, causal, window, dtype
    (2, 4, 2, 256, 256, 64, True, None, jnp.float32),
    (1, 4, 4, 128, 128, 32, True, None, jnp.float32),   # MHA
    (1, 8, 1, 128, 128, 64, True, None, jnp.float32),   # MQA
    (2, 8, 2, 256, 256, 64, True, 64, jnp.float32),     # SWA
    (1, 2, 1, 128, 256, 64, False, None, jnp.float32),  # cross/bidir
    (2, 4, 2, 256, 256, 64, True, None, jnp.bfloat16),
    (1, 4, 2, 256, 256, 128, True, 128, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FA_CASES,
                         ids=[f"fa{i}" for i in range(len(FA_CASES))])
def test_flash_attention_matches_ref(case):
    B, H, KVH, Sq, Skv, dh, causal, window, dtype = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, Sq, dh), dtype)
    k = jax.random.normal(ks[1], (B, KVH, Skv, dh), dtype)
    v = jax.random.normal(ks[2], (B, KVH, Skv, dh), dtype)
    o = flash_attention(q, k, v, causal=causal, window=window,
                        block_q=64, block_k=64, interpret=True)
    r = ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), **_tol(dtype))


def test_flash_attention_block_shape_invariance():
    """Output must not depend on the BlockSpec tiling."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
            for bq, bk in ((64, 64), (128, 64), (64, 128), (256, 256))]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


def test_flash_attention_schedule_props_skip_count():
    """Causal block-skip: executed pairs ≈ half of all pairs."""
    p_c = fa_props(1, 1, 1, 512, 512, 64, causal=True,
                   block_q=64, block_k=64)
    p_f = fa_props(1, 1, 1, 512, 512, 64, causal=False,
                   block_q=64, block_k=64)
    from repro.core import properties as props
    assert p_c[props.mxu_key(16)] < 0.6 * p_f[props.mxu_key(16)]
    assert p_c[props.BARRIER] == p_f[props.BARRIER]  # grid still walks


def _bhsd(B, H, KVH, Sq, Skv, dh, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, H, Sq, dh), jnp.float32),
            jax.random.normal(ks[1], (B, KVH, Skv, dh), jnp.float32),
            jax.random.normal(ks[2], (B, KVH, Skv, dh), jnp.float32),
            jax.random.normal(ks[3], (B, H, Sq, dh), jnp.float32))


def _plain_bhsd(q, k, v, causal, window):
    """``attention._plain_attention`` (the XLA path) in (B, H, S, dh)."""
    t = lambda x: x.transpose(0, 2, 1, 3)
    o = attn_mod._plain_attention(
        t(q), t(k), t(v), jnp.arange(q.shape[2]), jnp.arange(k.shape[2]),
        causal, window, 1.0 / np.sqrt(q.shape[-1]))
    return t(o)


@pytest.mark.parametrize("KVH,window", [(4, None), (2, None), (4, 96),
                                        (2, 96)],
                         ids=["causal-G1", "causal-G2", "window-G1",
                              "window-G2"])
def test_flash_attention_grads_match_plain(KVH, window):
    """dq, dk, dv of the backward kernels = jax.grad of the f32 XLA path."""
    q, k, v, w = _bhsd(1, 4, KVH, 256, 256, 64)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    ours = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=128, block_k=128,
        interpret=True)), (0, 1, 2))(q, k, v)
    theirs = jax.grad(loss(lambda q, k, v: _plain_bhsd(
        q, k, v, True, window)), (0, 1, 2))(q, k, v)
    for a, b in zip(ours, theirs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_flash_attention_lse_matches_logsumexp():
    q, k, v, _ = _bhsd(1, 4, 2, 256, 256, 64)
    o, lse = flash_attention_lse(q, k, v, causal=True, window=96,
                                 block_q=128, block_k=128, interpret=True)
    kg = jnp.repeat(k, 2, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kg,
                   precision=jax.lax.Precision.HIGHEST) / 8.0
    pos = jnp.arange(256)
    vis = (pos[:, None] >= pos[None, :]) & (pos[:, None] - pos[None, :] < 96)
    want = jax.nn.logsumexp(jnp.where(vis, s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(
        _plain_bhsd(q, k, v, True, 96)), atol=1e-5, rtol=1e-5)


def test_flash_attention_fully_masked_rows_are_zero():
    """Queries past the keys' window see no key: zero output, lse +inf,
    and finite gradients that are zero for those rows."""
    q, k, v, w = _bhsd(1, 2, 1, 256, 128, 64)
    kw = dict(causal=False, window=64, block_q=128, block_k=128,
              interpret=True)
    o, lse = flash_attention_lse(q, k, v, **kw)
    hidden = np.arange(256) - 127 >= 64          # no key within the window
    assert hidden.any() and not hidden.all()
    np.testing.assert_array_equal(np.asarray(o)[:, :, hidden], 0.0)
    assert np.isposinf(np.asarray(lse)[:, :, hidden]).all()
    assert np.isfinite(np.asarray(lse)[:, :, ~hidden]).all()
    grads = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, **kw) * w), (0, 1, 2))(q, k, v)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    np.testing.assert_array_equal(np.asarray(grads[0])[:, :, hidden], 0.0)


def _count_eqns(jaxpr, name: str) -> int:
    """Equations of primitive ``name`` in a jaxpr and every jaxpr in its
    equations' parameters (each occurrence counted)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    n += _count_eqns(sub, name)
    return n


def test_remat_keeps_the_flash_forward_outputs():
    """A block checkpointed with ``"full"`` saves the forward kernel's o
    and lse, so its gradient runs the forward kernel once (forward, dK/dV,
    dQ: three calls), where saving nothing runs it again (four).  The
    gradients are those of the unsaved and the unremat'd block, and each
    trace of the forward rule names its residuals once."""
    from repro.models.transformer import _remat
    q, k, v, w = _bhsd(1, 4, 2, 256, 256, 64)

    def block(q, k, v):
        return jnp.sin(ops.flash_attention(q, k, v, block_q=128,
                                           block_k=128, interpret=True) * w)

    policies = {
        "full": _remat(block, "full"),
        "dots": _remat(block, "dots"),
        "none": _remat(block, "none"),
        "nothing_saveable": jax.checkpoint(
            block, policy=jax.checkpoint_policies.nothing_saveable),
    }
    grads = {name: jax.grad(lambda q, k, v, f=f: jnp.sum(f(q, k, v)),
                            (0, 1, 2)) for name, f in policies.items()}
    named = obs_metrics.REGISTRY.counter("repro_flash_residuals_named_total")
    jax.clear_caches()           # the kernel's jit traces its rules afresh
    before = named.value()
    jaxprs = {name: jax.make_jaxpr(g)(q, k, v) for name, g in grads.items()}
    assert named.value() - before == 1
    calls = {name: _count_eqns(j.jaxpr, "pallas_call")
             for name, j in jaxprs.items()}
    assert calls == {"full": 3, "dots": 3, "none": 3, "nothing_saveable": 4}
    want = grads["full"](q, k, v)
    for name in ("none", "nothing_saveable"):
        for a, b in zip(grads[name](q, k, v), want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("chunked", [False, True], ids=["plain", "chunked"])
@pytest.mark.parametrize("policy,old", [
    ("full", jax.checkpoint_policies.nothing_saveable),
    ("dots", jax.checkpoint_policies.dots_with_no_batch_dims_saveable)])
def test_remat_saves_as_before_off_the_kernel_path(chunked, policy, old,
                                                   capsys):
    """The XLA attention paths carry no flash names, so ``_remat`` saves
    what the plain policy saved there."""
    from jax.ad_checkpoint import print_saved_residuals

    from repro.models.transformer import _remat
    q, k, v = _bshd(1, 512, 4, 2, 64)

    def block(q, k, v):
        return jnp.sin(attn_mod.attention_core(q, k, v,
                                               force_chunked=chunked))

    print_saved_residuals(_remat(block, policy), q, k, v)
    ours = capsys.readouterr().out
    print_saved_residuals(jax.checkpoint(block, policy=old), q, k, v)
    assert ours == capsys.readouterr().out and ours


def _lowerings():
    c = obs_metrics.REGISTRY.counter("repro_attention_lowerings_total")
    return {p: c.value(path=p)
            for p in ("pallas_flash", "xla_chunked", "xla_plain")}


def _took(before):
    after = _lowerings()
    return {p for p in after if after[p] > before[p]}


@pytest.fixture
def tpu_backend(monkeypatch):
    """``attention_core`` sees a TPU; the kernels still run interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "_default_interpret", lambda: True)


def _bshd(B, S, H, KVH, dh, Skv=None):
    ks = jax.random.split(KEY, 3)
    Skv = S if Skv is None else Skv
    return (jax.random.normal(ks[0], (B, S, H, dh), jnp.float32),
            jax.random.normal(ks[1], (B, Skv, KVH, dh), jnp.float32),
            jax.random.normal(ks[2], (B, Skv, KVH, dh), jnp.float32))


def test_attention_core_takes_the_kernels_on_tpu(tpu_backend):
    """An aligned training shape runs the Pallas path, forward and
    backward, with the XLA path's numbers."""
    q, k, v = _bshd(1, 256, 4, 2, 64)
    before = _lowerings()

    def loss(q, k, v):
        return jnp.sum(jnp.sin(attn_mod.attention_core(q, k, v)))

    val, grads = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
    assert _took(before) == {"pallas_flash"}
    pos = jnp.arange(256)
    want_val, want = jax.value_and_grad(lambda q, k, v: jnp.sum(jnp.sin(
        attn_mod._plain_attention(q, k, v, pos, pos, True, None, 0.125))),
        (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(val), float(want_val), rtol=1e-5)
    for a, b in zip(grads, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_attention_core_keeps_xla_where_the_kernels_do_not_fit(tpu_backend):
    """Decode (one query at a cache offset), a length off the block, and
    offsets traced under the context-parallel vmap take the XLA paths."""
    cases = {
        "decode": lambda: attn_mod.attention_core(
            *_bshd(2, 1, 4, 2, 64, Skv=256), q_offset=255),
        "unaligned": lambda: attn_mod.attention_core(*_bshd(1, 200, 4, 2,
                                                            64)),
        "vmapped offsets": lambda: jax.vmap(
            lambda qq, off: attn_mod.attention_core(
                qq, *_bshd(1, 256, 4, 2, 64)[1:], q_offset=off),
            in_axes=(1, 0), out_axes=1)(
                _bshd(1, 256, 4, 2, 64)[0].reshape(1, 2, 128, 4, 64),
                jnp.arange(2, dtype=jnp.float32) * 128),
    }
    for name, run in cases.items():
        before = _lowerings()
        jax.eval_shape(run)
        took = _took(before)
        assert took and "pallas_flash" not in took, name


def test_attention_core_keeps_xla_on_cpu():
    before = _lowerings()
    jax.eval_shape(lambda: attn_mod.attention_core(*_bshd(1, 256, 4, 2, 64)))
    assert _took(before) == {"xla_plain"}


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # Bz, H, G, L, P, N, chunk, dtype
    (2, 4, 1, 256, 32, 16, 64, jnp.float32),
    (1, 4, 2, 128, 64, 32, 32, jnp.float32),
    (2, 2, 2, 128, 16, 64, 128, jnp.float32),
    (1, 4, 1, 256, 64, 128, 64, jnp.float32),  # mamba2-370m-like ratios
    (2, 4, 1, 256, 32, 16, 64, jnp.bfloat16),
]


def _ssd_inputs(Bz, H, G, L, P, N, dtype):
    ks = jax.random.split(KEY, 5)
    x = (jax.random.normal(ks[0], (Bz, H, L, P), jnp.float32) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bz, H, L), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    B = jax.random.normal(ks[3], (Bz, G, L, N), jnp.float32) * 0.3
    C = jax.random.normal(ks[4], (Bz, G, L, N), jnp.float32) * 0.3
    return x, dt, A, B, C


@pytest.mark.parametrize("case", SSD_CASES,
                         ids=[f"ssd{i}" for i in range(len(SSD_CASES))])
def test_ssd_scan_matches_naive_recurrence(case):
    Bz, H, G, L, P, N, chunk, dtype = case
    x, dt, A, B, C = _ssd_inputs(Bz, H, G, L, P, N, dtype)
    y, h = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, hr = ref.ssd(x, dt, A, B, C)
    tol = dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 \
        else dict(atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=5e-4, rtol=5e-4)


def test_ssd_scan_matches_xla_production_path():
    """Kernel ≡ the chunked XLA path used by the models (same math)."""
    Bz, H, G, L, P, N = 2, 4, 1, 256, 32, 16
    x, dt, A, B, C = _ssd_inputs(Bz, H, G, L, P, N, jnp.float32)
    y_k, h_k = ssd_scan(x, dt, A, B, C, chunk=64, interpret=True)
    # _ssd_chunked uses (B, L, H, P) layout
    y_x, h_x = ssm_mod._ssd_chunked(
        x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), A,
        B.transpose(0, 2, 1, 3), C.transpose(0, 2, 1, 3), chunk=64)
    np.testing.assert_allclose(np.asarray(y_k),
                               np.asarray(y_x.transpose(0, 2, 1, 3)),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_x),
                               atol=5e-4, rtol=5e-4)


def test_ssd_chunk_invariance():
    Bz, H, G, L, P, N = 1, 2, 1, 256, 16, 16
    x, dt, A, B, C = _ssd_inputs(Bz, H, G, L, P, N, jnp.float32)
    outs = [ssd_scan(x, dt, A, B, C, chunk=c, interpret=True)[0]
            for c in (32, 64, 128, 256)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Matmul / transpose (measurement-kernel classes)
# ---------------------------------------------------------------------------

MM_CASES = [
    (256, 384, 512, 128, jnp.float32),
    (128, 128, 128, 128, jnp.float32),
    (512, 256, 256, 64, jnp.float32),
    (256, 2048, 256, 128, jnp.float32),   # skinny (n = l = m/8)
    (256, 256, 256, 128, jnp.bfloat16),
]


@pytest.mark.parametrize("case", MM_CASES,
                         ids=[f"mm{i}" for i in range(len(MM_CASES))])
def test_matmul_matches_ref(case):
    M, K, N, blk, dtype = case
    ks = jax.random.split(KEY, 2)
    a = jax.random.normal(ks[0], (M, K), dtype)
    b = jax.random.normal(ks[1], (K, N), dtype)
    o = ops.matmul(a, b, block_m=blk, block_n=blk, block_k=blk,
                   interpret=True)
    r = ref.matmul(a, b)
    tol = dict(atol=1.0, rtol=3e-2) if dtype == jnp.bfloat16 \
        else dict(atol=1e-3, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), **tol)


@pytest.mark.parametrize("shape,blk", [((256, 256), 128), ((512, 256), 128),
                                       ((128, 384), 64)])
def test_transpose_matches_ref(shape, blk):
    x = jax.random.normal(KEY, shape, jnp.float32)
    o = ops.transpose(x, block=blk, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(x.T))
