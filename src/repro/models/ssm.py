"""Mamba2 mixer: chunked SSD (state-space duality, arXiv:2405.21060) for
train/prefill (linear in sequence length) and an O(1) recurrence for decode.

The Pallas kernel in ``repro.kernels.ssd_scan`` implements the intra-chunk
quadratic piece for the TPU hot path; this module is the XLA production path
and the kernel's reference.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import logical
from repro.models import layers
from repro.obs import scopes


class SSMState(NamedTuple):
    conv: jnp.ndarray  # (B, d_conv-1, conv_dim) — trailing conv inputs
    h: jnp.ndarray     # (B, nH, P, N) — SSM recurrent state


def ssm_init(key, cfg, dtype):
    s = cfg.ssm
    d = cfg.d_model
    din = cfg.d_inner
    nH = cfg.ssm_heads
    N, G = s.d_state, s.n_groups
    conv_dim = din + 2 * G * N
    ks = jax.random.split(key, 5)
    p, a = {}, {}
    # in_proj -> [z, x, B, C, dt]
    out_dim = 2 * din + 2 * G * N + nH
    p["in_proj"], a["in_proj"] = layers.dense_init(
        ks[0], d, out_dim, dtype, "embed", "ssm_inner")
    p["conv_w"] = (jax.random.normal(ks[1], (s.d_conv, conv_dim), jnp.float32)
                   * 0.02).astype(dtype)
    a["conv_w"] = ("conv", "ssm_inner")
    p["conv_b"] = jnp.zeros((conv_dim,), dtype)
    a["conv_b"] = ("ssm_inner",)
    p["A_log"] = jnp.log(jnp.linspace(1.0, 16.0, nH).astype(jnp.float32))
    a["A_log"] = ("ssm_heads",)
    p["D"] = jnp.ones((nH,), jnp.float32)
    a["D"] = ("ssm_heads",)
    p["dt_bias"] = jnp.zeros((nH,), jnp.float32)
    a["dt_bias"] = ("ssm_heads",)
    p["norm"] = jnp.ones((din,), dtype)
    a["norm"] = ("ssm_inner",)
    p["out_proj"], a["out_proj"] = layers.dense_init(
        ks[4], din, d, dtype, "ssm_inner", "embed")
    return p, a


def _split_proj(cfg, proj):
    s = cfg.ssm
    din, nH = cfg.d_inner, cfg.ssm_heads
    GN = s.n_groups * s.d_state
    z, xbc_dt = jnp.split(proj, [din], axis=-1)
    xbc, dt = jnp.split(xbc_dt, [din + 2 * GN], axis=-1)
    return z, xbc, dt  # (…, din), (…, din+2GN), (…, nH)


def _causal_conv(xbc, w, b):
    """Depthwise causal conv1d.  xbc (B, L, Cd); w (k, Cd)."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    # windowed sum: sum_j w[j] * x[t-k+1+j]
    out = sum(pad[:, j:j + xbc.shape[1], :] * w[j] for j in range(k))
    return jax.nn.silu(out + b)


@scopes.scoped(scopes.SSD_SCAN)
def _ssd_chunked(x, dt, A, Bm, Cm, chunk: int, h0=None):
    """Chunked SSD.

    x  (B, L, H, P)    dt (B, L, H)      A (H,) negative
    Bm (B, L, G, N)    Cm (B, L, G, N)   h0 optional (B, H, P, N)
    Returns (y (B,L,H,P), h_final (B,H,P,N)).  G must divide H.
    """
    Bsz, L, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert L % chunk == 0, (L, chunk)
    nc, Q = L // chunk, chunk
    rep = H // G

    dA = dt * A  # (B, L, H), <= 0
    xc = x.reshape(Bsz, nc, Q, H, P)
    dtc = dt.reshape(Bsz, nc, Q, H)
    dAc = dA.reshape(Bsz, nc, Q, H)
    Bc = jnp.repeat(Bm.reshape(Bsz, nc, Q, G, N), rep, axis=3)  # (B,nc,Q,H,N)
    Cc = jnp.repeat(Cm.reshape(Bsz, nc, Q, G, N), rep, axis=3)

    cum = jnp.cumsum(dAc, axis=2)  # (B,nc,Q,H)

    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def chunk_body(h_prev, inp):
        xq, dtq, dAq, cumq, Bq, Cq = inp  # (B,Q,...) per chunk
        # --- intra-chunk (quadratic in Q) ---
        # decay L[i,j] = exp(cum[i]-cum[j]) for i>=j
        diff = cumq[:, :, None, :] - cumq[:, None, :, :]  # (B,Q,Q,H)
        tri = jnp.tril(jnp.ones((Q, Q), bool))
        # mask the *exponent* (upper triangle has diff > 0 -> exp overflow
        # -> inf*0 = NaN in the backward pass if masked after exp)
        diff = jnp.where(tri[None, :, :, None], diff, -1e30)
        Lmat = jnp.exp(diff)
        CB = jnp.einsum("bihn,bjhn->bijh", Cq, Bq)  # (B,Q,Q,H)
        W = CB * Lmat * dtq[:, None, :, :]  # weight of x_j in y_i
        y_intra = jnp.einsum("bijh,bjhp->bihp", W, xq.astype(jnp.float32))
        # --- inter-chunk: contribution of h_prev ---
        y_inter = jnp.einsum("bihn,bhpn->bihp", Cq * jnp.exp(cumq)[..., None],
                             h_prev)
        # --- state update ---
        decay_to_end = jnp.exp(cumq[:, -1:, :] - cumq)  # (B,Q,H)
        S_c = jnp.einsum("bjhn,bjhp->bhpn",
                         Bq * (dtq * decay_to_end)[..., None],
                         xq.astype(jnp.float32))
        h_new = h_prev * jnp.exp(cumq[:, -1])[:, :, None, None] + S_c
        return h_new, (y_intra + y_inter).astype(x.dtype)

    xs = (
        xc.transpose(1, 0, 2, 3, 4),
        dtc.transpose(1, 0, 2, 3),
        dAc.transpose(1, 0, 2, 3),
        cum.transpose(1, 0, 2, 3),
        Bc.transpose(1, 0, 2, 3, 4),
        Cc.transpose(1, 0, 2, 3, 4),
    )
    # checkpoint the chunk body: its (B, Q, Q, H) decay/weight tensors would
    # otherwise be stacked ×nc as scan residuals for the backward pass
    # (~10 GB/layer at zamba2 scale — EXPERIMENTS.md §Perf iteration A);
    # recomputing them from the (small) carried state is near-free
    h_fin, ys = jax.lax.scan(jax.checkpoint(chunk_body), h0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, L, H, P)
    return y, h_fin


@scopes.scoped(scopes.SSM)
def ssm_apply(p, x, cfg, *, state: SSMState | None = None
              ) -> Tuple[jnp.ndarray, SSMState | None]:
    """Mamba2 block.  x (B, S, d).  With ``state``, runs one decode step."""
    s = cfg.ssm
    Bsz, S, d = x.shape
    din, nH, N, G = cfg.d_inner, cfg.ssm_heads, s.d_state, s.n_groups
    P = s.head_dim
    A = -jnp.exp(p["A_log"])  # (nH,)

    proj = layers.dense(p["in_proj"], x)  # (B,S,out_dim)
    z, xbc, dt_raw = _split_proj(cfg, proj)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,nH)

    new_state = None
    if state is None:
        xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"])
        xin, BC = jnp.split(xbc, [din], axis=-1)
        Bm, Cm = jnp.split(BC, 2, axis=-1)
        xin = logical(xin.reshape(Bsz, S, nH, P),
                      ("act_batch", "act_seq", "act_heads", None))
        Bm = Bm.reshape(Bsz, S, G, N)
        Cm = Cm.reshape(Bsz, S, G, N)
        chunk = min(s.chunk, S)
        from repro.runtime import flags
        if flags.pallas_enabled():
            from repro.kernels import ops as kops
            with jax.named_scope(scopes.SSD_SCAN):
                y, _ = kops.ssd_scan(
                    xin.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), A,
                    Bm.transpose(0, 2, 1, 3), Cm.transpose(0, 2, 1, 3),
                    chunk=chunk, block_sizes="auto")
            y = y.transpose(0, 2, 1, 3)
        else:
            y, _ = _ssd_chunked(xin, dt, A, Bm, Cm, chunk)
    else:
        # ---- single-step decode (S == 1) ----
        conv_in = jnp.concatenate([state.conv, xbc], axis=1)  # (B,k,convdim)
        w, b = p["conv_w"], p["conv_b"]
        feat = jax.nn.silu(jnp.einsum("bkc,kc->bc", conv_in, w) + b)[:, None]
        new_conv = conv_in[:, 1:]
        xin, BC = jnp.split(feat, [din], axis=-1)
        Bm, Cm = jnp.split(BC, 2, axis=-1)
        xin = xin.reshape(Bsz, 1, nH, P).astype(jnp.float32)
        Bm = jnp.repeat(Bm.reshape(Bsz, 1, G, N), nH // G, axis=2)[:, 0]  # (B,H,N)
        Cm = jnp.repeat(Cm.reshape(Bsz, 1, G, N), nH // G, axis=2)[:, 0]
        dt1 = dt[:, 0]  # (B,H)
        dA = jnp.exp(dt1 * A)  # (B,H)
        upd = jnp.einsum("bhn,bhp->bhpn", Bm * dt1[..., None], xin[:, 0])
        h_new = state.h * dA[..., None, None] + upd
        yt = jnp.einsum("bhn,bhpn->bhp", Cm, h_new)  # (B,H,P)
        y = yt[:, None].astype(x.dtype)  # (B,1,H,P)
        new_state = SSMState(new_conv, h_new)
        xin = xin.astype(x.dtype)

    if state is None:
        xin_skip = xin
    else:
        xin_skip = xin.astype(x.dtype)
    y = y + xin_skip * p["D"][:, None].astype(x.dtype)
    y = y.reshape(Bsz, S, din)
    y = layers.rmsnorm({"scale": p["norm"]}, y * jax.nn.silu(z), cfg.norm_eps)
    out = layers.dense(p["out_proj"], y)
    return out, new_state


def init_ssm_state(cfg, B: int, dtype) -> SSMState:
    s = cfg.ssm
    conv_dim = cfg.d_inner + 2 * s.n_groups * s.d_state
    return SSMState(
        conv=jnp.zeros((B, s.d_conv - 1, conv_dim), dtype),
        h=jnp.zeros((B, cfg.ssm_heads, s.head_dim, s.d_state), jnp.float32),
    )
