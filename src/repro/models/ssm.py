"""Mamba-2 block (SSD — state-space duality) for mamba2-780m.

Training/prefill uses the chunked dual form (sequential lax.scan over
chunks carrying the (H, P, N) state — same math as kernels/ssd_scan.py,
which is the TPU Pallas fast path).  Decode is the O(1) recurrence on a
persistent state — the reason this arch runs long_500k.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import Spec


class SSMCache(NamedTuple):
    h: jnp.ndarray           # (L, B, H, P, N) float32
    conv: jnp.ndarray        # (L, B, conv-1, conv_dim)


def conv_dim(cfg: ModelConfig) -> int:
    return cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state


def ssm_specs(cfg: ModelConfig):
    d, di, h = cfg.d_model, cfg.d_inner, cfg.ssm_nheads
    g, n, w = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_conv
    cd = conv_dim(cfg)
    return {
        # order: [z (di) | x (di) | B (g*n) | C (g*n) | dt (h)]
        "in_proj": Spec((d, 2 * di + 2 * g * n + h), ("embed", "ssm_inner")),
        "conv_w": Spec((w, cd), (None, "ssm_inner"),
                       scale=1.0 / math.sqrt(w)),
        "conv_b": Spec((cd,), ("ssm_inner",), "zeros"),
        "a_log": Spec((h,), (None,), "const", scale=math.log(4.0)),
        "dt_bias": Spec((h,), (None,), "const", scale=-3.0),
        "d_skip": Spec((h,), (None,), "ones"),
        "norm_scale": Spec((di,), ("ssm_inner",), "ones"),
        "out_proj": Spec((di, d), ("ssm_inner", "embed")),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv over time.  x: (B, S, C); w: (W, C).
    ``state``: (B, W-1, C) left context (decode).  Returns (y, new_state)."""
    width = w.shape[0]
    ctx = (jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
           if state is None else state.astype(x.dtype))
    xp = jnp.concatenate([ctx, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :]
            for i in range(width))
    new_state = xp[:, -(width - 1):, :] if width > 1 else ctx
    return y + b[None, None, :], new_state


def ssd_jnp(x, dt, a, b, c, chunk, h0=None):
    """Chunked SSD (pure jnp mirror of kernels/ssd_scan.py).

    x: (B, L, H, P); dt: (B, L, H); a: (H,); b, c: (B, L, G, N).
    Sequential scan over L//chunk chunks; per-chunk work is MXU matmuls.
    Returns (y (B, L, H, P) f32, h_final (B, H, P, N) f32)."""
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    q = chunk
    pad = (-L) % q
    if pad:
        # dt = 0 on padding ⇒ decay 1, zero input: mathematically inert.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    nc = Lp // q

    xr = x.reshape(B, nc, q, H, P).astype(jnp.float32)
    dtr = dt.reshape(B, nc, q, H).astype(jnp.float32)
    br = b.reshape(B, nc, q, G, N).astype(jnp.float32)
    cr = c.reshape(B, nc, q, G, N).astype(jnp.float32)
    dta = dtr * a[None, None, None, :]
    cum = jnp.cumsum(dta, axis=2)                    # (B, nc, q, H)
    h0 = (jnp.zeros((B, H, P, N), jnp.float32) if h0 is None
          else h0.astype(jnp.float32))

    tri = jnp.tril(jnp.ones((q, q), bool))

    def body(h, inp):
        xc, dtc, bc, cc, cumc = inp                  # leading dim B
        decay = jnp.exp(cumc[:, :, None, :] - cumc[:, None, :, :])
        decay = jnp.where(tri[None, :, :, None], decay, 0.0)
        cb = jnp.einsum("bqgn,bkgn->bqkg", cc, bc)
        cb = jnp.repeat(cb, rep, axis=3)             # (B, q, q, H)
        w = cb * decay * dtc[:, None, :, :]
        y = jnp.einsum("bqkh,bkhp->bqhp", w, xc)
        # inter-chunk: y_i += exp(cum_i) C_i^T h_in
        cch = jnp.repeat(cc, rep, axis=2)            # (B, q, H, N)
        y = y + jnp.exp(cumc)[..., None] * jnp.einsum(
            "bqhn,bhpn->bqhp", cch, h)
        # state update
        wj = jnp.exp(cumc[:, -1:, :] - cumc) * dtc   # (B, q, H)
        bch = jnp.repeat(bc, rep, axis=2)            # (B, q, H, N)
        h = (jnp.exp(cumc[:, -1, :])[:, :, None, None] * h
             + jnp.einsum("bqhp,bqhn->bhpn", xc * wj[..., None], bch))
        return h, y

    xs = (xr.transpose(1, 0, 2, 3, 4), dtr.transpose(1, 0, 2, 3),
          br.transpose(1, 0, 2, 3, 4), cr.transpose(1, 0, 2, 3, 4),
          cum.transpose(1, 0, 2, 3))
    from repro.models.layers import scan_unroll
    hf, ys = jax.lax.scan(body, h0, xs, unroll=scan_unroll())
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, Lp, H, P)[:, :L]
    return y, hf


def _split_proj(cfg: ModelConfig, zxbcdt):
    di, g, n, h = (cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state,
                   cfg.ssm_nheads)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * g * n]
    dt = zxbcdt[..., di + di + 2 * g * n:]
    assert dt.shape[-1] == h
    return z, xbc, dt


def apply_ssm_layer(cfg: ModelConfig, p, x, cache=None):
    """One Mamba-2 mixing layer.  x: (B, S, D).
    cache: None (training/prefill from scratch) or (h, conv_state) for
    single-token decode.  Returns (y, new_cache)."""
    B, S, D = x.shape
    di, g, n = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state
    H, P = cfg.ssm_nheads, cfg.ssm_headdim

    zxbcdt = x @ p["in_proj"].astype(x.dtype)
    z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
    conv_state = None if cache is None else cache[1]
    xbc, new_conv = _causal_conv(xbc, p["conv_w"].astype(x.dtype),
                                 p["conv_b"].astype(x.dtype), conv_state)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di]
    b = xbc[..., di:di + g * n].reshape(B, S, g, n)
    c = xbc[..., di + g * n:].reshape(B, S, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh = xs.reshape(B, S, H, P)

    if cache is None:
        y, hf = ssd_jnp(xh, dt, a, b, c, cfg.ssm_chunk)
    else:
        # O(1) decode recurrence (S == 1).  The state may come as a
        # tuple of head slices in head order (a layer-list model reads
        # it from page rows so); each slice is updated on its own
        decay = jnp.exp(dt[:, 0] * a[None, :])       # (B, H)
        rep = H // g
        bh = jnp.repeat(b[:, 0], rep, axis=1)        # (B, H, N)
        ch = jnp.repeat(c[:, 0], rep, axis=1)
        dtx = dt[:, 0, :, None] * xh[:, 0].astype(jnp.float32)
        parts = cache[0] if isinstance(cache[0], tuple) else (cache[0],)
        hf, ys, lo = [], [], 0
        for h0 in parts:
            hi = lo + h0.shape[1]
            hs = (h0 * decay[:, lo:hi, None, None]
                  + dtx[:, lo:hi, :, None]
                  * bh[:, lo:hi, None, :].astype(jnp.float32))
            ys.append(jnp.einsum("bhpn,bhn->bhp", hs,
                                 ch[:, lo:hi].astype(jnp.float32)))
            hf.append(hs)
            lo = hi
        hf = tuple(hf) if isinstance(cache[0], tuple) else hf[0]
        y = jnp.concatenate(ys, 1)[:, None] if len(ys) > 1 else ys[0][:, None]

    y = y + p["d_skip"][None, None, :, None].astype(jnp.float32) \
        * xh.astype(jnp.float32)
    y = y.reshape(B, S, di).astype(x.dtype) * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    var = (yf ** 2).mean(-1, keepdims=True)
    y = (yf * jax.lax.rsqrt(var + cfg.norm_eps)
         * p["norm_scale"][None, None, :]).astype(x.dtype)
    out = y @ p["out_proj"].astype(x.dtype)
    return out, (hf, new_conv)
