"""Plain float32 Qwen2 forward pass: the reference that decides
``correct`` for the qwen2 serving cells.

Written from the published description (Qwen2 technical report,
arXiv:2407.10671; the Hugging Face ``modeling_qwen2`` equations): token
embedding, then per layer pre-RMSNorm, grouped-query attention with
biased Q/K/V projections and rotary position embedding (rotate-half,
base ``rope_theta``), causal softmax, output projection, residual;
pre-RMSNorm SwiGLU MLP, residual; final RMSNorm and logits through the
tied embedding.  It imports nothing of the program: it reads the
parameter arrays by name and the sizes from the configuration file.

Every product runs at ``precision=HIGHEST`` (true float32 on a TPU).
Attention is computed in blocks of query rows and logits in blocks of
positions, so a whole context fits beside the weights.  Logits are
taken over every row of the embedding the weights hold (the padded
rows are zero, and give logit 0 on both sides).

``quant="fp8"`` computes every product from float8 (e4m3) operands with
one scale per tensor, accumulating in float32: the control, one
precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
# the configuration file's keys this reference reads, and the ``quant``
# its control computes at
SIZES = ("rms_norm_eps", "rope_theta", "num_attention_heads",
         "num_key_value_heads", "head_dim")
CONTROL = "fp8"


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), s


def mm(spec, a, b, quant):
    """``einsum(spec, a, b)`` in float32, or from fp8 operands."""
    if quant is None:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    qa, sa = _q8(a)
    qb, sb = _q8(b)
    return jnp.einsum(spec, qa, qb,
                      preferred_element_type=jnp.float32) * (sa * sb)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (S, H, D); rotate-half rotary embedding at positions 0..S-1."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, quant, block: int):
    """Causal GQA softmax attention.  q: (S, Hq, D); k, v: (S, Hkv, D)."""
    S, Hq, D = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)        # kv head of q head h: h // rep
    v = jnp.repeat(v, rep, axis=1)
    nb = S // block
    qb = q.reshape(nb, block, Hq, D)

    def one(args):
        i, qi = args
        s = mm("qhd,khd->hqk", qi, k, quant) / jnp.sqrt(jnp.float32(D))
        rows = i * block + jnp.arange(block)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("hqk,khd->qhd", p, v, quant)

    out = jax.lax.map(one, (jnp.arange(nb), qb))
    return out.reshape(S, Hq, D)


def hidden(params, tokens, sizes: dict, quant: Optional[str] = None,
           block: int = 512):
    """Final-normed hidden states (S, d) for one token sequence."""
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    nh, nkv, hd = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    S = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        a = p["attn"]
        h = rms_norm(x, p["norm1"]["scale"], eps)
        q = (mm("sd,dn->sn", h, a["wq"], quant) + a["bq"]).reshape(S, nh, hd)
        k = (mm("sd,dn->sn", h, a["wk"], quant) + a["bk"]).reshape(S, nkv, hd)
        v = (mm("sd,dn->sn", h, a["wv"], quant) + a["bv"]).reshape(S, nkv, hd)
        o = attention(rope(q, theta), rope(k, theta), v, quant, block)
        x = x + mm("sn,nd->sd", o.reshape(S, nh * hd), a["wo"], quant)
        f = p["ffn"]
        h = rms_norm(x, p["norm2"]["scale"], eps)
        g = jax.nn.silu(mm("sd,df->sf", h, f["w_gate"], quant))
        u = mm("sd,df->sf", h, f["w_up"], quant)
        return x + mm("sf,fd->sd", g * u, f["w_down"], quant), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rms_norm(x, params["final_norm"]["scale"], eps)


def head_stats(embed, h, targets, quant: Optional[str] = None,
               block: int = 256):
    """Per position: the largest logit, the logit of ``targets`` and the
    argmax, with logits ``h @ embed.T`` computed ``block`` rows at a
    time."""
    S = h.shape[0]
    nb = S // block

    def one(args):
        hb, tb = args
        lg = mm("sd,vd->sv", hb, embed, quant)
        at = jnp.take_along_axis(lg, tb[:, None], axis=1)[:, 0]
        return lg.max(-1), at, jnp.argmax(lg, -1).astype(jnp.int32)

    mx, at, am = jax.lax.map(one, (h.reshape(nb, block, -1),
                                   targets.reshape(nb, block)))
    return mx.reshape(S), at.reshape(S), am.reshape(S)


@functools.partial(jax.jit, static_argnames=("sizes", "quant"))
def _gaps(params, tokens, targets, sizes, quant):
    sz = dict(sizes)
    h = hidden(params, tokens, sz)
    mx, at, _ = head_stats(params["embed"], h, targets)
    if quant is None:
        return mx - at
    hq = hidden(params, tokens, sz, quant)
    _, _, choice = head_stats(params["embed"], hq, targets, quant)
    _, at_c, _ = head_stats(params["embed"], h, choice)
    return mx - at_c


def served_gaps(params, sizes: dict, prompt, served, pad_to: int,
                quant: Optional[str] = None):
    """Gap, in float32 logits, by which each served token lies below the
    reference's best at its position (``quant`` set: the gap of the
    token the lower precision puts first there).  ``prompt`` and
    ``served`` are int sequences; the sequence is padded to ``pad_to``
    (a multiple of 512), which causal attention leaves without effect."""
    import numpy as np

    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    n_p, n_s = len(prompt), len(served)
    if len(seq) > pad_to or pad_to % 512:
        raise ValueError(f"sequence of {len(seq)} in a pad of {pad_to}")
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros(pad_to, np.int32)
    targets[n_p - 1:n_p - 1 + n_s] = served   # logits at p predict p + 1
    key = tuple(sorted(sizes.items()))
    g = _gaps(params, jnp.asarray(tokens), jnp.asarray(targets), key, quant)
    return np.asarray(g)[n_p - 1:n_p - 1 + n_s]
