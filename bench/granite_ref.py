"""Plain float32 Granite-4.0-H forward pass: the reference that decides
``correct`` for the granite-4.0-h-small serving cells.

Written from the published description (the Hugging Face
``modeling_granitemoehybrid`` equations; Mamba-2, arXiv:2405.21060):
the token embedding times ``embedding_multiplier``; then, layer by
layer in the order ``layer_types`` gives, pre-RMSNorm, the mixer and a
residual add of ``residual_multiplier`` × its output; pre-RMSNorm, the
mixture of experts plus the shared SwiGLU expert, and the same
residual add; a final RMSNorm and logits through the tied embedding,
divided by ``logits_scaling``.

- Mamba-2 is computed as its token-by-token recurrence, not the chunked
  dual form: in-projection to (z, xBC, dt); a causal depthwise conv of
  width ``mamba_d_conv`` with its bias, then SiLU; dt = softplus(dt +
  dt_bias), A = -exp(A_log); per token h ← exp(dt·A)·h + dt·x⊗B and
  y = h·C + D·x; y · SiLU(z) through an RMSNorm over the whole inner
  width (one group); the out-projection.
- Attention is causal grouped-query softmax attention with no position
  embedding (NoPE), scores scaled by ``attention_multiplier``, computed
  in blocks of query rows.
- The router scores all ``router_num_experts`` experts; the top
  ``num_experts_per_tok`` logits are weighted by a softmax over those
  k.  This chip's share of the experts, ``num_local_experts`` from
  ``held_expert_offset``, is computed; what the absent experts would
  add is left out, as the program leaves it out (the deployment adds
  it on the other chips of the stage).

It imports nothing of the program: it reads the parameter arrays by
name (``embed``, ``final_norm``, and each kind's stacked layers under
``mamba`` and ``attn``) and the sizes from the configuration file.
Weights are the program's (bfloat16), upcast to float32; every product
runs at ``precision=HIGHEST``.  ``quant="fp8"`` computes every matrix
product from float8 (e4m3) operands with one scale per tensor: the
control.  The recurrence, the conv, norms and softmaxes stay float32
in the control as well.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from bench.qwen2_ref import HIGHEST, head_stats, mm, rms_norm

SIZES = ("layer_types", "rms_norm_eps", "num_attention_heads",
         "num_key_value_heads", "attention_multiplier",
         "embedding_multiplier", "residual_multiplier", "logits_scaling",
         "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
         "mamba_d_conv", "num_experts_per_tok", "held_expert_offset")
CONTROL = "fp8"


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def attention(q, k, v, scale, quant, block: int):
    """Causal GQA softmax attention.  q: (S, Hq, D); k, v: (S, Hkv, D)."""
    S, Hq, D = q.shape
    rep = Hq // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)        # kv head of q head h: h // rep
    v = jnp.repeat(v, rep, axis=1)
    nb = S // block

    def one(args):
        i, qi = args
        s = mm("qhd,khd->hqk", qi, k, quant) * scale
        rows = i * block + jnp.arange(block)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, quant)

    out = jax.lax.map(one, (jnp.arange(nb), q.reshape(nb, block, Hq, D)))
    return out.reshape(S, Hq, D)


def mamba(p, x, sz: dict, quant):
    """One Mamba-2 mixer over a sequence x (S, d), token by token."""
    H, P, N = sz["mamba_n_heads"], sz["mamba_d_head"], sz["mamba_d_state"]
    G, W = sz["mamba_n_groups"], sz["mamba_d_conv"]
    S, di = x.shape[0], H * P
    zxbcdt = mm("sd,dn->sn", x, p["in_proj"], quant)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * G * N],
                  zxbcdt[:, 2 * di + 2 * G * N:])
    xp = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[i:i + S] * p["conv_w"][i] for i in range(W))
                      + p["conv_b"])
    xs = xbc[:, :di].reshape(S, H, P)
    b = jnp.repeat(xbc[:, di:di + G * N].reshape(S, G, N), H // G, 1)
    c = jnp.repeat(xbc[:, di + G * N:].reshape(S, G, N), H // G, 1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["a_log"])

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y = jnp.einsum("hpn,hn->hp", h, c_t, precision=HIGHEST)
        return h, y + p["d_skip"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xs, dt, b, c))
    y = rms_norm(y.reshape(S, di) * jax.nn.silu(z), p["norm_scale"],
                 sz["rms_norm_eps"])
    return mm("sn,nd->sd", y, p["out_proj"], quant)


def moe(p, x, sz: dict, quant):
    """This chip's share of the mixture of experts, plus the shared
    expert, for x (S, d)."""
    E, K = p["router"].shape[1], sz["num_experts_per_tok"]
    Eh, e0 = p["w_gate"].shape[0], sz["held_expert_offset"]
    logits = mm("sd,de->se", x, p["router"], quant)
    top, idx = jax.lax.top_k(logits, K)
    w = jax.nn.softmax(top, axis=-1)
    gate = jnp.einsum("sk,ske->se", w,
                      jax.nn.one_hot(idx - e0, Eh, dtype=jnp.float32))
    assert E >= e0 + Eh
    h = (jax.nn.silu(mm("sd,edf->sef", x, p["w_gate"], quant))
         * mm("sd,edf->sef", x, p["w_up"], quant))
    y = mm("sef,efd->sd", h * gate[:, :, None], p["w_down"], quant)
    s = p["shared"]
    g = jax.nn.silu(mm("sd,df->sf", x, s["w_gate"], quant))
    return y + mm("sf,fd->sd", g * mm("sd,df->sf", x, s["w_up"], quant),
                  s["w_down"], quant)


def hidden(params, tokens, sizes: dict, quant: Optional[str] = None,
           block: int = 512):
    """Final-normed hidden states (S, d) for one token sequence."""
    eps, r = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    S = tokens.shape[0]
    x = (params["embed"][tokens].astype(jnp.float32)
         * sizes["embedding_multiplier"])

    def layer(kind, x, p):
        p = _f32(p)
        h = rms_norm(x, p["norm1"]["scale"], eps)
        if kind == "mamba":
            y = mamba(p["mixer"], h, sizes, quant)
        else:
            a = p["attn"]
            hd = a["wq"].shape[1] // nh
            q = mm("sd,dn->sn", h, a["wq"], quant).reshape(S, nh, hd)
            k = mm("sd,dn->sn", h, a["wk"], quant).reshape(S, nkv, hd)
            v = mm("sd,dn->sn", h, a["wv"], quant).reshape(S, nkv, hd)
            o = attention(q, k, v, sizes["attention_multiplier"], quant,
                          block)
            y = mm("sn,nd->sd", o.reshape(S, nh * hd), a["wo"], quant)
        x = x + r * y
        h = rms_norm(x, p["norm2"]["scale"], eps)
        return x + r * moe(p["ffn"], h, sizes, quant), None

    # consecutive layers of one kind are one scan over their stack
    seen = {"mamba": 0, "attention": 0}
    types = list(sizes["layer_types"])
    i = 0
    while i < len(types):
        kind = types[i]
        n = next((j for j in range(i, len(types)) if types[j] != kind),
                 len(types)) - i
        stack = params["mamba" if kind == "mamba" else "attn"]
        k0 = seen[kind]
        x, _ = jax.lax.scan(functools.partial(layer, kind), x,
                            jax.tree.map(lambda a: a[k0:k0 + n], stack))
        seen[kind] += n
        i += n
    return rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32),
                    eps)


@functools.partial(jax.jit, static_argnames=("sizes", "quant"))
def _gaps(params, tokens, targets, sizes, quant):
    sz = {k: (list(v) if isinstance(v, tuple) else v) for k, v in sizes}
    embed = params["embed"].astype(jnp.float32) / sz["logits_scaling"]
    h = hidden(params, tokens, sz)
    mx, at, _ = head_stats(embed, h, targets)
    if quant is None:
        return mx - at
    hq = hidden(params, tokens, sz, quant)
    _, _, choice = head_stats(embed, hq, targets, quant)
    _, at_c, _ = head_stats(embed, h, choice)
    return mx - at_c


def served_gaps(params, sizes: dict, prompt, served, pad_to: int,
                quant: Optional[str] = None):
    """Gap, in float32 logits, by which each served token lies below the
    reference's best at its position (``quant`` set: the gap of the
    token the lower precision puts first there).  ``prompt`` and
    ``served`` are int sequences; the sequence is padded to ``pad_to``
    (a multiple of 512), which the causal mixers leave without effect
    on the positions compared."""
    import numpy as np

    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    n_p, n_s = len(prompt), len(served)
    if len(seq) > pad_to or pad_to % 512:
        raise ValueError(f"sequence of {len(seq)} in a pad of {pad_to}")
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros(pad_to, np.int32)
    targets[n_p - 1:n_p - 1 + n_s] = served   # logits at p predict p + 1
    key = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                       for k, v in sizes.items()))
    g = _gaps(params, jnp.asarray(tokens), jnp.asarray(targets), key, quant)
    return np.asarray(g)[n_p - 1:n_p - 1 + n_s]
