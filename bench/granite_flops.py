"""Operations and bytes a Granite-4.0-H stage needs, counted from its
configuration file (``"flops": "granite_flops"``): ``mfu.*`` reads the
operations, ``hbm_share.*`` the bytes.  Multiply-adds count as two
operations.  Only the work a token needs counts: no padding, no
recomputation, and of the experts only this chip's share.

Per token and layer: the router over every expert; the held experts'
share of the ``num_experts_per_tok`` routed applications
(``k · held / router width`` of them: 10 · 9 / 72 = 1.25); the shared
expert; in a Mamba layer its projections, conv, and the SSD update and
readout of every head's (head, state) matrix; in an attention layer
its projections and the scores and weighted values at its context.
"""
from __future__ import annotations

HEAP_WORD = 4          # the page heap is float32
WEIGHT_BYTES = 2       # bfloat16 parameters


def _kinds(sizes: dict):
    t = sizes["layer_types"]
    return t.count("mamba"), t.count("attention")


def _dims(sizes: dict):
    d = sizes["hidden_size"]
    H, P, N = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
               sizes["mamba_d_state"])
    G, W = sizes["mamba_n_groups"], sizes["mamba_d_conv"]
    hd = d // sizes["num_attention_heads"]
    nq = sizes["num_attention_heads"] * hd
    nkv = sizes["num_key_value_heads"] * hd
    return d, H, P, N, G, W, hd, nq, nkv


def mamba_params(sizes: dict) -> int:
    """Weights of one Mamba mixer every token multiplies by."""
    d, H, P, N, G, _, _, _, _ = _dims(sizes)
    di = H * P
    return d * (2 * di + 2 * G * N + H) + di * d


def attn_params(sizes: dict) -> int:
    d, _, _, _, _, _, _, nq, nkv = _dims(sizes)
    return d * nq + 2 * d * nkv + nq * d


def moe_token_params(sizes: dict) -> float:
    """Weights one token multiplies by in one MoE layer: the router, its
    share of the routed experts held here, the shared expert."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    e = sizes["router_num_experts"]
    held = sizes["num_local_experts"] * sizes["num_experts_per_tok"] / e
    return d * e + held * 3 * d * f + 3 * d * sizes["shared_intermediate_size"]


def held_weights(sizes: dict) -> int:
    """Every weight this chip holds (each read once a tick)."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    n_m, n_a = _kinds(sizes)
    L = n_m + n_a
    moe = (d * sizes["router_num_experts"]
           + sizes["num_local_experts"] * 3 * d * f
           + 3 * d * sizes["shared_intermediate_size"])
    vocab = -(-sizes["vocab_size"] // 256) * 256
    return (n_m * mamba_params(sizes) + n_a * attn_params(sizes)
            + L * moe + vocab * d)


def ssd_token_flops(sizes: dict) -> int:
    """One token through one Mamba layer's conv and SSD: the state
    decay, the input outer product, the readout, the skip."""
    _, H, P, N, G, W, _, _, _ = _dims(sizes)
    conv = 2 * W * (H * P + 2 * G * N)
    return conv + H * P * (5 * N + 2)


def logits_flops(sizes: dict) -> int:
    return 2 * sizes["hidden_size"] * sizes["vocab_size"]


def attention_flops(sizes: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys, in
    every attention layer."""
    _, n_a = _kinds(sizes)
    return 4 * context * _dims(sizes)[7] * n_a


def token_flops(sizes: dict) -> float:
    """Everything one token needs except attention at its context and
    the logits."""
    n_m, n_a = _kinds(sizes)
    return (2 * (n_m * mamba_params(sizes) + n_a * attn_params(sizes)
                 + (n_m + n_a) * moe_token_params(sizes))
            + n_m * ssd_token_flops(sizes))


def decode_token_flops(sizes: dict, context: int) -> float:
    """One generated token whose attention spans ``context`` positions
    (itself included), with its logits."""
    return (token_flops(sizes) + attention_flops(sizes, context)
            + logits_flops(sizes))


def prefill_flops(sizes: dict, n: int) -> float:
    """A prompt of ``n`` tokens, causal, with logits at its last position
    only: position ``i`` attends to ``i + 1`` keys."""
    attn = attention_flops(sizes, 1) * n * (n + 1) // 2
    return token_flops(sizes) * n + attn + logits_flops(sizes)


def state_bytes(sizes: dict) -> int:
    """One sequence's recurrent state: every Mamba layer's (head, state)
    matrices and conv tail, float32 in the page heap."""
    n_m, _ = _kinds(sizes)
    _, H, P, N, G, W, _, _, _ = _dims(sizes)
    return n_m * HEAP_WORD * (H * P * N + (W - 1) * (H * P + 2 * G * N))


def kv_bytes(sizes: dict, context: int) -> int:
    """K and V of ``context`` positions in every attention layer, float32
    in the page heap."""
    _, n_a = _kinds(sizes)
    return 2 * n_a * context * _dims(sizes)[8] * HEAP_WORD


def tick_bytes(sizes: dict, contexts) -> float:
    """Bytes one decode tick must move for live slots at ``contexts``:
    the held weights once, each slot's state read and written, and its
    K/V read at its context."""
    return (held_weights(sizes) * WEIGHT_BYTES
            + sum(2 * state_bytes(sizes) + kv_bytes(sizes, c)
                  for c in contexts))
