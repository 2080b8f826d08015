"""Operations a dense decoder needs, counted from its sizes: the
count a Qwen2 configuration names (``"flops": "flops"``), which
``mfu.*`` reads.  Multiply-adds count as two operations.  Only the work
a token needs counts: no padding, no recomputation."""
from __future__ import annotations


def matmul_params(sizes: dict) -> int:
    """Weights every token multiplies by, over all layers (projections
    and MLP; not the embedding, which is a lookup on the way in)."""
    d, f, L = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["num_hidden_layers"])
    nq = sizes["num_attention_heads"] * sizes["head_dim"]
    nkv = sizes["num_key_value_heads"] * sizes["head_dim"]
    return L * (d * nq + 2 * d * nkv + nq * d + 3 * d * f)


def logits_flops(sizes: dict) -> int:
    return 2 * sizes["hidden_size"] * sizes["vocab_size"]


def attention_flops(sizes: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys."""
    nq = sizes["num_attention_heads"] * sizes["head_dim"]
    return 4 * context * nq * sizes["num_hidden_layers"]


def decode_token_flops(sizes: dict, context: int) -> int:
    """One generated token whose attention spans ``context`` positions
    (itself included), with its logits."""
    return (2 * matmul_params(sizes) + attention_flops(sizes, context)
            + logits_flops(sizes))


def prefill_flops(sizes: dict, n: int) -> int:
    """A prompt of ``n`` tokens, causal, with logits at its last position
    only: position ``i`` attends to ``i + 1`` keys."""
    attn = attention_flops(sizes, 1) * n * (n + 1) // 2
    return 2 * matmul_params(sizes) * n + attn + logits_flops(sizes)
