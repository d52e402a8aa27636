"""Operations and bytes, from a configuration's sizes and the shapes of a
call.  These are the yardstick's counts: later changes to the program do
not change them.  What depends on the architecture (the matrix parameters
of a layer, a layer pattern, the model's FLOPs a token) is its family's
(``families/<family>.py``); what every family shares is here, and reads
only the ``dims`` keys ``heads``, ``kv_heads`` and ``head_dim``.

The rules every family counts by: model FLOPs count each multiply-add as
2 operations, count the matrix products of the forward pass (and, for
training, twice that again for the backward pass), and leave out what
remat recomputes.  Attention scores and the weighted sum of values count
at the causal half: query ``i`` of a sequence attends to ``i + 1`` keys
(or to the keys of its window).  The vocabulary projection counts once
per position that computes logits, tied or not; the embedding lookup is a
gather and counts nothing.  Norms, RoPE, softmax, routing and the loss
are left out.
"""

from __future__ import annotations


def attn_flops(dims: dict, keys: int) -> int:
    """Scores and weighted values of one query against ``keys`` keys, in
    one layer: 2·H·Dh·keys each."""
    return 4 * dims["heads"] * dims["head_dim"] * keys


def causal_attn_flops(dims: dict, rows: int, seq_len: int) -> int:
    """Forward attention of one layer over ``rows`` causal sequences of
    ``seq_len``: query i attends to i + 1 keys, so 4·H·Dh·rows·L(L+1)/2."""
    return attn_flops(dims, 1) * rows * seq_len * (seq_len + 1) // 2


def paged_attention_cost(dims: dict, ctx_lens, kv_bytes: int,
                         q_bytes: int) -> tuple:
    """(FLOPs, bytes) of one paged-attention call of one layer: one query
    token per sequence over its live context.  Bytes are the keys and
    values of the live context, read once, plus the query and the output.
    """
    ctx_lens = list(ctx_lens)
    kv, h, dh = dims["kv_heads"], dims["heads"], dims["head_dim"]
    flops = sum(attn_flops(dims, c) for c in ctx_lens)
    nbytes = sum(2 * c * kv * dh * kv_bytes for c in ctx_lens) \
        + 2 * len(ctx_lens) * h * dh * q_bytes
    return float(flops), float(nbytes)
