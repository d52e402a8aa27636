"""Operations and bytes, from a configuration's sizes and the shapes of a
call.  These are the yardstick's counts: later changes to the program do
not change them.

``dims`` is a dict with the keys ``d``, ``heads``, ``kv_heads``,
``head_dim``, ``ff``, ``vocab`` and ``layers`` (see ``spec.dims``).

Model FLOPs count each multiply-add as 2 operations, count the matrix
products of the forward pass (and, for training, twice that again for the
backward pass), and leave out what remat recomputes.  Attention scores and
the weighted sum of values count at the causal half: query ``i`` of a
sequence attends to ``i + 1`` keys.  The vocabulary projection counts once
per position that computes logits, tied or not; the embedding lookup is a
gather and counts nothing.  Norms, RoPE, softmax and the loss are left out.
"""

from __future__ import annotations


def layer_matmul_params(dims: dict) -> int:
    d, h, kv, dh, f = (dims["d"], dims["heads"], dims["kv_heads"],
                       dims["head_dim"], dims["ff"])
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    mlp = 3 * d * f
    return attn + mlp


def head_params(dims: dict) -> int:
    return dims["vocab"] * dims["d"]


def attn_flops(dims: dict, keys: int) -> int:
    """Scores and weighted values of one query against ``keys`` keys, in
    one layer: 2·H·Dh·keys each."""
    return 4 * dims["heads"] * dims["head_dim"] * keys


def train_flops_per_token(dims: dict, seq_len: int) -> float:
    """6·N + 3·L·(attention of the mean query), N = the matrix parameters
    of the layers and the head.  The mean query of a causal sequence of
    ``seq_len`` attends to (seq_len + 1) / 2 keys."""
    n = dims["layers"] * layer_matmul_params(dims) + head_params(dims)
    attn = dims["layers"] * attn_flops(dims, 1) * (seq_len + 1) / 2
    return 6.0 * n + 3.0 * attn


def prefill_flops(dims: dict, positions, logit_rows: int) -> float:
    """A chunk of prompt tokens at ``positions`` (0-based, real tokens
    only), and the vocabulary projection of ``logit_rows`` rows."""
    positions = list(positions)
    per_layer = 2 * layer_matmul_params(dims) * len(positions) \
        + sum(attn_flops(dims, p + 1) for p in positions)
    return float(dims["layers"] * per_layer
                 + 2 * head_params(dims) * logit_rows)


def decode_flops(dims: dict, ctx_lens) -> float:
    """One token for each sequence, whose context holds ``ctx`` tokens
    counting the new one."""
    ctx_lens = list(ctx_lens)
    per_layer = 2 * layer_matmul_params(dims) * len(ctx_lens) \
        + sum(attn_flops(dims, c) for c in ctx_lens)
    return float(dims["layers"] * per_layer
                 + 2 * head_params(dims) * len(ctx_lens))


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
