"""Paged attention Pallas TPU kernel (decode over a block KV cache).

The serving tier (serve/kv_cache.py) stores KV in fixed-size token pages:
``k_pages/v_pages: (num_pages, page_size, KV, Dh)`` plus a per-sequence
``block_table: (B, pages_per_seq)`` mapping logical page j of sequence b
to a physical page id.  This kernel computes one decode step — q is a
single token per sequence — attending over that paged layout WITHOUT
gathering the pages into a dense (B, S, KV, Dh) cache first.

Mechanically it extends the ``flash_attention.py`` online-softmax
pattern: grid = (batch, pages_per_seq) with f32 accumulators (acc, row-max
m, row-sum l) per kv head in VMEM scratch, persisting across the trailing
(innermost, sequential) page dimension.  A k/v block is one whole page,
every kv head of it: the TPU lowering takes a block's last two dims
(KV, Dh) only whole or in (8, 128) tiles, so a one-head block of a
KV < 8 page is refused.  The kernel loops over the heads.  The page
indirection rides ``pltpu.PrefetchScalarGridSpec``: the block table,
context lengths and sliding window arrive as scalar-prefetch operands, so
each k/v BlockSpec index map reads ``block_tables[b, j]`` and the pipeline
DMAs exactly the physical page the sequence needs — the canonical TPU
paged attention mechanism.  Dead pages (entirely past the context length, or
entirely left of the sliding window) are skipped via ``@pl.when``, so
decode compute is proportional to the LIVE context, not the allocated
maximum.

GQA queries come in grouped as (B, KV, G, Dh) — the G = H/KV query rows
of one kv head share its pages, giving the MXU a (G, page_size) matmul
per page.  Numerics follow the dense decode contract (models/layers.py
``_sdpa_decode``): logits, softmax and the accumulator are f32 whatever
the page dtype (f32/bf16); logit softcap, causal mask (j <= pos) and
sliding window (pos - j < w) are applied per element inside the page.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _paged_kernel(bt_ref, ctx_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page_size: int, scale: float,
                  softcap: Optional[float]):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    kv = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = ctx_ref[b]          # tokens 0..ctx-1 are live
    pos = ctx - 1             # the query's position (token already written)
    w = win_ref[0]            # <= 0 ⇒ full attention
    start = j * page_size
    lo = jnp.where(w > 0, jnp.maximum(pos - w + 1, 0), 0)
    live = jnp.logical_and(start < ctx, start + page_size > lo)

    @pl.when(live)
    def _body():
        for h in range(kv):
            q = q_ref[0, h].astype(jnp.float32) * scale   # (G, Dh)
            k = k_ref[0, :, h].astype(jnp.float32)        # (page, Dh)
            v = v_ref[0, :, h].astype(jnp.float32)        # (page, Dh)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            jj = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = jnp.logical_and(jj <= pos, jj >= lo)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("softcap", "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                    window=None, softcap: Optional[float] = None,
                    interpret: Optional[bool] = None):
    """q: (B, KV, G, Dh) grouped queries (one decode token per sequence);
    k_pages/v_pages: (num_pages, page_size, KV, Dh); block_tables:
    (B, pages_per_seq) int32 physical page ids; ctx_lens: (B,) int32 live
    context length per sequence (query position + 1).  ``window`` is a
    traced scalar (sliding window in tokens, <= 0 ⇒ full attention) so
    per-layer windows can ride a ``lax.scan`` over the stack.  Returns
    (B, KV, G, Dh) in q.dtype.

    Unallocated block-table entries may point anywhere valid (the engine
    points them at the reserved trash page 0): pages past ``ctx_lens``
    are skipped, in-page tails are masked.
    """
    from repro.kernels.ops import default_interpret
    interpret = default_interpret() if interpret is None else interpret
    b, kv, g, dh = q.shape
    n_pages, page_size, kv_p, dh_p = k_pages.shape
    assert (kv, dh) == (kv_p, dh_p), (q.shape, k_pages.shape)
    mb = block_tables.shape[1]

    win = jnp.full((1,), -1, jnp.int32) if window is None \
        else jnp.asarray(window, jnp.int32).reshape(1)
    bt = block_tables.astype(jnp.int32)
    ctx = ctx_lens.astype(jnp.int32)

    grid = (b, mb)
    kernel = functools.partial(_paged_kernel, page_size=page_size,
                               scale=dh ** -0.5, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, kv, g, dh),
                         lambda b_, j_, bt_, ctx_, win_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, page_size, kv, dh),
                         lambda b_, j_, bt_, ctx_, win_:
                         (bt_[b_, j_], 0, 0, 0)),
            pl.BlockSpec((1, page_size, kv, dh),
                         lambda b_, j_, bt_, ctx_, win_:
                         (bt_[b_, j_], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, kv, g, dh),
            lambda b_, j_, bt_, ctx_, win_: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g, dh), jnp.float32),   # acc
            pltpu.VMEM((kv, g, 1), jnp.float32),    # running max m
            pltpu.VMEM((kv, g, 1), jnp.float32),    # running sum l
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dh), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(bt, ctx, win, q, k_pages, v_pages)
