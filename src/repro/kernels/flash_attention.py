"""Flash attention Pallas TPU kernel (blocked online softmax).

TPU adaptation (DESIGN.md §2): grid = (batch·heads, q_blocks, kv_blocks)
with f32 accumulators (acc, row-max m, row-sum l) in VMEM scratch that
persist across the kv_block grid dimension (TPU grids iterate the trailing
dimension innermost, sequentially per core).  Block shapes default to
(128, 128) — MXU-aligned on the (8,128)/(128,128) tiles.  Sliding windows
(gemma3's 5:1 local:global) are handled by masking inside the block and by
*skipping* fully-masked kv blocks via ``@pl.when`` (compute proportional to
the window, the sub-quadratic property the long-context shapes need).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 causal: bool, window: int, block_q: int, block_k: int,
                 scale: float, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level skip: causal ⇒ kv blocks entirely above the diagonal are
    # dead; sliding window ⇒ kv blocks entirely left of the window are dead.
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if window > 0:
        live = jnp.logical_and(
            live, (q_start - (k_start + block_k - 1)) < window)

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)  # (bk, d)
        s = q @ k.T  # (bq, bk)

        ii = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        jj = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jj < kv_len
        if causal:
            mask &= jj <= ii
        if window > 0:
            mask &= (ii - jj) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + p @ v
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = -1,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q,k,v: (B, H, L, D) → (B, H, L, D)."""
    from repro.kernels.ops import default_interpret
    interpret = default_interpret() if interpret is None else interpret
    b, h, l, d = q.shape
    lk = k.shape[2]
    block_q = min(block_q, l)
    block_k = min(block_k, lk)
    pad_q = (-l) % block_q
    pad_k = (-lk) % block_k
    if pad_q or pad_k:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    lq_p, lk_p = l + pad_q, lk + pad_k
    qf = q.reshape(b * h, lq_p, d)
    kf = k.reshape(b * h, lk_p, d)
    vf = v.reshape(b * h, lk_p, d)

    grid = (b * h, lq_p // block_q, lk_p // block_k)
    kernel = functools.partial(
        _attn_kernel, causal=causal, window=window, block_q=block_q,
        block_k=block_k, scale=d ** -0.5, kv_len=lk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
        ],
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, h, lq_p, d)[:, :, :l]
