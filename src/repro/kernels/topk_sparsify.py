"""Block-local top-k gradient sparsification Pallas kernel (DGC, paper
§2.2.4).

TPU adaptation (DESIGN.md §2): Deep Gradient Compression's global top-k
needs a full sort — hostile to the VPU.  Block-local top-k keeps each
block's working set in VMEM, preserves the compression ratio, and each
grid step is independent (embarrassingly parallel over blocks).  Inside
the kernel we avoid sort entirely: k iterations of (max, mask) — for the
k ≪ block regime of gradient sparsification this is O(k·block) VPU work
with no data-dependent control flow.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1.0


def _resolve(interpret):
    if interpret is not None:
        return interpret
    from repro.kernels.ops import default_interpret
    return default_interpret()


def _topk_rows(t, k: int, block: int):
    """k iterations of (max, lowest-index, mask) over each row of the f32
    block ``t``.  The k picks build up in (rows, k) carries and are stored
    once, so no store lands at a lane offset that is not a multiple of 128.
    Returns (vals (rows, k), idx (rows, k) int32, dense (rows, block))."""
    rows = t.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)

    def body(i, carry):
        mag, dense, vals, idx = carry
        m = jnp.max(mag, axis=-1, keepdims=True)  # (rows,1)
        # first column achieving the max
        first = jnp.min(jnp.where(mag == m, cols, block), axis=-1,
                        keepdims=True)
        sel = cols == first
        val = jnp.sum(jnp.where(sel, t, 0.0), axis=-1, keepdims=True)
        vals = jnp.where(slot == i, val, vals)
        idx = jnp.where(slot == i, first, idx)
        dense = jnp.where(sel, t, dense)
        mag = jnp.where(sel, NEG, mag)
        return mag, dense, vals, idx

    init = (jnp.abs(t), jnp.zeros_like(t), jnp.zeros((rows, k), jnp.float32),
            jnp.zeros((rows, k), jnp.int32))
    _, dense, vals, idx = jax.lax.fori_loop(0, k, body, init)
    return vals, idx, dense


def _topk_kernel(x_ref, vals_ref, idx_ref, dense_ref, *, k: int, block: int):
    x = x_ref[...]  # (rows, block)
    vals, idx, dense = _topk_rows(x.astype(jnp.float32), k, block)
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = idx
    dense_ref[...] = dense.astype(dense_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "rows_per_step", "interpret"))
def topk_sparsify(x, k: int, rows_per_step: int = 8,
                  interpret: Optional[bool] = None):
    """x: (nblocks, block) → (vals (nb,k), idx (nb,k) int32, dense (nb,block))."""
    interpret = _resolve(interpret)
    nb, block = x.shape
    pad = (-nb) % rows_per_step
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    nbp = nb + pad
    grid = (nbp // rows_per_step,)
    kernel = functools.partial(_topk_kernel, k=k, block=block)
    vals, idx, dense = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows_per_step, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows_per_step, k), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_step, k), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_step, block), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, k), x.dtype),
            jax.ShapeDtypeStruct((nbp, k), jnp.int32),
            jax.ShapeDtypeStruct((nbp, block), x.dtype),
        ],
        interpret=interpret,
        name="topk_sparsify",
    )(x)
    return vals[:nb], idx[:nb], dense[:nb]


def _topk_ef_kernel(g_ref, r_ref, vals_ref, idx_ref, newr_ref,
                    *, k: int, block: int):
    """Fused DGC round: t = g + r, block-local top-k of |t| (the same
    iteration as ``_topk_kernel``), and the error-feedback residual
    t − dense(sent) — one VMEM pass."""
    t = g_ref[...].astype(jnp.float32) + r_ref[...]
    vals, idx, dense = _topk_rows(t, k, block)
    vals_ref[...] = vals
    idx_ref[...] = idx
    newr_ref[...] = t - dense


@functools.partial(jax.jit, static_argnames=("k", "rows_per_step", "interpret"))
def topk_encode_ef(g, r, k: int, rows_per_step: int = 8,
                   interpret: Optional[bool] = None):
    """g, r: (nblocks, block) → (vals (nb,k) f32, idx (nb,k) int32,
    new_r (nb,block) f32).  The production Fabric-path variant of
    ``topk_sparsify``: the target t = g + r and the residual update
    happen inside the kernel, so the whole encode+error-feedback round
    is one pass over VMEM."""
    interpret = _resolve(interpret)
    nb, block = g.shape
    pad = (-nb) % rows_per_step
    if pad:
        g = jnp.pad(g, ((0, pad), (0, 0)))
        r = jnp.pad(r, ((0, pad), (0, 0)))
    nbp = nb + pad
    grid = (nbp // rows_per_step,)
    kernel = functools.partial(_topk_ef_kernel, k=k, block=block)
    vals, idx, newr = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows_per_step, block), lambda i: (i, 0))] * 2,
        out_specs=[
            pl.BlockSpec((rows_per_step, k), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_step, k), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_step, block), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, k), jnp.float32),
            jax.ShapeDtypeStruct((nbp, k), jnp.int32),
            jax.ShapeDtypeStruct((nbp, block), jnp.float32),
        ],
        interpret=interpret,
        name="topk_sparsify_ef",
    )(g, r)
    return vals[:nb], idx[:nb], newr[:nb]
