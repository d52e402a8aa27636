"""Selective-scan (Mamba S6) Pallas TPU kernel.

TPU adaptation of the paper's "hardware-aware" CUDA scan (DESIGN.md §2):
the CUDA kernel keeps state in SRAM across a warp-parallel scan; here each
grid cell owns a (d_blk, N) state tile in VMEM and walks time sequentially,
FUSING discretization (Δ·A exponential, Δ·u·B) with the recurrence and the
C-projection so the (B, L, D, N) discretized tensors are never
materialized in HBM — the memory blow-up that forces chunking in the jnp
path (models/ssm.py) disappears entirely.

    h_t = exp(Δ_t ⊙ A) ⊙ h_{t-1} + (Δ_t u_t) ⊙ B_t
    y_t = (h_t · C_t) + D ⊙ u_t

Grid: (batch, D/d_blk); block shapes keep the working set
(L·d_blk activations + d_blk·N state) inside VMEM with MXU-aligned tiles.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(u_ref, delta_ref, a_ref, b_ref, c_ref, dskip_ref,
                 y_ref, hlast_ref, h_scratch, *, length: int):
    h_scratch[...] = jnp.zeros_like(h_scratch)

    def step(t, _):
        u_t = u_ref[0, t].astype(jnp.float32)  # (d_blk,)
        dt = delta_ref[0, t].astype(jnp.float32)  # (d_blk,)
        b_t = b_ref[0, t].astype(jnp.float32)  # (N,)
        c_t = c_ref[0, t].astype(jnp.float32)  # (N,)
        a = a_ref[...].astype(jnp.float32)  # (d_blk, N)
        abar = jnp.exp(dt[:, None] * a)
        h = abar * h_scratch[...] + (dt * u_t)[:, None] * b_t[None, :]
        h_scratch[...] = h
        y = h @ c_t + dskip_ref[...].astype(jnp.float32) * u_t
        y_ref[0, t] = y.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, length, step, 0)
    hlast_ref[0] = h_scratch[...]


@functools.partial(jax.jit, static_argnames=("d_block", "interpret"))
def mamba_scan(u, delta, a, b, c, d_skip, d_block: int = 128,
               interpret: Optional[bool] = None):
    """u, delta: (B, L, D); a: (D, N); b, c: (B, L, N); d_skip: (D,).
    Returns (y (B, L, D), h_last (B, D, N))."""
    from repro.kernels.ops import default_interpret
    interpret = default_interpret() if interpret is None else interpret
    bsz, l, d = u.shape
    n = a.shape[1]
    d_block = min(d_block, d)
    pad = (-d) % d_block
    if pad:
        u = jnp.pad(u, ((0, 0), (0, 0), (0, pad)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad)))
        a = jnp.pad(a, ((0, pad), (0, 0)))
        d_skip = jnp.pad(d_skip, (0, pad))
    dp = d + pad
    grid = (bsz, dp // d_block)
    kernel = functools.partial(_scan_kernel, length=l)
    y, hlast = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, l, d_block), lambda bi, di: (bi, 0, di)),
            pl.BlockSpec((1, l, d_block), lambda bi, di: (bi, 0, di)),
            pl.BlockSpec((d_block, n), lambda bi, di: (di, 0)),
            pl.BlockSpec((1, l, n), lambda bi, di: (bi, 0, 0)),
            pl.BlockSpec((1, l, n), lambda bi, di: (bi, 0, 0)),
            pl.BlockSpec((d_block,), lambda bi, di: (di,)),
        ],
        out_specs=[
            pl.BlockSpec((1, l, d_block), lambda bi, di: (bi, 0, di)),
            pl.BlockSpec((1, d_block, n), lambda bi, di: (bi, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, l, dp), u.dtype),
            jax.ShapeDtypeStruct((bsz, dp, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d_block, n), jnp.float32)],
        interpret=interpret,
        name="mamba_scan",
    )(u, delta, a, b, c, d_skip)
    return y[..., :d], hlast[:, :d]
