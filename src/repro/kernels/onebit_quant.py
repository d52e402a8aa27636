"""1-bit gradient quantization with error feedback — Pallas kernel
(Seide et al. [55], paper §2.2.4).

Fuses the whole error-feedback round in one VMEM pass:
    t = g + r;  sign = sgn(t);  scale = mean|t|;  r' = t − sign·scale
int8 signs + one f32 scale per block, (8,128)-tile aligned.

``onebit_quant_packed`` is the production variant on the Fabric path
(core/fabric.py): it additionally emits the TRUE wire format from inside
the kernel — packed uint8 sign bytes (8 signs/byte, via one MXU matmul
against a constant bit-weight matrix) and bf16 scales — and computes the
residual against the bf16-rounded decode, so the encode+pack+error-
feedback round is ONE pass with no separate XLA ``pack_signs`` op and is
bitwise identical to the pure-jnp wire codec.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _resolve(interpret):
    if interpret is not None:
        return interpret
    from repro.kernels.ops import default_interpret
    return default_interpret()


def _onebit_kernel(g_ref, r_ref, sign_ref, scale_ref, newr_ref):
    t = g_ref[...].astype(jnp.float32) + r_ref[...]
    sign = jnp.where(t >= 0, 1, -1).astype(jnp.int8)
    scale = jnp.mean(jnp.abs(t), axis=-1, keepdims=True)  # (rows, 1)
    decoded = sign.astype(jnp.float32) * scale
    sign_ref[...] = sign
    scale_ref[...] = scale
    newr_ref[...] = t - decoded


@functools.partial(jax.jit, static_argnames=("rows_per_step", "interpret"))
def onebit_quant(g, r, rows_per_step: int = 8,
                 interpret: Optional[bool] = None):
    """g, r: (nblocks, block) → (sign int8, scale (nb,1) f32, new_r f32)."""
    interpret = _resolve(interpret)
    nb, block = g.shape
    pad = (-nb) % rows_per_step
    if pad:
        g = jnp.pad(g, ((0, pad), (0, 0)))
        r = jnp.pad(r, ((0, pad), (0, 0)))
    nbp = nb + pad
    grid = (nbp // rows_per_step,)
    sign, scale, newr = pl.pallas_call(
        _onebit_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows_per_step, block), lambda i: (i, 0))] * 2,
        out_specs=[
            pl.BlockSpec((rows_per_step, block), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_step, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_step, block), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, block), jnp.int8),
            jax.ShapeDtypeStruct((nbp, 1), jnp.float32),
            jax.ShapeDtypeStruct((nbp, block), jnp.float32),
        ],
        interpret=interpret,
        name="onebit_quant",
    )(g, r)
    return sign[:nb], scale[:nb], newr[:nb]


def _pack_matrix(block: int):
    """(block, block//8) bit-weight matrix P with P[i, i//8] = 1 << (i%8):
    ``bits_f32 @ P`` packs 8 consecutive sign bits into one byte value —
    exactly the ``compression.pack_signs`` order — as one MXU matmul."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block // 8), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, block // 8), 1)
    weight = jnp.left_shift(1, rows % 8)
    return jnp.where(rows // 8 == cols, weight, 0).astype(jnp.float32)


def _onebit_packed_kernel(g_ref, r_ref, packed_ref, scale_ref, newr_ref,
                          *, block: int):
    t = g_ref[...].astype(jnp.float32) + r_ref[...]
    bits = (t >= 0).astype(jnp.float32)
    packed = jnp.dot(bits, _pack_matrix(block),
                     preferred_element_type=jnp.float32)
    # byte values 0..255 are exact in f32; Mosaic casts to uint8 only
    # from an integer type
    packed_ref[...] = packed.astype(jnp.int32).astype(jnp.uint8)
    scale = jnp.mean(jnp.abs(t), axis=-1, keepdims=True)  # (rows, 1) f32
    scale_bf16 = scale.astype(jnp.bfloat16)
    scale_ref[...] = scale_bf16
    # residual against the bf16-rounded decode the receivers will see
    sign = jnp.where(t >= 0, 1.0, -1.0)
    newr_ref[...] = t - sign * scale_bf16.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("rows_per_step", "interpret"))
def onebit_quant_packed(g, r, rows_per_step: int = 8,
                        interpret: Optional[bool] = None):
    """g, r: (nblocks, block) → (packed (nb, block//8) uint8,
    scale (nb, 1) bf16, new_r (nb, block) f32).

    The wire-format-emitting fused round: packed bytes and bf16 scales
    come straight out of VMEM, and ``new_r`` already accounts for the
    bf16 scale rounding (t − sign·f32(bf16(scale)))."""
    interpret = _resolve(interpret)
    nb, block = g.shape
    if block % 8:
        raise ValueError(f"packed onebit needs block % 8 == 0, got {block}")
    pad = (-nb) % rows_per_step
    if pad:
        g = jnp.pad(g, ((0, pad), (0, 0)))
        r = jnp.pad(r, ((0, pad), (0, 0)))
    nbp = nb + pad
    grid = (nbp // rows_per_step,)
    kernel = functools.partial(_onebit_packed_kernel, block=block)
    packed, scale, newr = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows_per_step, block), lambda i: (i, 0))] * 2,
        out_specs=[
            pl.BlockSpec((rows_per_step, block // 8), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_step, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_step, block), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, block // 8), jnp.uint8),
            jax.ShapeDtypeStruct((nbp, 1), jnp.bfloat16),
            jax.ShapeDtypeStruct((nbp, block), jnp.float32),
        ],
        interpret=interpret,
        name="onebit_quant_packed",
    )(g, r)
    return packed[:nb], scale[:nb], newr[:nb]
