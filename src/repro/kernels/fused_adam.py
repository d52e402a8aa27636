"""Fused Adam update Pallas kernel.

One VMEM pass per tile updates (p, m, v) in place of the 10+ elementwise
HLO ops of the unfused optimizer — the optimizer is HBM-bandwidth-bound,
so fusing the read-modify-write chain is the whole win.  Bias correction
factors are precomputed on the host side of the call (scalar prefetch).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _adam_kernel(c_ref, p_ref, g_ref, m_ref, v_ref,
                 p_out, m_out, v_out, *, b1, b2, eps):
    lr, bc1, bc2 = c_ref[0], c_ref[1], c_ref[2]
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1 - b1) * g
    v = b2 * v_ref[...] + (1 - b2) * g * g
    mh = m / bc1
    vh = v / bc2
    p = p_ref[...].astype(jnp.float32) - lr * mh / (jnp.sqrt(vh) + eps)
    p_out[...] = p.astype(p_out.dtype)
    m_out[...] = m
    v_out[...] = v


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "eps", "block", "interpret"))
def fused_adam(p, g, m, v, lr, t, b1=0.9, b2=0.999, eps=1e-8,
               block: int = 4096, interpret: Optional[bool] = None):
    """p,g,m,v: (N,) flat; lr scalar; t: 1-based step. → (p', m', v')."""
    from repro.kernels.ops import default_interpret
    interpret = default_interpret() if interpret is None else interpret
    n = p.shape[0]
    pad = (-n) % block
    if pad:
        p, g, m, v = (jnp.pad(a, (0, pad)) for a in (p, g, m, v))
    npad = n + pad
    tt = jnp.asarray(t, jnp.float32)
    consts = jnp.stack([jnp.asarray(lr, jnp.float32),
                        1.0 - b1 ** tt, 1.0 - b2 ** tt])
    grid = (npad // block,)
    kernel = functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps)
    p1, m1, v1 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((3,), lambda i: (0,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad,), p.dtype),
            jax.ShapeDtypeStruct((npad,), jnp.float32),
            jax.ShapeDtypeStruct((npad,), jnp.float32),
        ],
        interpret=interpret,
        name="fused_adam",
    )(consts, p, g, m, v)
    return p1[:n], m1[:n], v1[:n]
