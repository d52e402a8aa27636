"""jit'd dispatch wrappers for the Pallas kernels.

On an accelerator backend (TPU/GPU) the kernels compile natively;
everywhere else they execute in interpret mode (the kernel body runs in
Python on CPU) — numerically identical, validated against ``ref.py`` in
tests/test_kernels_*.  The policy lives in ``default_interpret`` and the
kernel entry points resolve it lazily from an ``interpret=None`` default,
so a direct kernel-module call gets the same backend-aware behaviour as
these wrappers.
"""

from __future__ import annotations

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.fused_adam import fused_adam as _adam
from repro.kernels.mamba_scan import mamba_scan as _mamba
from repro.kernels.onebit_quant import onebit_quant as _onebit
from repro.kernels.onebit_quant import onebit_quant_packed as _onebit_packed
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.topk_sparsify import topk_encode_ef as _topk_ef
from repro.kernels.topk_sparsify import topk_sparsify as _topk


def default_interpret() -> bool:
    """THE backend-aware interpret policy (single definition, threaded
    through every kernel): compile natively on an accelerator backend
    (TPU/GPU), interpret everywhere else.  Kernel entry points default
    ``interpret=None`` and resolve it here lazily, so importing a kernel
    module never forces backend initialization."""
    return jax.default_backend() not in ("tpu", "gpu")


_interpret = default_interpret  # backward-compat alias


def flash_attention(q, k, v, *, causal=True, window=-1,
                    block_q=None, block_k=None):
    return _flash(q, k, v, causal=causal, window=window,
                  block_q=block_q, block_k=block_k)


def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                    window=None, softcap=None):
    return _paged(q, k_pages, v_pages, block_tables, ctx_lens,
                  window=window, softcap=softcap)


def topk_sparsify(x, k, rows_per_step=8):
    return _topk(x, k, rows_per_step=rows_per_step)


def topk_encode_ef(g, r, k, rows_per_step=8):
    return _topk_ef(g, r, k, rows_per_step=rows_per_step)


def onebit_quant(g, r, rows_per_step=8):
    return _onebit(g, r, rows_per_step=rows_per_step)


def onebit_quant_packed(g, r, rows_per_step=8):
    return _onebit_packed(g, r, rows_per_step=rows_per_step)


def fused_adam(p, g, m, v, lr, t, **kw):
    return _adam(p, g, m, v, lr, t, **kw)


def mamba_scan(u, delta, a, b, c, d_skip, d_block=128):
    return _mamba(u, delta, a, b, c, d_skip, d_block=d_block)
