"""The main path's Pallas kernels compile for a TPU v5e at qwen2-1.5b widths.

Nothing runs: each kernel is lowered with ``interpret=False`` against a
described (not attached) v5e and compiled by the TPU compiler installed
with jax.  That catches what interpret mode cannot — tiling and layout
rules, unsupported casts, VMEM limits — at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, so a test file that
loads it while being imported would make parallel test workers collect
different tests.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D_MODEL, D_FF, KV, G, DH = 1536, 8960, 2, 6, 128  # qwen2-1.5b
W = 2  # replicas folded into one bucket, as in the compressed trainer run


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # the TPU compiler otherwise writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *sds):
    text = jax.jit(fn).lower(*sds).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel is in the program
    return text


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fused_adam_compiles(one_chip):
    from repro.kernels.fused_adam import fused_adam

    n = D_MODEL * D_FF
    vec = _sds((n,), jnp.float32, one_chip)
    _compile(lambda p, g, m, v: fused_adam(p, g, m, v, 1e-3, 1,
                                           interpret=False),
             vec, vec, vec, vec)


@pytest.mark.parametrize("name,block", [("onebit", 256), ("topk", 1024)])
def test_fused_encode_compiles(one_chip, compiled_kernels, name, block):
    """The Fabric's fused encode kernels (``onebit_quant_packed``,
    ``topk_encode_ef``) over the rows of one W-replica bucket of a
    d_model x d_ff leaf, with the row block the compiled path picks."""
    from repro.core.compression import _kernel_rows
    from repro.kernels.onebit_quant import onebit_quant_packed
    from repro.kernels.topk_sparsify import topk_encode_ef

    rows = W * D_MODEL * D_FF // block
    per_step = _kernel_rows(rows, block)
    if name == "onebit":
        def encode(g, r):
            return onebit_quant_packed(g, r, rows_per_step=per_step,
                                       interpret=False)
    else:
        def encode(g, r):
            return topk_encode_ef(g, r, round(block * 0.01),
                                  rows_per_step=per_step, interpret=False)
    bucket = _sds((rows, block), jnp.float32, one_chip)
    _compile(encode, bucket, bucket)


def test_paged_attention_compiles(one_chip):
    """One decode step over bf16 pages of 16 tokens: 12 query heads
    grouped 6 to each of 2 KV heads of 128."""
    from repro.kernels.paged_attention import paged_attention

    b, pages_per_seq, page = 4, 32, 16
    n_pages = 1 + b * pages_per_seq
    q = _sds((b, KV, G, DH), jnp.bfloat16, one_chip)
    kv = _sds((n_pages, page, KV, DH), jnp.bfloat16, one_chip)
    tables = _sds((b, pages_per_seq), jnp.int32, one_chip)
    ctx = _sds((b,), jnp.int32, one_chip)
    text = _compile(lambda *a: paged_attention(*a, interpret=False),
                    q, kv, kv, tables, ctx)
    # the kernel's instruction keeps the name paged_attention_roofline reads
    assert any(line.split(" = ")[0].strip().startswith("%paged_attention")
               for line in text.splitlines() if "tpu_custom_call" in line)


L_CELL, B_CELL, H = 2048, 4, KV * G  # the training cell's sequences


def test_flash_attention_compiles(one_chip):
    """The flash kernel's forward and backward at the training cell's
    shapes: 12 bf16 query heads grouped 6 to each of 2 KV heads of 128."""
    from repro.kernels.flash_attention import flash_attention

    q = _sds((B_CELL, H, L_CELL, DH), jnp.bfloat16, one_chip)
    kv = _sds((B_CELL, KV, L_CELL, DH), jnp.bfloat16, one_chip)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(lambda *a: flash_attention(*a, interpret=False),
                         q, k, v)
        return o, vjp(do)

    text = _compile(fwd_bwd, q, kv, kv, q)
    kernels = {line.split(" = ")[0].strip().lstrip("%").split(".")[0]
               for line in text.splitlines() if "tpu_custom_call" in line}
    assert {"flash_attention_fwd", "flash_attention_dq",
            "flash_attention_dkv"} <= kernels, kernels


def test_layer_grad_holds_no_score_square(one_chip, compiled_kernels):
    """One qwen2-1.5b layer's remat gradient in bf16 at the cell's shapes:
    with the kernel on, no (B, H, L, L) buffer is left in the program."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import FULL_ATTENTION
    from repro.models import transformer as T

    cfg = dataclasses.replace(get_config("qwen2-1.5b").with_depth(1),
                              param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    spec = cfg.superblock()[0][0]
    params = jax.eval_shape(
        lambda: T._init_layer(jax.random.PRNGKey(0), cfg, spec, False))
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), params)
    h = _sds((B_CELL, L_CELL, D_MODEL), jnp.bfloat16, one_chip)

    @jax.checkpoint
    def layer(p, h):
        pos = jnp.broadcast_to(jnp.arange(L_CELL), (B_CELL, L_CELL))
        out, _, _ = T._apply_layer(p, cfg, spec, h, pos, FULL_ATTENTION,
                                   cfg.rope_theta, None, None, None,
                                   plain_causal=True)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(layer, argnums=(0, 1)), params, h)
    assert f"{H},{L_CELL},{L_CELL}]" not in text
