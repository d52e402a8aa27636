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
import re

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


def _mellum2_period_grad(one_chip, **changes):
    """The compiled text of the gradient of the loss over one period of
    Mellum2 (three 1024-token window layers, one full layer with YaRN) at
    its published widths, 8 of its 64 experts held, in bf16 over 1 ×
    2048 tokens, remat on; ``changes`` to the configuration."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.train.losses import lm_loss

    cfg = dataclasses.replace(
        get_config("mellum2-12b-a2.5b").with_depth(4).with_expert_share(8),
        vocab_size=1024, param_dtype="bfloat16", compute_dtype="bfloat16",
        **changes)
    params = jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), params)
    toks = _sds((1, L_CELL), jnp.int32, one_chip)

    def loss(p, t):
        logits, aux = T.forward(p, cfg, tokens=t, remat=True)
        return lm_loss(logits, t, aux)

    return _compile(jax.grad(loss), params, toks)


def _branches(comps):
    """The branch computations of every conditional."""
    return [c for instrs in comps.values() for i in instrs.values()
            if i["opcode"] == "conditional"
            for kind, c in i["callees"] if kind == "branch"]


def _reach(comps, comp):
    """``comp`` and every computation it calls, transitively."""
    out, todo = set(), [comp]
    while todo:
        c = todo.pop()
        if c in comps and c not in out:
            out.add(c)
            todo += [callee for i in comps[c].values()
                     for _, callee in i["callees"]]
    return out


def _scopes(op_name):  # jvp(scope), transpose(jvp(scope)) name it too
    return set(re.split(r"[/()]", op_name))


def test_mellum2_period_compiles_with_its_kernels(one_chip, compiled_kernels):
    """One period of Mellum2 (``_mellum2_period_grad``): the plain and the
    window flash kernels and the grouped matmuls are in the program, and
    no (B, H, L, L) buffer is."""
    text = _mellum2_period_grad(one_chip)
    kernels = {line.split(" = ")[0].strip().lstrip("%").split(".")[0]
               for line in text.splitlines() if "tpu_custom_call" in line}
    assert {"flash_attention_fwd", "flash_attention_dq",
            "flash_attention_dkv", "flash_attention_window_fwd",
            "flash_attention_window_dq", "flash_attention_window_dkv",
            "expert_gmm", "expert_tgmm"} <= kernels, kernels
    assert f"32,{L_CELL},{L_CELL}]" not in text


def test_expert_layer_moves_only_the_held_rows(one_chip, compiled_kernels):
    """Mellum2's expert layer at its published widths, 8 of 64 experts
    held, one 8192-token sequence (T·k = 65,536 assignments, 16,384 rows
    kept), loss and gradients under remat in bf16.  Outside the spill
    branch, which carries every assignment, no (T·k, F) buffer is left,
    and the only (T·k, D) buffers are the combine's gather in the forward
    and the dispatch gradient's gather in the backward (the TPU's gathers
    do not fuse into the sum over k that reads them): no branch keeps a
    T·k-row residual for the other."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.scopes import _parse
    from repro.models import layers as L

    cfg = dataclasses.replace(
        get_config("mellum2-12b-a2.5b").with_expert_share(8),
        param_dtype="bfloat16", compute_dtype="bfloat16", frozen_router=True)
    t, d, f = 8192, cfg.d_model, cfg.expert_d_ff
    r = t * cfg.top_k
    assert L._compact_rows(r, 8, cfg.num_experts) == 16384
    params = jax.eval_shape(lambda: L.init_moe(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), params)
    x = _sds((1, t, d), jnp.bfloat16, one_chip)

    @jax.checkpoint
    def loss(p, x):
        out, _ = L.moe(p, cfg, x)
        return jnp.sum(out.astype(jnp.float32) * jnp.cos(out.shape[-1]))

    comps, _ = _parse(_compile(jax.value_and_grad(loss, argnums=(0, 1)),
                               params, x))
    fused = {c for instrs in comps.values() for i in instrs.values()
             for kind, c in i["callees"]
             if kind in ("calls", "to_apply") and i["opcode"] != "call"}

    branches = _branches(comps)
    spill = set().union(*(
        _reach(comps, b) for b in branches
        if any("moe.spill" in _scopes(i["op"]) for c in _reach(comps, b)
               for i in comps[c].values())))
    assert len(branches) == 4 and spill  # forward and backward, two each
    rows = {}
    for c, instrs in comps.items():
        if c in fused or c in spill:
            continue
        for name, i in instrs.items():
            if i["opcode"] in ("parameter", "get-tuple-element", "tuple",
                               "bitcast", "conditional"):  # its branches'
                continue
            for width in (d, f):
                if f"[{r},{width}]" in i["type"] or \
                        f"[{t},{cfg.top_k},{width}]" in i["type"]:
                    rows.setdefault(width, []).append(i["op"])
    assert f not in rows, rows[f]
    assert len(rows[d]) == 2, rows[d]
    combine, dispatch_grad = sorted(rows[d], key=lambda op: "transpose(" in op)
    assert combine.endswith("moe.compact/gather"), combine
    assert "transpose(jvp(moe.compact))" in dispatch_grad and \
        dispatch_grad.endswith("/gather"), dispatch_grad


def test_expert_paths_keep_their_ops_in_their_branches(one_chip,
                                                       compiled_kernels):
    """One period of Mellum2 as its cell trains it (``_mellum2_period_grad``,
    the router frozen; 2048 tokens top-8, 4096 of the 16,384 assignments
    kept): every op under ``moe.compact`` or ``moe.spill``, forward and
    backward, stays inside a branch of the layer's choice.  XLA hoists the
    ops that end both branches alike out of a conditional, under one
    branch's scope, unless the branches' results pass a barrier; a trace
    would then count them as that branch's work whichever branch ran
    (``moe_compact_share.train``)."""
    from repro.core.scopes import _parse

    comps, _ = _parse(_mellum2_period_grad(one_chip, frozen_router=True))
    branches = _branches(comps)
    assert len(branches) == 16  # 4 layers, forward and backward, two each
    inside = set().union(*(_reach(comps, b) for b in branches))
    outside = [i["op"] for c, instrs in comps.items() if c not in inside
               for i in instrs.values()
               if {"moe.compact", "moe.spill"} & _scopes(i["op"])]
    assert not outside, outside
    for b in branches:
        ops = {scope for c in _reach(comps, b) for i in comps[c].values()
               for scope in {"moe.compact", "moe.spill"} & _scopes(i["op"])}
        assert len(ops) == 1, (b, ops)
