"""Gradient compression with error feedback (paper §2.2.4).

Two families, exactly the two the paper surveys:

  * quantization — 1-bit SGD (Seide et al. [55]): per-block sign + scale,
    with the error-feedback residual that makes it converge; plus an int8
    variant.
  * sparsification — top-k with residual accumulation (Strom [39], Deep
    Gradient Compression [54]), realized as *block-local* top-k which is
    the TPU-friendly form (no global sort; see DESIGN.md §2).

Every compressor is a pair (encode, decode) threaded through an
error-feedback wrapper:   c = encode(g + r);  r ← (g + r) − decode(c).
The communicated object is ``decode(encode(·))`` — strategies communicate
the *decompressed* tensor (wire format is an implementation detail of the
transport; the wire-size accounting lives in ``wire_bytes``).

The hot loops have Pallas kernels in ``repro/kernels`` (onebit_quant,
topk_sparsify).  ``compress``/``decompress`` are the pure-jnp reference;
``fused_encode`` (when present) is the production encode+error-feedback
round dispatched to the fused kernel — one VMEM pass computing
``t = g + r``, the narrowed wire arrays (packed sign bytes / top-k
values+indices) and the residual update, bitwise identical to the jnp
path (tests/test_fused_compression.py).  ``core/fabric.py`` dispatches
to it by default.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax


@dataclass(frozen=True)
class Compressor:
    name: str
    compress: Callable  # (x) -> (wire, meta)  [wire: what's transmitted]
    decompress: Callable  # (wire, meta, shape, dtype) -> x_hat
    wire_bits_per_element: float  # analytic bits/elem (see wire_bytes)
    # (g, r) flat f32 arrays of shape lead + (n,) -> (narrow_arrs, widen,
    # new_residual): the fused kernel encode+error-feedback round.
    # ``narrow_arrs`` match the _narrow_wire output for compress(g + r)
    # byte-for-byte; ``widen(arrs)`` maps ONE replica's narrow arrays back
    # to what ``decompress`` expects.  None -> no fused path (jnp only).
    fused_encode: Optional[Callable] = None


# ---------------------------------------------------------------------------
# identity
# ---------------------------------------------------------------------------
def none_compressor() -> Compressor:
    return Compressor(
        name="none",
        compress=lambda x: (x, None),
        decompress=lambda w, m, shape, dtype: w,
        wire_bits_per_element=32.0,
    )


# ---------------------------------------------------------------------------
# 1-bit quantization (sign + per-block mean-|x| scale)
# ---------------------------------------------------------------------------
def onebit_compressor(block: int = 256) -> Compressor:
    def compress(x):
        flat = x.reshape(-1).astype(jnp.float32)
        n = flat.shape[0]
        pad = (-n) % block
        flat = jnp.pad(flat, (0, pad))
        blocks = flat.reshape(-1, block)
        sign = jnp.where(blocks >= 0, 1.0, -1.0)
        scale = jnp.mean(jnp.abs(blocks), axis=-1, keepdims=True)
        return (sign.astype(jnp.int8), scale), None

    def decompress(wire, meta, shape, dtype):
        sign, scale = wire
        n = 1
        for s in shape:
            n *= s
        flat = (sign.astype(jnp.float32) * scale).reshape(-1)[:n]
        return flat.reshape(shape).astype(dtype)

    # 1 bit per element + one fp32 scale per block
    return Compressor("onebit", compress, decompress,
                      wire_bits_per_element=1.0 + 32.0 / block,
                      fused_encode=(_fused_onebit(block)
                                    if block % 8 == 0 else None))


# ---------------------------------------------------------------------------
# int8 linear quantization (per-block max-abs scale)
# ---------------------------------------------------------------------------
def int8_compressor(block: int = 256) -> Compressor:
    def compress(x):
        flat = x.reshape(-1).astype(jnp.float32)
        pad = (-flat.shape[0]) % block
        blocks = jnp.pad(flat, (0, pad)).reshape(-1, block)
        scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0
        q = jnp.clip(jnp.round(blocks / jnp.maximum(scale, 1e-30)), -127, 127)
        return (q.astype(jnp.int8), scale), None

    def decompress(wire, meta, shape, dtype):
        q, scale = wire
        n = 1
        for s in shape:
            n *= s
        flat = (q.astype(jnp.float32) * scale).reshape(-1)[:n]
        return flat.reshape(shape).astype(dtype)

    return Compressor("int8", compress, decompress,
                      wire_bits_per_element=8.0 + 32.0 / block)


# ---------------------------------------------------------------------------
# block-local top-k sparsification (DGC-style)
# ---------------------------------------------------------------------------
def topk_compressor(ratio: float = 0.01, block: int = 1024) -> Compressor:
    if block > 1 << 16:
        raise ValueError(  # the packed wire format uses uint16 indices
            f"topk block must be <= 65536 (got {block}); in-block indices "
            "are shipped as uint16 (core/fabric.py)")
    k = max(1, int(round(block * ratio)))

    def compress(x):
        flat = x.reshape(-1).astype(jnp.float32)
        pad = (-flat.shape[0]) % block
        blocks = jnp.pad(flat, (0, pad)).reshape(-1, block)
        vals, idx = jax.lax.top_k(jnp.abs(blocks), k)
        taken = jnp.take_along_axis(blocks, idx, axis=-1)
        return (taken, idx.astype(jnp.int32)), None

    def decompress(wire, meta, shape, dtype):
        taken, idx = wire
        n = 1
        for s in shape:
            n *= s
        nblocks = idx.shape[0]
        blocks = jnp.zeros((nblocks, block), jnp.float32).at[
            jnp.arange(nblocks)[:, None], idx].set(taken)
        return blocks.reshape(-1)[:n].reshape(shape).astype(dtype)

    # k values (32b) + k indices (16b suffices for block≤64k) per block
    return Compressor(f"topk{ratio}", compress, decompress,
                      wire_bits_per_element=ratio * (32.0 + 16.0),
                      fused_encode=_fused_topk(k, block))


# ---------------------------------------------------------------------------
# fused kernel encode+error-feedback rounds (the production Fabric path)
# ---------------------------------------------------------------------------
def _kernel_rows(rows: int, block: int) -> int:
    """rows_per_step for the block-row kernels, a multiple of the (8, 128)
    sublane tile.  Compiled, a step holds about 1 MiB of f32 per input
    block, so the double-buffered blocks stay well inside the scoped VMEM.
    Interpret mode unrolls the Pallas grid at trace time, so there the
    grid is capped at ~64 steps instead."""
    from repro.kernels.ops import default_interpret

    def up(x):
        return max(8, -(-x // 8) * 8)

    if default_interpret():
        return up(-(-rows // 64))  # ceil: grid ≤ 64
    return min(up(rows), up((1 << 18) // block))


def _fold_blocks(g, r, block: int):
    """lead + (n,) f32 pair → (rows, block) kernel inputs.  Replica lead
    axes fold into kernel rows AFTER per-replica zero-padding to a block
    multiple, so a compression block never mixes values from two
    replicas (the same guarantee as the vmapped jnp path)."""
    n = g.shape[-1]
    pad = (-n) % block
    g2 = g.astype(jnp.float32).reshape((-1, n))
    r2 = r.astype(jnp.float32).reshape((-1, n))
    if pad:
        g2 = jnp.pad(g2, ((0, 0), (0, pad)))
        r2 = jnp.pad(r2, ((0, 0), (0, pad)))
    nb = (n + pad) // block
    rows = g2.shape[0] * nb
    return g2.reshape(rows, block), r2.reshape(rows, block), nb, pad


def _unfold_residual(newr, lead, n: int, pad: int):
    """Kernel residual rows → lead + (n,) (padded tail dropped — the jnp
    path never materializes it either)."""
    return newr.reshape((-1, n + pad))[:, :n].reshape(lead + (n,))


def _fused_onebit(block: int):
    def fused_encode(g, r):
        from repro.kernels import ops
        lead, n = g.shape[:-1], g.shape[-1]
        gb, rb, nb, pad = _fold_blocks(g, r, block)
        packed, scale, newr = ops.onebit_quant_packed(
            gb, rb, rows_per_step=_kernel_rows(gb.shape[0], block))
        arrs = [packed.reshape(lead + (nb * (block // 8),)),
                scale.reshape(lead + (nb, 1))]

        def widen(a):  # one replica's narrow arrays → decompress wire
            p, s = a
            sign = unpack_signs(p.reshape(-1), nb * block)
            return sign.reshape(nb, block), s.astype(jnp.float32)

        return arrs, widen, _unfold_residual(newr, lead, n, pad)

    return fused_encode


def _fused_topk(k: int, block: int):
    def fused_encode(g, r):
        from repro.kernels import ops
        lead, n = g.shape[:-1], g.shape[-1]
        gb, rb, nb, pad = _fold_blocks(g, r, block)
        vals, idx, newr = ops.topk_encode_ef(
            gb, rb, k, rows_per_step=_kernel_rows(gb.shape[0], block))
        arrs = [vals.reshape(lead + (nb, k)),
                idx.astype(jnp.uint16).reshape(lead + (nb, k))]

        def widen(a):
            return a[0], a[1].astype(jnp.int32)

        return arrs, widen, _unfold_residual(newr, lead, n, pad)

    return fused_encode


REGISTRY = {
    "none": none_compressor,
    "onebit": onebit_compressor,
    "int8": int8_compressor,
    "topk": topk_compressor,
}


def get_compressor(name: str, **kw) -> Compressor:
    return REGISTRY[name](**kw)


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------
def ef_init(params):
    """Error-feedback residual state (one per communicated leaf)."""
    return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)


def ef_compress_tree(comp: Compressor, grads, residual):
    """Apply compressor with error feedback leaf-wise.

    Returns (g_hat, new_residual): ``g_hat`` is what gets communicated
    (already decompressed — see module docstring), residual carries the
    compression error to the next round."""

    def one(g, r):
        target = g.astype(jnp.float32) + r
        wire, meta = comp.compress(target)
        g_hat = comp.decompress(wire, meta, g.shape, jnp.float32)
        return g_hat.astype(g.dtype), target - g_hat

    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = jax.tree.leaves(residual)
    out = [one(g, r) for g, r in zip(flat_g, flat_r)]
    g_hat = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_r = jax.tree.unflatten(treedef, [o[1] for o in out])
    return g_hat, new_r


def wire_bytes(comp: Compressor, tree) -> float:
    """EXACT bytes on the wire to ship ``tree`` once under ``comp``:
    each leaf is compressed independently (the leaf-wise contract of
    ``ef_compress_tree``/``dgc_compress_tree``), so padded tail blocks
    ship their full scale/index payloads and are charged here.  Derived
    from the actual packing code (``packed_nbytes``), matching
    ``fabric.wire_nbytes`` by construction; ``wire_bits_per_element``
    remains the analytic (padding-free) figure for scaling models."""
    return float(sum(packed_nbytes(comp, x.size)
                     for x in jax.tree.leaves(tree)))


# ---------------------------------------------------------------------------
# Deep Gradient Compression momentum correction (Lin et al. [54], §2.2.4):
# accumulate MOMENTUM (not raw gradients) into the residual before top-k,
# so sparsified-away velocity keeps accumulating instead of being lost.
# ---------------------------------------------------------------------------
def dgc_init(params):
    z = lambda p: jnp.zeros_like(p, jnp.float32)  # noqa: E731
    return {"velocity": jax.tree.map(z, params),
            "residual": jax.tree.map(z, params)}


def dgc_compress_tree(comp: Compressor, grads, state, momentum: float = 0.9):
    """Returns (g_hat, new_state): g_hat is the communicated (decompressed)
    sparse velocity; velocity/residual carry what wasn't sent."""

    def one(g, u, r):
        u1 = momentum * u + g.astype(jnp.float32)
        target = r + u1
        wire, meta = comp.compress(target)
        sent = comp.decompress(wire, meta, g.shape, jnp.float32)
        # what was sent leaves both accumulators (DGC eq. 4-5)
        mask = (sent != 0).astype(jnp.float32)
        return sent.astype(g.dtype), u1 * (1 - mask), target - sent

    flat_g, treedef = jax.tree.flatten(grads)
    flat_u = jax.tree.leaves(state["velocity"])
    flat_r = jax.tree.leaves(state["residual"])
    outs = [one(g, u, r) for g, u, r in zip(flat_g, flat_u, flat_r)]
    g_hat = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_state = {
        "velocity": jax.tree.unflatten(treedef, [o[1] for o in outs]),
        "residual": jax.tree.unflatten(treedef, [o[2] for o in outs]),
    }
    return g_hat, new_state


def pack_signs(sign_int8):
    """True 1-bit wire format: pack 8 int8 signs into one uint8.  This is
    the jnp reference codec; the fused kernel (onebit_quant_packed) emits
    the same bytes from inside VMEM — no separate XLA pack op on the
    fused Fabric path (DESIGN.md §2 table)."""
    bits = (sign_int8 > 0).astype(jnp.uint8).reshape(-1, 8)
    weights = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128], jnp.uint8)
    return jnp.sum(bits * weights, axis=-1).astype(jnp.uint8)


def unpack_signs(packed, n):
    """Inverse of ``pack_signs``: the first ``n`` signs as int8 ±1.

    Bytes go in rows of 32.  The eight bit planes of a row are laid side
    by side (column 32·k + b holds bit k of byte b), and one 0/1
    permutation matmul moves column 32·k + b to 8·b + k, the wire's order.
    Every output is a single 0/1 product, so the matmul is exact at any
    precision.  On a TPU this keeps every array lane-dense: expanding the
    bits along a minor axis of 8 made XLA materialize a 16×-padded copy of
    the decoded bucket (14 GiB for qwen2-1.5b's embedding at W=2)."""
    rows = jnp.pad(packed, (0, (-packed.shape[0]) % 32)).reshape(-1, 32)
    planes = jnp.concatenate([(rows >> k) & 1 for k in range(8)], axis=-1)
    src = lax.broadcasted_iota(jnp.int32, (256, 256), 0)
    dst = lax.broadcasted_iota(jnp.int32, (256, 256), 1)
    perm = ((src % 32) * 8 + src // 32 == dst).astype(jnp.bfloat16)
    bits = jnp.dot(planes.astype(jnp.bfloat16), perm,
                   preferred_element_type=jnp.float32)
    return jnp.where(bits.reshape(-1)[:n] > 0, 1, -1).astype(jnp.int8)


# ---------------------------------------------------------------------------
# wire codecs: compressor wire tuple ↔ one packed uint8 buffer.
# The narrowing IS the wire format (packed sign bits, bf16 scales, uint16
# top-k indices); core/fabric.py ships exactly these bytes per bucket.
# ---------------------------------------------------------------------------
def _to_bytes(x):
    """Any array → flat uint8 view."""
    if x.dtype == jnp.uint8:
        return x.reshape(-1)
    return lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)


def _from_bytes(buf, shape, dtype):
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 1:
        seg = buf.reshape(shape)
        return seg if dtype == jnp.uint8 \
            else lax.bitcast_convert_type(seg, dtype)
    return lax.bitcast_convert_type(
        buf.reshape(tuple(shape) + (dtype.itemsize,)), dtype)


def _narrow_wire(name: str, wire):
    """Narrow a compressor's wire tuple to its true on-the-wire dtypes.

    Returns (arrays, widen) where ``widen`` maps the narrowed arrays back
    to the structure ``Compressor.decompress`` expects.  Unknown
    compressors fall through to an identity codec."""
    if name == "onebit":
        sign, scale = wire
        n = sign.size
        flat = sign.reshape(-1)
        pad = (-n) % 8
        if pad:
            flat = jnp.concatenate([flat, jnp.ones((pad,), flat.dtype)])
        packed = pack_signs(flat)

        def widen(arrs):
            p, s = arrs
            return (unpack_signs(p, n).reshape(sign.shape),
                    s.astype(jnp.float32))

        return [packed, scale.astype(jnp.bfloat16)], widen
    if name == "int8":
        q, scale = wire

        def widen(arrs):
            return (arrs[0], arrs[1].astype(jnp.float32))

        return [q, scale.astype(jnp.bfloat16)], widen
    if name.startswith("topk"):
        taken, idx = wire  # blocks ≤ 64k ⇒ uint16 indices

        def widen(arrs):
            return (arrs[0], arrs[1].astype(jnp.int32))

        return [taken, idx.astype(jnp.uint16)], widen
    arrs, tdef = jax.tree.flatten(wire)
    return arrs, lambda a: jax.tree.unflatten(tdef, list(a))


def _pack(arrs):
    """Arrays → (uint8 buffer, static segment specs)."""
    bufs = [_to_bytes(a) for a in arrs]
    specs = [(a.shape, a.dtype, b.shape[-1]) for a, b in zip(arrs, bufs)]
    buf = bufs[0] if len(bufs) == 1 else jnp.concatenate(bufs, axis=-1)
    return buf, specs


def _unpack(buf, specs):
    out, off = [], 0
    for shape, dtype, nb in specs:
        seg = lax.slice_in_dim(buf, off, off + nb, axis=buf.ndim - 1)
        out.append(_from_bytes(seg, shape, dtype))
        off += nb
    return out


def packed_nbytes(comp: Optional[Compressor], n: int) -> int:
    """Exact packed-wire bytes to ship ``n`` f32 elements once under
    ``comp`` — derived from the actual packing code via eval_shape, so it
    equals the size of the uint8 buffer an exchange really gathers
    (padded tail blocks included)."""
    if comp is None or comp.name == "none":
        return 4 * n

    def f(t):
        wire, _ = comp.compress(t)
        arrs, _ = _narrow_wire(comp.name, wire)
        buf, _ = _pack(arrs)
        return buf

    return int(jax.eval_shape(
        f, jax.ShapeDtypeStruct((n,), jnp.float32)).shape[0])
