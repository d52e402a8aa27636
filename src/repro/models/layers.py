"""Core transformer layers: norms, RoPE, GQA attention (sliding-window /
bias / qk-norm / softcap / cross), SwiGLU MLP, and capacity-based MoE.

All layers are pure functions over nested-dict parameter pytrees.

Precision contract (core/precision.py, DESIGN.md §4): matmuls and
activations run in whatever dtype the inputs carry (``cfg.compute_dtype``
after the forward-boundary cast in models/transformer.py), but every
numerically-sensitive reduction accumulates in f32 regardless —
``rms_norm`` statistics, RoPE angles, attention logits + softmax (all
four sdpa paths), and the MoE router logits/aux loss.  Keeping those
invariants here is what lets the bf16 policy train within tolerance of
f32 (tests/test_precision.py) without any per-layer dtype plumbing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import FULL_ATTENTION, ModelConfig
from repro.core import jax_compat as compat
from repro.launch.compile_cache import record_attention_path, record_moe_path
from repro.launch.sharding import BATCH, MODEL, heads_ax, seq_ax, shard

NEG_INF = -2.0e38


def _dtype(cfg: ModelConfig, kind: str):
    return jnp.dtype(cfg.param_dtype if kind == "param" else cfg.compute_dtype)


# ---------------------------------------------------------------------------
# tensor parallelism (DESIGN.md §12, models/tensor_parallel.py)
#
# TP blocks the WHOLE sub-layer, not individual contractions: rank i's
# subgraph is (q/k/v head-slice → sdpa over its heads → out-projection
# partial) for attention and (gate/up column-slice → act → down-projection
# partial) for the MLP, combined with ONE all-sum per sub-layer (Megatron's
# g operator) plus the f operator's cotangent psum at the input.  The
# unsharded reference with cfg.tp_degree = T > 1 computes the SAME T
# per-block subgraphs and reduces them with jnp.sum(jnp.stack(...)) — the
# identical dataflow graph per block and the identical combine, which is
# what makes a TP run bitwise-equal to its blocked reference in f32
# (blocking per-contraction instead would re-order the input-cotangent
# accumulation across q/k/v and gate/up and break bitwise backward).
# tp_degree == 1 (every config's default) keeps the historical
# single-einsum paths untouched.
# ---------------------------------------------------------------------------
def _current_tp():
    from repro.models.tensor_parallel import current_tp

    return current_tp()


def _attn_slice(p, i: int, t: int):
    """Head-block i of t of an attention param dict — exactly what
    ``tp_split_params`` puts on rank i."""
    h, kv = p["wq"].shape[1], p["wk"].shape[1]
    hb, kb = h // t, kv // t
    out = dict(p)
    out["wq"] = p["wq"][:, i * hb:(i + 1) * hb]
    out["wk"] = p["wk"][:, i * kb:(i + 1) * kb]
    out["wv"] = p["wv"][:, i * kb:(i + 1) * kb]
    out["wo"] = p["wo"][i * hb:(i + 1) * hb]
    if "bq" in p:
        out["bq"] = p["bq"][i * hb:(i + 1) * hb]
        out["bk"] = p["bk"][i * kb:(i + 1) * kb]
        out["bv"] = p["bv"][i * kb:(i + 1) * kb]
    return out


def dense_init(key, shape, dtype, in_axis=0):
    fan_in = shape[in_axis]
    scale = 1.0 / max(1, fan_in) ** 0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def init_rms_norm(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(x, p, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return out.astype(x.dtype) * p["scale"].astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope(x, positions, theta, yarn=None):
    """x: (..., L, H, Dh), positions: (..., L) int, theta: scalar.
    ``yarn`` (static ``configs.base.Yarn``, with a static ``theta``)
    rescales the frequencies and scales cos and sin."""
    dh = x.shape[-1]
    half = dh // 2
    if yarn is None:
        freq = jnp.arange(half, dtype=jnp.float32) * (2.0 / dh)
        inv = jnp.power(jnp.asarray(theta, jnp.float32), -freq)  # (half,)
    else:
        inv = jnp.asarray(yarn.inv_freq(theta, dh), jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., L, half)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]  # (..., L, 1, half)
    if yarn is not None:
        sin, cos = sin * yarn.scale, cos * yarn.scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, cross: bool = False):
    d, h, kv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pdt = _dtype(cfg, "param")
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (d, h, dh), pdt),
        "wk": dense_init(ks[1], (d, kv, dh), pdt),
        "wv": dense_init(ks[2], (d, kv, dh), pdt),
        "wo": dense_init(ks[3], (h, dh, d), pdt, in_axis=0),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), pdt)
        p["bk"] = jnp.zeros((kv, dh), pdt)
        p["bv"] = jnp.zeros((kv, dh), pdt)
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(dh, pdt)
        p["k_norm"] = init_rms_norm(dh, pdt)
    return p


def _qkv(p, cfg, xq, xkv):
    q = jnp.einsum("bld,dhk->blhk", xq, p["wq"])
    k = jnp.einsum("bld,dhk->blhk", xkv, p["wk"])
    v = jnp.einsum("bld,dhk->blhk", xkv, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _softcap(cfg, logits):
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def _sdpa(cfg: ModelConfig, q, k, v, mask):
    """Full-sequence attention.  q: (B,Lq,H,Dh) k/v: (B,Lk,KV,Dh),
    mask: (B,1,Lq,Lk) or (1,1,Lq,Lk).

    GQA KV heads are EXPANDED to H before the einsum: the (H → KV, G)
    reshape of the grouped form is unrepresentable for a head sharding and
    makes the SPMD partitioner all-gather activations across the mesh
    (observed: 1 GiB gathers on qwen2-1.5b).  Expansion keeps the "model"
    head sharding intact end-to-end; the extra KV bytes are activation-
    sized and compute is unchanged."""
    b, lq, h, dh = q.shape
    kvh = k.shape[2]
    if cfg.sharding_mode == "cp":
        # context parallel: q rows stay sequence-sharded; the (small, GQA)
        # KV is all-gathered over "model" (constraining seq to replicated).
        # KV stays UN-expanded (grouped einsum): heads are not sharded in
        # cp mode, and expanding first makes the backward reduce dk/dv at
        # H instead of KV heads (§Perf hillclimb 2 it. 2: 8× extra wire).
        k = shard(k, BATCH, None, None, None)
        v = shard(v, BATCH, None, None, None)
        g = h // kvh
        qg = q.reshape(b, lq, kvh, g, dh)
        logits = jnp.einsum("blkgd,bskd->bkgls", qg, k).astype(jnp.float32)
        logits *= dh ** -0.5
        logits = _softcap(cfg, logits)
        logits = jnp.where(mask[:, :, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgls,bskd->blkgd", probs, v).reshape(b, lq, h, dh)
        return shard(out, BATCH, seq_ax(cfg), None, None)
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    k = shard(k, BATCH, None, MODEL, None)
    v = shard(v, BATCH, None, MODEL, None)
    logits = jnp.einsum("blhd,bshd->bhls", q, k).astype(jnp.float32)
    logits *= dh ** -0.5
    logits = _softcap(cfg, logits)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhls,bshd->blhd", probs, v)
    return shard(out, BATCH, seq_ax(cfg), heads_ax(cfg), None)


def _sdpa_banded(cfg: ModelConfig, q, k, v, window: int):
    """Block-banded sliding-window attention (exact for window ≤ block).

    q,k,v: (B, L, H|KV, Dh); block = window; each q block attends to k
    blocks [prev, self] with in-band masking — (2·w)/L of the dense FLOPs."""
    b, l, h, dh = q.shape
    kvh = k.shape[2]
    if kvh != h and cfg.sharding_mode != "cp":
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
        kvh = h
    w = window
    nb = l // w
    qb = q.reshape(b, nb, w, h, dh)
    kb = k.reshape(b, nb, w, kvh, dh)
    vb = v.reshape(b, nb, w, kvh, dh)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    kk = jnp.concatenate([kprev, kb], axis=2)  # (B, nb, 2w, KV, Dh)
    vv = jnp.concatenate([vprev, vb], axis=2)

    g = h // kvh
    qg = qb.reshape(b, nb, w, kvh, g, dh)
    logits = jnp.einsum("bnikgd,bnjkd->bnkgij", qg, kk).astype(jnp.float32)
    logits *= dh ** -0.5
    logits = _softcap(cfg, logits)
    # in-band mask: global i = n·w + ii, global j = n·w − w + jj
    ii = jnp.arange(w)[:, None]
    jj = jnp.arange(2 * w)[None, :]
    rel = ii + w - jj  # = i − j
    first = jnp.arange(nb) == 0  # block 0 has no prev
    valid = (rel >= 0) & (rel < w)  # causal ∧ window
    valid = valid[None, :, :] & ~(first[:, None, None] & (jj < w)[None])
    logits = jnp.where(valid[None, :, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(vv.dtype)
    out = jnp.einsum("bnkgij,bnjkd->bnikgd", probs, vv)
    out = out.reshape(b, l, h, dh)
    return shard(out, BATCH, seq_ax(cfg), heads_ax(cfg), None)


def _sdpa_decode(cfg: ModelConfig, q, k, v, mask):
    """Single-token decode attention against the (unexpanded) KV cache.
    q: (B,1,H,Dh), k/v: (B,S,KV,Dh) — the grouped einsum is fine here
    because q is tiny and stays replicated over "model" while the cache's
    sequence dim carries the sharding."""
    b, lq, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q = q.reshape(b, lq, kvh, g, dh)
    logits = jnp.einsum("blkgd,bskd->bkgls", q, k).astype(jnp.float32)
    logits *= dh ** -0.5
    logits = _softcap(cfg, logits)
    logits = jnp.where(mask[:, :, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgls,bskd->blkgd", probs, v)
    return out.reshape(b, lq, h, dh)


def _sdpa_flash(q, k, v, window=FULL_ATTENTION):
    """Causal self-attention through the Pallas flash kernel (scores stay
    in VMEM), with a static sliding ``window`` or none.  q: (B,L,H,Dh),
    k/v: (B,L,KV,Dh) → (B,L,H,Dh)."""
    from repro.kernels import ops

    out = ops.flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                              v.swapaxes(1, 2), window=window)
    return out.swapaxes(1, 2)


def _takes_flash(cfg: ModelConfig, plain_causal: bool) -> bool:
    """Whether causal self-attention takes the flash kernel: only where its
    semantics are exactly the kernel's (positions 0..L-1 and a static
    window or none, which the caller certifies with ``plain_causal``; no
    logit softcap),
    where this device holds whole heads (no cp, no TP, no auto-partitioned
    mesh axis larger than 1) and where the kernel compiles (a chip, not
    interpret mode).  Everything else keeps the jnp ``_sdpa``."""
    from repro.kernels import ops

    if (not plain_causal or cfg.attn_logit_softcap
            or cfg.sharding_mode == "cp" or cfg.tp_degree > 1
            or _current_tp() is not None):
        return False
    return _unpartitioned() and not ops.default_interpret()


def _unpartitioned() -> bool:
    """Whether no auto-partitioned mesh axis larger than 1 is in scope:
    what the layer computes here is this device's whole share."""
    mesh = compat.get_abstract_mesh()
    if mesh is not None and not mesh.empty:
        manual = compat.manual_axis_names(mesh)
        if any(n > 1 for a, n in dict(mesh.shape).items()
               if a not in manual):
            return False
    return True


def attention(p, cfg: ModelConfig, x, positions, window, theta,
              cache=None, cache_pos=None, memory=None, causal=True,
              collect_cache=False, plain_causal=False, stacked=1,
              yarn=None):
    """One attention sub-layer.

    Training: ``cache is None`` — full-sequence causal (+sliding window) attn;
              with ``collect_cache`` the full-sequence (k, v) are returned as
              a populated decode cache (prefill).  ``plain_causal`` (static)
              says that ``positions`` are 0..L-1 and that ``window`` is a
              static int: see ``_takes_flash``.  ``yarn`` (static) rescales
              RoPE (``rope``).
    Decode:   ``cache`` holds (k, v) of length S; x has Lq=1; ``cache_pos`` is
              the write position.  Returns (out, new_cache).
    Cross-attention: ``memory`` is the encoder output; no cache, no causality.

    Full-sequence calls record the path they took (``flash`` or ``dense``)
    for the ``stacked`` layers this trace stands for
    (``compile_cache.attention_paths``).
    """
    xkv = memory if memory is not None else x
    b, lq = x.shape[0], x.shape[1]

    if memory is not None:  # cross attention: full visibility
        record_attention_path("dense", stacked)
        q, k, v = _qkv(p, cfg, x, xkv)
        lk = memory.shape[1]
        mask = jnp.ones((1, 1, lq, lk), bool)
        out = _sdpa(cfg, q, k, v, mask)
        new_cache = cache
    elif cache is None:  # training / prefill self-attention
        flash = causal and _takes_flash(cfg, plain_causal)
        record_attention_path("flash" if flash else "dense", stacked)

        def head_block(p_, xx):
            """One head-block's full attention subgraph: qkv slice → rope
            → sdpa over its heads → out-projection PARTIAL."""
            q, k, v = _qkv(p_, cfg, xx, xx)
            q = rope(q, positions, theta, yarn)
            k = rope(k, positions, theta, yarn)
            q = shard(q, BATCH, seq_ax(cfg), heads_ax(cfg), None)
            k = shard(k, BATCH, seq_ax(cfg), heads_ax(cfg), None)
            if flash:
                out = _sdpa_flash(q, k, v, window)
            elif (isinstance(window, int) and window > 0 and causal
                    and lq % window == 0 and lq // window >= 2):
                # static sliding window ⇒ block-banded attention: each q
                # block attends only to (prev, self) k blocks — compute
                # ∝ L·window, the jnp analogue of the Pallas kernel's
                # block skipping.
                out = _sdpa_banded(cfg, q, k, v, window)
            else:
                i = positions[:, :, None]  # (B, L, 1)
                j = positions[:, None, :]  # (B, 1, L)
                mask = (j <= i) if causal else jnp.ones_like(j <= i)
                w = jnp.where(window == FULL_ATTENTION,
                              jnp.iinfo(jnp.int32).max, window)
                mask = mask & (i - j < w)
                out = _sdpa(cfg, q, k, v, mask[:, None])
            return jnp.einsum("blhk,hkd->bld", out, p_["wo"]), {"k": k,
                                                                "v": v}
        tp = _current_tp()
        t = cfg.tp_degree
        if tp is not None:
            # TP rank: params already hold this rank's head block
            partial, kv_c = head_block(p, x)
            proj = tp.all_sum(partial)
        elif (t > 1 and not collect_cache
              and p["wq"].shape[1] % t == 0 and p["wk"].shape[1] % t == 0):
            # blocked reference: T per-block subgraphs + stacked sum
            parts = [head_block(_attn_slice(p, i, t), x)[0]
                     for i in range(t)]
            proj = jnp.sum(jnp.stack(parts), axis=0)
            kv_c = None
        else:
            proj, kv_c = head_block(p, x)
        new_cache = kv_c if collect_cache else None
        out = shard(proj, BATCH, seq_ax(cfg), None)
        return out, new_cache
    else:  # single-token decode; cache_pos: scalar OR (B,) ragged positions
        q, k, v = _qkv(p, cfg, x, xkv)
        pos = cache_pos
        ragged = hasattr(pos, "ndim") and pos.ndim == 1
        pos_b = pos[:, None] if ragged else jnp.full((b, lq), pos, jnp.int32)
        q = rope(q, pos_b, theta, yarn)
        k = rope(k, pos_b, theta, yarn)
        if ragged:  # per-row scatter write (continuous batching)
            rows = jnp.arange(b)
            ck = cache["k"].at[rows, pos].set(k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, pos].set(v[:, 0].astype(cache["v"].dtype))
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
        s = ck.shape[1]
        j = jnp.arange(s, dtype=jnp.int32)[None, None, :]  # (1,1,S)
        w = jnp.where(window == FULL_ATTENTION, jnp.iinfo(jnp.int32).max, window)
        p_ = pos[:, None, None] if ragged else pos
        mask = (j <= p_) & (p_ - j < w)  # (1,1,S) or ragged (B,1,S)
        out = _sdpa_decode(cfg, q, ck, cv, mask[:, None])  # → (.,1,1,S)
        new_cache = {"k": ck, "v": cv}
    out = jnp.einsum("blhk,hkd->bld", out, p["wo"])
    out = shard(out, BATCH, seq_ax(cfg), None)
    return out, new_cache


def init_attn_cache(cfg: ModelConfig, batch, max_seq, dtype):
    kv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_seq, kv, dh), dtype),
        "v": jnp.zeros((batch, max_seq, kv, dh), dtype),
    }


# ---------------------------------------------------------------------------
# paged attention (serving tier — block KV cache, DESIGN.md §10)
# ---------------------------------------------------------------------------
def init_paged_attn_cache(cfg: ModelConfig, num_pages, page_size, dtype):
    """Block KV cache: ``(num_pages, page_size, KV, Dh)`` k/v page pools.
    Physical page 0 is RESERVED as the trash page (never allocated — idle
    or padded token writes are routed there and no block table ever
    references it for a live position).  ``int8`` pages add per-token-
    per-head f32 scale pools for symmetric quantization."""
    kv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    c = {
        "k_pages": jnp.zeros((num_pages, page_size, kv, dh), dtype),
        "v_pages": jnp.zeros((num_pages, page_size, kv, dh), dtype),
    }
    if jnp.dtype(dtype) == jnp.int8:
        c["k_scale"] = jnp.zeros((num_pages, page_size, kv), jnp.float32)
        c["v_scale"] = jnp.zeros((num_pages, page_size, kv), jnp.float32)
    return c


def _quant_kv_int8(x):
    """Per-token-per-head symmetric int8: x (..., Dh) → (int8, f32 scale)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _paged_write(cache, block_tables, positions, k, v):
    """Scatter a chunk's KV (B, C, KV, Dh) into the pages.  positions:
    (B, C) int32 with -1 ⇒ pad/idle — those writes land in trash page 0."""
    bs = cache["k_pages"].shape[1]
    rows = jnp.arange(positions.shape[0])[:, None]
    valid = positions >= 0
    pc = jnp.maximum(positions, 0)
    blk = jnp.where(valid, block_tables[rows, pc // bs], 0)
    off = jnp.where(valid, pc % bs, 0)
    new = dict(cache)
    if cache["k_pages"].dtype == jnp.int8:
        kq, ksc = _quant_kv_int8(k)
        vq, vsc = _quant_kv_int8(v)
        new["k_pages"] = cache["k_pages"].at[blk, off].set(kq)
        new["v_pages"] = cache["v_pages"].at[blk, off].set(vq)
        new["k_scale"] = cache["k_scale"].at[blk, off].set(ksc)
        new["v_scale"] = cache["v_scale"].at[blk, off].set(vsc)
    else:
        dt = cache["k_pages"].dtype
        new["k_pages"] = cache["k_pages"].at[blk, off].set(k.astype(dt))
        new["v_pages"] = cache["v_pages"].at[blk, off].set(v.astype(dt))
    return new


def _paged_gather(cache, block_tables, dtype):
    """Dense (B, MB·page_size, KV, Dh) view of each sequence's pages.
    f32/bf16 pages keep their stored dtype (bitwise-identical numerics to
    the dense decode cache); int8 pages dequantize through the scale
    pools into ``dtype``."""
    ks = cache["k_pages"][block_tables]  # (B, MB, bs, KV, Dh)
    vs = cache["v_pages"][block_tables]
    if cache["k_pages"].dtype == jnp.int8:
        ks = (ks.astype(jnp.float32)
              * cache["k_scale"][block_tables][..., None]).astype(dtype)
        vs = (vs.astype(jnp.float32)
              * cache["v_scale"][block_tables][..., None]).astype(dtype)
    b = block_tables.shape[0]
    kv, dh = ks.shape[-2:]
    return ks.reshape(b, -1, kv, dh), vs.reshape(b, -1, kv, dh)


def attention_paged(p, cfg: ModelConfig, x, positions, window, theta,
                    cache, block_tables, use_kernel=False, yarn=None):
    """Attention over a paged KV cache — decode (C=1) and chunked prefill
    (C>1) through ONE code path.

    x: (B, C, D); positions: (B, C) int32 token positions (-1 ⇒ pad/idle:
    the KV write is routed to trash page 0 and the output row is garbage —
    callers mask it); block_tables: (B, pages_per_seq) int32.

    Write-then-attend: the chunk's roped KV is scattered into the pages
    FIRST, then attention reads the updated pages with mask ``j <= pos``,
    so each token sees itself and its whole prefix without a separate
    dense prefill pass.  Decode single tokens take the Pallas kernel when
    ``use_kernel`` (f32/bf16 pages); prefill chunks and int8 pages take
    the jnp gather path (same oracle as kernels/ref.py).
    """
    q, k, v = _qkv(p, cfg, x, x)
    b, c = x.shape[0], x.shape[1]
    pc = jnp.maximum(positions, 0)
    q = rope(q, pc, theta, yarn)
    k = rope(k, pc, theta, yarn)
    new_cache = _paged_write(cache, block_tables, positions, k, v)

    h, dh = q.shape[2], q.shape[3]
    kvh = cfg.num_kv_heads
    int8 = cache["k_pages"].dtype == jnp.int8
    if use_kernel and c == 1 and not int8:
        from repro.kernels import ops
        qg = q[:, 0].reshape(b, kvh, h // kvh, dh)  # grouped, (kv, g) order
        ctx = pc[:, 0] + 1
        out = ops.paged_attention(
            qg, new_cache["k_pages"], new_cache["v_pages"], block_tables,
            ctx, window=window, softcap=cfg.attn_logit_softcap)
        out = out.reshape(b, 1, h, dh)
    else:
        ks, vs = _paged_gather(new_cache, block_tables, x.dtype)
        s = ks.shape[1]
        i = pc[:, :, None]                                    # (B, C, 1)
        j = jnp.arange(s, dtype=jnp.int32)[None, None, :]     # (1, 1, S)
        w = jnp.where(window == FULL_ATTENTION,
                      jnp.iinfo(jnp.int32).max, window)
        mask = (j <= i) & (i - j < w)                         # (B, C, S)
        out = _sdpa_decode(cfg, q, ks, vs, mask[:, None])
    out = jnp.einsum("blhk,hkd->bld", out, p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU)
# ---------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    pdt = _dtype(cfg, "param")
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d, f), pdt),
        "w_up": dense_init(k2, (d, f), pdt),
        "w_down": dense_init(k3, (f, d), pdt),
    }


def _act(name):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def mlp(p, cfg: ModelConfig, x):
    def ffn_block(wg, wu, wd, xx):
        """One d_ff-block's full MLP subgraph: gate/up column slice → act
        → down-projection PARTIAL."""
        h = _act(cfg.act)(xx @ wg) * (xx @ wu)
        h = shard(h, BATCH, seq_ax(cfg), heads_ax(cfg))
        return h @ wd

    tp = _current_tp()
    t = cfg.tp_degree
    if tp is not None:  # TP rank: params already hold this rank's columns
        return tp.all_sum(ffn_block(p["w_gate"], p["w_up"], p["w_down"], x))
    f = p["w_down"].shape[0]
    if t == 1 or f % t:  # shared-expert widths need not divide tp_degree
        return ffn_block(p["w_gate"], p["w_up"], p["w_down"], x)
    blk = f // t
    parts = [ffn_block(p["w_gate"][:, i * blk:(i + 1) * blk],
                       p["w_up"][:, i * blk:(i + 1) * blk],
                       p["w_down"][i * blk:(i + 1) * blk], x)
             for i in range(t)]
    return jnp.sum(jnp.stack(parts), axis=0)


# ---------------------------------------------------------------------------
# Mixture of Experts.  Where the layer runs alone on its device (no
# partitioned mesh axis, no "model" axis), the dropless grouped path:
# every assignment to an expert held here is computed.  Under a "model"
# mesh axis, capacity-based scatter/gather dispatch (no T×E×C one-hot: see
# DESIGN.md §3), experts sharded over "model".
# ---------------------------------------------------------------------------
MOE_SCOPE = "moe.experts"


def init_moe(key, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.expert_d_ff
    e = cfg.experts_held  # dummy experts: zero weights, never routed
    pdt = _dtype(cfg, "param")
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, cfg.num_experts), pdt),
        "w_gate": dense_init(ks[1], (e, d, f), pdt, in_axis=1),
        "w_up": dense_init(ks[2], (e, d, f), pdt, in_axis=1),
        "w_down": dense_init(ks[3], (e, f, d), pdt, in_axis=1),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=cfg.num_shared_experts * f)
    return p


def _route(p, cfg: ModelConfig, xt, e_pad, cap):
    """Shared routing math.  xt: (T, D) → (flat_idx, slot, keep, gate, aux)."""
    t = xt.shape[0]
    k = cfg.top_k
    logits = (xt @ p["router"]).astype(jnp.float32)  # (T, E) active experts
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)  # (T, k), idx < E ≤ E_pad
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    onehot = jax.nn.one_hot(idx, e_pad, dtype=jnp.float32)  # (T, k, E_pad)
    aux = _balance_loss(cfg, probs, idx)

    flat_idx = idx.reshape(t * k)
    flat_gate = gate_vals.reshape(t * k)
    oh = onehot.reshape(t * k, e_pad)
    pos_in_e = jnp.cumsum(oh, axis=0) - oh  # position among same-expert rows
    slot = jnp.sum(pos_in_e * oh, axis=-1).astype(jnp.int32)
    keep = slot < cap
    slot = jnp.where(keep, slot, cap)  # overflow → dump slot
    return flat_idx, slot, keep, flat_gate, aux


def _expert_ffn(cfg, buf, w_gate, w_up, w_down):
    h = _act(cfg.act)(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_dense(p, cfg: ModelConfig, x):
    """Reference path (no mesh / tiny token counts): capacity dispatch with
    jnp scatter/gather on one device's view."""
    b, l, d = x.shape
    e_pad, k = cfg.num_experts_padded, cfg.top_k
    t = b * l
    xt = x.reshape(t, d)
    cap = int(max(k, round(t * k / e_pad * cfg.capacity_factor)))
    flat_idx, slot, keep, flat_gate, aux = _route(p, cfg, xt, e_pad, cap)

    src = jnp.repeat(xt, k, axis=0) if k > 1 else xt  # (T*k, D)
    buf = jnp.zeros((e_pad, cap + 1, d), x.dtype)
    buf = buf.at[flat_idx, slot].set(src.astype(x.dtype))
    buf = shard(buf, MODEL, None, None)
    out_buf = _expert_ffn(cfg, buf, p["w_gate"], p["w_up"], p["w_down"])

    gathered = out_buf[flat_idx, slot]  # (T*k, D)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    combined = jnp.sum((gathered * flat_gate[:, None].astype(gathered.dtype))
                       .reshape(t, k, d), axis=1)
    return combined.reshape(b, l, d), aux


def _moe_ep(p, cfg: ModelConfig, x, mesh):
    """Expert-parallel MoE via shard_map (beyond-paper perf path; see
    EXPERIMENTS.md §Perf hillclimb 1).

    The pjit-auto scatter dispatch makes the SPMD partitioner replicate a
    GLOBAL (E, T·k·cf/E, D) buffer (observed: 80 GiB all-reduces/layer on
    qwen2-moe).  Here dispatch is token-local per data shard, experts are
    exchanged with two tiled ``all_to_all``s over the "model" axis, and
    expert weights are explicitly FSDP-gathered over "data" (ZeRO-3: gather
    the small weights, never the activations)."""
    from jax.sharding import PartitionSpec as P

    b, l, d = x.shape
    e_pad, k = cfg.num_experts_padded, cfg.top_k
    names = mesh.axis_names
    dp = _fit_batch_axes(mesh, b, tuple(a for a in ("pod", "data")
                                        if a in names))
    ep = _axsize(mesh, "model")
    n_dp = 1
    for a in dp:
        n_dp *= _axsize(mesh, a)
    t_loc = (b // n_dp) * l
    if t_loc % ep:
        return _moe_dense(p, cfg, x)  # token slice must divide the EP axis
    t_slice = t_loc // ep  # tokens dispatched by each model-device
    e_loc = e_pad // ep
    cap = int(max(k, round(t_slice * k / e_pad * cfg.capacity_factor)))
    cap = -(-cap // 8) * 8  # tile-align

    def local_fn(xl, router, wg, wu, wd):
        # xl: (b_loc, L, D) — REPLICATED over "model"; each model-device
        # dispatches only its 1/ep token slice (otherwise all ep devices
        # dispatch identical tokens and expert compute + wire blow up ep×:
        # §Perf hillclimb 1 it. 3).
        bl = xl.shape[0]
        xt = xl.reshape(bl * l, d)
        midx = jax.lax.axis_index("model")
        xt = jax.lax.dynamic_slice_in_dim(xt, midx * t_slice, t_slice, 0)
        flat_idx, slot, keep, flat_gate, aux = _route(
            {"router": router}, cfg, xt, e_pad, cap)
        src = jnp.repeat(xt, k, axis=0) if k > 1 else xt
        buf = jnp.zeros((e_pad, cap + 1, d), xl.dtype)
        buf = buf.at[flat_idx, slot].set(src.astype(xl.dtype))
        buf = buf[:, :cap]  # drop dump slot before the wire

        # dispatch a2a: (E_pad, C, D) → (E_loc, ep·C, D).  Named so the
        # opt-in remat policy can SAVE the a2a results (§Perf h1 it. 2).
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                  tiled=True)
        recv = checkpoint_name(recv, "moe_dispatch")
        # ZeRO-3 weight gather over the fsdp tier (grads reduce-scatter via AD)
        if "data" in names:
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
        out_loc = _expert_ffn(cfg, recv, wg, wu, wd)  # (E_loc, ep·C, D)
        # combine a2a: back to (E_pad, C, D) for my token slice
        back = jax.lax.all_to_all(out_loc, "model", split_axis=1,
                                  concat_axis=0, tiled=True)
        back = checkpoint_name(back, "moe_combine")
        back = jnp.concatenate(
            [back, jnp.zeros((e_pad, 1, d), back.dtype)], axis=1)  # dump slot
        gathered = back[flat_idx, slot]
        gathered = jnp.where(keep[:, None], gathered, 0.0)
        combined = jnp.sum(
            (gathered * flat_gate[:, None].astype(gathered.dtype))
            .reshape(t_slice, k, d), axis=1)
        # reassemble the full local token set (cheap: t_slice·D)
        combined = jax.lax.all_gather(combined, "model", axis=0, tiled=True)
        aux = jax.lax.pmean(aux, "model")
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return combined.reshape(bl, l, d), aux

    batch_spec = P(dp if dp else None, None, None)
    out, aux = compat.shard_map(
        local_fn, mesh=mesh,
        in_specs=(batch_spec, P(None, None),
                  P("model", "data" if "data" in names else None, None),
                  P("model", "data" if "data" in names else None, None),
                  P("model", None, "data" if "data" in names else None)),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux


def _axsize(mesh, name):
    return dict(mesh.shape).get(name, 1)


def _fit_batch_axes(mesh, b, candidates):
    axes = []
    prod = 1
    for a in candidates:
        s = _axsize(mesh, a)
        if s > 1 and b % (prod * s) == 0:
            axes.append(a)
            prod *= s
    return tuple(axes)


def _balance_loss(cfg: ModelConfig, probs, idx):
    """Switch-style balance loss over all ``num_experts``: E · Σ_e f_e·p_e
    · ``router_aux_coef`` (f_e: the share of assignments to e, p_e: its
    mean router probability); zero where the coefficient is."""
    if not cfg.router_aux_coef:
        return jnp.zeros((), jnp.float32)
    e = cfg.num_experts
    f_e = jnp.mean(jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32),
                           axis=1), axis=0)
    return e * jnp.sum(f_e * jnp.mean(probs, axis=0)) * cfg.router_aux_coef


@jax.custom_vjp
def _dispatch(xt, order, inv, mine):
    """Token rows in the order of the sorted assignments: (T, D) →
    (T·k, D), row r = token ``order[r] // k``.  The backward gathers
    back through ``inv`` (no scatter) and sums each token's k rows,
    counting only the assignments held here (``mine``: (T, k))."""
    return xt[order // mine.shape[1]]


def _dispatch_fwd(xt, order, inv, mine):
    return _dispatch(xt, order, inv, mine), (inv, mine)


def _dispatch_bwd(res, g):
    inv, mine = res
    gu = jnp.where(mine.reshape(-1, 1), g[inv], 0).astype(g.dtype)
    return gu.reshape(mine.shape + g.shape[-1:]).sum(axis=1), None, None, \
        None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(x, to, back):
    """``x[to]`` for a permutation ``to`` whose inverse is ``back``; the
    backward is the gather ``g[back]``."""
    return x[to]


def _permute_fwd(x, to, back):
    return x[to], (to, back)


def _permute_bwd(res, g):
    return g[res[1]], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _takes_gmm() -> bool:
    """Whether the grouped matmuls run as the Pallas kernels (compiled,
    on a chip); elsewhere ``jax.lax.ragged_dot`` computes the same."""
    from repro.kernels import ops

    return not ops.default_interpret()


def _grouped_matmul(xs, w, sizes):
    """Rows of group g (held expert g, in order, ``sizes[g]`` rows) times
    ``w[g]``; rows after the held groups (``sizes[-1]`` of them) are left
    unspecified."""
    if _takes_gmm():
        from repro.kernels import ops

        return ops.grouped_matmul(xs, w, sizes)
    return jax.lax.ragged_dot(xs, w, sizes[:-1])


def _moe_grouped(p, cfg: ModelConfig, x):
    """Dropless expert layer over the experts held here
    (``cfg.held_experts`` from ``cfg.first_held_expert``; all by default):
    the router's product in f32 over all ``num_experts``, softmax, top-k,
    gates renormalised; the assignments to held experts sorted by expert
    and run through grouped matmuls whose work follows the rows routed to
    them (no capacity, no drop, no padded expert); the gate-weighted
    outputs summed back per token in f32.  Assignments to experts held
    elsewhere contribute nothing here.  ``cfg.frozen_router``: the gates
    carry no gradient.  Returns (out, aux)."""
    b, l, d = x.shape
    t, k = b * l, cfg.top_k
    held, first = cfg.experts_held, cfg.first_held_expert
    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.frozen_router:
        logits = jax.lax.stop_gradient(logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    aux = _balance_loss(cfg, probs, idx)

    local = idx.reshape(t * k) - first
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)  # held elsewhere: one last group
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32), unique_indices=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)

    xs = _dispatch(xt, order, inv, mine.reshape(t, k))
    h = _act(cfg.act)(_grouped_matmul(xs, p["w_gate"], sizes)) \
        * _grouped_matmul(xs, p["w_up"], sizes)
    ys = _permute(_grouped_matmul(h, p["w_down"], sizes), inv, order)
    ys = jnp.where(mine[:, None], ys, 0).reshape(t, k, d)
    out = jnp.sum(ys.astype(jnp.float32) * gate[..., None], axis=1)
    return out.astype(x.dtype).reshape(b, l, d), aux


def moe(p, cfg: ModelConfig, x, stacked: int = 1):
    """x: (B, L, D) → (out, aux_loss).  Where the layer runs alone on its
    device (``_unpartitioned``, no "model" axis larger than 1) the
    dropless grouped path; else, under a mesh with a "model" axis (and
    enough tokens), the expert-parallel shard_map path, or the capacity
    dispatch.  Records its path for the ``stacked`` layers this trace
    stands for (``compile_cache.moe_paths``)."""
    mesh = compat.get_abstract_mesh()
    meshed = mesh is not None and not mesh.empty
    with jax.named_scope(MOE_SCOPE):
        if _unpartitioned() and (not meshed
                                 or _axsize(mesh, "model") == 1):
            record_moe_path("grouped", stacked)
            out, aux = _moe_grouped(p, cfg, x)
        else:
            if cfg.held_experts is not None or cfg.frozen_router:
                raise ValueError(
                    f"{cfg.name}: a share of the experts and a frozen "
                    f"router run only where the layer runs alone on its "
                    f"device")
            record_moe_path("capacity", stacked)
            use_ep = (meshed and "model" in mesh.axis_names
                      and cfg.num_experts_padded
                      % _axsize(mesh, "model") == 0
                      and x.shape[0] * x.shape[1] >= 4096)
            if use_ep:
                out, aux = _moe_ep(p, cfg, x, mesh)
            else:
                out, aux = _moe_dense(p, cfg, x)
    if "shared" in p:
        out = out + mlp(p["shared"], cfg, x)
    return out, aux
