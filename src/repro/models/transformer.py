"""Model assembly: decoder-only LM and encoder-decoder, built from
ModelConfig super-blocks and executed as ``lax.scan`` over stacked layer
parameters (compile-time O(1) in depth).

Public API:
    init_model(key, cfg)                       → params
    forward(params, cfg, tokens/embeds, ...)   → (logits, aux)   [training]
    init_cache(cfg, batch, max_seq, dtype)     → cache pytree
    decode_step(params, cfg, token, pos, cache, memory) → (logits, cache)
    encode(params, cfg, embeds/tokens)         → memory            [enc-dec]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import FULL_ATTENTION, LayerSpec, ModelConfig
from repro.launch.sharding import BATCH, MODEL, seq_ax, shard
from repro.models import layers as L
from repro.models import ssm as S


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------
def _init_layer(key, cfg: ModelConfig, spec: LayerSpec, cross: bool):
    ks = jax.random.split(key, 8)
    pdt = jnp.dtype(cfg.param_dtype)
    p = {"pre_norm": L.init_rms_norm(cfg.d_model, pdt)}
    if spec.mixer == "attn":
        p["attn"] = L.init_attention(ks[0], cfg)
    elif spec.mixer == "mamba":
        p["mamba"] = S.init_mamba(ks[0], cfg)
    elif spec.mixer == "mlstm":
        p["mlstm"] = S.init_mlstm(ks[0], cfg)
    elif spec.mixer == "slstm":
        p["slstm"] = S.init_slstm(ks[0], cfg)
    if cross:
        p["cross_norm"] = L.init_rms_norm(cfg.d_model, pdt)
        p["cross_attn"] = L.init_attention(ks[1], cfg, cross=True)
    if spec.ffn != "none":
        p["ffn_norm"] = L.init_rms_norm(cfg.d_model, pdt)
        if spec.ffn == "moe":
            p["moe"] = L.init_moe(ks[2], cfg)
        else:
            p["mlp"] = L.init_mlp(ks[2], cfg)
    return p


def _apply_layer(p, cfg, spec, h, positions, window, theta, cache, cache_pos,
                 memory, causal=True, collect_cache=False, block_tables=None,
                 paged_kernel=False, plain_causal=False, stacked=1):
    """One (mixer → [cross] → ffn) layer. Returns (h, new_cache, aux).
    ``plain_causal`` and ``stacked`` are static: see ``L.attention``; so
    is ``spec.rope_yarn``."""
    aux = jnp.zeros((), jnp.float32)
    x = L.rms_norm(h, p["pre_norm"], cfg.norm_eps)
    if spec.mixer == "attn":
        if block_tables is not None:
            out, new_cache = L.attention_paged(
                p["attn"], cfg, x, positions, window, theta, cache,
                block_tables, use_kernel=paged_kernel, yarn=spec.rope_yarn)
        else:
            out, new_cache = L.attention(
                p["attn"], cfg, x, positions, window, theta, cache=cache,
                cache_pos=cache_pos, causal=causal,
                collect_cache=collect_cache, plain_causal=plain_causal,
                stacked=stacked, yarn=spec.rope_yarn)
    elif spec.mixer == "mamba":
        out, new_cache = S.mamba(p["mamba"], cfg, x, cache=cache,
                                 collect_cache=collect_cache)
    elif spec.mixer == "mlstm":
        out, new_cache = S.mlstm(p["mlstm"], cfg, x, cache=cache,
                                 collect_cache=collect_cache)
    elif spec.mixer == "slstm":
        out, new_cache = S.slstm(p["slstm"], cfg, x, cache=cache,
                                 collect_cache=collect_cache)
    else:
        out, new_cache = jnp.zeros_like(h), cache
    h = h + out

    if "cross_attn" in p and memory is not None:
        x = L.rms_norm(h, p["cross_norm"], cfg.norm_eps)
        out, _ = L.attention(p["cross_attn"], cfg, x, positions, window,
                             theta, memory=memory, stacked=stacked)
        h = h + out

    if spec.ffn != "none":
        x = L.rms_norm(h, p["ffn_norm"], cfg.norm_eps)
        if spec.ffn == "moe":
            out, aux = L.moe(p["moe"], cfg, x, stacked=stacked)
        else:
            out = L.mlp(p["mlp"], cfg, x)
        h = h + out
    return h, new_cache, aux


def _init_layer_cache(cfg, spec, batch, max_seq, dtype):
    if spec.mixer == "attn":
        return L.init_attn_cache(cfg, batch, max_seq, dtype)
    if spec.mixer == "mamba":
        return S.init_mamba_cache(cfg, batch, dtype)
    if spec.mixer == "mlstm":
        return S.init_mlstm_cache(cfg, batch, dtype)
    if spec.mixer == "slstm":
        return S.init_slstm_cache(cfg, batch, dtype)
    return {}


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------
def init_model(key, cfg: ModelConfig):
    specs, repeat = cfg.superblock()
    pdt = jnp.dtype(cfg.param_dtype)
    k_emb, k_stack, k_enc, k_head = jax.random.split(key, 4)

    def init_superblock(k):
        ks = jax.random.split(k, len(specs))
        return {str(i): _init_layer(ks[i], cfg, spec, cross=cfg.is_encoder_decoder)
                for i, spec in enumerate(specs)}

    params = {
        "embed": L.dense_init(k_emb, (cfg.vocab_size, cfg.d_model), pdt),
        "stack": jax.vmap(init_superblock)(jax.random.split(k_stack, repeat)),
        "final_norm": L.init_rms_norm(cfg.d_model, pdt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, (cfg.d_model, cfg.vocab_size), pdt)
    if cfg.is_encoder_decoder:
        enc_spec = LayerSpec(mixer="attn", ffn="mlp")

        def init_enc_layer(k):
            return _init_layer(k, cfg, enc_spec, cross=False)

        params["encoder"] = {
            "stack": jax.vmap(init_enc_layer)(
                jax.random.split(k_enc, cfg.num_encoder_layers)),
            "final_norm": L.init_rms_norm(cfg.d_model, pdt),
        }
    return params


# ---------------------------------------------------------------------------
# stack traversal (shared by training forward and decode)
# ---------------------------------------------------------------------------
def _run_stack(params, cfg: ModelConfig, h, positions, cache, cache_pos,
               memory, remat=False, collect_cache=False, block_tables=None,
               paged_kernel=False, arange_positions=False):
    """Every decoder stack, one period at a time.  A period is the
    superblock (``cfg.superblock()``; parameters and cache
    ``{str(i): (repeat, ...)}``) or, for a uniform stack whose windows
    repeat (``cfg.window_pattern()``), P layers of its superblock of one,
    the (layers, ...) stack viewed as (layers // P, P, ...).  Each
    position's layer kind, window and YaRN are static (closed over), so a
    windowed layer reaches ``L.attention`` with its window and takes the
    flash kernel where ``L._takes_flash`` holds.  Whole periods are
    scanned (``cfg.scan_layers``); layers past the last whole period, and
    every period when unscanned (exact cost_analysis for the dry-run), run
    after the scan with a static θ.  The scan of a superblock takes θ as a
    scanned value, one per layer, though each is its spec's: that keeps
    the step of the stacks without windows (qwen2's) the one it was,
    instruction for instruction.  Remat: the superblock whole, or each
    layer of a window period.  ``arange_positions`` (static):
    ``positions`` are 0..L-1 in every row, as ``forward`` and ``prefill``
    make them."""
    pattern = cfg.window_pattern()
    per_layer = pattern is not None
    if not per_layer:
        specs, n_periods = cfg.superblock()
        stack, layer_cache = params["stack"], cache
        whole = n_periods

        def to_sb(tree):
            return tree

        def from_sb(nc):
            return nc

        def rows(r):  # the stack's rows of period r
            return r
    else:
        specs, n = pattern, cfg.num_layers
        period = len(specs)
        whole, n_periods = n // period, -(-n // period)
        stack = params["stack"]["0"]
        layer_cache = cache["0"] if cache is not None else None

        def to_sb(tree):  # (count, ...) → the superblock's {str(i): ...}
            if tree is None:
                return None
            count = jax.tree.leaves(tree)[0].shape[0]
            return {str(i): jax.tree.map(lambda x: x[i], tree)
                    for i in range(count)}

        def from_sb(nc):
            return {"0": jax.tree.map(lambda *c: jnp.stack(c),
                                      *nc.values())}

        def rows(r):
            return slice(r * period, min(n, (r + 1) * period))
    if not cfg.scan_layers:
        whole = 0
    # save the named MoE a2a results across the remat boundary: −2
    # a2a/layer of wire, +~2.7 GB/layer of HBM (see §Perf it. 2)
    policy = jax.checkpoint_policies.save_only_these_names(
        "moe_dispatch", "moe_combine") if cfg.save_moe_a2a else None

    def layer(i, stacked):
        def apply(carry, p_l, theta, c_l):
            h, aux_acc = carry
            h, nc, aux = _apply_layer(
                p_l, cfg, specs[i], h, positions, specs[i].window, theta,
                c_l, cache_pos, memory, collect_cache=collect_cache,
                block_tables=block_tables, paged_kernel=paged_kernel,
                plain_causal=arange_positions, stacked=stacked)
            return (h, aux_acc + aux), (nc if nc is not None else {})
        # a window period's θ is static (its spec's)
        return jax.checkpoint(apply, policy=policy, static_argnums=(2,)) \
            if remat and per_layer else apply

    def period_fn(stacked):
        """One period, or the first positions of one (those ``xs``'
        parameters hold): xs (parameters, θ per position or None for the
        specs' static θ, cache)."""
        fs = [layer(i, stacked) for i in range(len(specs))]

        def run(carry, xs):
            p_sb, th, c_sb = xs
            new = {}
            for i, f in enumerate(fs[:len(p_sb)]):
                carry, new[str(i)] = f(
                    carry, p_sb[str(i)],
                    specs[i].rope_theta if th is None else th[i],
                    c_sb[str(i)] if c_sb is not None else None)
            return carry, new
        return jax.checkpoint(run, policy=policy) \
            if remat and not per_layer else run

    def at(tree, r):
        return None if tree is None else jax.tree.map(lambda x: x[r], tree)

    carry = (h, jnp.zeros((), jnp.float32))
    collected = []  # new caches, each (layers, ...) of the stack
    if whole:
        scanned = period_fn(whole)

        def body(carry, xs):
            p_per, th, c_per = xs
            carry, nc = scanned(carry, (to_sb(p_per), th, to_sb(c_per)))
            return carry, from_sb(nc)

        def periods(x):  # (layers, ...) → (whole, P, ...)
            return x[:whole * period].reshape(
                (whole, period) + x.shape[1:]) if per_layer else x
        thetas = None if per_layer else jnp.asarray(
            [[s.rope_theta for s in specs]] * whole, jnp.float32)
        carry, nc = jax.lax.scan(
            body, carry, (jax.tree.map(periods, stack), thetas,
                          jax.tree.map(periods, layer_cache)))
        collected.append(jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), nc)
            if per_layer else nc)
    for r in range(whole, n_periods):
        carry, nc = period_fn(1)(
            carry, (to_sb(at(stack, rows(r))), None,
                    to_sb(at(layer_cache, rows(r)))))
        nc = from_sb(nc)
        collected.append(nc if per_layer
                         else jax.tree.map(lambda x: x[None], nc))
    h, aux = carry
    new_cache = None
    if cache is not None or collect_cache:
        new_cache = collected[0] if len(collected) == 1 else jax.tree.map(
            lambda *c: jnp.concatenate(c), *collected)
    return h, aux, new_cache


def _logits(params, cfg, h):
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    if cfg.sharding_mode == "cp":
        # gather the (seq-sharded) stream once at the head so the vocab
        # projection stays TP-sharded — otherwise the (V, D) embed/lm_head
        # gradient is replicated and all-reduced densely (§Perf h2 it. 2)
        h = shard(h, BATCH, None, None)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bld,vd->blv", h, params["embed"])
    else:
        logits = jnp.einsum("bld,dv->blv", h, params["lm_head"])
    return shard(logits, BATCH, None, MODEL).astype(jnp.float32)


def _embed(params, cfg, tokens=None, embeds=None):
    if embeds is not None:
        h = embeds.astype(jnp.dtype(cfg.compute_dtype))
    else:
        h = params["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype) if cfg.qk_norm else h
    return shard(h, BATCH, seq_ax(cfg), None)


def _cast_compute(params, cfg: ModelConfig):
    """Weights → ``compute_dtype`` at the forward boundary (DESIGN.md §4).

    Matmuls and activations run in the compute dtype; loss, softmax and
    norm statistics still accumulate in f32 inside the layers.  A no-op
    when ``param_dtype == compute_dtype`` (every preset policy), so the
    f32 path is untouched; with f32 storage + bf16 compute this is the
    classic AMP cast, and AD transposes it so gradients flow back in the
    storage dtype."""
    cdt = jnp.dtype(cfg.compute_dtype)
    if jnp.dtype(cfg.param_dtype) == cdt:
        return params
    return jax.tree.map(
        lambda w: w.astype(cdt)
        if jnp.issubdtype(w.dtype, jnp.floating) else w, params)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, tokens=None, embeds=None, positions=None,
            memory=None, remat=False):
    """Training/prefill forward pass. Returns (logits, aux_loss)."""
    params = _cast_compute(params, cfg)
    h = _embed(params, cfg, tokens, embeds)
    b, l = h.shape[:2]
    arange_positions = positions is None
    if arange_positions:
        positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32), (b, l))
    if cfg.is_encoder_decoder and memory is None:
        raise ValueError("encoder-decoder model requires encoder `memory`")
    h, aux, _ = _run_stack(params, cfg, h, positions, None, None, memory,
                           remat=remat, arange_positions=arange_positions)
    return _logits(params, cfg, h), aux


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None, memory=None,
            last_only=False):
    """Full-sequence forward that also returns a populated decode cache
    (inference prefill).  Returns (logits, cache); ``last_only`` projects
    only the final position (what a real prefill needs — avoids the
    (B, L, V) logits tensor)."""
    params = _cast_compute(params, cfg)
    h = _embed(params, cfg, tokens, embeds)
    b, l = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32), (b, l))
    h, _, cache = _run_stack(params, cfg, h, positions, None, None, memory,
                             collect_cache=True, arange_positions=True)
    if last_only:
        h = h[:, -1:]
    return _logits(params, cfg, h), cache


def encode(params, cfg: ModelConfig, embeds=None, tokens=None):
    """Encoder pass (enc-dec models): bidirectional self-attention stack."""
    params = _cast_compute(params, cfg)
    enc = params["encoder"]
    h = _embed(params, cfg, tokens, embeds)
    b, l = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32), (b, l))
    spec = LayerSpec(mixer="attn", ffn="mlp")

    def body(carry, p_layer):
        h, _ = carry
        h, _, _ = _apply_layer(p_layer, cfg, spec, h, positions,
                               jnp.int32(FULL_ATTENTION),
                               jnp.float32(cfg.rope_theta),
                               None, None, None, causal=False,
                               stacked=cfg.num_encoder_layers)
        return (h, 0.0), None

    (h, _), _ = jax.lax.scan(body, (h, 0.0), enc["stack"])
    return L.rms_norm(h, enc["final_norm"], cfg.norm_eps)


def init_cache(cfg: ModelConfig, batch, max_seq, dtype=None):
    """Decode cache, stacked (repeat, ...) to ride the same scan."""
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    specs, repeat = cfg.superblock()

    def one(spec):
        return _init_layer_cache(cfg, spec, batch, max_seq, dtype)

    sb = {str(i): one(spec) for i, spec in enumerate(specs)}
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (repeat,) + x.shape).copy()
                        if hasattr(x, "shape") else x, sb)


def pad_prefill_cache(cfg: ModelConfig, cache, total):
    """Grow a ``prefill``-collected cache (attention S = prompt length) to
    ``total`` sequence slots.  The pad is keyed off the cache LAYOUT — only
    attention layers' k/v leaves get padded, along their sequence axis
    (axis 2 of the stacked (repeat, B, S, KV, Dh)) — never off shape
    coincidence, so a recurrent leaf whose trailing dim happens to equal
    the prompt length is left alone."""
    specs, _ = cfg.superblock()
    out = dict(cache)
    for i, spec in enumerate(specs):
        if spec.mixer != "attn":
            continue

        def pad(x):
            lp = x.shape[2]
            if lp >= total:
                return x
            w = [(0, 0)] * x.ndim
            w[2] = (0, total - lp)
            return jnp.pad(x, w)

        out[str(i)] = jax.tree.map(pad, cache[str(i)])
    return out


def init_paged_cache(cfg: ModelConfig, num_pages, page_size, dtype=None):
    """Paged decode cache (serving tier): per-layer k/v page pools, stacked
    (repeat, ...) to ride the same layer scan as ``init_cache``.  Physical
    page 0 is the reserved trash page.  Attention-only decoder stacks —
    recurrent mixers keep per-slot dense state and stay on the dense
    engine."""
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    specs, repeat = cfg.superblock()
    if cfg.is_encoder_decoder:
        raise ValueError("paged cache does not support encoder-decoder models")
    for spec in specs:
        if spec.mixer not in ("attn", "none"):
            raise ValueError(
                f"paged cache supports attention-only stacks; got mixer "
                f"{spec.mixer!r} (use the dense DecodeEngine)")
    sb = {str(i): L.init_paged_attn_cache(cfg, num_pages, page_size, dtype)
          for i in range(len(specs))}
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (repeat,) + x.shape).copy(), sb)


def decode_step_paged(params, cfg: ModelConfig, token, pos, cache,
                      block_tables, use_kernel=False):
    """One decode token per slot against the paged cache.  token: (B,)
    int32; pos: (B,) int32 token position per slot, -1 ⇒ idle (the write
    goes to trash page 0, the logits row is garbage — caller masks it);
    block_tables: (B, pages_per_seq) int32.  ``use_kernel`` (static)
    routes attention through the Pallas paged kernel; off, the jnp gather
    path.  Returns (logits (B, V) f32, new_cache)."""
    params = _cast_compute(params, cfg)
    h = _embed(params, cfg, tokens=jnp.maximum(token, 0)[:, None])
    positions = pos[:, None].astype(jnp.int32)
    h, _, new_cache = _run_stack(params, cfg, h, positions, cache, None,
                                 None, block_tables=block_tables,
                                 paged_kernel=use_kernel)
    return _logits(params, cfg, h)[:, 0], new_cache


def prefill_chunk_paged(params, cfg: ModelConfig, tokens, positions, cache,
                        block_tables, last_idx):
    """Chunked batched prefill: consume a whole (B, C) chunk of prompt
    tokens per step, writing KV straight into the pages (write-then-
    attend, so in-chunk causality needs no dense pass).  positions: (B, C)
    int32, -1 ⇒ pad; last_idx: (B,) int32 index of each row's last REAL
    token in the chunk (clamped for idle rows).  Returns (logits (B, V)
    f32 — next-token logits at last_idx, new_cache)."""
    params = _cast_compute(params, cfg)
    h = _embed(params, cfg, tokens=jnp.maximum(tokens, 0))
    h, _, new_cache = _run_stack(params, cfg, h,
                                 positions.astype(jnp.int32), cache, None,
                                 None, block_tables=block_tables)
    b = tokens.shape[0]
    hl = h[jnp.arange(b), jnp.maximum(last_idx, 0)][:, None]
    return _logits(params, cfg, hl)[:, 0], new_cache


def decode_step(params, cfg: ModelConfig, token=None, pos=None, cache=None,
                memory=None, embeds=None):
    """One-token decode against a KV/state cache.  token: (B,) int32;
    pos: scalar int32 write position, or (B,) int32 for ragged slots
    (continuous batching). Returns (logits (B, V), new_cache)."""
    params = _cast_compute(params, cfg)
    if embeds is None:
        h = _embed(params, cfg, tokens=token[:, None])
    else:
        h = embeds
    b = h.shape[0]
    if hasattr(pos, "ndim") and pos.ndim == 1:
        positions = pos[:, None].astype(jnp.int32)
    else:
        positions = jnp.full((b, 1), pos, jnp.int32)
    h, _, new_cache = _run_stack(params, cfg, h, positions, cache, pos, memory)
    return _logits(params, cfg, h)[:, 0], new_cache
