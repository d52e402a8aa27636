"""The Mellum2 family: a decoder whose every layer is sparse, with grouped
KV heads, no attention bias, RMSNorm and a softmax router over
fine-grained SwiGLU experts, from the published configuration
(``config.json`` of JetBrains/Mellum2-12B-A2.5B-Instruct).

Per layer: x += Wo·attn(RoPE(Wq·n(x)), RoPE(Wk·n(x)), Wv·n(x)) with
grouped KV heads (query head i reads KV head i // G) and the layer's own
mask and RoPE (``layer_types``):

* ``sliding_attention``: query i sees keys j with 0 ≤ i − j < window;
  RoPE rotates the two halves of each head by θ^(-2i/Dh);
* ``full_attention``: causal; YaRN: each frequency θ^(-2i/Dh) is kept
  where its wavelength fits the original context ``beta_fast`` times or
  more, divided by ``factor`` where ``beta_slow`` times or fewer, with a
  linear ramp between (the ramp's ends floor / ceil of
  Dh·ln(orig / (β·2π)) / (2·ln θ), clamped to [0, Dh − 1]); cos and sin
  both scaled by ``attention_factor``.

Then x += Σ_e w_e(x)·Wd_e(silu(Wg_e·n(x)) ⊙ Wu_e·n(x)): softmax over all
``num_experts`` router outputs, the top ``num_experts_per_tok``, their
gates renormalised to sum to 1 (``norm_topk_prob``), w_e the gate of
expert e where chosen, else 0.  A configuration holds a share of the
experts (``reduced.num_experts``: ``here`` of ``published``, from
``first_expert``): the sum runs over those only, in the program and here
alike; the router keeps every output.  With ``frozen_router`` the
router's product carries no gradient, here and in the program.  The head is the untied
``lm_head`` over the (possibly sliced) vocabulary.

The parameter tree is the program's (layers stacked on a leading axis)::

    embed (V, D); final_norm.scale (D,); lm_head (D, V);
    stack["0"]: pre_norm.scale, ffn_norm.scale (L, D);
      attn: wq (L, D, H, Dh), wk, wv (L, D, KV, Dh), wo (L, H, Dh, D);
      moe: router (L, D, E); w_gate, w_up (L, E_held, D, F),
           w_down (L, E_held, F, D).

Matrices are normal with standard deviation fan_in^-1/2; norm scales
1 + 0.1·normal; the embedding's rows at unit RMS (standard deviation 1).
At 0.02 the residual stream of random weights is dominated by a direction
common to all tokens, and the router then sends a share of the rows to
the held experts that depends on the seed (0.06 to 2.8 times the
expected share, layer by layer, over six seeds at 2048 tokens); at unit
RMS the share starts within 0.94 to 1.06 of it at 8192 tokens.  A router
that learns then moves it: the held experts are the only ones that add
to the output, so the router learns to send them more rows (3 to 4 times
as many within 40 steps at small widths on the CPU); frozen, it stays
within 0.93 to 1.11 over the same steps.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops
from chipbench.reference import mm, rms_norm, rope
from chipbench.spec import SpecError

QUERY_BLOCK = 512  # the reference's attention, a block of queries at a time


def dims(conf: dict) -> dict:
    """The sizes the rest of the family uses, from the file's published
    keys, which it holds at its top level as the catalog's ``config``
    has them; the experts held are ``num_experts`` of
    ``reduced.num_experts.published``."""
    layers = conf["num_hidden_layers"]
    share = conf.get("reduced", {}).get("num_experts")
    full = conf["rope_parameters"]["full_attention"]
    return {
        "family": "mellum2",
        "d": conf["hidden_size"],
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["head_dim"],
        "ff": conf["moe_intermediate_size"],
        "dense_ff": conf["intermediate_size"],
        "experts": share["published"] if share else conf["num_experts"],
        "held": conf["num_experts"],
        "first_expert": conf.get("first_expert", 0),
        "frozen_router": bool(conf.get("frozen_router", False)),
        "top_k": conf["num_experts_per_tok"],
        "vocab": conf["vocab_size"],
        "layers": layers,
        "eps": conf["rms_norm_eps"],
        "window": conf["sliding_window"],
        "types": tuple(conf["layer_types"][:layers]),
        "theta": conf["rope_parameters"]["sliding_attention"]["rope_theta"],
        "full_theta": full["rope_theta"],
        "yarn_factor": full["factor"],
        "yarn_original": full["original_max_position_embeddings"],
        "yarn_beta_fast": full["beta_fast"],
        "yarn_beta_slow": full["beta_slow"],
        "yarn_attention_factor": full["attention_factor"],
    }


def program_config(conf: dict, dims: dict):
    """The registered architecture cut to the file's depth, experts held
    and vocabulary, with every published size checked against the file."""
    from repro.configs import get_config

    arch = conf["program"]["arch"]
    try:
        cfg = get_config(arch)
    except KeyError as e:
        raise SpecError(f"family mellum2: the program registers no "
                        f"architecture {arch!r}") from e
    if not hasattr(cfg, "with_expert_share"):
        raise SpecError("family mellum2: the program's expert layer cannot "
                        "hold a share of the experts")
    cfg = dataclasses.replace(
        cfg.with_depth(dims["layers"]).with_expert_share(
            dims["held"], dims["first_expert"]), vocab_size=dims["vocab"],
        frozen_router=dims["frozen_router"])
    period = dims["types"].index("full_attention") + 1
    want = {
        "d_model": dims["d"], "num_heads": dims["heads"],
        "num_kv_heads": dims["kv_heads"],
        "resolved_head_dim": dims["head_dim"],
        "expert_d_ff": dims["ff"], "d_ff": dims["dense_ff"],
        "num_experts": dims["experts"], "top_k": dims["top_k"],
        "experts_held": dims["held"],
        "first_held_expert": dims["first_expert"],
        "num_shared_experts": 0, "vocab_size": dims["vocab"],
        "num_layers": dims["layers"], "norm_eps": dims["eps"],
        "tie_embeddings": False, "qkv_bias": False, "qk_norm": False,
        "act": "silu", "attn_logit_softcap": None, "family": "moe",
        "sliding_window": dims["window"], "global_every": period,
        "rope_theta": dims["theta"], "global_rope_theta": dims["full_theta"],
        "router_aux_coef": 0.0,
    }
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    y = cfg.global_rope_yarn
    got_yarn = None if y is None else (
        y.factor, y.original_max_position, y.beta_fast, y.beta_slow,
        y.attention_factor)
    want_yarn = (dims["yarn_factor"], dims["yarn_original"],
                 dims["yarn_beta_fast"], dims["yarn_beta_slow"],
                 dims["yarn_attention_factor"])
    if got_yarn != want_yarn:
        bad["global_rope_yarn"] = (got_yarn, want_yarn)
    windows = tuple(s.window for s in cfg.window_pattern())
    want_w = tuple(dims["window"] if t == "sliding_attention" else -1
                   for t in dims["types"][:period])
    if windows != want_w or set(dims["types"]) - {"sliding_attention",
                                                  "full_attention"}:
        bad["layer_types"] = (windows, dims["types"])
    if bad:
        raise ValueError(f"{arch}: the program's config differs from the "
                         f"file (program, file): {bad}")
    return cfg


def shapes(dims: dict) -> dict:
    d, h, kv, dh, f, v, n = (dims["d"], dims["heads"], dims["kv_heads"],
                             dims["head_dim"], dims["ff"], dims["vocab"],
                             dims["layers"])
    e, held = dims["experts"], dims["held"]
    layer = {
        "pre_norm": {"scale": (n, d)},
        "ffn_norm": {"scale": (n, d)},
        "attn": {"wq": (n, d, h, dh), "wk": (n, d, kv, dh),
                 "wv": (n, d, kv, dh), "wo": (n, h, dh, d)},
        "moe": {"router": (n, d, e), "w_gate": (n, held, d, f),
                "w_up": (n, held, d, f), "w_down": (n, held, f, d)},
    }
    return {"embed": (v, d), "final_norm": {"scale": (d,)},
            "lm_head": (d, v), "stack": {"0": layer}}


def std(path: str, shape: tuple) -> tuple:
    """(mean, std) of a leaf."""
    if path.endswith("scale"):
        return 1.0, 0.1
    if path == "embed":
        return 0.0, 1.0
    if path == "lm_head":
        return 0.0, shape[0] ** -0.5
    if path.endswith("wo"):
        return 0.0, (shape[1] * shape[2]) ** -0.5
    if "/moe/w_" in path:  # (L, E_held, fan_in, fan_out)
        return 0.0, shape[2] ** -0.5
    return 0.0, shape[1] ** -0.5  # stacked (L, fan_in, ...)


# ---------------------------------------------------------------------------
# the reference's model-specific parts
# ---------------------------------------------------------------------------
def embed(params, tokens, dims):
    return params["embed"][tokens].astype(jnp.float32)


def plan(dims: dict) -> list:
    """Each layer as (layer function, path of its stack, index in it)."""
    fns = {"sliding_attention": window_layer, "full_attention": full_layer}
    return [(fns[t], ("stack", "0"), i) for i, t in enumerate(dims["types"])]


def yarn_inv_freq(dims: dict) -> np.ndarray:
    """(Dh/2,) float64 inverse frequencies of a full layer."""
    dh, theta = dims["head_dim"], dims["full_theta"]
    orig, factor = dims["yarn_original"], dims["yarn_factor"]

    def ramp_end(beta):
        return dh * math.log(orig / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(ramp_end(dims["yarn_beta_fast"])), 0)
    hi = min(math.ceil(ramp_end(dims["yarn_beta_slow"])), dh - 1)
    i = np.arange(dh // 2, dtype=np.float64)
    base = theta ** (-2.0 * i / dh)
    interp_share = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return base * (1.0 - interp_share) + base / factor * interp_share


def yarn_rope(x, pos, dims):
    """x (S, heads, Dh); pos (S,)."""
    half = x.shape[-1] // 2
    inv = jnp.asarray(yarn_inv_freq(dims), jnp.float32)
    ang = pos[:, None].astype(jnp.float32) * inv
    a = dims["yarn_attention_factor"]
    sin, cos = a * jnp.sin(ang)[:, None], a * jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, pos, window, dims, rnd):
    """Causal attention of one sequence, a block of queries at a time
    (each block recomputed in the backward pass), keys limited to the
    window where there is one.  q (S, H, Dh); k, v (S, H, Dh)."""
    s = q.shape[0]
    bq = min(QUERY_BLOCK, s)
    pad = (-s) % bq
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    qpos = jnp.pad(pos, (0, pad)).reshape(-1, bq)
    span = s if window is None else min(s, window + bq)
    # keys padded in front, so that each block reads a fixed span
    kp = jnp.pad(k, ((span, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((span, 0), (0, 0), (0, 0)))
    kpos = jnp.pad(pos, (span, 0), constant_values=-1)

    @jax.checkpoint
    def block(args):
        qb, qp, start = args
        end = start + bq + span  # keys up to the block's last query
        lo = end - span if window is not None else span
        kb = jax.lax.dynamic_slice_in_dim(kp, lo, span)
        vb = jax.lax.dynamic_slice_in_dim(vp, lo, span)
        kq = jax.lax.dynamic_slice_in_dim(kpos, lo, span)
        sc = mm("qhk,shk->hqs", qb, kb, rnd) * dims["head_dim"] ** -0.5
        see = (kq[None, :] >= 0) & (kq[None, :] <= qp[:, None])
        if window is not None:
            see &= qp[:, None] - kq[None, :] < window
        sc = jnp.where(see[None], sc, -jnp.inf)
        return mm("hqs,shk->qhk", jax.nn.softmax(sc, axis=-1), vb, rnd)

    starts = jnp.arange(0, s + pad, bq)
    out = jax.lax.map(block, (q.reshape(-1, bq, *q.shape[1:]), qpos,
                              starts))
    return out.reshape(-1, *out.shape[2:])[:s]


def _experts(m, h, dims, rnd):
    """The held experts' share of the expert layer for every token of
    h (S, D)."""
    logits = mm("sd,de->se", h, m["router"], rnd)
    if dims["frozen_router"]:
        logits = jax.lax.stop_gradient(logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, dims["top_k"])
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    out = jnp.zeros_like(h)
    for j in range(dims["held"]):
        w = jnp.sum(jnp.where(idx == dims["first_expert"] + j, gate, 0.0),
                    axis=-1)
        u = jax.nn.silu(mm("sd,df->sf", h, m["w_gate"][j], rnd)) \
            * mm("sd,df->sf", h, m["w_up"][j], rnd)
        out = out + w[:, None] * mm("sf,fd->sd", u, m["w_down"][j], rnd)
    return out


def _layer(p, x, pos, dims, rnd, window, rotate):
    a = p["attn"]
    h = rms_norm(x, p["pre_norm"]["scale"], dims["eps"])
    q = rotate(mm("sd,dhk->shk", h, a["wq"], rnd))
    k = rotate(mm("sd,dhk->shk", h, a["wk"], rnd))
    v = mm("sd,dhk->shk", h, a["wv"], rnd)
    g = dims["heads"] // dims["kv_heads"]
    o = _attend(q, jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1),
                pos, window, dims, rnd)
    x = x + mm("qhk,hkd->qd", o, a["wo"], rnd)
    h = rms_norm(x, p["ffn_norm"]["scale"], dims["eps"])
    return x + _experts(p["moe"], h, dims, rnd)


def window_layer(p, x, pos, dims, rnd=None):
    """A sliding-window layer on one sequence; ``p`` holds this layer's
    slices (any float dtype), x (S, D) float32."""
    return _layer(p, x, pos, dims, rnd, dims["window"],
                  lambda t: rope(t, pos, dims["theta"]))


def full_layer(p, x, pos, dims, rnd=None):
    """A full-attention layer with YaRN RoPE on one sequence."""
    return _layer(p, x, pos, dims, rnd, None,
                  lambda t: yarn_rope(t, pos, dims))


def head(params, h, dims, rnd=None):
    h = rms_norm(h, params["final_norm"]["scale"], dims["eps"])
    return mm("sd,dv->sv", h, params["lm_head"], rnd)


# ---------------------------------------------------------------------------
# operation counts (the rules are in ``chipbench/flops.py``)
# ---------------------------------------------------------------------------
def expert_rows(dims: dict) -> float:
    """Expected rows a token gives the held experts: k · held / E."""
    return dims["top_k"] * dims["held"] / dims["experts"]


def layer_matmul_params(dims: dict) -> float:
    """Matrix parameters a token meets in one layer: attention, and the
    held experts' matrices for its expected rows (the router is routing,
    left out)."""
    d, h, kv, dh, f = (dims["d"], dims["heads"], dims["kv_heads"],
                       dims["head_dim"], dims["ff"])
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    return attn + expert_rows(dims) * 3 * d * f


def head_params(dims: dict) -> int:
    return dims["vocab"] * dims["d"]


def keys_seen(dims: dict, kind: str, position: int) -> int:
    """Keys query ``position`` (0-based) attends to in a layer of kind
    ``kind``."""
    if kind == "sliding_attention":
        return min(position + 1, dims["window"])
    return position + 1


def _keys_total(dims: dict, kind: str, seq_len: int) -> int:
    if kind == "sliding_attention":
        w = min(dims["window"], seq_len)
        return w * (w + 1) // 2 + (seq_len - w) * w
    return seq_len * (seq_len + 1) // 2


def train_flops_per_token(dims: dict, seq_len: int) -> float:
    """6·N + 3·Σ_layers (attention of the mean query), N the matrix
    parameters of the layers (held experts at their expected rows) and the
    head; a window layer's query attends to min(i + 1, window) keys."""
    n = dims["layers"] * layer_matmul_params(dims) + head_params(dims)
    attn = sum(flops.attn_flops(dims, 1) * _keys_total(dims, t, seq_len)
               / seq_len for t in dims["types"])
    return 6.0 * n + 3.0 * attn


def prefill_flops(dims: dict, positions, logit_rows: int) -> float:
    """A chunk of prompt tokens at ``positions`` (0-based, real tokens
    only), and the vocabulary projection of ``logit_rows`` rows."""
    positions = list(positions)
    total = 2 * head_params(dims) * logit_rows
    for t in dims["types"]:
        total += 2 * layer_matmul_params(dims) * len(positions) \
            + sum(flops.attn_flops(dims, keys_seen(dims, t, p))
                  for p in positions)
    return float(total)


def decode_flops(dims: dict, ctx_lens) -> float:
    """One token for each sequence, whose context holds ``ctx`` tokens
    counting the new one."""
    return float(sum(prefill_flops(dims, [c - 1], 1) for c in ctx_lens))


def window_attn_flops(dims: dict, rows: int, seq_len: int) -> float:
    """Forward attention of one window layer over ``rows`` sequences:
    4·H·Dh·rows·Σ_i min(i + 1, window), the work of one call of
    ``flash_attention_window_fwd``."""
    return float(flops.attn_flops(dims, 1) * rows
                 * _keys_total(dims, "sliding_attention", seq_len))

