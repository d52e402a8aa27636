"""The Qwen2 family: a dense decoder with grouped KV heads, QKV bias, RoPE,
RMSNorm and a SwiGLU MLP, with a tied or an untied head, from the
published description (arXiv:2407.10671, arXiv:2412.15115).

Per layer: x += Wo·attn(RoPE(Wq·n(x) + bq), RoPE(Wk·n(x) + bk),
Wv·n(x) + bv) with grouped KV heads (query head i reads KV head i // G);
then x += Wd·(silu(Wg·n(x)) ⊙ Wu·n(x)).  n is RMSNorm with a learned
scale (no offset), RoPE rotates the two halves of each head with
θ^(-2i/Dh).  The head is the tied embedding or ``lm_head``.

The parameter tree is the one the program keeps (layers stacked on a
leading axis)::

    embed (V, D); final_norm.scale (D,); lm_head (D, V) when untied;
    stack["0"]: pre_norm.scale, ffn_norm.scale (L, D);
      attn: wq (L, D, H, Dh), wk, wv (L, D, KV, Dh), wo (L, H, Dh, D),
            bq (L, H, Dh), bk, bv (L, KV, Dh);
      mlp: w_gate, w_up (L, D, F), w_down (L, F, D).

Matrices are normal with standard deviation fan_in^-1/2; the embedding
0.02; the QKV biases 0.1; norm scales 1 + 0.1·normal, so that a reference
that dropped a bias or a scale would show it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench import flops
from chipbench.reference import mm, rms_norm, rope


def dims(conf: dict) -> dict:
    """The sizes the rest of the family uses, from the file's published
    keys."""
    c = conf["config"]
    return {
        "d": c["hidden_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim",
                          c["hidden_size"] // c["num_attention_heads"]),
        "ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "layers": c["num_hidden_layers"],
        "eps": c["rms_norm_eps"],
        "theta": c["rope_theta"],
        "tied": c["tie_word_embeddings"],
    }


def program_config(conf: dict, dims: dict):
    """The registered architecture cut to the file's depth, with every
    published size checked against the file."""
    from repro.configs import get_config

    cfg = get_config(conf["program"]["arch"]).with_depth(dims["layers"])
    cfg = dataclasses.replace(cfg, norm_eps=dims["eps"])
    want = {
        "d_model": dims["d"], "num_heads": dims["heads"],
        "num_kv_heads": dims["kv_heads"],
        "resolved_head_dim": dims["head_dim"], "d_ff": dims["ff"],
        "vocab_size": dims["vocab"], "num_layers": dims["layers"],
        "tie_embeddings": dims["tied"], "rope_theta": dims["theta"],
        "qkv_bias": True, "qk_norm": False, "act": "silu",
        "sliding_window": None, "attn_logit_softcap": None,
        "family": "dense",
    }
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"{conf['program']['arch']}: the program's config "
                         f"differs from the file (program, file): {bad}")
    return cfg


def shapes(dims: dict) -> dict:
    d, h, kv, dh, f, v, n = (dims["d"], dims["heads"], dims["kv_heads"],
                             dims["head_dim"], dims["ff"], dims["vocab"],
                             dims["layers"])
    layer = {
        "pre_norm": {"scale": (n, d)},
        "ffn_norm": {"scale": (n, d)},
        "attn": {"wq": (n, d, h, dh), "wk": (n, d, kv, dh),
                 "wv": (n, d, kv, dh), "wo": (n, h, dh, d),
                 "bq": (n, h, dh), "bk": (n, kv, dh), "bv": (n, kv, dh)},
        "mlp": {"w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)},
    }
    tree = {"embed": (v, d), "final_norm": {"scale": (d,)},
            "stack": {"0": layer}}
    if not dims["tied"]:
        tree["lm_head"] = (d, v)
    return tree


def std(path: str, shape: tuple) -> tuple:
    """(mean, std) of a leaf."""
    if path.endswith("scale"):
        return 1.0, 0.1
    if path == "embed":
        return 0.0, 0.02
    if path.split("/")[-1] in ("bq", "bk", "bv"):
        return 0.0, 0.1
    if path.endswith("wo"):
        fan_in = shape[1] * shape[2]
    elif path == "lm_head":
        fan_in = shape[0]
    else:  # stacked (L, fan_in, ...)
        fan_in = shape[1]
    return 0.0, fan_in ** -0.5


# ---------------------------------------------------------------------------
# the reference's model-specific parts
# ---------------------------------------------------------------------------
def embed(params, tokens, dims):
    return params["embed"][tokens].astype(jnp.float32)


def plan(dims: dict) -> list:
    """Each layer as (layer function, path of its stack, index in it)."""
    return [(layer, ("stack", "0"), i) for i in range(dims["layers"])]


def layer(p, x, pos, dims, rnd=None):
    """One decoder layer on one sequence; ``p`` holds this layer's slices
    (any float dtype), x (S, D) float32."""
    eps, theta = dims["eps"], dims["theta"]
    f32 = jnp.float32
    a = p["attn"]
    h = rms_norm(x, p["pre_norm"]["scale"], eps)
    q = mm("sd,dhk->shk", h, a["wq"], rnd) + a["bq"].astype(f32)
    k = mm("sd,dhk->shk", h, a["wk"], rnd) + a["bk"].astype(f32)
    v = mm("sd,dhk->shk", h, a["wv"], rnd) + a["bv"].astype(f32)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    g = dims["heads"] // dims["kv_heads"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = mm("qhk,shk->hqs", q, k, rnd) * dims["head_dim"] ** -0.5
    causal = pos[None, :, None] >= pos[None, None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = mm("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v, rnd)
    x = x + mm("qhk,hkd->qd", o, a["wo"], rnd)
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
    m = p["mlp"]
    u = jax.nn.silu(mm("sd,df->sf", h, m["w_gate"], rnd)) \
        * mm("sd,df->sf", h, m["w_up"], rnd)
    return x + mm("sf,fd->sd", u, m["w_down"], rnd)


def head(params, h, dims, rnd=None):
    h = rms_norm(h, params["final_norm"]["scale"], dims["eps"])
    if dims["tied"]:
        return mm("sd,vd->sv", h, params["embed"], rnd)
    return mm("sd,dv->sv", h, params["lm_head"], rnd)


# ---------------------------------------------------------------------------
# operation counts (the rules are in ``chipbench/flops.py``)
# ---------------------------------------------------------------------------
def layer_matmul_params(dims: dict) -> int:
    d, h, kv, dh, f = (dims["d"], dims["heads"], dims["kv_heads"],
                       dims["head_dim"], dims["ff"])
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    mlp = 3 * d * f
    return attn + mlp


def head_params(dims: dict) -> int:
    return dims["vocab"] * dims["d"]


def train_flops_per_token(dims: dict, seq_len: int) -> float:
    """6·N + 3·L·(attention of the mean query), N = the matrix parameters
    of the layers and the head.  The mean query of a causal sequence of
    ``seq_len`` attends to (seq_len + 1) / 2 keys."""
    n = dims["layers"] * layer_matmul_params(dims) + head_params(dims)
    attn = dims["layers"] * flops.attn_flops(dims, 1) * (seq_len + 1) / 2
    return 6.0 * n + 3.0 * attn


def prefill_flops(dims: dict, positions, logit_rows: int) -> float:
    """A chunk of prompt tokens at ``positions`` (0-based, real tokens
    only), and the vocabulary projection of ``logit_rows`` rows."""
    positions = list(positions)
    per_layer = 2 * layer_matmul_params(dims) * len(positions) \
        + sum(flops.attn_flops(dims, p + 1) for p in positions)
    return float(dims["layers"] * per_layer
                 + 2 * head_params(dims) * logit_rows)


def decode_flops(dims: dict, ctx_lens) -> float:
    """One token for each sequence, whose context holds ``ctx`` tokens
    counting the new one."""
    ctx_lens = list(ctx_lens)
    per_layer = 2 * layer_matmul_params(dims) * len(ctx_lens) \
        + sum(flops.attn_flops(dims, c) for c in ctx_lens)
    return float(dims["layers"] * per_layer
                 + 2 * head_params(dims) * len(ctx_lens))
