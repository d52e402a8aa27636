"""A family for tests only, brought into a tiny root as a file: a dense
decoder with RMSNorm on each query and key head (QK-norm) before RoPE,
no QKV bias, the embedding scaled by sqrt(D), a SwiGLU MLP and an untied
head, as the program builds a ``ModelConfig`` with ``qk_norm=True`` and
``qkv_bias=False``.

Per layer: q = RoPE(n_q(Wq·n(x))), k = RoPE(n_k(Wk·n(x))), v = Wv·n(x),
x += Wo·attn(q, k, v) with grouped KV heads; x += Wd·(silu(Wg·n(x)) ⊙
Wu·n(x)).  The tree is the program's::

    embed (V, D); final_norm.scale (D,); lm_head (D, V);
    stack["0"]: pre_norm.scale, ffn_norm.scale (L, D);
      attn: wq (L, D, H, Dh), wk, wv (L, D, KV, Dh), wo (L, H, Dh, D),
            q_norm.scale, k_norm.scale (L, Dh);
      mlp: w_gate, w_up (L, D, F), w_down (L, F, D).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import flops
from chipbench.reference import mm, rms_norm, rope


def dims(conf: dict) -> dict:
    c = conf["config"]
    return {"d": c["hidden_size"], "heads": c["num_attention_heads"],
            "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
            "ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "layers": c["num_hidden_layers"], "eps": c["rms_norm_eps"],
            "theta": c["rope_theta"]}


def program_config(conf: dict, dims: dict):
    from repro.configs import get_config

    cfg = get_config(conf["program"]["arch"]).with_depth(dims["layers"])
    want = {"d_model": dims["d"], "num_heads": dims["heads"],
            "num_kv_heads": dims["kv_heads"],
            "resolved_head_dim": dims["head_dim"], "d_ff": dims["ff"],
            "vocab_size": dims["vocab"], "num_layers": dims["layers"],
            "norm_eps": dims["eps"], "rope_theta": dims["theta"],
            "tie_embeddings": False, "qkv_bias": False, "qk_norm": True,
            "act": "silu", "sliding_window": None, "family": "dense"}
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"the program's config differs from the file "
                         f"(program, file): {bad}")
    return cfg


def shapes(dims: dict) -> dict:
    d, h, kv, dh, f, v, n = (dims["d"], dims["heads"], dims["kv_heads"],
                             dims["head_dim"], dims["ff"], dims["vocab"],
                             dims["layers"])
    return {"embed": (v, d), "final_norm": {"scale": (d,)},
            "lm_head": (d, v),
            "stack": {"0": {
                "pre_norm": {"scale": (n, d)},
                "ffn_norm": {"scale": (n, d)},
                "attn": {"wq": (n, d, h, dh), "wk": (n, d, kv, dh),
                         "wv": (n, d, kv, dh), "wo": (n, h, dh, d),
                         "q_norm": {"scale": (n, dh)},
                         "k_norm": {"scale": (n, dh)}},
                "mlp": {"w_gate": (n, d, f), "w_up": (n, d, f),
                        "w_down": (n, f, d)}}}}


def std(path: str, shape: tuple) -> tuple:
    if path.endswith("scale"):
        return 1.0, 0.1
    if path == "embed":
        return 0.0, 0.02
    if path.endswith("wo"):
        return 0.0, (shape[1] * shape[2]) ** -0.5
    return 0.0, shape[0 if path == "lm_head" else 1] ** -0.5


def embed(params, tokens, dims):
    return params["embed"][tokens].astype(jnp.float32) * dims["d"] ** 0.5


def plan(dims: dict) -> list:
    return [(layer, ("stack", "0"), i) for i in range(dims["layers"])]


def layer(p, x, pos, dims, rnd=None):
    eps, a, m = dims["eps"], p["attn"], p["mlp"]
    h = rms_norm(x, p["pre_norm"]["scale"], eps)
    q = rms_norm(mm("sd,dhk->shk", h, a["wq"], rnd), a["q_norm"]["scale"],
                 eps)
    k = rms_norm(mm("sd,dhk->shk", h, a["wk"], rnd), a["k_norm"]["scale"],
                 eps)
    v = mm("sd,dhk->shk", h, a["wv"], rnd)
    q, k = rope(q, pos, dims["theta"]), rope(k, pos, dims["theta"])
    g = dims["heads"] // dims["kv_heads"]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = mm("qhk,shk->hqs", q, k, rnd) * dims["head_dim"] ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = mm("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v, rnd)
    x = x + mm("qhk,hkd->qd", o, a["wo"], rnd)
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
    u = jax.nn.silu(mm("sd,df->sf", h, m["w_gate"], rnd)) \
        * mm("sd,df->sf", h, m["w_up"], rnd)
    return x + mm("sf,fd->sd", u, m["w_down"], rnd)


def head(params, h, dims, rnd=None):
    h = rms_norm(h, params["final_norm"]["scale"], dims["eps"])
    return mm("sd,dv->sv", h, params["lm_head"], rnd)


def _layer_params(dims):
    d, h, kv, dh = dims["d"], dims["heads"], dims["kv_heads"], \
        dims["head_dim"]
    return 2 * d * h * dh + 2 * d * kv * dh + 3 * d * dims["ff"]


def train_flops_per_token(dims: dict, seq_len: int) -> float:
    n = dims["layers"] * _layer_params(dims) + dims["vocab"] * dims["d"]
    attn = dims["layers"] * flops.attn_flops(dims, 1) * (seq_len + 1) / 2
    return 6.0 * n + 3.0 * attn


def prefill_flops(dims: dict, positions, logit_rows: int) -> float:
    positions = list(positions)
    per_layer = 2 * _layer_params(dims) * len(positions) \
        + sum(flops.attn_flops(dims, p + 1) for p in positions)
    return float(dims["layers"] * per_layer
                 + 2 * dims["vocab"] * dims["d"] * logit_rows)


def decode_flops(dims: dict, ctx_lens) -> float:
    ctx_lens = list(ctx_lens)
    per_layer = 2 * _layer_params(dims) * len(ctx_lens) \
        + sum(flops.attn_flops(dims, c) for c in ctx_lens)
    return float(dims["layers"] * per_layer
                 + 2 * dims["vocab"] * dims["d"] * len(ctx_lens))
