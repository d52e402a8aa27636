"""Seeded random weights of a Qwen2-style decoder, made on the device.

The tree is the one the program keeps (layers stacked on a leading axis)::

    embed (V, D); final_norm.scale (D,); lm_head (D, V) when untied;
    stack["0"]: pre_norm.scale, ffn_norm.scale (L, D);
      attn: wq (L, D, H, Dh), wk, wv (L, D, KV, Dh), wo (L, H, Dh, D),
            bq (L, H, Dh), bk, bv (L, KV, Dh);
      mlp: w_gate, w_up (L, D, F), w_down (L, F, D).

Matrices are normal with standard deviation fan_in^-1/2; the embedding
0.02; the QKV biases 0.1; norm scales 1 + 0.1·normal, so that a reference
that dropped a bias or a scale would show it.  Each leaf has its own key,
folded from the seed and the leaf's name, so the same seed gives the same
weights in any program that calls ``init``.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from chipbench.traffic import jax_key

WEIGHT_STREAM = 7


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


def _std(path: str, shape: tuple) -> tuple:
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


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, tree


def _set(tree, path, value):
    keys = path.split("/")
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


def key(seed: int):
    """The weights' key for a seed; pass it to ``init`` as an argument, so
    that one compiled ``init`` serves every seed."""
    return jax_key(seed, WEIGHT_STREAM)


def init(base, dims: dict, dtype=jnp.float32) -> dict:
    """The weights in ``dtype`` from ``key(seed)``; traceable (call it
    inside ``jax.jit`` with the key as an argument)."""
    out = {}
    for path, shape in _leaves(shapes(dims)):
        key = jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF)
        mean, std = _std(path, shape)
        x = mean + std * jax.random.normal(key, shape, jnp.float32)
        _set(out, path, x.astype(dtype))
    return out
