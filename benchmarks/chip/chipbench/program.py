"""The program's model configuration for a configuration file: the
registered architecture cut to the file's depth, with every published
size checked against the file.  A size that differs is an error, not a
silent change."""

from __future__ import annotations

import dataclasses


def model_config(conf: dict, dims: dict):
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


def tree_paths(tree, prefix=""):
    """{path: shape} of a nested dict whose leaves are arrays, shape
    structs or shape tuples."""
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(tree_paths(tree[k], f"{prefix}/{k}" if prefix else k))
    else:
        out[prefix] = tuple(getattr(tree, "shape", tree))
    return out
