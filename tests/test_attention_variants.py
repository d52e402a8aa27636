"""Attention-path equivalence tests: banded vs dense-masked, cp vs tp,
decode grouped vs full."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models.layers import _sdpa, _sdpa_banded, _sdpa_decode


@pytest.mark.parametrize("l,w", [(256, 64), (512, 128), (256, 32)])
@pytest.mark.parametrize("kv", [1, 2, 4])
def test_banded_equals_dense_masked(l, w, kv, rng):
    cfg = ModelConfig(num_heads=4, num_kv_heads=kv)
    b, h, dh = 2, 4, 32
    q = jax.random.normal(rng, (b, l, h, dh))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, l, kv, dh))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, l, kv, dh))
    i = jnp.arange(l)[:, None]
    j = jnp.arange(l)[None, :]
    mask = ((j <= i) & (i - j < w))[None, None]
    dense = _sdpa(cfg, q, k, v, mask)
    banded = _sdpa_banded(cfg, q, k, v, w)
    np.testing.assert_allclose(np.asarray(banded), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_banded_with_softcap(rng):
    cfg = ModelConfig(num_heads=2, num_kv_heads=2, attn_logit_softcap=30.0)
    b, l, h, dh, w = 1, 256, 2, 16, 64
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (b, l, h, dh))
               for i in range(3))
    i_ = jnp.arange(l)[:, None]
    j_ = jnp.arange(l)[None, :]
    mask = ((j_ <= i_) & (i_ - j_ < w))[None, None]
    np.testing.assert_allclose(
        np.asarray(_sdpa_banded(cfg, q, k, v, w)),
        np.asarray(_sdpa(cfg, q, k, v, mask)), atol=2e-5, rtol=2e-5)


def test_decode_grouped_equals_expanded(rng):
    """The grouped decode einsum ≡ expanded full attention on one row."""
    cfg = ModelConfig(num_heads=4, num_kv_heads=2)
    b, s, h, kv, dh = 2, 64, 4, 2, 16
    q = jax.random.normal(rng, (b, 1, h, dh))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, s, kv, dh))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, s, kv, dh))
    pos = 40
    j = jnp.arange(s)[None, None, :]
    mask = j <= pos
    got = _sdpa_decode(cfg, q, k, v, mask[:, None])
    want = _sdpa(cfg, q, k, v, mask[:, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_gemma_window_pattern():
    """gemma3's 5:1 local:global layout survives the config machinery."""
    from repro.configs import get_config

    cfg = get_config("gemma3-1b")
    windows, thetas = cfg.layer_windows()
    assert windows.shape == (26, 1)
    globals_ = [i for i in range(26) if windows[i, 0] == -1]
    assert globals_ == [5, 11, 17, 23]
    assert all(windows[i, 0] == 512 for i in range(26) if i not in globals_)
    assert thetas[5, 0] == 1_000_000.0 and thetas[0, 0] == 10_000.0


# ---------------------------------------------------------------------------
# which path full-sequence attention takes (flash kernel vs jnp _sdpa)
# ---------------------------------------------------------------------------
def _tiny(arch="qwen2-1.5b", **kw):
    import dataclasses

    from repro.configs import get_config

    return dataclasses.replace(get_config(arch).reduced(), **kw)


def _loss_grad(cfg, positions=None):
    from repro.models import transformer as T
    from repro.train.losses import lm_loss

    params = jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg))
    toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)

    def loss(p, t):
        logits, aux = T.forward(p, cfg, tokens=t, positions=positions,
                                remat=True)
        return lm_loss(logits, t, aux)

    return jax.value_and_grad(loss), (params, toks)


def _cross_attention():
    from repro.models import layers as L

    cfg = _tiny()
    p = L.init_attention(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((2, 128, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(128), (2, 128))

    def f(p, x):
        return L.attention(p, cfg, x, pos, -1, cfg.rope_theta, memory=x,
                           plain_causal=True)[0]

    return f, (p, x)


@pytest.mark.parametrize("case,paths", [
    ("qwen2", {"flash": 2, "dense": 0}),
    ("sliding_window", {"flash": 0, "dense": 2}),
    ("cp", {"flash": 0, "dense": 2}),
    ("given_positions", {"flash": 0, "dense": 2}),
    ("cross_attention", {"flash": 0, "dense": 1}),
    ("interpret_mode", {"flash": 0, "dense": 2}),
])
def test_attention_path_dispatch(case, paths, request):
    """Tracing a loss (or one cross-attention call) records one path per
    attention layer, and only the kernel's exact case takes the kernel:
    arange positions, no window, whole heads, compiled kernels.  Tracing
    only: nothing is lowered."""
    import re

    from repro.launch.compile_cache import attention_paths

    if case != "interpret_mode":
        request.getfixturevalue("compiled_kernels")
    if case == "cross_attention":
        fn, args = _cross_attention()
    elif case == "sliding_window":
        fn, args = _loss_grad(_tiny("gemma3-1b"))
    elif case == "cp":
        fn, args = _loss_grad(_tiny(sharding_mode="cp"))
    elif case == "given_positions":
        fn, args = _loss_grad(_tiny(), jnp.broadcast_to(jnp.arange(128),
                                                        (2, 128)))
    else:
        fn, args = _loss_grad(_tiny())
    before = attention_paths()
    text = str(jax.make_jaxpr(fn)(*args))
    after = attention_paths()
    assert {k: after[k] - before[k] for k in paths} == paths
    kernels = set(re.findall(r"flash_attention_(?:fwd|dq|dkv)", text))
    want = {"flash_attention_fwd", "flash_attention_dq",
            "flash_attention_dkv"} if paths["flash"] else set()
    assert kernels == want


def test_flash_path_matches_dense_model(monkeypatch):
    """A tiny qwen2's loss and gradients through the flash path (kernels in
    interpret mode) equal the dense path's in f32."""
    from repro.launch.compile_cache import attention_paths
    from repro.models import layers as L
    from repro.models import transformer as T

    cfg = _tiny()
    fn, (_, toks) = _loss_grad(cfg)
    params = T.init_model(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), toks.shape, 0,
                              cfg.vocab_size)
    loss_d, grads_d = jax.jit(fn)(params, toks)
    monkeypatch.setattr(L, "_takes_flash", lambda cfg, plain: plain)
    before = attention_paths()["flash"]
    fn, _ = _loss_grad(cfg)  # a new function: traced anew
    loss_f, grads_f = jax.jit(fn)(params, toks)
    assert attention_paths()["flash"] - before == cfg.num_layers
    assert abs(float(loss_f) - float(loss_d)) <= 1e-6 * abs(float(loss_d))
    for gf, gd in zip(jax.tree.leaves(grads_f), jax.tree.leaves(grads_d)):
        assert np.linalg.norm(np.asarray(gf - gd)) <= \
            1e-5 * np.linalg.norm(np.asarray(gd))
