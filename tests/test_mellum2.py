"""Mellum2's mechanisms in the program: a period of sliding-window and
full layers scanned with static windows (the flash kernels take both),
YaRN RoPE on the full layers, and the dropless grouped expert layer over
a device's share of the experts, with its grouped-matmul kernels."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import FULL_ATTENTION, ModelConfig, Yarn
from repro.kernels import ops
from repro.kernels.grouped_matmul import grouped_matmul
from repro.launch.compile_cache import attention_paths, moe_paths
from repro.models import layers as L
from repro.models import transformer as T
from repro.train.losses import lm_loss


def _tiny(**kw):
    """A tiny Mellum2: d 64, the S,S,S,F period twice, window 8, 8
    experts top-2."""
    return dataclasses.replace(
        get_config("mellum2-12b-a2.5b"), **{
            "num_layers": 8, "d_model": 64, "num_heads": 4,
            "num_kv_heads": 2, "head_dim": 16, "moe_d_ff": 32,
            "vocab_size": 128, "num_experts": 8, "top_k": 2,
            "sliding_window": 8,
            "global_rope_yarn": Yarn(16.0, 16, 32.0, 1.0,
                                     1.2772588722239782), **kw})


def _loss_grad(cfg, toks):
    def loss(p):
        logits, aux = T.forward(p, cfg, tokens=toks, remat=True)
        return lm_loss(logits, toks, aux)
    return jax.jit(jax.value_and_grad(loss))


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------
def test_published_sizes_and_pattern():
    cfg = get_config("mellum2-12b-a2.5b")
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim) == (2304, 32, 4, 128)
    assert (cfg.num_experts, cfg.top_k, cfg.expert_d_ff,
            cfg.num_shared_experts) == (64, 8, 896, 0)
    pattern = cfg.window_pattern()
    assert [s.window for s in pattern] == [1024, 1024, 1024, FULL_ATTENTION]
    assert [s.rope_yarn is not None for s in pattern] == [False] * 3 + [True]
    assert {s.rope_theta for s in pattern} == {500_000.0}
    assert cfg.experts_held == 64
    share = cfg.with_expert_share(8, 16)
    assert (share.experts_held, share.first_held_expert) == (8, 16)
    with pytest.raises(ValueError, match="not a share"):
        cfg.with_expert_share(8, 60)


def test_yarn_inv_freq_at_published_numbers():
    """transformers' ``_compute_yarn_parameters`` at θ 5e5, Dh 128, ×16
    over 8192, β 32/1: the ramp runs from dimension pair 18 to 35; below
    it θ^(-2i/d) is kept, above it divided by 16; cos and sin carry
    1.2773."""
    y = get_config("mellum2-12b-a2.5b").global_rope_yarn
    theta, d = 500_000.0, 128
    low = math.floor(d * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(d * math.log(8192 / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (18, 35)
    i = np.arange(d // 2)
    extrap = theta ** (-2.0 * i / d)
    m = 1 - np.clip((i - low) / (high - low), 0, 1)
    want = extrap / 16 * (1 - m) + extrap * m
    np.testing.assert_allclose(y.inv_freq(theta, d), want, rtol=1e-12)
    assert y.inv_freq(theta, d)[low] == extrap[low]
    assert y.inv_freq(theta, d)[high] == extrap[high] / 16
    assert y.scale == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)


def test_yarn_scales_the_full_layer_rope():
    """A full layer's q·k carries attention_factor²: at position 0 RoPE
    rotates nothing, so q and k come out scaled by 1.2773 each."""
    y = get_config("mellum2-12b-a2.5b").global_rope_yarn
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 2, 128))
    pos = jnp.zeros((1, 1), jnp.int32)
    np.testing.assert_allclose(L.rope(x, pos, 500_000.0, y),
                               y.scale * x, rtol=1e-6)
    np.testing.assert_allclose(L.rope(x, pos, 500_000.0), x, rtol=1e-6)


# ---------------------------------------------------------------------------
# the window's edge: a key window − 1 back is seen, one window back is not
# ---------------------------------------------------------------------------
def _edge(attend, l, w, back):
    """Output at the last query of uniform attention (q = 0) over values
    that are 1 only at the key ``back`` positions behind it."""
    q = jnp.zeros((1, l, 1, 128))
    v = jnp.zeros((1, l, 1, 128)).at[:, l - 1 - back].set(1.0)
    return float(attend(q, jnp.zeros_like(q), v, w)[0, l - 1, 0, 0])


@pytest.mark.parametrize("path", ["flash", "banded", "dense"])
def test_window_edge(path):
    cfg = ModelConfig(num_heads=1, num_kv_heads=1)
    l, w = 2048, 1024

    def attend(q, k, v, w):
        if path == "flash":  # the kernel, interpreted
            return L._sdpa_flash(q, k, v, w)
        if path == "banded":
            return L._sdpa_banded(cfg, q, k, v, w)
        i, j = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
        return L._sdpa(cfg, q, k, v, ((j <= i) & (i - j < w))[None, None])

    assert _edge(attend, l, w, w - 1) == pytest.approx(1 / w, rel=1e-5)
    assert _edge(attend, l, w, w) == 0.0


@pytest.mark.parametrize("l,window,block", [(640, 200, 128), (512, 128, 128),
                                            (384, 256, 128)])
@pytest.mark.parametrize("groups", [1, 2])
def test_window_band_grad_matches_reference(l, window, block, groups, rng):
    """Under a window the kernels walk only the band of blocks the window
    reaches; forward and backward equal the dense reference's."""
    from repro.kernels import ref

    kv = 2
    q = jax.random.normal(rng, (1, kv * groups, l, 64))
    k, v = (jax.random.normal(jax.random.fold_in(rng, i), (1, kv, l, 64))
            for i in (1, 2))
    do = jax.random.normal(jax.random.fold_in(rng, 3), q.shape)

    def run(fn):
        o, vjp = jax.vjp(fn, q, k, v)
        return (o,) + vjp(do)

    got = run(lambda q, k, v: ops.flash_attention(
        q, k, v, window=window, block_q=block, block_k=block))
    want = run(lambda q, k, v: ref.flash_attention_ref(
        q, jnp.repeat(k, groups, 1), jnp.repeat(v, groups, 1),
        window=window))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# the period scan
# ---------------------------------------------------------------------------
def test_period_stack_takes_the_kernels(compiled_kernels):
    """Tracing a tiny Mellum2's loss: every attention layer takes the
    flash kernel (window layers the ``window`` kernels), every expert
    layer the grouped path with its grouped matmuls.  Tracing only."""
    import re

    cfg = _tiny()
    params = jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0),
                                                 cfg))
    toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    a0, m0 = attention_paths(), moe_paths()

    def loss(p, t):
        logits, aux = T.forward(p, cfg, tokens=t, remat=True)
        return lm_loss(logits, t, aux)

    text = str(jax.make_jaxpr(jax.value_and_grad(loss))(params, toks))
    a1, m1 = attention_paths(), moe_paths()
    assert {k: a1[k] - a0[k] for k in a1} == {"flash": 8, "dense": 0}
    assert {k: m1[k] - m0[k] for k in m1} == {"grouped": 8, "capacity": 0}
    names = set(re.findall(r"(flash_attention_\w+|expert_t?gmm)\b", text))
    assert names == {"flash_attention_fwd", "flash_attention_dq",
                     "flash_attention_dkv", "flash_attention_window_fwd",
                     "flash_attention_window_dq",
                     "flash_attention_window_dkv", "expert_gmm",
                     "expert_tgmm"}, names


@pytest.mark.parametrize("layers", [8, 6])
def test_kernel_path_matches_jnp_path(monkeypatch, layers):
    """A tiny Mellum2 (a remainder of two layers after the scanned periods
    where ``layers`` is 6) through the flash and grouped-matmul kernels
    (interpreted) equals its jnp path (banded window attention,
    ``ragged_dot``) in f32: loss and every gradient."""
    cfg = _tiny(num_layers=layers).with_expert_share(4, 2)
    params = T.init_model(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 128)
    loss_j, grads_j = _loss_grad(cfg, toks)(params)
    monkeypatch.setattr(L, "_takes_flash", lambda cfg, plain: plain)
    monkeypatch.setattr(L, "_takes_gmm", lambda: True)
    loss_k, grads_k = _loss_grad(cfg, toks)(params)
    assert abs(float(loss_k) - float(loss_j)) <= 1e-6 * abs(float(loss_j))
    for gk, gj in zip(jax.tree.leaves(grads_k), jax.tree.leaves(grads_j)):
        assert np.linalg.norm(np.asarray(gk - gj)) <= \
            1e-5 * np.linalg.norm(np.asarray(gj))


def test_period_stack_matches_unscanned_and_prefill():
    """The scanned periods equal the same stack unrolled layer by layer,
    and prefill's logits equal the forward's."""
    cfg = _tiny(num_layers=6)
    params = T.init_model(jax.random.PRNGKey(3), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 128)
    scanned, _ = T.forward(params, cfg, tokens=toks)
    unrolled, _ = T.forward(params, dataclasses.replace(
        cfg, scan_layers=False), tokens=toks)
    np.testing.assert_allclose(np.asarray(scanned), np.asarray(unrolled),
                               atol=2e-5, rtol=2e-5)
    pf, cache = T.prefill(params, cfg, tokens=toks)
    np.testing.assert_allclose(np.asarray(pf), np.asarray(scanned),
                               atol=2e-5, rtol=2e-5)
    assert cache["0"]["k"].shape[:3] == (6, 2, 32)


def test_stack_adds_every_layers_aux(monkeypatch):
    """The walker sums the aux loss of every layer of a superblock (a
    hybrid one holds several expert layers), scanned and unscanned."""
    cfg = dataclasses.replace(get_config("jamba-1.5-large-398b").reduced(),
                              num_layers=16)
    moe = L.moe
    monkeypatch.setattr(L, "moe", lambda p, c, x, stacked=1: (
        moe(p, c, x, stacked)[0], jnp.ones((), jnp.float32)))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    n_moe = sum(s.ffn == "moe" for s in cfg.superblock()[0]) \
        * cfg.superblock()[1]
    assert n_moe == 8
    for scan in (True, False):
        c = dataclasses.replace(cfg, scan_layers=scan)
        _, aux = T.forward(params, c, tokens=toks)
        assert float(aux) == n_moe


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sizes", [(100, 0, 150, 134), (0, 0, 0, 384),
                                   (128, 128, 128, 0), (7, 300, 1, 3)])
def test_grouped_matmul_matches_ragged_dot(sizes, rng):
    """The kernels (interpreted) against ``jax.lax.ragged_dot``, forward
    and both gradients, on the rows of the groups they hold: empty
    groups, groups that straddle tiles, and a last group held
    elsewhere."""
    m, k, n, g = 384, 256, 128, 3
    lhs = jax.random.normal(rng, (m, k))
    rhs = jax.random.normal(jax.random.fold_in(rng, 1), (g, k, n))
    gs = jnp.asarray(sizes, jnp.int32)
    held = int(gs[:g].sum())
    mask = (jnp.arange(m) < held)[:, None]
    weight = jnp.cos(jnp.arange(n, dtype=jnp.float32))

    def loss(fn):
        return lambda a, b: jnp.sum(jnp.where(mask, fn(a, b), 0) * weight)

    out = grouped_matmul(lhs, rhs, gs)
    want = jax.lax.ragged_dot(lhs, rhs, gs[:g])
    np.testing.assert_allclose(np.asarray(out[:held]),
                               np.asarray(want[:held]), atol=1e-4, rtol=1e-5)
    got = jax.grad(loss(lambda a, b: grouped_matmul(a, b, gs)),
                   argnums=(0, 1))(lhs, rhs)
    ref = jax.grad(loss(lambda a, b: jax.lax.ragged_dot(a, b, gs[:g])),
                   argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(got[0][:held]),
                               np.asarray(ref[0][:held]), atol=1e-4,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(ref[1]),
                               atol=1e-3, rtol=1e-5)


def _moe_cfg(**kw):
    return ModelConfig(d_model=64, num_experts=8, top_k=2, moe_d_ff=32,
                       expert_pad_to=1, **kw)


def test_grouped_equals_capacity_when_nothing_overflows(rng):
    """With room for every assignment, the capacity dispatch computes what
    the dropless layer does: outputs, aux loss and gradients."""
    cfg = _moe_cfg(capacity_factor=8.0)
    p = L.init_moe(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 64, 64))

    def loss(fn):
        def f(p, x):
            out, aux = fn(p, cfg, x)
            return jnp.sum(out * jnp.sin(out)) + aux
        return jax.value_and_grad(f, argnums=(0, 1))

    (lg, gg), (lc, gc) = loss(L._moe_grouped)(p, x), \
        loss(L._moe_dense)(p, x)
    assert abs(float(lg) - float(lc)) <= 1e-5 * abs(float(lc))
    for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


def test_capacity_drops_where_grouped_does_not(rng):
    """At a capacity factor that overflows, the capacity path drops
    assignments and the grouped path keeps them all."""
    cfg = _moe_cfg(capacity_factor=0.25)
    p = L.init_moe(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 64, 64))
    full, _ = L._moe_grouped(p, dataclasses.replace(cfg,
                                                    capacity_factor=8.0), x)
    np.testing.assert_allclose(np.asarray(L._moe_grouped(p, cfg, x)[0]),
                               np.asarray(full))
    assert float(jnp.max(jnp.abs(L._moe_dense(p, cfg, x)[0] - full))) > 0.1


def test_expert_shares_sum_to_the_whole_layer(rng):
    """Four devices holding two experts each (routing over all eight)
    compute parts that add up to the layer that holds them all."""
    cfg = _moe_cfg()
    p = L.init_moe(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 32, 64))
    whole, _ = L.moe(p, cfg, x)
    parts = []
    for first in range(0, 8, 2):
        share = cfg.with_expert_share(2, first)
        ps = dict(p, **{w: p[w][first:first + 2]
                        for w in ("w_gate", "w_up", "w_down")})
        assert L.init_moe(rng, share)["w_gate"].shape == ps["w_gate"].shape
        parts.append(L.moe(ps, share, x)[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)


def test_frozen_router_passes_no_gradient_through_the_gates(rng):
    """A frozen router routes and weighs as a learning one does, but no
    gradient reaches the router, and the input's gradient is only what
    flows through the experts."""
    fn = L._moe_grouped
    cfg = _moe_cfg(capacity_factor=8.0, router_aux_coef=0.0)
    p = L.init_moe(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 64, 64))

    def grads(c):
        def f(p, x):
            out, aux = fn(p, c, x)
            return jnp.sum(out * jnp.sin(out)) + aux
        return jax.value_and_grad(f, argnums=(0, 1))(p, x)

    (l_learn, (gp_learn, gx_learn)), (l_frozen, (gp_frozen, gx_frozen)) = \
        grads(cfg), grads(dataclasses.replace(cfg, frozen_router=True))
    assert float(l_frozen) == float(l_learn)
    assert float(jnp.abs(gp_frozen["router"]).max()) == 0.0
    assert float(jnp.abs(gp_learn["router"]).max()) > 0.0
    for w in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(np.asarray(gp_frozen[w]),
                                      np.asarray(gp_learn[w]))
    assert float(jnp.max(jnp.abs(gx_frozen - gx_learn))) > 0.0


def test_moe_path_is_recorded_per_layer():
    """Under a mesh with no "model" axis the layer is the grouped one, and
    a trace records it once for each layer it stands for."""
    from jax.sharding import Mesh

    from repro.core.jax_compat import set_mesh

    cfg = _moe_cfg().with_expert_share(2, 0)
    p = jax.eval_shape(lambda: L.init_moe(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((1, 8, 64), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("pod", "data"))
    m0 = moe_paths()
    with set_mesh(mesh):
        jax.eval_shape(lambda p, x: L.moe(p, cfg, x, stacked=3), p, x)
    assert moe_paths()["grouped"] - m0["grouped"] == 3
    assert moe_paths()["capacity"] == m0["capacity"]
