"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs the
pure-jnp oracle in ref.py (kernels run in interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,l,d", [
    (1, 1, 128, 64), (2, 3, 256, 64), (1, 2, 300, 128), (2, 1, 64, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(b, h, l, d, dtype, rng):
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i),
                                 (b, h, l, d), dtype) for i in range(3))
    out = ops.flash_attention(q, k, v)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 64, 100])
def test_flash_attention_sliding_window(window, rng):
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i),
                                 (1, 2, 256, 64)) for i in range(3))
    out = ops.flash_attention(q, k, v, window=window)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_noncausal(rng):
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i),
                                 (1, 1, 128, 64)) for i in range(3))
    out = ops.flash_attention(q, k, v, causal=False)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_matches_model_attention(rng):
    """Kernel agrees with the model's dense-masked attention path."""
    from repro.configs.base import ModelConfig
    from repro.models.layers import _sdpa

    cfg = ModelConfig(num_heads=4, num_kv_heads=4)
    b, h, l, d = 2, 4, 128, 64
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i),
                                 (b, l, h, d)) for i in range(3))
    i_ = jnp.arange(l)[:, None]
    j_ = jnp.arange(l)[None, :]
    mask = (j_ <= i_)[None, None]
    dense = _sdpa(cfg, q, k, v, mask)  # (B,L,H,D)
    fl = ops.flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                             v.swapaxes(1, 2)).swapaxes(1, 2)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(dense),
                               atol=3e-5, rtol=3e-5)


def _rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("groups", [1, 2, 6])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("l", [256, 300])  # two blocks of 128; padding
@pytest.mark.parametrize("d", [64, 128])
def test_flash_attention_grad_matches_sdpa(groups, dtype, l, d, rng):
    """Causal GQA through the kernel, forward and ``jax.grad`` w.r.t. q, k
    and v, against the model's dense ``_sdpa`` computed in f32, by the
    relative norm of the difference.  In f32 the kernel agrees to 2e-5; in
    bf16 it is no further from the f32 answer than ``_sdpa`` run in bf16
    itself (the path it replaces)."""
    from repro.configs.base import ModelConfig
    from repro.models.layers import _sdpa

    kv = 1 if groups == 6 else 2
    h = groups * kv
    cfg = ModelConfig(num_heads=h, num_kv_heads=kv)
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (1, l, h, d), dtype)
    k = jax.random.normal(ks[1], (1, l, kv, d), dtype)
    v = jax.random.normal(ks[2], (1, l, kv, d), dtype)
    do = jax.random.normal(ks[3], (1, l, h, d), dtype)
    mask = (jnp.arange(l)[None, :] <= jnp.arange(l)[:, None])[None, None]

    def flash(q, k, v):
        return ops.flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                   v.swapaxes(1, 2), block_q=128,
                                   block_k=128).swapaxes(1, 2)

    def dense(q, k, v):
        return _sdpa(cfg, q, k, v, mask)

    def out_and_grads(f, *args):
        o, vjp = jax.vjp(f, *args[:3])
        return (o,) + vjp(args[3])

    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    want = out_and_grads(dense, *f32)
    got = out_and_grads(flash, q, k, v, do)
    if dtype == jnp.float32:
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            assert _rel_err(a, b) < 2e-5, name
    else:
        base = out_and_grads(dense, q, k, v, do)
        for name, a, c, b in zip(("o", "dq", "dk", "dv"), got, base, want):
            assert a.dtype == dtype, name
            assert _rel_err(a, b) <= _rel_err(c, b), name


# ---------------------------------------------------------------------------
# top-k sparsify
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nb,block,k", [(4, 128, 4), (37, 256, 8), (1, 64, 1),
                                        (8, 512, 32)])
def test_topk_sweep(nb, block, k, rng):
    x = jax.random.normal(rng, (nb, block))
    vals, idx, dense = ops.topk_sparsify(x, k)
    rvals, ridx, rdense = ref.topk_sparsify_ref(x, k)
    # sets of |values| must match (tie order may differ)
    np.testing.assert_allclose(np.sort(np.abs(vals), -1),
                               np.sort(np.abs(rvals), -1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(rdense),
                               atol=1e-6)


def test_topk_dense_is_subset(rng):
    x = jax.random.normal(rng, (8, 128))
    _, _, dense = ops.topk_sparsify(x, 4)
    nz = np.asarray(dense) != 0
    assert nz.sum(axis=1).max() <= 4
    np.testing.assert_allclose(np.asarray(dense)[nz], np.asarray(x)[nz])


# ---------------------------------------------------------------------------
# onebit quant
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nb,block", [(1, 128), (17, 128), (64, 256)])
def test_onebit_sweep(nb, block, rng):
    g = jax.random.normal(rng, (nb, block))
    r = jax.random.normal(jax.random.fold_in(rng, 1), (nb, block)) * 0.1
    s, sc, nr = ops.onebit_quant(g, r)
    rs, rsc, rnr = ref.onebit_quant_ref(g, r)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(rs))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(rsc), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(nr), np.asarray(rnr),
                               rtol=1e-5, atol=1e-6)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_onebit_property_ef_identity(seed):
    """decoded + residual' == input + residual (mass conservation)."""
    key = jax.random.PRNGKey(seed)
    g = jax.random.normal(key, (4, 64))
    r = jnp.zeros((4, 64))
    s, sc, nr = ops.onebit_quant(g, r)
    decoded = np.asarray(s, np.float32) * np.asarray(sc)
    np.testing.assert_allclose(decoded + np.asarray(nr), np.asarray(g),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# fused adam
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [100, 4096, 10_000])
@pytest.mark.parametrize("t", [1, 100])
def test_fused_adam_sweep(n, t, rng):
    p, g, m = (jax.random.normal(jax.random.fold_in(rng, i), (n,))
               for i in range(3))
    v = jnp.abs(jax.random.normal(jax.random.fold_in(rng, 3), (n,)))
    p1, m1, v1 = ops.fused_adam(p, g, m, v, 1e-3, t)
    rp, rm, rv = ref.fused_adam_ref(p, g, m, v, 1e-3, t=t)
    # kernel computes bias-correction powers in f32 on device; ref uses
    # python-float (f64) powers — 1e-8-level differences are expected
    np.testing.assert_allclose(np.asarray(p1), np.asarray(rp),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(rm),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(rv),
                               rtol=1e-4, atol=1e-7)


def test_fused_adam_matches_optimizer(rng):
    """Kernel agrees with the optim/ Adam used by the trainer."""
    from repro.optim import adam

    n = 512
    p = jax.random.normal(rng, (n,))
    g = jax.random.normal(jax.random.fold_in(rng, 1), (n,))
    opt = adam(1e-3)
    st_ = opt.init({"w": p})
    new, st1 = opt.update({"w": g}, st_, {"w": p}, 0)
    p1, m1, v1 = ops.fused_adam(p, g, jnp.zeros(n), jnp.zeros(n), 1e-3, 1)
    np.testing.assert_allclose(np.asarray(new["w"]), np.asarray(p1),
                               rtol=1e-5, atol=1e-6)


# (The adam(fused=True) Optimizer-API parity tests live in
# tests/test_precision.py, which runs without the hypothesis dependency
# this module is gated on.)


# ---------------------------------------------------------------------------
# mamba selective scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,l,d,n", [(2, 32, 64, 8), (1, 16, 128, 16),
                                     (2, 24, 96, 4)])
def test_mamba_scan_sweep(b, l, d, n, rng):
    u = jax.random.normal(rng, (b, l, d)) * 0.5
    delta = jax.nn.softplus(jax.random.normal(jax.random.fold_in(rng, 1),
                                              (b, l, d)))
    a = -jnp.abs(jax.random.normal(jax.random.fold_in(rng, 2), (d, n)))
    bb = jax.random.normal(jax.random.fold_in(rng, 3), (b, l, n)) * 0.5
    cc = jax.random.normal(jax.random.fold_in(rng, 4), (b, l, n)) * 0.5
    ds = jax.random.normal(jax.random.fold_in(rng, 5), (d,))
    y_k, h_k = ops.mamba_scan(u, delta, a, bb, cc, ds, d_block=64)
    y_r, h_r = ref.mamba_scan_ref(u, delta, a, bb, cc, ds)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               atol=1e-4, rtol=1e-4)


def test_mamba_scan_matches_model_layer(rng):
    """Kernel ≡ the chunked associative-scan path in models/ssm.py."""
    from repro.configs.base import ModelConfig
    from repro.models.ssm import (_causal_conv, _mamba_bcdt, init_mamba,
                                  mamba)

    cfg = ModelConfig(d_model=32, ssm_expand=2, ssm_state_dim=8, ssm_chunk=16)
    p = init_mamba(rng, cfg)
    x = jax.random.normal(rng, (2, 32, 32)) * 0.5
    out_model, _ = mamba(p, cfg, x)
    d_in = 64
    xz = x @ p["in_proj"]
    u0, z = xz[..., :d_in], xz[..., d_in:]
    uc, _ = _causal_conv(p, u0)
    delta, bb, cc = _mamba_bcdt(p, cfg, uc)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    yk, _ = ops.mamba_scan(uc, delta, a, bb, cc, p["D"], d_block=64)
    out_kernel = (yk.astype(x.dtype) * jax.nn.silu(z)) @ p["out_proj"]
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_model),
                               atol=1e-4, rtol=1e-4)
