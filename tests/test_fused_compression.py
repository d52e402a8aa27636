"""Fused Pallas compression on the Fabric path (DESIGN.md §2/§3).

The production dispatch (``Fabric(fused=True)``, the default) must be
BITWISE identical to the pure-jnp wire codec it replaces — encode, decode,
error-feedback residual and DGC velocity masking, on padded and unpadded
buckets, on both Comm realizations — and must emit NO separate XLA pack
op (the uint8 sign bytes come out of the kernel)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression as C
from repro.core.comm import LocalComm
from repro.core.compression import (dgc_init, ef_init, get_compressor,
                                    pack_signs, packed_nbytes, wire_bytes)
from repro.core.fabric import Fabric, wire_nbytes
from repro.kernels import ops, ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 4

COMPRESSORS = [
    ("onebit", {"block": 64}),
    ("topk", {"ratio": 0.1, "block": 64}),
]


@pytest.fixture(scope="module")
def tree(rng):
    # "c" (300) is NOT a multiple of any block used here — padded tail
    # blocks exercised on every test; "b" (8*16=128) divides evenly
    return {"b": jax.random.normal(rng, (W, 8, 16)),
            "c": jax.random.normal(jax.random.fold_in(rng, 2), (W, 300))}


def _fabrics():
    return (Fabric(LocalComm(W), bucket_bytes=1 << 12, fused=True),
            Fabric(LocalComm(W), bucket_bytes=1 << 12, fused=False))


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# kernel vs jnp wire codec, bitwise
# ---------------------------------------------------------------------------
def test_onebit_packed_kernel_bitwise(rng):
    nb, block = 13, 64
    g = jax.random.normal(rng, (nb, block))
    r = jax.random.normal(jax.random.fold_in(rng, 1), (nb, block)) * 0.1
    packed, scale, newr = ops.onebit_quant_packed(g, r)
    s, sc, _ = ref.onebit_quant_ref(g, r)
    want_packed = pack_signs(s.reshape(-1)).reshape(nb, block // 8)
    want_scale = sc.astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(want_packed))
    np.testing.assert_array_equal(np.asarray(scale, np.float32),
                                  np.asarray(want_scale, np.float32))
    # residual accounts for the bf16 scale the receivers decode with
    t = g + r
    dec = jnp.where(t >= 0, 1.0, -1.0) * want_scale.astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(newr), np.asarray(t - dec))


def test_topk_encode_ef_kernel_bitwise(rng):
    nb, block, k = 13, 64, 5
    g = jax.random.normal(rng, (nb, block))
    r = jax.random.normal(jax.random.fold_in(rng, 1), (nb, block)) * 0.1
    vals, idx, newr = ops.topk_encode_ef(g, r, k)
    t = g + r
    rvals, ridx, rdense = ref.topk_sparsify_ref(t, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    np.testing.assert_array_equal(np.asarray(newr), np.asarray(t - rdense))


# ---------------------------------------------------------------------------
# Fabric dispatch parity (LocalComm simulator, padded + unpadded buckets)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name,kw", COMPRESSORS)
def test_exchange_parity_bitwise(name, kw, tree):
    comp = get_compressor(name, **kw)
    assert comp.fused_encode is not None
    fa, fb = _fabrics()
    res = ef_init(tree)
    ma, ra, meta_a = fa.exchange(tree, res, comp)
    mb, rb, meta_b = fb.exchange(tree, res, comp)
    _assert_tree_equal(ma, mb)
    _assert_tree_equal(ra, rb)
    assert float(meta_a["wire_bytes"]) == float(meta_b["wire_bytes"])
    # second round: nonzero residual feeds the fused t = g + r
    ma2, ra2, _ = fa.exchange(tree, ra, comp)
    mb2, rb2, _ = fb.exchange(tree, rb, comp)
    _assert_tree_equal(ma2, mb2)
    _assert_tree_equal(ra2, rb2)


@pytest.mark.parametrize("name,kw", COMPRESSORS)
def test_dgc_parity_bitwise(name, kw, tree):
    comp = get_compressor(name, **kw)
    fa, fb = _fabrics()
    sa = sb = dgc_init(tree)
    for _ in range(2):  # round 2: nonzero velocity AND residual
        ga, sa, _ = fa.exchange_dgc(tree, sa, comp, momentum=0.9)
        gb, sb, _ = fb.exchange_dgc(tree, sb, comp, momentum=0.9)
        _assert_tree_equal(ga, gb)
        _assert_tree_equal(sa["velocity"], sb["velocity"])
        _assert_tree_equal(sa["residual"], sb["residual"])


@pytest.mark.parametrize("name,kw", COMPRESSORS)
def test_compress_no_collective_parity(name, kw, tree):
    comp = get_compressor(name, **kw)
    fa, fb = _fabrics()
    res = ef_init(tree)
    ca, ra, wa = fa.compress(tree, res, comp)
    cb, rb, wb = fb.compress(tree, res, comp)
    _assert_tree_equal(ca, cb)
    _assert_tree_equal(ra, rb)
    assert wa == wb


def test_fused_dispatch_is_default(tree):
    fab = Fabric(LocalComm(W))
    assert fab.fused
    assert get_compressor("onebit").fused_encode is not None
    assert get_compressor("topk").fused_encode is not None
    # int8 has no fused kernel: the jnp path must still serve it
    comp = get_compressor("int8", block=64)
    assert comp.fused_encode is None
    m, r, _ = fab.exchange(tree, ef_init(tree), comp)
    assert jax.tree.structure(m) == jax.tree.structure(tree)


# ---------------------------------------------------------------------------
# no separate pack op on the fused path
# ---------------------------------------------------------------------------
def test_fused_path_emits_no_separate_pack_op(tree, monkeypatch):
    """The fused dispatch must never reach the XLA ``pack_signs`` codec —
    the uint8 bytes come out of the kernel — and its jaxpr must contain
    the pallas_call; the jnp codec is the control.  (The abstract
    ``packed_nbytes`` accounting also touches ``pack_signs`` under
    ``eval_shape``, so the counter is scoped to the encode paths.)"""
    calls = {"n": 0}
    orig = C.pack_signs

    def counting(sign):
        calls["n"] += 1
        return orig(sign)

    monkeypatch.setattr(C, "pack_signs", counting)
    comp = get_compressor("onebit", block=64)
    g = jax.random.normal(jax.random.PRNGKey(0), (W, 300))
    r = jnp.zeros((W, 300))

    def encode(gg, rr):  # drop the (non-jax-typed) widen closure
        arrs, _, new_r = comp.fused_encode(gg, rr)
        return arrs, new_r

    jx = str(jax.make_jaxpr(encode)(g, r))
    assert calls["n"] == 0
    assert "pallas_call" in jx

    def jnp_codec(t):
        wire, _ = comp.compress(t)
        return C._narrow_wire(comp.name, wire)[0]

    jax.make_jaxpr(jnp_codec)(g[0])
    assert calls["n"] > 0

    # full exchange graphs: the kernel appears on the fused dispatch only
    res = ef_init(tree)
    fused, unfused = _fabrics()
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda t, rr: fused.exchange(t, rr, comp))(tree, res))
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda t, rr: unfused.exchange(t, rr, comp))(tree, res))


# ---------------------------------------------------------------------------
# parity on the sharded realization (subprocess: needs >1 device)
# ---------------------------------------------------------------------------
def test_shardcomm_fused_parity_bitwise():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.comm import ShardComm
        from repro.core.compression import get_compressor, ef_init
        from repro.core.fabric import Fabric
        from repro.core.jax_compat import make_mesh, set_mesh, shard_map

        W = 4
        mesh = make_mesh((W,), ("w",))
        g = {"a": jax.random.normal(jax.random.PRNGKey(0), (W, 8, 16)),
             "c": jax.random.normal(jax.random.PRNGKey(1), (W, 300))}
        r = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), g)
        for name, kw in (("onebit", {"block": 64}),
                         ("topk", {"ratio": 0.1, "block": 64})):
            comp = get_compressor(name, **kw)
            outs = {}
            for fused in (True, False):
                def body(gg, rr):
                    fab = Fabric(ShardComm("w", W), bucket_bytes=1 << 12,
                                 fused=fused)
                    m, nr, _ = fab.exchange(gg, rr, comp)
                    return m, nr
                fn = shard_map(body, mesh=mesh,
                               in_specs=(P("w"), P("w")),
                               out_specs=(P("w"), P("w")), check_vma=False)
                with set_mesh(mesh):
                    outs[fused] = jax.jit(fn)(g, r)
            for a, b in zip(jax.tree.leaves(outs[True]),
                            jax.tree.leaves(outs[False])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            print("SHARD_PARITY_OK", name)
    """)], capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.count("SHARD_PARITY_OK") == 2


# ---------------------------------------------------------------------------
# wire-byte accounting under block padding (exact, both layers)
# ---------------------------------------------------------------------------
def test_wire_bytes_exact_on_padded_buckets():
    """300 elems / block 64 → 5 blocks: the padded tail block ships a full
    scale (onebit) / k values + k indices (topk).  ``compression.
    wire_bytes`` must charge them, matching ``fabric.wire_nbytes``."""
    tree = {"w": jnp.zeros((300,))}
    onebit = get_compressor("onebit", block=64)
    # 5 blocks * 64/8 sign bytes + 5 * 2 bf16 scale bytes
    assert wire_bytes(onebit, tree) == 5 * 8 + 5 * 2
    assert wire_nbytes(onebit, 300) == wire_bytes(onebit, tree)

    topk = get_compressor("topk", ratio=0.125, block=64)  # k = 8
    # 5 blocks * 8 * (4 value + 2 index) bytes
    assert wire_bytes(topk, tree) == 5 * 8 * 6
    assert wire_nbytes(topk, 300) == wire_bytes(topk, tree)

    # exact accounting charges the padded tail: 300 elems cost the same
    # wire as 5 full blocks, and differ from the analytic per-element rate
    assert wire_bytes(onebit, {"w": jnp.zeros((320,))}) == \
        wire_bytes(onebit, tree)
    assert wire_bytes(onebit, tree) != 300 * onebit.wire_bits_per_element / 8


def test_wire_bytes_matches_shipped_buffer():
    """The accounting equals the byte size of the buffer an exchange
    actually packs (per leaf), padded and unpadded."""
    for name, kw, n in (("onebit", {"block": 64}, 300),
                        ("onebit", {"block": 64}, 256),
                        ("topk", {"ratio": 0.1, "block": 64}, 300),
                        ("int8", {"block": 64}, 100)):
        comp = get_compressor(name, **kw)
        x = jax.random.normal(jax.random.PRNGKey(0), (n,))
        wire, _ = comp.compress(x)
        arrs, _ = C._narrow_wire(comp.name, wire)
        buf, _ = C._pack(arrs)
        assert packed_nbytes(comp, n) == buf.size, (name, n)
        assert wire_bytes(comp, {"x": x}) == buf.size


def test_wire_bytes_none_unchanged():
    tree = {"w": jnp.zeros((1000,))}
    assert wire_bytes(get_compressor("none"), tree) == 4000.0


# ---------------------------------------------------------------------------
# backend-aware interpret default (kernels/ops.py helper)
# ---------------------------------------------------------------------------
def test_default_interpret_backend_aware(monkeypatch):
    assert ops.default_interpret() == (jax.default_backend()
                                       not in ("tpu", "gpu"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not ops.default_interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert not ops.default_interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops.default_interpret()


# ---------------------------------------------------------------------------
# fused Adam at the ZeRO-1 shard-bucket boundary
# ---------------------------------------------------------------------------
def test_zero1_fused_adam_parity(rng):
    from repro.core.strategies import get_strategy
    from repro.optim import adam
    from repro.train.loop import init_train_state, make_replica_train_step

    w = 2
    comm = LocalComm(w)
    params = {"w1": jax.random.normal(rng, (16, 32)) * 0.1,
              "b1": jnp.zeros((32,))}
    params = comm.replicate(params)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (w, 4, 16))

    def loss_fn(p, xb):
        return jnp.mean((xb @ p["w1"] + p["b1"]) ** 2)

    states = {}
    for fused in (False, True):
        opt = adam(1e-3, fused=fused)
        strat = get_strategy("sync_zero1")
        state = init_train_state(params, opt, strat, comm)
        step = make_replica_train_step(loss_fn, opt, strat, comm,
                                       donate=False)
        for _ in range(3):
            state, metrics = step(state, x)
        states[fused] = state
    for a, b in zip(jax.tree.leaves(states[True]["params"]),
                    jax.tree.leaves(states[False]["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)
    assert float(metrics["loss"]) > 0
