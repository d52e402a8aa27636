"""End-to-end mixed-precision subsystem tests (ISSUE 4 acceptance):

  (a) the f32 policy is a strict no-op: sync and sync_zero1 training is
      BITWISE identical to the policy-less pre-precision path,
  (b) loss-scaled bf16 training of the tiny transformer reaches a loss
      within 5% of f32 on the LocalComm rig,
  (c) the bf16 wire halves exchange bytes (Fabric accounting) and the
      lowered ZeRO-1 HLO ships bf16 reduce-scatters — no f32 ones,
  (d) the loss-scale skip-step leaves params, optimizer state and comm
      state untouched on overflow (and the dynamic scale backs off /
      regrows),
  (e) checkpoint round-trip preserves the policy record and the f32
      master dtype across worker counts (save at W=4 → restore at W=2),
  (f) every spectrum strategy stays green under the bf16 policy
      (the ``bf16`` marker sweep — CI runs it as its own job).
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (read_meta, read_precision, restore_checkpoint,
                              save_checkpoint)
from repro.configs.base import ModelConfig
from repro.core import strategies as ST
from repro.core.comm import LocalComm
from repro.core.fabric import Fabric
from repro.core.precision import (PrecisionPolicy, apply_policy, get_policy,
                                  policy_from_spec)
from repro.optim import adam, momentum, sgd
from repro.train.loop import init_train_state, make_replica_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 4


def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# policy object + config validation
# ---------------------------------------------------------------------------
def test_policy_presets_and_spec_roundtrip():
    bf = get_policy("bf16")
    assert bf.param_dt == jnp.bfloat16 and bf.master_dt == jnp.float32
    assert bf.wire_dt == jnp.bfloat16 and bf.keeps_master and bf.uses_scaling
    assert get_policy(None).is_noop and get_policy("f32").is_noop
    assert not get_policy("bf16-pure").keeps_master
    assert policy_from_spec(bf.spec()) == bf
    assert get_policy(bf) is bf
    with pytest.raises(KeyError, match="unknown precision"):
        get_policy("fp8")
    with pytest.raises(ValueError, match="wire_dtype"):
        PrecisionPolicy("bad", wire_dtype="float64")


def test_config_dtype_validated_at_construction():
    """A bad dtype fails at ModelConfig construction, not inside model
    init (satellite: configs/base.py validation)."""
    with pytest.raises(ValueError, match="param_dtype"):
        ModelConfig(name="bad", param_dtype="float8")
    with pytest.raises(ValueError, match="compute_dtype"):
        dataclasses.replace(ModelConfig(), compute_dtype="tf32")
    cfg = apply_policy(ModelConfig(), get_policy("bf16"))
    assert cfg.param_dtype == "bfloat16" and cfg.compute_dtype == "bfloat16"


# ---------------------------------------------------------------------------
# shared problems
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mlp_problem():
    key = jax.random.PRNGKey(0)
    dims = (12, 16, 8, 1)
    params = {f"w{i}": jax.random.normal(jax.random.fold_in(key, i),
                                         (a, b)) * 0.3
              for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))}
    X = jax.random.normal(jax.random.fold_in(key, 9), (W, 32, dims[0]))
    Y = jnp.sum(X, axis=-1, keepdims=True)

    def loss_fn(p, batch):
        x, y = batch
        h = x
        for i in range(len(dims) - 1):
            h = (h @ p[f"w{i}"].astype(h.dtype))
            if i < len(dims) - 2:
                h = jnp.tanh(h)
        return jnp.mean((h.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)

    return params, (X, Y), loss_fn


def _train(strategy, problem, policy, steps=20, opt=None, seed_params=None):
    base, batches, loss_fn = problem
    comm = LocalComm(W)
    opt = opt or sgd(0.05)
    pol = None if policy is None else get_policy(policy)
    params = comm.replicate(seed_params if seed_params is not None else base)
    if pol is not None:
        params = pol.cast_to_param(params)
        batches = jax.tree.map(
            lambda x: x.astype(pol.compute_dt), batches)
    state = init_train_state(params, opt, strategy, comm, policy=pol)
    step = make_replica_train_step(loss_fn, opt, strategy, comm, policy=pol)
    m = {}
    for _ in range(steps):
        state, m = step(state, batches)
    return state, m


# ---------------------------------------------------------------------------
# (a) f32 policy is bitwise the pre-precision path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strat_fn", [
    lambda pol: ST.sync(policy=pol),
    lambda pol: ST.sync_zero1(bucket_bytes=4 * 50, policy=pol),
], ids=["sync", "sync_zero1"])
def test_f32_policy_bitwise_identical(strat_fn, mlp_problem):
    s_none, _ = _train(strat_fn(None), mlp_problem, None, steps=10,
                       opt=adam(0.02))
    s_f32, _ = _train(strat_fn(get_policy("f32")), mlp_problem, "f32",
                      steps=10, opt=adam(0.02))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        s_none["params"], s_f32["params"])
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        s_none["opt_state"], s_f32["opt_state"])


# ---------------------------------------------------------------------------
# (c) wire accounting: bf16 halves exchange bytes
# ---------------------------------------------------------------------------
def test_bf16_wire_halves_exchange_bytes(rng):
    tree = {"a": jax.random.normal(rng, (W, 301)),
            "b": jax.random.normal(jax.random.fold_in(rng, 1), (W, 13, 7))}
    f32 = Fabric(LocalComm(W), bucket_bytes=4 * 100)
    bf16 = Fabric(LocalComm(W), bucket_bytes=4 * 100,
                  wire_dtype=jnp.bfloat16)
    assert f32.flat_bytes(tree) == 2 * bf16.flat_bytes(tree)
    _, _, m32 = f32.exchange(tree)
    g16, _, m16 = bf16.exchange(tree)
    assert float(m32["wire_bytes"]) == 2 * float(m16["wire_bytes"])
    # bf16-rounded mean stays close to the f32 mean
    ref = f32.all_mean(tree)
    for k in tree:
        np.testing.assert_allclose(np.asarray(g16[k]), np.asarray(ref[k]),
                                   rtol=2e-2, atol=2e-2)
    # partitioned path reports the same (halved) bytes
    play = bf16.partitioned_layout(tree)
    shards, mp = bf16.exchange_partitioned(tree, play)
    assert float(mp["wire_bytes"]) == float(m16["wire_bytes"])
    assert all(s.dtype == jnp.float32 for s in shards)  # f32 shard math


# ---------------------------------------------------------------------------
# (b) loss-scaled bf16 training of the tiny transformer: within 5% of f32
# ---------------------------------------------------------------------------
def test_bf16_transformer_loss_within_5pct_of_f32():
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, worker_batches
    from repro.models import transformer as T
    from repro.train.loop import make_loss_fn

    w, steps = 2, 12
    results = {}
    for pname in ("f32", "bf16"):
        pol = get_policy(pname)
        cfg = dataclasses.replace(
            apply_policy(get_config("qwen2-1.5b").reduced(), pol),
            num_layers=2, d_model=32, num_heads=2, num_kv_heads=1,
            head_dim=16, d_ff=64, vocab_size=32)
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                          batch_per_worker=2, seed=0)
        lf = make_loss_fn(cfg, remat=False)

        def loss_fn(p, toks):
            return lf(p, {"tokens": toks, "labels": toks})

        comm = LocalComm(w)
        opt = adam(3e-3)
        strat = ST.sync(policy=None if pol.is_noop else pol)
        params = comm.replicate(T.init_model(jax.random.PRNGKey(0), cfg))
        state = init_train_state(params, opt, strat, comm,
                                 policy=None if pol.is_noop else pol)
        step = make_replica_train_step(loss_fn, opt, strat, comm,
                                       policy=None if pol.is_noop else pol)
        for t in range(steps):
            state, m = step(state, worker_batches(dcfg, w, t))
        results[pname] = float(m["loss"])
        if pname == "bf16":
            assert float(m.get("overflow", 0.0)) == 0.0
            assert state["params"]["embed"].dtype == jnp.bfloat16
            assert state["master"]["embed"].dtype == jnp.float32
    assert np.isfinite(results["bf16"])
    rel = abs(results["bf16"] - results["f32"]) / results["f32"]
    assert rel < 0.05, results


@pytest.mark.bf16
def test_bf16_zero1_matches_bf16_sync(mlp_problem):
    """The bf16 ZeRO-1 path (f32 master in the opt-state shard) tracks the
    dense bf16 path (f32 master in the train state) to f32-master
    tolerance, and keeps the 1/W master layout."""
    base, _, _ = mlp_problem
    s_sync, _ = _train(ST.sync(policy=get_policy("bf16")), mlp_problem,
                       "bf16", steps=15, opt=adam(0.02))
    s_z1, _ = _train(
        ST.sync_zero1(bucket_bytes=4 * 50, policy=get_policy("bf16")),
        mlp_problem, "bf16", steps=15, opt=adam(0.02))
    assert "master" in s_sync and "master" not in s_z1
    assert "master" in s_z1["opt_state"]
    for x in jax.tree.leaves(s_z1["opt_state"]):
        assert x.dtype == jnp.float32 and x.shape[0] == W
    for k in base:
        np.testing.assert_allclose(
            np.asarray(s_z1["params"][k], np.float32),
            np.asarray(s_sync["params"][k], np.float32), atol=1e-2)


# ---------------------------------------------------------------------------
# (d) skip-step on overflow
# ---------------------------------------------------------------------------
def test_loss_scale_skip_step_leaves_state_untouched():
    pol = dataclasses.replace(get_policy("bf16"), growth_interval=3)
    comm = LocalComm(W)
    opt = adam(0.05)
    strat = ST.sync(policy=pol)
    base = {"w": jnp.ones((6, 2))}
    X = jnp.ones((W, 4, 6))

    def loss_fn(p, batch):
        x, boom = batch
        # boom=1 drives the loss to inf -> non-finite gradients
        return jnp.mean((x @ p["w"].astype(x.dtype)).astype(jnp.float32) ** 2
                        ) * jnp.where(boom > 0, jnp.inf, 1.0)

    params = pol.cast_to_param(comm.replicate(base))
    state = init_train_state(params, opt, strat, comm, policy=pol)
    step = make_replica_train_step(loss_fn, opt, strat, comm, policy=pol)
    ok_batch = (X.astype(jnp.bfloat16), jnp.zeros((W,)))
    bad_batch = (X.astype(jnp.bfloat16), jnp.ones((W,)))

    state, m = step(state, ok_batch)  # one good step to move off init
    scale0 = float(state["loss_scale"]["scale"])
    # np.array, not np.asarray: the step donates its input state
    # (DESIGN.md §8), and np.asarray of a CPU jax array is a zero-copy
    # VIEW — a donated-and-reused buffer would silently mutate the
    # snapshot and make the untouched-state assertion tautological
    snap = jax.tree.map(lambda x: np.array(x),
                        {k: state[k] for k in
                         ("params", "master", "opt_state")})
    state, m = step(state, bad_batch)  # overflow: must be a no-op + backoff
    assert float(m["overflow"]) == 1.0
    for k in snap:
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), state[k], snap[k])
    assert float(state["loss_scale"]["scale"]) == scale0 / 2
    assert int(state["loss_scale"]["good_steps"]) == 0
    # growth: growth_interval consecutive finite steps double the scale
    for _ in range(pol.growth_interval):
        state, m = step(state, ok_batch)
    assert float(state["loss_scale"]["scale"]) == scale0
    # and the good steps actually moved the params
    assert not np.array_equal(np.asarray(state["master"]["w"], np.float32),
                              np.asarray(snap["master"]["w"], np.float32))


# ---------------------------------------------------------------------------
# (e) checkpoint: policy + master dtype survive a W=4 -> W=2 round trip
# ---------------------------------------------------------------------------
def test_checkpoint_preserves_policy_and_master_across_workers(tmp_path):
    pol = get_policy("bf16")
    d = str(tmp_path)
    key = jax.random.PRNGKey(3)
    base = {"w": jax.random.normal(key, (9, 7)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (23,))}
    grads = jax.tree.map(lambda x: (x * 0.1).astype(jnp.bfloat16), base)
    opt = momentum(0.1, 0.9)
    bb = 4 * 40

    def build_state(w):
        comm = LocalComm(w)
        strat = ST.sync_zero1(bucket_bytes=bb, policy=pol)
        fab = Fabric(comm, bb, wire_dtype=pol.wire_dt)
        rep = pol.cast_to_param(comm.replicate(base))
        play = fab.partitioned_layout(rep)
        state = strat.init_opt(rep, opt, comm)
        _, state, _, _ = strat.update(rep, comm.replicate(grads), state, {},
                                      jnp.zeros((), jnp.int32), opt, comm)
        return comm, fab, play, rep, state

    _, fab4, play4, rep4, state4 = build_state(4)
    save_checkpoint(d, 0, {"params": rep4, "opt_state": state4},
                    partition=play4.spec(), precision=pol.spec())
    # the recorded policy round-trips
    assert read_precision(d, 0) == pol.spec()
    assert policy_from_spec(read_precision(d, 0)) == pol
    assert read_meta(d)["partitions"]["0"]["n_parts"] == 4

    comm2, fab2, play2, rep2, template2 = build_state(2)
    template2 = jax.tree.map(jnp.zeros_like, template2)
    restored = restore_checkpoint(
        d, 0, {"params": jax.tree.map(jnp.zeros_like, rep2),
               "opt_state": template2}, repartition=True)
    # master dtype preserved (f32 on disk AND in the restored shard)
    for x in jax.tree.leaves(restored["opt_state"]["master"]):
        assert np.asarray(x).dtype == np.float32
    # params restored CASTED to the working dtype
    assert np.asarray(restored["params"]["w"]).dtype == \
        jnp.dtype(jnp.bfloat16)
    # reassembled master agrees across worker counts
    full4 = fab4.unpartition(state4["master"], play4)
    full2 = fab2.unpartition(
        jax.tree.map(jnp.asarray, restored["opt_state"]["master"]), play2)
    for k in base:
        np.testing.assert_allclose(np.asarray(full2[k][0], np.float32),
                                   np.asarray(full4[k][0], np.float32),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# (c) HLO proof: the bf16-wire ZeRO-1 path emits no f32 reduce-scatter
# ---------------------------------------------------------------------------
def test_zero1_bf16_hlo_has_no_f32_reduce_scatter():
    """The bf16-wire ZeRO-1 lowering ships ONLY bf16 on the wire: the
    gradient reduction is one bf16 all-to-all per bucket + local f32
    accumulate (a bf16 reduce-scatter would be convert-promoted back to
    an f32 wire by XLA), and the param all-gather is bf16.  No f32
    reduce-scatter, no gradient all-reduce."""
    out = _run("""
        import json
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.analysis import collective_budget, promotion_proof
        from repro.core import strategies as ST
        from repro.core.comm import ShardComm
        from repro.core.fabric import BucketLayout, Fabric
        from repro.core.jax_compat import make_mesh, set_mesh, shard_map
        from repro.core.precision import get_policy
        from repro.optim import adam
        from repro.train.loop import zero1_opt_template

        PODS, LAYERS = 4, 6
        pol = get_policy("bf16")
        mesh = make_mesh((PODS,), ("pod",))
        params = {f"l{i}": {"w": jax.ShapeDtypeStruct((64, 32), jnp.bfloat16),
                            "b": jax.ShapeDtypeStruct((32,), jnp.bfloat16)}
                  for i in range(LAYERS)}
        bucket_bytes = 4 * 8000
        lay = BucketLayout.build(params, bucket_bytes, lead_axes=0)
        opt = adam(1e-3)
        opt_state = zero1_opt_template(params, opt, PODS, bucket_bytes,
                                       policy=pol)
        assert "master" in opt_state
        strat = ST.sync_zero1(bucket_bytes=bucket_bytes, policy=pol)
        comm = ShardComm("pod", PODS)

        def body(p, g, s):
            p, s, _, _ = strat.update(p, g, s, {}, jnp.zeros((), jnp.int32),
                                      opt, comm)
            return p, s

        rep = jax.tree.map(lambda _: P(), params)
        ssp = jax.tree.map(lambda _: P("pod"), opt_state)
        fn = shard_map(body, mesh=mesh,
                       in_specs=(rep, rep, ssp), out_specs=(rep, ssp),
                       check_vma=False)
        with set_mesh(mesh):
            c = jax.jit(fn).lower(params, params, opt_state).compile()
        txt = c.as_text()
        # rule API: the narrow partitioned contract is a2a+AG per bucket
        # (NO reduce-scatter — it would be convert-promoted), and the
        # promotion proof rejects any non-tuple f32 wire payload
        contract = Fabric(comm, bucket_bytes,
                          wire_dtype=pol.wire_dt).collective_contract(
            lay, strat.wire_profile)
        assert set(contract) == {"all-to-all", "all-gather"}, contract
        res = collective_budget(txt, contract)
        assert res.status == "pass", res.findings
        promo = promotion_proof(txt, pol.narrow_wire)
        assert promo.status == "pass", promo.findings
        print("BF16_HLO_OK", json.dumps(res.details))
    """)
    assert "BF16_HLO_OK" in out


# ---------------------------------------------------------------------------
# (f) strategy-spectrum sweep under the bf16 policy (CI marker job)
# ---------------------------------------------------------------------------
BF16_STRATEGIES = [
    ("sync", lambda pol: ST.sync(policy=pol)),
    ("sync_zero1", lambda pol: ST.sync_zero1(bucket_bytes=4 * 50,
                                             policy=pol)),
    ("local_sgd", lambda pol: ST.local_sgd(sync_every=4, policy=pol)),
    ("easgd", lambda pol: ST.easgd(alpha=0.2, sync_every=3, policy=pol)),
    ("ssp", lambda pol: ST.ssp(staleness=3, policy=pol)),
    ("downpour", lambda pol: ST.downpour(push_every=4, policy=pol)),
    ("gossip", lambda pol: ST.gossip(policy=pol)),
]


@pytest.mark.bf16
@pytest.mark.parametrize("name,strat_fn", BF16_STRATEGIES,
                         ids=[n for n, _ in BF16_STRATEGIES])
def test_strategy_trains_under_bf16(name, strat_fn, mlp_problem):
    """Every spectrum strategy converges under --precision bf16: finite
    loss, big reduction vs. init, bf16 working params, halved wire."""
    pol = get_policy("bf16")
    state, m = _train(strat_fn(pol), mlp_problem, pol, steps=60,
                      opt=adam(0.02))
    base, batches, loss_fn = mlp_problem
    init_loss = float(loss_fn(base, jax.tree.map(lambda x: x[0], batches)))
    final = float(m["loss"])
    assert np.isfinite(final) and final < 0.5 * init_loss, (name, final)
    assert state["params"]["w0"].dtype == jnp.bfloat16
    # the uncompressed gradient exchanges report a 2-byte wire
    if name in ("sync", "sync_zero1"):
        n = sum(x.size for x in jax.tree.leaves(base))
        assert float(m["wire_bytes"]) <= 2 * n * W + 64, name
    # complete strategies keep replicas consistent under the bf16 wire
    if name in ("sync", "sync_zero1"):
        assert float(m["replica_divergence"]) == 0.0, name


def test_dense_sync_bf16_hlo_has_no_f32_all_reduce():
    """The UNCOMPRESSED bf16-wire sync exchange is also promotion-proof:
    XLA convert-promotes a bf16 all-reduce back to an f32 wire, so the
    fabric expresses it as bf16 all-to-all + local f32 accumulate + u16
    all-gather (ring bytes of the all-reduce it replaces).  Without this,
    wire_bytes would claim 2 bytes/elem while the wire ships 4."""
    out = _run("""
        import json
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.analysis import collective_budget, promotion_proof
        from repro.core import strategies as ST
        from repro.core.comm import ShardComm
        from repro.core.fabric import BucketLayout, Fabric
        from repro.core.jax_compat import make_mesh, set_mesh, shard_map
        from repro.core.precision import get_policy
        from repro.optim import sgd

        PODS, LAYERS = 4, 6
        pol = get_policy("bf16")
        mesh = make_mesh((PODS,), ("pod",))
        params = {f"l{i}": jax.ShapeDtypeStruct((64, 32), jnp.bfloat16)
                  for i in range(LAYERS)}
        bucket_bytes = 4 * 8000
        lay = BucketLayout.build(params, bucket_bytes, lead_axes=0)
        strat = ST.sync(bucket_bytes=bucket_bytes, policy=pol)
        comm = ShardComm("pod", PODS)

        def body(p, g):
            p, _, _, _ = strat.update(p, g, {}, {}, jnp.zeros((), jnp.int32),
                                      sgd(0.1), comm)
            return p

        rep = jax.tree.map(lambda _: P(), params)
        fn = shard_map(body, mesh=mesh,
                       in_specs=(rep, rep), out_specs=rep, check_vma=False)
        with set_mesh(mesh):
            c = jax.jit(fn).lower(params, params).compile()
        txt = c.as_text()
        # rule API: the narrow DENSE contract replaces the all-reduce
        # with a2a+AG per bucket; no all-reduce may survive, and no
        # non-tuple f32 payload may ride the wire
        contract = Fabric(comm, bucket_bytes,
                          wire_dtype=pol.wire_dt).collective_contract(
            lay, strat.wire_profile)
        assert set(contract) == {"all-to-all", "all-gather"}, contract
        res = collective_budget(txt, contract)
        assert res.status == "pass", res.findings
        promo = promotion_proof(txt, pol.narrow_wire)
        assert promo.status == "pass", promo.findings
        print("DENSE_BF16_HLO_OK", json.dumps(res.details))
    """)
    assert "DENSE_BF16_HLO_OK" in out


def test_production_zero1_step_lowers_with_bf16_policy():
    """build_step(precision="bf16") compiles the partition_grads path on a
    3-axis mesh: f32 master buckets in the sharded opt state, loss-scale
    state threaded, and still no gradient all-reduce."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.core.jax_compat import make_mesh, set_mesh
        from repro.launch.specs import build_step, resolve_config, truncate
        from repro.roofline.analysis import parse_collectives

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = truncate(resolve_config("gemma3-1b", "train_4k"), 1)
        step, sds, sh, don = build_step(cfg, "train_4k", mesh,
                                        partition_grads=True,
                                        precision="bf16")
        state_sds = sds[0]
        assert "master" in state_sds["opt_state"]
        assert all(s.dtype == jnp.float32 for s in
                   state_sds["opt_state"]["master"])
        assert state_sds["loss_scale"]["scale"].dtype == jnp.float32
        assert state_sds["params"]["embed"].dtype == jnp.bfloat16
        with set_mesh(mesh):
            c = jax.jit(step, in_shardings=sh,
                        donate_argnums=don).lower(*sds).compile()
        counts = parse_collectives(c.as_text())["counts"]
        # pmin of the finite flag joins the loss pmean as scalar traffic;
        # the GRADIENT reduction is the bucketed a2a + shard update
        assert counts["all-to-all"] > 0, counts
        print("BF16_STEP_OK", counts)
    """, devices=8)
    assert "BF16_STEP_OK" in out


# ---------------------------------------------------------------------------
# fused Adam behind the Optimizer API (satellite: kernels/fused_adam.py)
# ---------------------------------------------------------------------------
def test_adam_fused_flag_parity(rng):
    """adam(fused=True) (the Pallas kernel, ref/interpret mode on CPU)
    tracks the pure-JAX adam leaf-for-leaf over several steps, including
    non-flat leaves and a schedule."""
    from repro.optim.optimizers import warmup_cosine

    tree = {"a": jax.random.normal(rng, (700,)),
            "b": jax.random.normal(jax.random.fold_in(rng, 1), (13, 5)),
            "nest": {"c": jax.random.normal(jax.random.fold_in(rng, 2),
                                            (2, 3, 4))}}
    grads = jax.tree.map(lambda x: x * 0.1, tree)
    sched = warmup_cosine(1e-2, warmup=2, total_steps=10)
    pure, fused = adam(sched), adam(sched, fused=True)
    sp, sf = pure.init(tree), fused.init(tree)
    pp, pf = tree, tree
    for t in range(4):
        tt = jnp.asarray(t, jnp.int32)
        pp, sp = pure.update(grads, sp, pp, tt)
        pf, sf = fused.update(grads, sf, pf, tt)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), pp, pf)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), sp, sf)


def test_adam_fused_rejects_weight_decay():
    with pytest.raises(ValueError, match="weight_decay"):
        adam(1e-3, weight_decay=0.1, fused=True)


def test_adam_fused_runs_on_zero1_shards(mlp_problem):
    """The fused optimizer slots into the ZeRO-1 strategy (flat shard
    buckets) exactly like the pure one."""
    s_pure, _ = _train(ST.sync_zero1(bucket_bytes=4 * 50), mlp_problem,
                       None, steps=8, opt=adam(0.02))
    s_fused, _ = _train(ST.sync_zero1(bucket_bytes=4 * 50), mlp_problem,
                        None, steps=8, opt=adam(0.02, fused=True))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        s_pure["params"], s_fused["params"])


# ---------------------------------------------------------------------------
# serving: bf16 KV cache end-to-end
# ---------------------------------------------------------------------------
def test_decode_engine_bf16_cache():
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve.engine import DecodeEngine, Request

    cfg = dataclasses.replace(get_config("gemma3-1b").reduced(),
                              num_layers=2, d_model=64, vocab_size=64)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_seq=16,
                       cache_dtype="bfloat16")
    leaves = jax.tree.leaves(eng.cache)
    assert any(x.dtype == jnp.bfloat16 for x in leaves)  # KV narrowed
    eng.submit(Request(rid=0, prompt=np.array([1, 2, 3], np.int32),
                       max_new_tokens=4))
    done = eng.run(max_steps=64)
    assert len(done) == 1 and len(done[0].generated) == 4
    f32_eng = DecodeEngine(params, cfg, batch_slots=2, max_seq=16,
                           cache_dtype="float32")
    nbytes = lambda c: sum(x.size * x.dtype.itemsize  # noqa: E731
                           for x in jax.tree.leaves(c))
    assert nbytes(eng.cache) < nbytes(f32_eng.cache)
