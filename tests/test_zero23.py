"""ZeRO-2/3 tests (core/strategies.py, train/loop.py, DESIGN.md §12).

Acceptance (ISSUE 9):

  * ``sync_zero2`` / ``sync_zero3`` train BITWISE-equal to ``sync`` for
    sgd and adam at ``accum_steps=1`` on the LocalComm rig; under
    accumulation ZeRO-2's shard accumulator matches to float tolerance
    (sum-of-means vs mean-of-sums re-association only),
  * ZeRO-3's parameter train state is 1/W per worker — the W× shrink
    ``step_state_peak_bytes`` models — and ``gather_params``
    reconstructs the replicated tree exactly,
  * ZeRO-3 checkpoints written sharded at W restore re-sharded at W′,
  * the sharded production path (``build_train_step(zero_stage=2|3)``)
    lowers and compiles on a (pod, data, model) mesh.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import read_meta, restore_checkpoint, save_checkpoint
from repro.core import strategies as ST
from repro.core.comm import LocalComm
from repro.core.fabric import Fabric
from repro.optim import adam, sgd
from repro.roofline import analysis as RA
from repro.train.loop import init_train_state, make_replica_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 4
BB = 4 * 50  # small buckets so every tree spans several


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


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
            h = h @ p[f"w{i}"]
            if i < len(dims) - 2:
                h = jnp.tanh(h)
        return jnp.mean((h - y) ** 2)

    return params, (X, Y), loss_fn


def _train(strat, opt, base, batches, loss_fn, steps=12, accum=1):
    comm = LocalComm(W)
    params = comm.replicate(base)
    state = init_train_state(params, opt, strat, comm)
    step = make_replica_train_step(loss_fn, opt, strat, comm,
                                   accum_steps=accum, bucket_bytes=BB)
    for _ in range(steps):
        state, m = step(state, batches)
    return state, m, comm


def _full_params(state, strat, comm):
    p = state["params"]
    if getattr(strat, "owns_params", False):
        p = strat.gather_params(p, comm)
    return p


# ---------------------------------------------------------------------------
# bitwise equivalence to sync at accum=1
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("stage", ["sync_zero2", "sync_zero3"])
@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_zero23_bitwise_vs_sync(stage, opt_name, mlp_problem):
    base, batches, loss_fn = mlp_problem
    make_opt = {"sgd": lambda: sgd(0.05), "adam": lambda: adam(0.02)}[opt_name]
    finals = {}
    for name in ("sync", stage):
        strat = ST.get_strategy(name, bucket_bytes=BB) if name != "sync" \
            else ST.sync()
        state, m, comm = _train(strat, make_opt(), base, batches, loss_fn)
        finals[name] = _full_params(state, strat, comm)
        assert float(m["replica_divergence"]) == 0.0
    for k in base:
        np.testing.assert_allclose(np.asarray(finals[stage][k]),
                                   np.asarray(finals["sync"][k]), atol=0,
                                   err_msg=k)


def test_zero2_accum_matches_sync(mlp_problem):
    """Under accumulation the ZeRO-2 shard accumulator holds the sum of
    per-microbatch reduce-scattered means — the same floats as sync's
    mean-of-sums up to re-association (~1e-7)."""
    base, (X, Y), loss_fn = mlp_problem
    accum = 4
    Xa = jnp.stack([X * (0.5 + 0.25 * i) for i in range(accum)])
    Ya = jnp.stack([Y] * accum)
    finals = {}
    for name in ("sync", "sync_zero2"):
        strat = ST.get_strategy(name, bucket_bytes=BB) if name != "sync" \
            else ST.sync()
        state, _, comm = _train(strat, adam(0.02), base, (Xa, Ya),
                                loss_fn, steps=8, accum=accum)
        finals[name] = state["params"]
    for k in base:
        np.testing.assert_allclose(np.asarray(finals["sync_zero2"][k]),
                                   np.asarray(finals["sync"][k]),
                                   atol=2e-6, err_msg=k)


# ---------------------------------------------------------------------------
# the W× state shrink
# ---------------------------------------------------------------------------
def test_zero3_param_state_is_one_over_w(mlp_problem):
    base, batches, loss_fn = mlp_problem
    strat = ST.sync_zero3(bucket_bytes=BB)
    state, _, comm = _train(strat, adam(0.02), base, batches, loss_fn,
                            steps=2)
    n_dense = sum(x.size for x in jax.tree.leaves(base))
    # stacked replica rig: leaves are (W, shard) — per-worker share is
    # total/W, equal to the dense count up to bucket padding
    n_total = sum(x.size for x in jax.tree.leaves(state["params"]))
    per_worker = n_total / W
    assert n_dense <= n_total < n_dense + W * BB
    assert per_worker == pytest.approx(n_dense / W, rel=0.25)
    # gather reconstructs the dense tree exactly (shapes and dtypes)
    full = strat.gather_params(state["params"], comm)
    for k in base:
        assert full[k].shape[1:] == base[k].shape


def test_roofline_zero_accounting():
    """step_state_peak_bytes applies the stage factors: 1 shards opt
    state, 2 shards the accumulator, 3 shards the parameters."""
    n = 1_000_000
    p = RA.param_bytes(n)           # 4 MB dense f32
    o = RA.opt_state_bytes(n, 2)    # adam: 8 MB
    peak = {z: RA.step_state_peak_bytes(p, o, n, accum_steps=4, w=W,
                                        zero_stage=z)
            for z in (0, 1, 2, 3)}
    acc = RA.accum_state_bytes(n, 4)
    assert peak[0] == p + o + acc
    assert peak[1] == p + o / W + acc
    assert peak[2] == p + o / W + acc / W
    assert peak[3] == p / W + o / W + acc / W
    # stage-3 param sharding also shows up in param_bytes itself
    assert RA.param_bytes(n, w=W, zero_stage=3) == p / W
    # TP combine wire: zero at degree 1, ring-scaled above
    assert RA.tp_wire_bytes(1e6, 1, 24) == 0.0
    assert RA.tp_wire_bytes(1e6, 2, 24) == 24 * 4 * 1.0 * 1e6
    assert RA.tp_wire_bytes(1e6, 4, 24) == 24 * 4 * 1.5 * 1e6


# ---------------------------------------------------------------------------
# checkpoint: sharded save at W, restore re-sharded at W'
# ---------------------------------------------------------------------------
def test_zero3_ckpt_restores_resharded(tmp_path, mlp_problem):
    """Save the ZeRO-3 PARAM shard buckets at W=4, restore re-sharded at
    W'=2: the reassembled full parameters are bitwise identical."""
    d = str(tmp_path)
    base, batches, loss_fn = mlp_problem
    strat4 = ST.sync_zero3(bucket_bytes=BB)
    state4, _, comm4 = _train(strat4, adam(0.02), base, batches, loss_fn,
                              steps=5)
    fab4 = Fabric(comm4, BB)
    # same layout init_params recorded (built over the replicated tree)
    play4 = fab4.partitioned_layout(comm4.replicate(base))
    shards4 = state4["params"]
    save_checkpoint(d, 0, {"param_shards": shards4},
                    partition=play4.spec())
    assert read_meta(d)["partitions"]["0"]["n_parts"] == W

    comm2 = LocalComm(2)
    fab2 = Fabric(comm2, BB)
    rep2 = comm2.replicate(base)
    play2 = fab2.partitioned_layout(rep2)
    template = jax.tree.map(jnp.zeros_like, fab2.shard_params(rep2, play2))
    restored = restore_checkpoint(d, 0, {"param_shards": template},
                                  repartition=True)["param_shards"]
    full4 = fab4.unpartition(shards4, play4)
    full2 = fab2.unpartition(jax.tree.map(jnp.asarray, restored), play2)
    for k in base:
        np.testing.assert_allclose(np.asarray(full2[k][0]),
                                   np.asarray(full4[k][0]), atol=0)


# ---------------------------------------------------------------------------
# production sharded path lowers for stages 2 and 3
# ---------------------------------------------------------------------------
def test_sharded_step_lowers_zero23():
    out = _run("""
        import dataclasses, jax
        from repro.configs.base import get_config
        from repro.core.jax_compat import set_mesh
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import ShapeSpec, build_train_step
        cfg = dataclasses.replace(
            get_config("qwen2-1.5b").reduced(),
            num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
            head_dim=16, d_ff=64, vocab_size=64)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = ShapeSpec("train_tiny", 16, 4, "train")
        for zs in (2, 3):
            with set_mesh(mesh):
                fn, sds, sh, donate = build_train_step(
                    cfg, shape, mesh, zero_stage=zs, accum_steps=2)
                jax.jit(fn, in_shardings=sh,
                        donate_argnums=donate).lower(*sds).compile()
            print(f"zero_stage={zs}: compiled OK")
    """)
    assert "zero_stage=2: compiled OK" in out
    assert "zero_stage=3: compiled OK" in out


def test_trainer_cli_zero_stage_flag():
    """--zero-stage wires the strategy and the sharded checkpoint path
    end-to-end (the smallest real training run)."""
    out = _run("""
        from repro.launch.train import main
        main(["--arch", "qwen2-1.5b", "--reduced", "--workers", "4",
              "--steps", "2", "--seq-len", "32", "--batch-per-worker", "2",
              "--zero-stage", "3", "--log-every", "1"])
    """, devices=1)
    assert "loss" in out
