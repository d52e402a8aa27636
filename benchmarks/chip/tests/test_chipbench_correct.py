"""What decides ``correct`` fails what it must: the control (the reference
in float8 put in the program's place), and runs of the harness with the
timed path broken underneath, at a tiny size on the CPU with the tiny
limits (set from tiny readings, see ``tiny.LIMITS``)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import pytest

import _paths  # noqa: F401
import tiny
from chipbench import harness, peaks, reference, spec, training

HERE = Path(__file__).resolve().parent


@pytest.fixture
def cpu_run(monkeypatch):
    import repro.launch.compile_cache as cc

    monkeypatch.setitem(peaks.PEAKS, "cpu", {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
        "source": "test"})
    monkeypatch.setattr(cc, "use_compile_cache", lambda: "off")

    def go(root, workload, seconds=1.0):
        return harness.run(["--workload", workload, "--seed", "2718281828",
                            "--seconds", str(seconds), "--trace", "0"],
                           root=root, require_tpu=False)
    return go


def _fails(res):
    lim = res["checks"]
    return not res["correct"] and any(c["value"] > c["limit"]
                                      for c in lim.values())


def test_training_control_in_float8_fails_the_limits():
    dims = spec.dims({"config": tiny.TINY})
    rows = [reference.train_tokens(0, s, 1, 2, 32, 256, 0.9, 31, 7)
            for s in range(3)]
    opt = tiny.TRAIN["optimizer"]
    for seed in (1, 2, 3):
        ref = reference.train_steps(seed, dims, rows, opt)
        low = reference.train_steps(seed, dims, rows, opt,
                                    rnd=reference.fp8)
        got = training.compare(low, ref)
        assert any(got[k] > v for k, v in tiny.LIMITS["train"].items()), got


def test_serving_control_in_float8_fails_the_limit():
    dims = spec.dims({"config": dict(tiny.TINY, tie_word_embeddings=False)})
    params = reference.weights.init(reference.weights.key(4), dims,
                                    jnp.bfloat16)
    import numpy as np
    r = np.random.default_rng(0)
    served = [(r.integers(0, 256, 30), list(r.integers(0, 256, 12)))
              for _ in range(3)]
    g = reference.served_gaps(params, dims, served, 64, rnd=reference.fp8)
    worst = max(float(x.max()) for x in g["control_gaps"])
    assert worst > tiny.LIMITS["serve"]["token_gap"]


def test_step_that_returns_its_state_unchanged_fails(tmp_path, cpu_run,
                                                     monkeypatch):
    import repro.launch.specs as specs

    build = specs.build_train_step

    def broken(*a, **kw):
        step, *rest = build(*a, **kw)
        return (lambda state, batch: (state, step(state, batch)[1]), *rest)

    monkeypatch.setattr(specs, "build_train_step", broken)
    root = tiny.make_root(tmp_path, precision="bf16")
    assert _fails(cpu_run(root, "train.tiny"))


def test_step_on_half_the_batch_fails(tmp_path, cpu_run, monkeypatch):
    import repro.launch.specs as specs

    build = specs.build_train_step

    def broken(*a, **kw):
        step, *rest = build(*a, **kw)

        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return (half, *rest)

    monkeypatch.setattr(specs, "build_train_step", broken)
    root = tiny.make_root(tmp_path, precision="bf16")
    assert _fails(cpu_run(root, "train.tiny"))


def test_served_token_altered_where_produced_fails(tmp_path, cpu_run,
                                                    monkeypatch):
    from repro.serve.engine import PagedDecodeEngine

    decode = PagedDecodeEngine._step_decode
    done = {"n": 0}

    def broken(self):
        decode(self)
        for r in self.slot:
            if r is not None and len(r.generated) == 3 and done["n"] < 50:
                r.generated[-1] = (r.generated[-1] + 1) % 256
                done["n"] += 1

    monkeypatch.setattr(PagedDecodeEngine, "_step_decode", broken)
    root = tiny.make_root(tmp_path)
    res = cpu_run(root, "serve.tiny", seconds=1.5)
    assert done["n"] > 0 and _fails(res)


ZERO1 = textwrap.dedent("""
    import json, os, sys
    from pathlib import Path
    sys.path.insert(0, {here!r})
    import _paths, tiny
    from chipbench import harness, peaks
    import repro.launch.compile_cache as cc
    from repro.core import fabric
    peaks.PEAKS["cpu"] = {{"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                          "hbm_bytes": 1e10, "source": "t"}}
    cc.use_compile_cache = lambda: "off"
    if {broken!r}:
        # the exchange between chips left out: each chip keeps its own
        # shard of its own gradient
        fabric.Fabric.exchange_partitioned_accumulated = (
            lambda self, buckets, play, events=1.0:
            (self.comm.shard_chunk(buckets), {{}}))
    root = tiny.make_root(Path({root!r}), precision="bf16")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({{"name": "train4.tiny", "config": "tiny",
                           "traffic": "train.tiny.zero1", "chips": 4,
                           "why": "t"}})
    for m in b["end_to_end"] + b["per_layer"]:
        if "train.tiny" in m.get("workloads", []):
            m["workloads"].append("train4.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    chip = root / "benchmarks" / "chip"
    (chip / "traffic" / "train.tiny.zero1.json").write_text(
        json.dumps(dict(tiny.TRAIN, zero_stage=1)))
    (chip / "limits" / "train4.tiny.json").write_text(
        json.dumps({{"limits": tiny.LIMITS["train"]}}))
    res = harness.run(["--workload", "train4.tiny", "--seed", "31",
                       "--seconds", "0.5", "--trace", "0"], root=root,
                      require_tpu=False)
    print(json.dumps(res))
""")


@pytest.mark.parametrize("broken", [False, True],
                         ids=["sound", "exchange_left_out"])
def test_zero1_on_four_devices_and_without_its_exchange(tmp_path, broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ZERO1.format(here=str(HERE), root=str(tmp_path),
                        broken=broken)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    if broken:
        assert _fails(res), res["checks"]
    else:
        assert res["correct"], res["checks"]
