"""Family modules: the Qwen2 family reads what the harness read before it
was split into families, and a second family (``families/qknorm.py``
beside this file) enters a root as files and entries only, and trains,
serves and calibrates there on the CPU."""

import hashlib
import importlib.util
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
import tiny
from chipbench import harness, peaks, reference, spec, training, weights

HERE = Path(__file__).resolve().parent
FROZEN = json.loads((HERE / "data" / "qwen2_frozen.json").read_text())


@pytest.fixture
def cpu_run(monkeypatch):
    """Runs a tiny cell on the CPU: no compile cache, a peak for "cpu"."""
    import repro.launch.compile_cache as cc

    monkeypatch.setitem(peaks.PEAKS, "cpu", {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
        "source": "test"})
    monkeypatch.setattr(cc, "use_compile_cache", lambda: "off")

    def go(root, workload, seed=4294967301, seconds=1.0):
        return harness.run(["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0"],
                           root=root, require_tpu=False)
    return go


def _rows():
    return [reference.train_tokens(0, s, 1, 2, 32, 256, 0.9, 31, 7)
            for s in range(3)]


def test_qwen2_family_reads_as_before():
    """Weights of a seed, the reference's three steps in float32 and in
    float8, a served sequence's logits and the step's FLOPs, at the tiny
    size, equal what the harness read before the families (frozen in
    ``data/qwen2_frozen.json``)."""
    fam = spec.family({"family": "qwen2"})
    for tied in (True, False):
        dims = fam.dims({"config": dict(tiny.TINY, tie_word_embeddings=tied)})
        w = weights.init(fam, weights.key(3), dims, jnp.float32)
        got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()
               for k, v in reference.flat(w).items()}
        assert got == FROZEN[f"weights_sha256.{'tied' if tied else 'untied'}"]
    dims = fam.dims({"config": tiny.TINY})
    opt = tiny.TRAIN["optimizer"]
    assert reference.train_steps(fam, 11, dims, _rows(), opt) == \
        FROZEN["train_steps"]
    low = reference.train_steps(fam, 11, dims, _rows(), opt,
                                rnd=reference.fp8)
    assert low["losses"] == FROZEN["train_steps_fp8_losses"]
    ud = fam.dims({"config": dict(tiny.TINY, tie_word_embeddings=False)})
    uw = weights.init(fam, weights.key(3), ud, jnp.float32)
    toks = (np.arange(40, dtype=np.int32) * 37 + 5) % 256
    lg = reference.sequence_logits(fam, uw, toks, ud)
    assert hashlib.sha256(np.asarray(lg).tobytes()).hexdigest() == \
        FROZEN["sequence_logits_sha256.untied"]
    assert fam.train_flops_per_token(dims, 32) * 2 * 32 == \
        FROZEN["flops_per_step"]


def test_a_configuration_names_a_family_that_exists(tmp_path):
    with pytest.raises(spec.SpecError, match="names no family"):
        spec.family({"name": "x", "config": tiny.TINY})
    with pytest.raises(spec.SpecError, match="no family module .*nosuch"):
        spec.family({"family": "nosuch", "config": tiny.TINY})
    root = tiny.make_root(tmp_path)
    assert spec.family({"family": "qknorm"}, root).plan
    with pytest.raises(spec.SpecError, match="no family module"):
        spec.family({"family": "qknorm"})  # not in the checkout


def test_the_second_family_enters_as_files_only(tmp_path):
    """The qknorm cells are found by name, and its family's tree is the
    program's: QK-norm scales and no QKV bias."""
    import jax

    from repro.configs import get_config
    from repro.models import transformer as T

    root = tiny.make_root(tmp_path)
    bench = spec.load_benchmark(root)
    conf = spec.config(bench, spec.cell(bench, "train.tiny-qknorm")["config"],
                       root)
    fam = spec.family(conf, root)
    dims = fam.dims(conf)
    cfg = fam.program_config(conf, dims)
    assert cfg.qk_norm and not cfg.qkv_bias and not cfg.tie_embeddings
    tree = weights.tree_paths(fam.shapes(dims))
    assert tree == weights.tree_paths(jax.eval_shape(
        lambda: T.init_model(jax.random.PRNGKey(0), cfg)))
    assert "stack/0/attn/q_norm/scale" in tree
    assert not any(k.endswith("bq") for k in tree)
    # a size that differs from the program's is an error, not a change
    bad = dict(conf, config=dict(conf["config"], intermediate_size=256))
    with pytest.raises(ValueError, match="d_ff"):
        fam.program_config(bad, fam.dims(bad))
    assert get_config("chipbench-tiny-qknorm").qk_norm


def test_the_second_family_forward_matches_the_program(tmp_path):
    from repro.models import transformer as T

    root = tiny.make_root(tmp_path)
    fam = spec.family({"family": "qknorm"}, root)
    conf = {"config": tiny.TINY_QK, "program":
            {"arch": "chipbench-tiny-qknorm"}}
    dims = fam.dims(conf)
    cfg = fam.program_config(conf, dims)
    params = weights.init(fam, weights.key(5), dims, jnp.float32)
    toks = (np.arange(40, dtype=np.int32) * 11 + 3) % 256
    prog, _ = T.forward(params, cfg, tokens=jnp.asarray(toks)[None])
    ref = reference.sequence_logits(fam, params, toks, dims)
    np.testing.assert_allclose(np.asarray(prog[0]), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cell", ["train.tiny-qknorm", "serve.tiny-qknorm"])
def test_the_second_family_runs_correct(tmp_path, cpu_run, cell):
    precision = "bf16" if cell.startswith("train") else "f32"
    root = tiny.make_root(tmp_path, precision=precision)
    res = cpu_run(root, cell, seconds=1.5)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    limits = tiny.LIMITS_QK[cell.split(".")[0]]
    assert {k: c["limit"] for k, c in res["checks"].items()} == limits


def test_the_second_family_float8_control_fails_its_limits(tmp_path):
    root = tiny.make_root(tmp_path)
    fam = spec.family({"family": "qknorm"}, root)
    dims = fam.dims({"config": tiny.TINY_QK})
    opt = tiny.TRAIN["optimizer"]
    for seed in (1, 2, 3):
        ref = reference.train_steps(fam, seed, dims, _rows(), opt)
        low = reference.train_steps(fam, seed, dims, _rows(), opt,
                                    rnd=reference.fp8)
        got = training.compare(low, ref)
        assert any(got[k] > v for k, v in tiny.LIMITS_QK["train"].items()), \
            got
    params = weights.init(fam, weights.key(4), dims, jnp.bfloat16)
    r = np.random.default_rng(0)
    served = [(r.integers(0, 256, 30), list(r.integers(0, 256, 12)))
              for _ in range(3)]
    g = reference.served_gaps(fam, params, dims, served, 64,
                              rnd=reference.fp8)
    worst = max(float(x.max()) for x in g["control_gaps"])
    assert worst > tiny.LIMITS_QK["serve"]["token_gap"]


def test_calibrate_runs_the_second_family_reference(tmp_path, monkeypatch,
                                                    capsys):
    """``calibrate.py`` with control seeds only runs the reference of the
    qknorm family, its float8 control and the half-batch fault."""
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "use_compile_cache", lambda: "off")
    monkeypatch.setitem(peaks.PEAKS, "cpu", {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
        "source": "test"})
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_calibrate", HERE.parent / "calibrate.py")
    calibrate = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(calibrate)
    root = tiny.make_root(tmp_path, precision="bf16")
    assert calibrate.main(["--workload", "train.tiny-qknorm",
                           "--control-seeds", "5"], root=root,
                          require_tpu=False) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    kinds = {x["kind"]: x for x in lines}
    assert set(kinds) == {"control_fp8", "fault_half_batch"}
    lim = tiny.LIMITS_QK["train"]
    for x in kinds.values():
        assert any(x[k] > v for k, v in lim.items()), x
