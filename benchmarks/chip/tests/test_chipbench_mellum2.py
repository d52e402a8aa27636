"""The Mellum2 family (``families/mellum2.py``) at a tiny size on the CPU:
its reference against the program on seeded weights (logits, loss,
gradients), its expert shares against the uncut layer, its YaRN and its
window against the published formulas, its cell trained ``correct``
through the harness as files and entries only, and the four readers the
cell adds, on a synthetic record and trace."""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
import tiny
from chipbench import harness, peaks, reference, spec, training, weights

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ARCH = "chipbench-tiny-mellum2"

# the published configuration at toy widths: the S,S,S,F period, window 8
# at length 32, YaRN over 16 original positions (so that the ramp and
# both ends of it show at 32), 8 experts top-2 of which 2 held; the file
# holds the published keys at its top level
PUBLISHED = json.loads((CHIP / "configs" / "mellum2-12b-L4-E8.json")
                       .read_text())
TINY_M2 = dict(PUBLISHED, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=96,
               moe_intermediate_size=32, num_hidden_layers=4, num_experts=2,
               num_experts_per_tok=2, vocab_size=128, sliding_window=8,
               rope_parameters={
                   "full_attention": dict(
                       PUBLISHED["rope_parameters"]["full_attention"],
                       original_max_position_embeddings=16),
                   "sliding_attention":
                       PUBLISHED["rope_parameters"]["sliding_attention"]})

# set from tiny readings on the CPU, as a cell's limits are on the chip
# (bf16 program, first expert 2): program over 8 seeds loss_gap <= 1.94e-3,
# grad_gap <= 0.029 (a token that bf16 sends to another expert moves that
# expert's gradient, at this size, by much), change_gap <= 5.9e-3; float8
# control on 3 seeds loss_gap 3.07e-3-5.92e-3, grad_gap 8.5e-3-0.028,
# change_gap 4.9e-3-0.011: the loss separates them
LIMITS_M2 = {"loss_gap": 2.5e-3, "grad_gap": 0.1, "change_gap": 0.01}


def register():
    from repro.configs import get_config
    from repro.configs.base import ModelConfig, Yarn, register as reg

    try:
        get_config(ARCH)
    except KeyError:
        reg(ModelConfig(
            name=ARCH, family="moe", num_layers=8, d_model=64, num_heads=4,
            num_kv_heads=2, head_dim=16, d_ff=96, moe_d_ff=32,
            vocab_size=256, norm_eps=1e-6, tie_embeddings=False,
            rope_theta=500_000.0, sliding_window=8, global_every=4,
            global_rope_theta=500_000.0,
            global_rope_yarn=Yarn(16.0, 16, 32.0, 1.0, 1.2772588722239782),
            num_experts=8, top_k=2, router_aux_coef=0.0))


def conf(first=2, held=2, frozen_router=False):
    register()
    return dict(TINY_M2, name="tiny-mellum2", family="mellum2",
                num_experts=held,
                reduced={"num_experts": {"published": 8, "here": held}},
                first_expert=first, frozen_router=frozen_router,
                precision="f32", program={"arch": ARCH})


def make_root(tmp, precision="bf16"):
    """``tiny.make_root`` plus the Mellum2 cell, entered as files and
    entries only: a configuration, limits, the cell, and the cell's name
    in the lists of the metrics it reports, the four new among them."""
    root = tiny.make_root(tmp, precision=precision)
    chip = root / "benchmarks" / "chip"
    (chip / "configs" / "tiny-mellum2.json").write_text(json.dumps(
        dict(conf(), precision=precision)))
    (chip / "limits" / "train.tiny-mellum2.json").write_text(json.dumps(
        {"limits": LIMITS_M2}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-mellum2", "source": "test",
                             "reduced": [], "why": "test",
                             "file": "benchmarks/chip/configs/"
                                     "tiny-mellum2.json"})
    bench["workloads"].append({"name": "train.tiny-mellum2",
                               "config": "tiny-mellum2",
                               "traffic": "train.tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "train.tiny" in m.get("workloads", []):
            m["workloads"].append("train.tiny-mellum2")
    for name in NEW_READERS:
        bench["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "test",
            "moves": "train_tokens_per_s",
            "workloads": ["train.tiny-mellum2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


NEW_READERS = ("moe_ms.train", "expert_gmm_ms.train",
               "flash_attention_window_roofline", "moe_grouped_share.train")


@pytest.fixture
def fam():
    return spec.family({"family": "mellum2"})


def _rows():
    return [reference.train_tokens(0, s, 1, 2, 32, 128, 0.9, 31, 7)
            for s in range(3)]


# ---------------------------------------------------------------------------
# the reference against the program
# ---------------------------------------------------------------------------
def test_tree_and_sizes_are_the_programs(fam):
    from repro.models import transformer as T

    c = conf()
    dims = fam.dims(c)
    cfg = fam.program_config(c, dims)
    assert (cfg.experts_held, cfg.first_held_expert, cfg.num_experts) == \
        (2, 2, 8)
    assert weights.tree_paths(fam.shapes(dims)) == weights.tree_paths(
        jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg)))
    # a size that differs from the program's is an error, not a change
    bad = dict(c, moe_intermediate_size=64)
    with pytest.raises(ValueError, match="expert_d_ff"):
        fam.program_config(bad, fam.dims(bad))
    bad = dict(c, sliding_window=16)
    with pytest.raises(ValueError, match="sliding_window"):
        fam.program_config(bad, fam.dims(bad))


def test_a_program_without_the_architecture_fails_at_once(fam):
    c = dict(conf(), program={"arch": "no-such-mellum2"})
    with pytest.raises(spec.SpecError, match="family mellum2"):
        fam.program_config(c, fam.dims(c))


@pytest.mark.parametrize("frozen_router", [False, True])
def test_program_matches_reference(fam, frozen_router):
    """f32 on both sides, on seeded weights: logits of a sequence, and
    the loss and every gradient of a row, with the router learning or
    frozen (then its gradient is zero on both sides).  Tolerances: the
    two sum in other orders (blocked vs whole attention, per-expert vs
    grouped products), f32 round-off over four layers; routing agrees
    exactly at f32 on these weights."""
    from repro.models import transformer as T
    from repro.train.losses import lm_loss

    c = conf(frozen_router=frozen_router)
    dims = fam.dims(c)
    cfg = fam.program_config(c, dims)
    params = weights.init(fam, weights.key(5), dims, jnp.float32)
    toks = _rows()[0][0]
    prog, _ = T.forward(params, cfg, tokens=jnp.asarray(toks)[None])
    ref = reference.sequence_logits(fam, params, toks, dims)
    np.testing.assert_allclose(np.asarray(prog[0]), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def prog_loss(p):
        logits, aux = T.forward(p, cfg, tokens=jnp.asarray(toks)[None],
                                remat=True)
        return lm_loss(logits, jnp.asarray(toks)[None], aux)

    lp, gp = jax.value_and_grad(prog_loss)(params)
    lr, gr = jax.value_and_grad(reference.row_loss, argnums=1)(
        fam, params, jnp.asarray(toks), reference._hashable(dims))
    assert abs(float(lp) - float(lr)) <= 1e-5 * float(lr)
    for path, g in reference.flat(gr).items():
        got = reference.flat(gp)[path]
        assert float(jnp.linalg.norm(got - g)) <= \
            1e-4 * float(jnp.linalg.norm(g)) + 1e-7, path
        if path.endswith("router"):
            assert (float(jnp.abs(g).max()) == 0.0) == frozen_router


def test_reference_shares_sum_to_the_uncut_layer(fam):
    """Four shares of two experts each, routing over all eight, add up in
    the reference to the expert layer that holds all eight; with the
    attention, which every share computes alike, counted once, to the
    whole layer."""
    whole_dims = fam.dims(conf(first=0, held=8))
    params = weights.init(fam, weights.key(7), whole_dims, jnp.float32)
    layer = jax.tree.map(lambda w: w[0], params["stack"]["0"])
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
    pos = jnp.arange(32)
    whole = fam.window_layer(layer, x, pos, whole_dims)
    parts = []
    for first in range(0, 8, 2):
        dims = fam.dims(conf(first=first, held=2))
        share = dict(layer, moe=dict(layer["moe"], **{
            w: layer["moe"][w][first:first + 2]
            for w in ("w_gate", "w_up", "w_down")}))
        parts.append(fam.window_layer(share, x, pos, dims))
    # each part is x + attention + its experts' share; with none held,
    # x + attention
    shared = fam.window_layer(dict(layer, moe=dict(layer["moe"], **{
        w: layer["moe"][w][:0] for w in ("w_gate", "w_up", "w_down")})),
        x, pos, dict(whole_dims, held=0))
    got = shared + sum(p - shared for p in parts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_yarn_at_the_published_numbers(fam):
    """The family's YaRN frequencies at θ 5e5, Dh 128, ×16 over 8192,
    β 32 and 1, against the formula written out: ramp from pair 18 to
    35."""
    dims = fam.dims(PUBLISHED)
    theta, d = 500_000.0, 128
    i = np.arange(64)
    base = theta ** (-2.0 * i / d)
    share = np.clip((i - 18) / (35 - 18), 0.0, 1.0)
    want = base * (1 - share) + base / 16 * share
    np.testing.assert_allclose(fam.yarn_inv_freq(dims), want, rtol=1e-12)
    assert math.floor(d * math.log(8192 / (32 * 2 * math.pi))
                      / (2 * math.log(theta))) == 18
    x = jnp.ones((1, 2, 128))
    np.testing.assert_allclose(
        fam.yarn_rope(x, jnp.zeros((1,), jnp.int32), dims),
        1.2772588722239782 * x, rtol=1e-6)


def test_reference_window_edge(fam):
    """A key 1023 back is seen, one 1024 back is not (window 1024)."""
    dims = dict(fam.dims(PUBLISHED), head_dim=128)
    l = 2048
    q = jnp.zeros((l, 1, 128))
    pos = jnp.arange(l)
    for back, seen in ((1023, True), (1024, False)):
        v = jnp.zeros((l, 1, 128)).at[l - 1 - back].set(1.0)
        out = fam._attend(q, q, v, pos, 1024, dims, None)[l - 1, 0, 0]
        assert float(out) == (pytest.approx(1 / 1024) if seen else 0.0)


def test_flops_count_the_window_and_the_held_rows(fam):
    dims = fam.dims(json.loads((CHIP / "configs" / "mellum2-12b-L4-E8.json")
                               .read_text()))
    assert fam.expert_rows(dims) == 1.0  # top-8 of 64, 8 held
    seq = 8192
    window_keys = 1024 * 1025 // 2 + (seq - 1024) * 1024
    assert fam.window_attn_flops(dims, 4, seq) == \
        4 * 32 * 128 * 4 * window_keys
    per_token = fam.train_flops_per_token(dims, seq)
    attn = 2304 * 32 * 128 * 2 + 2 * 2304 * 4 * 128
    n = 4 * (attn + 3 * 2304 * 896) + 12288 * 2304
    keys = (3 * window_keys + seq * (seq + 1) // 2) / seq
    assert per_token == pytest.approx(6 * n + 3 * 4 * 32 * 128 * keys,
                                      rel=1e-12)
    assert 1.17e9 < per_token < 1.172e9


# ---------------------------------------------------------------------------
# the harness
# ---------------------------------------------------------------------------
@pytest.fixture
def cpu_run(monkeypatch):
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


def test_the_family_trains_correct_through_the_harness(tmp_path, cpu_run):
    root = make_root(tmp_path)
    res = cpu_run(root, "train.tiny-mellum2", seconds=1.5)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {k: c["limit"] for k, c in res["checks"].items()} == LIMITS_M2


def test_float8_control_fails_the_limits(fam):
    dims = fam.dims(conf())
    opt = tiny.TRAIN["optimizer"]
    for seed in (1, 2, 3):
        ref = reference.train_steps(fam, seed, dims, _rows(), opt)
        low = reference.train_steps(fam, seed, dims, _rows(), opt,
                                    rnd=reference.fp8)
        got = training.compare(low, ref)
        assert any(got[k] > v for k, v in LIMITS_M2.items()), got


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def _reader(name):
    return spec.metric_reader(name)


def _rec(fam):
    dims = fam.dims(json.loads((CHIP / "configs" / "mellum2-12b-L4-E8.json")
                               .read_text()))
    return {"dims": dims, "batch_per_chip": 4, "seq_len": 8192,
            "traced_steps": 4, "peaks": {"flops_per_s": 197e12},
            "counters": {"/repro/moe_path/grouped": 4,
                         "/repro/attention_path/flash": 4}}


def test_readers_on_a_synthetic_trace(fam):
    rec = _rec(fam)
    f = fam.window_attn_flops(rec["dims"], 4, 8192)
    trace = {
        "op_s": {"flash_attention_window_fwd.3": 0.02,
                 "flash_attention_window_dq": 0.01,
                 "flash_attention_window_dkv.1": 0.03,
                 "flash_attention_fwd": 0.5,
                 "expert_gmm.2": 0.06, "expert_tgmm": 0.04,
                 "fusion.7": 1.0},
        "op_n": {"flash_attention_window_fwd.3": 24,
                 "flash_attention_window_dq": 12,
                 "flash_attention_window_dkv.1": 12,
                 "flash_attention_fwd": 8,
                 "expert_gmm.2": 144, "expert_tgmm": 48, "fusion.7": 4},
        "scope_s": {"moe.experts": 0.2, "train.forward": 0.5},
    }
    assert _reader("moe_ms.train")(rec, trace) == pytest.approx(50.0)
    want = 100 * (24 + 2 * 12) * f / (0.06 * 197e12)
    assert _reader("flash_attention_window_roofline")(rec, trace) == \
        pytest.approx(want)
    assert _reader("expert_gmm_ms.train")(rec, trace) == \
        pytest.approx(1e3 * 0.1 / 4)
    assert _reader("moe_grouped_share.train")(rec, None) == 100.0
    rec["counters"]["/repro/moe_path/capacity"] = 4
    assert _reader("moe_grouped_share.train")(rec, None) == 50.0


def test_readers_find_nothing_where_there_is_nothing(fam):
    """A cell of another family, or a trace without the kernels or the
    scope: no number, and no error."""
    rec = _rec(fam)
    empty = {"op_s": {"fusion.1": 1.0}, "op_n": {"fusion.1": 1},
             "scope_s": {}}
    qwen = dict(rec, dims=spec.family({"family": "qwen2"}).dims(
        {"config": tiny.TINY}), counters={})
    for name in NEW_READERS:
        assert _reader(name)(qwen, empty) is None
        if name != "moe_grouped_share.train":
            assert _reader(name)(rec, empty) is None
    assert _reader("moe_grouped_share.train")(dict(rec, counters={}),
                                              None) is None
