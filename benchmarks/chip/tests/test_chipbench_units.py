"""Peaks, operation counts and trace reduction of the chip benchmark."""

import json
import math
from pathlib import Path

import pytest

import _paths  # noqa: F401
from chipbench import flops, peaks, spec, trace_reduce

DATA = Path(__file__).resolve().parent / "data"
QWEN2 = spec.family({"family": "qwen2"})
TINY = {"d": 64, "heads": 4, "kv_heads": 2, "head_dim": 16, "ff": 128,
        "vocab": 256, "layers": 2}


def test_peaks_known_kind_and_unknown_kind_raises():
    p = peaks.peaks("TPU v5 lite")
    assert (p["flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")


def test_flops_against_hand_counts():
    # one layer: q 64·64, k and v 64·32 each, o 64·64, mlp 3·64·128
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert QWEN2.layer_matmul_params(TINY) == per_layer
    n = 2 * per_layer + 256 * 64
    seq = 8
    # causal attention: query i sees i+1 keys, 4·H·Dh FLOPs per key
    attn = sum(4 * 4 * 16 * (i + 1) for i in range(seq)) / seq * 2
    assert QWEN2.train_flops_per_token(TINY, seq) == pytest.approx(
        6 * n + 3 * attn)
    pre = QWEN2.prefill_flops(TINY, [0, 1, 2], logit_rows=1)
    assert pre == 2 * (2 * per_layer * 3 + 4 * 4 * 16 * (1 + 2 + 3)) \
        + 2 * 256 * 64
    dec = QWEN2.decode_flops(TINY, [5, 7])
    assert dec == 2 * (2 * per_layer * 2 + 4 * 4 * 16 * 12) \
        + 2 * 2 * 256 * 64
    f, b = flops.paged_attention_cost(TINY, [5, 7], kv_bytes=2, q_bytes=2)
    assert f == 4 * 4 * 16 * 12
    assert b == 2 * 12 * 2 * 16 * 2 + 2 * 2 * 4 * 16 * 2


def _ev(name, s, e):
    return [name, float(s), float(e - s)]


def test_reduce_hand_built_trace():
    """Two chips over a window of 100 ns.  Chip 0: ops 0-30 and 50-80, a
    collective 20-60 (exposed 30-50); a while op 0-30 encloses a fusion
    10-20.  Chip 1: one op 0-100, a collective in flight 40-70 under it."""
    tr = {"devices": {
        "0": [_ev("%while.1 = ()", 0, 30), _ev("%fusion.2 = f32[]", 10, 20),
              _ev("%fusion.3 = f32[]", 50, 80),
              _ev("%all-reduce.4 = f32[]", 20, 60)],
        "1": [_ev("%fusion.5 = f32[]", 0, 100)]},
        "async": {"1": [_ev("%all-gather-start.6 = f32[]", 40, 70)]},
        "host": [_ev("bench.window", 0, 100), _ev("bench.batch", 60, 100),
                 _ev("bench.step", 0, 60)]}
    r = trace_reduce.reduce_trace(tr)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(100 * ns)
    # chip 0 busy: 0-80 minus nothing idle except 80-100 → 80; chip 1: 100
    assert r["busy_s"] == pytest.approx(90 * ns)
    assert r["idle_share"] == pytest.approx(0.1)
    assert r["collective_s"] == pytest.approx((40 + 30) / 2 * ns)
    assert r["exposed_collective_s"] == pytest.approx(20 / 2 * ns)
    # self time: the while op loses its enclosed fusion
    assert r["op_s"]["%while.1 = ()"] == pytest.approx(20 / 2 * ns)
    assert r["op_s"]["%fusion.2 = f32[]"] == pytest.approx(10 / 2 * ns)
    # chip 0 idles 80-100, under bench.batch (innermost, not the window)
    assert r["idle_gaps"] == [["bench.batch", pytest.approx(10 * ns)]]
    assert trace_reduce.short_name("%fusion.3 = f32[] fusion(x)") == \
        "fusion.3 f32[] fusion(x)"


def test_reduce_clips_to_window_and_needs_a_device():
    tr = {"devices": {"0": [_ev("%a = f32[]", 0, 50)]},
          "host": [_ev("bench.window", 25, 75)]}
    r = trace_reduce.reduce_trace(tr)
    assert r["busy_s"] == pytest.approx(25e-9)
    assert r["idle_share"] == pytest.approx(0.5)
    assert r["idle_gaps"] == [["(no annotation)", pytest.approx(25e-9)]]
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"devices": {}, "host": []})


def test_reduce_recorded_chip_trace():
    """A trace recorded on a TPU v5e: engine steps of the 14B-L8 server,
    each inside an ``engine.step`` annotation."""
    files = sorted(DATA.glob("*.xplane.pb"))
    assert files, "the recorded trace is missing"
    expect = json.loads((DATA / "recorded_trace.json").read_text())
    tr = trace_reduce.load_xplane(str(files[0]))
    assert list(tr["devices"]) == ["0"]
    r = trace_reduce.reduce_trace(tr)
    for k in ("window_s", "busy_s", "collective_s"):
        assert r[k] == pytest.approx(expect[k], rel=1e-9, abs=1e-12)
    assert 0 < r["busy_s"] <= r["window_s"]
    kernel = trace_reduce.op_seconds(r, lambda k: "paged_attention" in k)
    assert kernel == pytest.approx(expect["paged_attention_s"], rel=1e-9)
    assert not math.isclose(kernel, 0.0)
    # every op's self time adds up to the union of busy time or more
    assert sum(r["op_s"].values()) >= r["busy_s"] * (1 - 1e-9)


def test_spec_metric_selection():
    bench = spec.load_benchmark()
    names = {w["name"] for w in bench["workloads"]}
    for cell in names:
        e2e = [m["name"] for m in spec.metrics(bench, cell, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        per = spec.metrics(bench, cell, "per_layer")
        assert per
        for m in per:
            assert m["moves"] in e2e
            assert callable(spec.metric_reader(m["name"]))


STEP_TEXT = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %multiply.1 = f32[8]{0} multiply(f32[8]{0} %param_0, f32[8]{0} %param_0), metadata={op_name="jit(step)/transpose(jvp(train.forward))/moe.dispatch/mul"}
  ROOT %bitcast.2 = f32[8]{0} bitcast(f32[8]{0} %multiply.1)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %add.3 = f32[8]{0} add(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(step)/jvp(train.forward)/moe.dispatch/add"}
  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %add.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jvp(train.forward)/add"}
  %subtract.5 = f32[8]{0} subtract(f32[8]{0} %fusion.4, f32[8]{0} %p), metadata={op_name="jit(step)/train.optimizer/sub"}
  ROOT %copy.6 = f32[8]{0} copy(f32[8]{0} %subtract.5)
}
"""


def test_reduce_joins_ops_to_the_steps_phases_and_scopes():
    """One chip, window 0-100 ns.  The step's module runs 0-60: add.3
    (forward) twice, fusion.4 (its fused root is a backward product under
    ``moe.dispatch``, though the fusion's own op_name is forward),
    subtract.5 (optimizer), copy.6 (no op_name).  Another module runs
    70-90, and its op of the same instruction name is not the step's.
    The chip idles 55-70 under the program's ``data.batch`` span and
    80-100 under the harness's ``bench.batch``."""
    add = "%add.3 = f32[8]{0} add(f32[8]{0} %p, f32[8]{0} %p)"
    tr = {"devices": {"0": [
        _ev(add, 0, 10), _ev("%fusion.4 = f32[8]{0} fusion()", 10, 30),
        _ev("%subtract.5 = f32[8]{0} subtract()", 30, 40), _ev(add, 40, 50),
        _ev("%copy.6 = f32[8]{0} copy()", 50, 55),
        _ev("%add.3 = f32[] add()", 70, 80)]},
        "modules": {"0": [_ev("jit_step(77)", 0, 60),
                          _ev("jit_other(5)", 70, 90)]},
        "host": [_ev("bench.window", 0, 100), _ev("bench.batch", 50, 100),
                 _ev("data.batch", 55, 70)]}
    r = trace_reduce.reduce_trace(tr, step_text=STEP_TEXT)
    ns = 1e-9
    assert r["phase_s"] == {
        "forward": pytest.approx(20 * ns), "backward": pytest.approx(20 * ns),
        "optimizer": pytest.approx(10 * ns), "unscoped": pytest.approx(5 * ns),
        trace_reduce.OTHER_MODULE: pytest.approx(10 * ns)}
    assert sum(r["phase_s"].values()) == pytest.approx(
        sum(r["op_s"].values()))
    assert r["scope_s"] == {
        "jit(step)": pytest.approx(50 * ns),
        "train.forward": pytest.approx(40 * ns),
        "moe.dispatch": pytest.approx(40 * ns),
        "train.optimizer": pytest.approx(10 * ns)}
    assert r["op_n"][add] == 2 and r["op_n"]["%add.3 = f32[] add()"] == 1
    assert r["op_s"][add] == pytest.approx(20 * ns)
    assert r["idle_gaps"] == [["bench.batch", pytest.approx(20 * ns)],
                              ["data.batch", pytest.approx(15 * ns)]]
    # with no compiled text there is nothing to join
    bare = trace_reduce.reduce_trace(tr)
    assert bare["phase_s"] == {} and bare["scope_s"] == {}
    assert bare["op_n"] == r["op_n"]


def test_op_names_and_scope_names():
    module, ops = trace_reduce.op_names(STEP_TEXT)
    assert module == "jit_step"
    assert ops["fusion.4"].endswith("moe.dispatch/mul")
    assert ops["copy.6"] == ""
    assert trace_reduce.scope_names(
        "jit(step)/transpose(jvp(train.forward))/while/body/"
        "rematted_computation/dot_general;jit(step)/train.optimizer/add") == \
        {"jit(step)", "train.forward", "while", "body",
         "rematted_computation", "train.optimizer"}
    assert trace_reduce.instruction(
        "%flash_attention_fwd.2 = bf16[4] custom-call()") == \
        "flash_attention_fwd.2"


def _reader(name):
    return spec.metric_reader(name)


@pytest.mark.parametrize("phase,ms", [("forward", 48.79),
                                      ("backward", 93.61),
                                      ("recompute", 15.64),
                                      ("optimizer", 19.08)])
def test_phase_readers(phase, ms):
    """Six traced steps whose phase's device seconds sum to 6 × ``ms``."""
    read = _reader(f"{phase}_ms.train")
    trace = {"phase_s": {phase: 6 * ms * 1e-3, "unscoped": 1.0}}
    assert read({"traced_steps": 6}, trace) == pytest.approx(ms)
    assert read({"traced_steps": 6}, {"phase_s": {}}) is None
    assert read({"traced_steps": 6}, None) is None


def test_flash_attention_roofline_reader():
    """qwen2-1.5b's widths, 4 × 2048 tokens a chip, one step of 4 layers:
    8 forward calls (two instructions, forward and recompute) in 7.42 ms,
    4 dQ calls in 2.90 ms and 4 dK/dV calls in 3.36 ms: 16F =
    825,036,374,016 FLOPs in 13.68 ms, 30.61 % of 197 TFLOP/s.  A copy
    that reads the kernel's output is not the kernel."""
    dims = {"heads": 12, "kv_heads": 2, "head_dim": 128}
    rec = {"dims": dims, "batch_per_chip": 4, "seq_len": 2048,
           "peaks": peaks.peaks("TPU v5 lite")}
    f = "%flash_attention_fwd.{} = bf16[4,2048,12,128] custom-call()"
    trace = {"op_s": {f.format(1): 3.69e-3, f.format(2): 3.73e-3,
                      "%flash_attention_dq.3 = bf16[] custom-call()": 2.90e-3,
                      "%flash_attention_dkv.4 = (bf16[]) custom-call()":
                      3.36e-3,
                      "%copy.9 = bf16[] copy(bf16[] %flash_attention_fwd.1)":
                      9.0},
             "op_n": {f.format(1): 4, f.format(2): 4,
                      "%flash_attention_dq.3 = bf16[] custom-call()": 4,
                      "%flash_attention_dkv.4 = (bf16[]) custom-call()": 4,
                      "%copy.9 = bf16[] copy(bf16[] %flash_attention_fwd.1)":
                      4}}
    read = _reader("flash_attention_roofline")
    assert flops.causal_attn_flops(dims, 4, 2048) == 51_564_773_376
    assert read(rec, trace) == pytest.approx(
        100 * 825_036_374_016 / (13.68e-3 * 197e12))
    assert read(rec, trace) == pytest.approx(30.614, abs=1e-3)
    assert read(rec, {"op_s": {"%fusion.1 = f32[]": 1.0},
                      "op_n": {"%fusion.1 = f32[]": 1}}) is None


def test_attention_flash_share_reader():
    read = _reader("attention_flash_share.train")
    p = "/repro/attention_path/"
    assert read({"counters": {p + "flash": 4}}, None) == 100.0
    assert read({"counters": {p + "flash": 3, p + "dense": 1}}, None) == 75.0
    assert read({"counters": {p + "dense": 8}}, None) == 0.0
    assert read({"counters": {}}, None) is None
