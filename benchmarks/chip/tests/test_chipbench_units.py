"""Peaks, operation counts and trace reduction of the chip benchmark."""

import json
import math
from pathlib import Path

import pytest

import _paths  # noqa: F401
from chipbench import flops, peaks, spec, trace_reduce

DATA = Path(__file__).resolve().parent / "data"
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
    assert flops.layer_matmul_params(TINY) == per_layer
    n = 2 * per_layer + 256 * 64
    seq = 8
    # causal attention: query i sees i+1 keys, 4·H·Dh FLOPs per key
    attn = sum(4 * 4 * 16 * (i + 1) for i in range(seq)) / seq * 2
    assert flops.train_flops_per_token(TINY, seq) == pytest.approx(
        6 * n + 3 * attn)
    pre = flops.prefill_flops(TINY, [0, 1, 2], logit_rows=1)
    assert pre == 2 * (2 * per_layer * 3 + 4 * 4 * 16 * (1 + 2 + 3)) \
        + 2 * 256 * 64
    dec = flops.decode_flops(TINY, [5, 7])
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
