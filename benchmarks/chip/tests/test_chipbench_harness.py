"""Traffic, discovery by name, the device guard, and whole runs of the
harness at a tiny size on the CPU (``require_tpu=False``)."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import _paths  # noqa: F401
import tiny
from chipbench import harness, peaks, spec, traffic

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
MIX = json.loads((CHIP / "traffic" / "serve.chat.json").read_text())


@pytest.fixture
def cpu_run(monkeypatch):
    """Runs a tiny cell on the CPU: no compile cache, a peak for "cpu"."""
    import repro.launch.compile_cache as cc

    monkeypatch.setitem(peaks.PEAKS, "cpu", {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
        "source": "test"})
    monkeypatch.setattr(cc, "use_compile_cache", lambda: "off")

    def go(root, workload, seed=4294967301, seconds=1.0, trace=0):
        return harness.run(["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace",
                            str(trace)], root=root, require_tpu=False)
    return go


def test_schedule_is_deterministic_and_the_seed_only_reorders():
    a = traffic.serve_schedule(MIX, 2 ** 33 + 5, 45, 1000)
    b = traffic.serve_schedule(MIX, 2 ** 33 + 5, 45, 1000)
    c = traffic.serve_schedule(MIX, 5, 45, 1000)
    assert [(x.due, x.max_new_tokens, x.prompt.tolist()) for x in a] == \
        [(x.due, x.max_new_tokens, x.prompt.tolist()) for x in b]
    # a seed's high bits count: 5 and 2^33 + 5 differ
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    for part in (True, False):
        pa = [x for x in a if x.in_window == part]
        pc = [x for x in c if x.in_window == part]
        assert sorted(len(x.prompt) for x in pa) == \
            sorted(len(x.prompt) for x in pc)
        assert sorted(x.max_new_tokens for x in pa) == \
            sorted(x.max_new_tokens for x in pc)
    win = [x for x in a if x.in_window]
    assert len(win) == round(MIX["rate_per_s"] * 45)
    assert all(MIX["warmup_s"] <= x.due < MIX["warmup_s"] + 45
               for x in win)
    lens = [len(x.prompt) for x in win]
    assert min(lens) >= 128 and max(lens) <= 2048
    assert abs(np.median(lens) - 512) < 60


def test_discovery_finds_new_files_with_no_edit(tmp_path):
    """A configuration, a mix, a metric and a cell added as new files and
    entries are found by name; no existing file is edited."""
    root = tiny.make_root(tmp_path)
    chip = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    (chip / "configs" / "tiny-wide.json").write_text(json.dumps(
        {"family": "qwen2", "config": dict(tiny.TINY, intermediate_size=256)}))
    (chip / "traffic" / "train.tiny.long.json").write_text(json.dumps(
        dict(tiny.TRAIN, seq_len=64)))
    (chip / "metrics" / "steps.train.py").write_text(
        "def read(rec, trace):\n    return rec.get('steps')\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-wide", "source": "t",
                             "file": "benchmarks/chip/configs/tiny-wide.json",
                             "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "train.tiny-wide.long",
                               "config": "tiny-wide",
                               "traffic": "train.tiny.long", "chips": 1,
                               "why": "t"})
    bench["per_layer"].append({"name": "steps.train", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "train step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["train.tiny-wide.long"]})
    for m in bench["end_to_end"]:
        if "train.tiny" in m.get("workloads", []):
            m["workloads"].append("train.tiny-wide.long")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    b = spec.load_benchmark(root)
    cell = spec.cell(b, "train.tiny-wide.long")
    assert spec.dims(spec.config(b, cell["config"], root), root)["ff"] == 256
    assert spec.traffic(cell["traffic"], root)["seq_len"] == 64
    names = [m["name"] for m in spec.metrics(b, cell["name"], "per_layer")]
    assert names == ["steps.train"]
    assert spec.metric_reader("steps.train", root)({"steps": 3}, None) == 3
    after = {p: p.read_bytes() for p in before}
    assert after == before
    with pytest.raises(spec.SpecError):
        spec.cell(b, "no.such.cell")


def test_run_py_exits_nonzero_on_the_cpu_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload",
         "train.qwen2-1.5b-L4.s2k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_py_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    files has no program to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "train.qwen2-1.5b-L4.s2k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_train_run_on_cpu_is_correct_and_reports_its_metrics(tmp_path,
                                                             cpu_run):
    root = tiny.make_root(tmp_path, precision="bf16")
    res = cpu_run(root, "train.tiny")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert res["device"]["platform"] == "cpu"


def test_serve_run_on_cpu_is_correct_and_times_from_due(tmp_path, cpu_run,
                                                        monkeypatch):
    """A stall of the engine after the window opens delays requests that
    are due during it; their first-token time counts the stall, which a
    time from submission would hide (they are submitted after it)."""
    from repro.serve.engine import PagedDecodeEngine

    root = tiny.make_root(tmp_path)
    res = cpu_run(root, "serve.tiny", seconds=1.5)
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "serve_tokens_per_s", "setup_s"}
    calm = res["metrics"]["ttft_p90_ms"]["value"]

    step = PagedDecodeEngine.step
    state = {"n": 0}

    def stalled(self):
        state["n"] += 1
        if state["n"] == 40:
            time.sleep(1.0)
        return step(self)

    monkeypatch.setattr(PagedDecodeEngine, "step", stalled)
    res = cpu_run(root, "serve.tiny", seconds=1.5)
    assert res["metrics"]["ttft_p90_ms"]["value"] > calm + 300


@pytest.mark.parametrize("cell", ["train.tiny", "serve.tiny"])
def test_traced_run_reports_per_layer_metrics(tmp_path, cpu_run,
                                              monkeypatch, cell):
    """A ``--trace 1`` run reports the cell's per-layer metrics, its
    device's busy and window seconds, and a breakdown.  The CPU has no TPU
    plane, so the trace is a hand-built one with the kernel's op in it."""
    from chipbench import trace_reduce

    fake = {"devices": {"0": [["%paged_attention.1 = bf16[]", 0.0, 2e6],
                              ["%fusion.2 = f32[]", 2e6, 6e6]]},
            "async": {}, "host": [["bench.window", 0.0, 1e7],
                                  ["engine.step", 0.0, 9e6]]}
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda path: fake)
    root = tiny.make_root(tmp_path)
    res = cpu_run(root, cell, seconds=2.0, trace=1)
    bench = spec.load_benchmark(root)
    want = {m["name"] for m in spec.metrics(bench, cell, "per_layer")}
    if cell == "train.tiny":  # one chip: no collective to read
        want.discard("exposed_collective_share.train")
    assert set(res["metrics"]) == want
    assert res["device"]["busy_s"] == pytest.approx(8e-3)
    assert res["device"]["window_s"] == pytest.approx(1e-2)
    idle = [v for k, v in res["metrics"].items() if "idle" in k]
    assert idle and idle[0]["value"] == pytest.approx(20.0)
    for name, m in res["metrics"].items():
        assert m["value"] > 0, name
    assert res["breakdown"]["device_ops"][0][0].startswith("fusion.2")
    assert res["correct"]


PHASE_READERS = ["forward_ms.train", "backward_ms.train",
                 "recompute_ms.train", "optimizer_ms.train",
                 "flash_attention_roofline", "attention_flash_share.train"]


def test_traced_train_run_reports_the_phase_readers(tmp_path, cpu_run,
                                                    monkeypatch):
    """The six readers added to a root as entries only.  The CPU has no
    TPU plane, so the trace is made from the compiled text the run hands
    to the reduction: each instruction of the step that runs as an op
    takes 1 us, inside a run of the step's module.  The phase readers read
    those; the CPU takes the dense attention path, so the attention share
    reads 0 and no flash kernel runs."""
    from chipbench import trace_reduce

    from repro.core import scopes

    reduce = trace_reduce.reduce_trace
    seen = {}

    def from_text(trace, top=10, step_text=None):
        assert step_text and step_text.startswith("HloModule jit_step")
        phases = scopes.phases(step_text)
        ops = [[f"%{n} = f32[] op()", i * 1e3, 1e3]
               for i, n in enumerate(sorted(phases))]
        end = len(ops) * 1e3
        seen["phases"] = list(phases.values())
        return reduce({"devices": {"0": ops},
                       "modules": {"0": [["jit_step(1)", 0.0, end]]},
                       "host": [["bench.window", 0.0, end]]}, top,
                      step_text=step_text)

    monkeypatch.setattr(trace_reduce, "load_xplane", lambda path: {})
    monkeypatch.setattr(trace_reduce, "reduce_trace", from_text)
    root = tiny.make_root(tmp_path, precision="bf16")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name in PHASE_READERS:
        bench["per_layer"].append({
            "name": name, "unit": "%" if "roofline" in name else "ms",
            "better": "lower", "source": "device_trace", "layer": "t",
            "moves": "train_tokens_per_s", "workloads": ["train.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = cpu_run(root, "train.tiny", seconds=2.0, trace=1)
    assert res["correct"]
    steps = tiny.TRAIN["trace_steps"]
    for phase in ("forward", "backward", "recompute", "optimizer"):
        want = 1e-3 * seen["phases"].count(phase) / steps
        assert res["metrics"][f"{phase}_ms.train"]["value"] == \
            pytest.approx(want)
    assert res["metrics"]["attention_flash_share.train"]["value"] == 0.0
    assert "flash_attention_roofline" not in res["metrics"]
