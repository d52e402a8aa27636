"""A tiny copy of the benchmark for tests on the CPU, in a directory of its
own: the benchmark's family modules and a test-only one
(``families/qknorm.py`` beside this file), three registered
configurations at toy widths (two of the ``qwen2`` family, tied and
untied, and one of the ``qknorm`` family), small traffic, and a
``BENCHMARK.json`` naming them.  The ``qknorm`` family enters as files and
entries only, as a new architecture would."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
TEST_FAMILIES = Path(__file__).resolve().parent / "families"

TINY = {"hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000.0, "tie_word_embeddings": True,
        "vocab_size": 256}

# the qknorm family's configuration: QK-norm, no QKV bias, untied
TINY_QK = dict(TINY, head_dim=16, tie_word_embeddings=False)

TRAIN = {"kind": "train", "seq_len": 32, "batch_per_chip": 2,
         "zero_stage": 0,
         "optimizer": {"name": "adam", "lr": 0.0003, "b1": 0.9,
                       "b2": 0.999, "eps": 1e-08},
         "data": {"structure": 0.9, "a": 31, "b": 7, "seed": 0},
         "trace_steps": 2}

SERVE = {"kind": "serve", "rate_per_s": 20.0, "warmup_s": 0.3,
         "drain_s": 20, "trace_seconds": 0.3, "batch_slots": 4,
         "max_seq": 96, "kv_dtype": "float32",
         "prompt_len": {"median": 24, "sigma": 0.6, "min": 8, "max": 48},
         "output_len": {"median": 6, "sigma": 0.5, "min": 3, "max": 12}}

# set from tiny readings on the CPU, as a cell's limits are on the chip:
# bf16 program grad_gap <= 0.007 over 6 runs, float8 control 0.038-0.040;
# bf16 served-token gap <= 0.021, float8 control 0.29-0.59, a token
# changed to the next id 2.2-2.8
LIMITS = {"train": {"loss_gap": 1e-3, "grad_gap": 0.02, "change_gap": 0.1},
          "serve": {"token_gap": 0.1}}
# the qknorm family's, set the same way: bf16 program over 6 seeds
# loss_gap <= 8.3e-4, grad_gap <= 0.0055, change_gap <= 0.0026; float8
# control on 3 seeds 3.7e-3-5.5e-3, 0.015-0.044, 0.0062-0.011; served
# tokens 0.0, float8 control 0.17
LIMITS_QK = {"train": {"loss_gap": 2e-3, "grad_gap": 0.01,
                       "change_gap": 0.005},
             "serve": {"token_gap": 0.1}}


def register():
    from repro.configs.base import ModelConfig, register as reg
    from repro.configs import get_config

    for name, tied, qk in (("chipbench-tiny", True, False),
                           ("chipbench-tiny-untied", False, False),
                           ("chipbench-tiny-qknorm", False, True)):
        try:
            get_config(name)
        except Exception:
            reg(ModelConfig(
                name=name, family="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                vocab_size=256, qkv_bias=not qk, qk_norm=qk,
                tie_embeddings=tied, rope_theta=1_000_000.0, norm_eps=1e-6))


def make_root(tmp: Path, precision: str = "f32", serve_kv="float32") -> Path:
    """A checkout-like directory: BENCHMARK.json plus benchmarks/chip with
    tiny configurations, mixes and limits, every metric the readers
    define, the real metric readers and family modules, and the test-only
    ``qknorm`` family."""
    register()
    root = Path(tmp)
    chip = root / "benchmarks" / "chip"
    for sub in ("configs", "traffic", "limits"):
        (chip / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(CHIP / "metrics", chip / "metrics", dirs_exist_ok=True)
    shutil.copytree(CHIP / "families", chip / "families", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(TEST_FAMILIES / "qknorm.py", chip / "families" / "qknorm.py")
    confs = []
    for name, family, c, arch in (
            ("tiny", "qwen2", TINY, "chipbench-tiny"),
            ("tiny-untied", "qwen2", dict(TINY, tie_word_embeddings=False),
             "chipbench-tiny-untied"),
            ("tiny-qknorm", "qknorm", TINY_QK, "chipbench-tiny-qknorm")):
        (chip / "configs" / f"{name}.json").write_text(json.dumps(
            {"name": name, "family": family, "config": c,
             "precision": precision, "program": {"arch": arch}}))
        confs.append({"name": name, "source": "test", "reduced": [],
                      "file": f"benchmarks/chip/configs/{name}.json",
                      "why": "test"})
    (chip / "traffic" / "train.tiny.json").write_text(json.dumps(TRAIN))
    (chip / "traffic" / "serve.tiny.json").write_text(json.dumps(
        dict(SERVE, kv_dtype=serve_kv)))
    cells = [
        {"name": "train.tiny", "config": "tiny", "traffic": "train.tiny",
         "chips": 1, "why": "test"},
        {"name": "serve.tiny", "config": "tiny-untied",
         "traffic": "serve.tiny", "chips": 1, "why": "test"},
        {"name": "train.tiny-qknorm", "config": "tiny-qknorm",
         "traffic": "train.tiny", "chips": 1, "why": "test"},
        {"name": "serve.tiny-qknorm", "config": "tiny-qknorm",
         "traffic": "serve.tiny", "chips": 1, "why": "test"},
    ]
    for c in cells:
        kind = c["name"].split(".")[0]
        lim = LIMITS_QK if c["config"] == "tiny-qknorm" else LIMITS
        (chip / "limits" / f"{c['name']}.json").write_text(json.dumps(
            {"limits": lim[kind]}))
    bench = {"command": ["python3", "benchmarks/chip/run.py"],
             "paths": ["benchmarks/chip"], "run_seconds": 1,
             "configs": confs, "workloads": cells,
             "end_to_end": [_m(n, u, TRAIN_CELLS if n.startswith("train")
                               else SERVE_CELLS, bound=0.25)
                            for n, u in E2E],
             "per_layer": [_m(n, "%", TRAIN_CELLS if n.endswith(".train")
                              else SERVE_CELLS, moves=mv)
                           for n, mv in PER_LAYER]}
    bench["end_to_end"].append({"name": "setup_s", "unit": "s",
                                "better": "lower", "bound": 0.25,
                                "source": "host_clock"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


TRAIN_CELLS = ["train.tiny", "train.tiny-qknorm"]
SERVE_CELLS = ["serve.tiny", "serve.tiny-qknorm"]
E2E = [("train_tokens_per_s", "tokens/s"), ("ttft_p90_ms", "ms"),
       ("itl_p95_ms", "ms"), ("serve_tokens_per_s", "tokens/s")]
PER_LAYER = [("input_wait_ms.train", "train_tokens_per_s"),
             ("mfu.train", "train_tokens_per_s"),
             ("device_idle_share.train", "train_tokens_per_s"),
             ("exposed_collective_share.train", "train_tokens_per_s"),
             ("admit_wait_p50_ms.serve", "ttft_p90_ms"),
             ("device_idle_share.serve", "itl_p95_ms"),
             ("mfu.serve", "itl_p95_ms"),
             ("paged_attention_roofline", "itl_p95_ms")]


def _m(name, unit, cells, moves=None, bound=None):
    m = {"name": name, "unit": unit, "better": "lower",
         "source": "host_clock", "workloads": list(cells)}
    if moves:
        m.update(layer="test", moves=moves)
    else:
        m["bound"] = bound
    return m
