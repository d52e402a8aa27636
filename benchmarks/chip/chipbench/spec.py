"""Find a cell's parts by name: ``BENCHMARK.json`` at the checkout's root,
``configs/<config>.json`` (the file the benchmark names), ``traffic/<mix>.json``
and ``metrics/<metric>.py`` beside this package.  A later change adds a
configuration, a mix or a metric as a new file and a new entry, and edits
nothing that is there."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[1]   # benchmarks/chip
CHECKOUT = CHIP_DIR.parents[1]


class SpecError(ValueError):
    pass


def load_benchmark(root: Path = CHECKOUT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _one(entries, name, what):
    hits = [e for e in entries if e["name"] == name]
    if len(hits) != 1:
        raise SpecError(f"{what} {name!r}: {len(hits)} entries in "
                        f"BENCHMARK.json (have "
                        f"{sorted(e['name'] for e in entries)})")
    return hits[0]


def cell(bench: dict, name: str) -> dict:
    return _one(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: Path = CHECKOUT) -> dict:
    entry = _one(bench["configs"], name, "configuration")
    return json.loads((Path(root) / entry["file"]).read_text())


def traffic(name: str, root: Path = CHECKOUT) -> dict:
    path = Path(root) / CHIP_DIR.relative_to(CHECKOUT) / "traffic" \
        / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic mix file {path}")
    return json.loads(path.read_text())


def metrics(bench: dict, cell_name: str, kind: str) -> list:
    """The cell's ``end_to_end`` or ``per_layer`` metrics: those that list
    it under ``workloads``, or that have no such list and move an
    end-to-end metric that the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in names)]


def metric_reader(name: str, root: Path = CHECKOUT):
    """``read(rec, trace)`` from ``metrics/<name>.py``."""
    path = Path(root) / CHIP_DIR.relative_to(CHECKOUT) / "metrics" \
        / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for metric {name!r}")
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def dims(conf: dict) -> dict:
    """The sizes ``flops`` and the reference use, from a configuration
    file's published keys."""
    c = conf["config"]
    return {
        "d": c["hidden_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim",
                          c["hidden_size"] // c["num_attention_heads"]),
        "ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "layers": c["num_hidden_layers"],
        "eps": c["rms_norm_eps"],
        "theta": c["rope_theta"],
        "tied": c["tie_word_embeddings"],
    }
