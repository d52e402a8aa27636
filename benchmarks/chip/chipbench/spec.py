"""Find a cell's parts by name: ``BENCHMARK.json`` at the checkout's root,
``configs/<config>.json`` (the file the benchmark names), the family module
``families/<family>.py`` that the configuration names, ``traffic/<mix>.json``
and ``metrics/<metric>.py`` beside this package.  A later change adds a
configuration, a family, a mix or a metric as a new file and a new entry,
and edits nothing that is there.

A family module holds what depends on the architecture, and exports:

* ``dims(conf)``: the sizes the rest uses, from the file's published keys;
  a dict of numbers, strings and tuples that holds at least ``vocab``,
  ``layers``, ``heads``, ``kv_heads`` and ``head_dim``;
* ``program_config(conf, dims)``: the program's ``ModelConfig`` cut to the
  file's depth, every published size it depends on checked against the
  file (a difference raises);
* ``shapes(dims)`` and ``std(path, shape)``: the parameter tree as the
  program keeps it, and each leaf's (mean, std) (``weights.init``);
* the reference's parts (``reference``): ``embed``, ``plan``, the layer
  functions it names, ``head``;
* FLOP counts (``flops``): ``train_flops_per_token(dims, seq_len)``,
  ``prefill_flops(dims, positions, logit_rows)``,
  ``decode_flops(dims, ctx_lens)``, and the cost of any kernel that its
  cells' readers read."""

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
        "chipbench_metric_" + _ident(name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


_FAMILIES = {}


def family(conf: dict, root: Path = CHECKOUT):
    """The family module that a configuration file names under
    ``"family"``, from ``families/<family>.py`` (loaded once a file)."""
    name = conf.get("family")
    if not name:
        raise SpecError(f"configuration {conf.get('name', '?')!r} names no "
                        f"family (a \"family\" key)")
    path = (Path(root) / CHIP_DIR.relative_to(CHECKOUT) / "families"
            / f"{name}.py").resolve()
    if not path.is_file():
        raise SpecError(f"no family module {path} for family {name!r}")
    if path not in _FAMILIES:
        mod_spec = importlib.util.spec_from_file_location(
            "chipbench_family_" + _ident(name), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _FAMILIES[path] = mod
    return _FAMILIES[path]


def dims(conf: dict, root: Path = CHECKOUT) -> dict:
    """The sizes of a configuration, as its family reads them."""
    return family(conf, root).dims(conf)


def _ident(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")
