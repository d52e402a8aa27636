"""One run of one cell: find its parts by name, guard the device, hand the
cell to the module of its traffic's kind, and print the result line.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), and last ``checks``: each number compared with its limit.
The same numbers are the last lines of standard error."""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from chipbench import peaks as peaks_mod
from chipbench import spec


class DeviceError(RuntimeError):
    pass


@dataclass
class Ctx:
    workload: str
    seed: int
    seconds: float
    trace: bool
    cell: dict
    conf: dict
    mix: dict
    limits: dict
    dims: dict
    family: object  # the module families/<family>.py
    chips: int
    peaks: dict
    root: Path
    t_process: float
    devices: list = field(default_factory=list)
    trace_dir: Path = None

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip (0 where the backend
        reports none)."""
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    def log(self, msg: str) -> None:
        print(f"[{self.workload}] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description="one run of a benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def limits(cell_name: str, root: Path) -> dict:
    path = Path(root) / spec.CHIP_DIR.relative_to(spec.CHECKOUT) / "limits" \
        / f"{cell_name}.json"
    if not path.is_file():
        raise spec.SpecError(f"no limits file {path}")
    return json.loads(path.read_text())["limits"]


def check_device(chips: int, require_tpu: bool):
    """The devices the cell runs on; raises ``DeviceError`` off a TPU, in
    the kernels' interpret mode, or with fewer chips than the cell asks."""
    import jax

    from repro.kernels.ops import default_interpret

    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise DeviceError(f"needs a TPU, found platform "
                              f"{devs[0].platform!r}")
        if default_interpret():
            raise DeviceError("the Pallas kernels would run in interpret "
                              f"mode on backend {jax.default_backend()!r}")
    if len(devs) < chips:
        raise DeviceError(f"the cell asks for {chips} chips, JAX finds "
                          f"{len(devs)}")
    return devs[:chips]


def run(argv, *, t0: float = None, root: Path = spec.CHECKOUT,
        require_tpu: bool = True) -> dict:
    """Runs the cell and returns the result dict (``None`` when the device
    is wrong, after saying why on standard error)."""
    t0 = time.time() if t0 is None else t0
    args = parse(argv)
    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, args.workload)
    conf = spec.config(bench, cell["config"], root)
    fam = spec.family(conf, root)
    mix = spec.traffic(cell["traffic"], root)
    lim = limits(cell["name"], root)

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    try:
        devs = check_device(cell["chips"], require_tpu)
    except DeviceError as e:
        print(f"run.py: {e}; no result", file=sys.stderr, flush=True)
        return None
    kind = devs[0].device_kind
    ctx = Ctx(args.workload, args.seed, args.seconds, bool(args.trace), cell,
              conf, mix, lim, fam.dims(conf), fam, cell["chips"],
              peaks_mod.peaks(kind), Path(root), t0, devs,
              Path(root) / ".bench_trace" / args.workload)
    ctx.log(f"device {devs[0].platform} {kind} x{len(devs)}; compile "
            f"cache {cache}; seed {args.seed}; {args.seconds} s; trace "
            f"{args.trace}")
    if mix["kind"] == "train":
        from chipbench import training as cell_kind
    elif mix["kind"] == "serve":
        from chipbench import serving as cell_kind
    else:
        raise spec.SpecError(f"unknown traffic kind {mix['kind']!r}")
    rec = cell_kind.run(ctx)
    gc.collect()
    return result(bench, ctx, rec)


def result(bench: dict, ctx: Ctx, rec: dict) -> dict:
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": rec["correct"], "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": {}, "device": device}
    if ctx.trace:
        tr = rec["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        for m in spec.metrics(bench, ctx.workload, "per_layer"):
            value = spec.metric_reader(m["name"], ctx.root)(rec, tr)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    else:
        for m in spec.metrics(bench, ctx.workload, "end_to_end"):
            out["metrics"][m["name"]] = {"value": rec["e2e"][m["name"]],
                                         "unit": m["unit"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in rec["checks"].items()}
    return out


def main(argv, *, t0: float = None) -> int:
    res = run(argv, t0=t0)
    if res is None:
        return 2
    for k, c in res["checks"].items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {res['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0
