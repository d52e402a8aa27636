"""Readings from which a cell's limits are set: the program's numbers on
many seeds, and the control's and the planted faults' numbers on a few,
each against the plain float32 reference, at the cell's own size.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,3 --control-seeds 4,5,6 [--seconds 20]

Training cells: the program's first three steps (as a run's set-up drives
them) on each of ``--seeds``; on each of ``--control-seeds`` the control
(the reference with every matrix product's operands rounded to float8)
and the faults planted in the reference put in the program's place (half
of each chip's rows left out; with several chips, each optimizer shard
kept to its own chip's gradient).  With no ``--seeds`` only the reference runs, on one chip or more, at the
cell's own size (its chips' rows).  Serving cells: a window of
``--seconds`` at the mix's load per seed, the served tokens' widest gap,
and on the control seeds the gap of the tokens the float8 model puts
first and of a served token changed to the next id.  One JSON line per
reading; the benchmark's runs never run this."""

import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

from chipbench import harness, peaks, reference, spec  # noqa: E402


def emit(**kw):
    print(json.dumps(kw), flush=True)


def context(args, seed, root=spec.CHECKOUT, require_tpu=True):
    """The cell's context; with no program seeds only the reference runs,
    and it runs on the devices there are (one is enough)."""
    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, args.workload)
    conf = spec.config(bench, cell["config"], root)
    devs = harness.check_device(cell["chips"] if args.seeds else 1,
                                require_tpu)
    return harness.Ctx(args.workload, seed, args.seconds, False, cell, conf,
                       spec.traffic(cell["traffic"], root),
                       harness.limits(cell["name"], root), spec.dims(conf),
                       cell["chips"], peaks.peaks(devs[0].device_kind),
                       Path(root), time.time(), devs)


def train(args, seeds, controls, **kw):
    import gc

    from chipbench import training as td

    refs = {}
    for seed in seeds:
        ctx = context(args, seed, **kw)
        tr = td.Trainer(ctx)
        prog = td.check_steps(tr)
        del tr
        gc.collect()
        ref = reference.train_steps(seed, ctx.dims, td.reference_batches(ctx),
                                    ctx.mix["optimizer"], devices=ctx.devices)
        refs[seed] = ref
        emit(kind="program", seed=seed, **td.compare(prog, ref),
             zero_grad_leaves=td.zero_grad_leaves(ref["grad_norms"]),
             losses=prog["losses"], ref_losses=ref["losses"])
    for seed in controls:
        ctx = context(args, seed, **kw)
        batches = td.reference_batches(ctx)
        opt = ctx.mix["optimizer"]
        ref = refs.get(seed) or reference.train_steps(
            seed, ctx.dims, batches, opt, devices=ctx.devices)
        variants = {"control_fp8": dict(rnd=reference.fp8),
                    "fault_half_batch": dict(fault="half",
                                             n_chips=ctx.chips)}
        if ctx.chips > 1:
            variants["fault_no_exchange"] = dict(fault="no_exchange",
                                                 n_chips=ctx.chips)
        for name, v in variants.items():
            got = reference.train_steps(seed, ctx.dims, batches, opt,
                                        devices=ctx.devices, **v)
            emit(kind=name, seed=seed, **td.compare(got, ref))


def serve(args, seeds, controls, **kw):
    import numpy as np

    from chipbench import serving as sd

    for seed in sorted(set(seeds) | set(controls)):
        ctx = context(args, seed, **kw)
        rec, served, wkey, make = sd.serve(ctx)
        params = make(wkey)
        ctrl = seed in controls
        gaps = reference.served_gaps(params, ctx.dims, served,
                                     ctx.mix["max_seq"],
                                     rnd=reference.fp8 if ctrl else None)
        out = {"token_gap": float(max(g.max() for g in gaps["gaps"])),
               "failed": rec["failed"], "attempted": rec["attempted"],
               "served_tokens": sum(len(g) for _, g in served),
               "ttft_p90_ms": rec["e2e"]["ttft_p90_ms"],
               "itl_p95_ms": rec["e2e"]["itl_p95_ms"]}
        if seed in seeds:
            emit(kind="program", seed=seed, **out)
        if ctrl:
            emit(kind="control_fp8", seed=seed, token_gap=float(max(
                g.max() for g in gaps["control_gaps"])))
            prompt, gen = served[0]
            altered = list(gen)
            altered[len(gen) // 2] = (altered[len(gen) // 2] + 1) \
                % ctx.dims["vocab"]
            g = reference.served_gaps(params, ctx.dims, [(prompt, altered)],
                                      ctx.mix["max_seq"])["gaps"][0]
            emit(kind="fault_token_altered", seed=seed,
                 token_gap=float(np.max(g)))
        del params


def main(argv=None, **kw):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    bench = spec.load_benchmark(kw.get("root", spec.CHECKOUT))
    mix = spec.traffic(spec.cell(bench, args.workload)["traffic"],
                       kw.get("root", spec.CHECKOUT))
    (train if mix["kind"] == "train" else serve)(args, seeds, controls, **kw)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.DeviceError as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        sys.exit(2)
