"""Benchmark: roofline table from the multi-pod dry-run artifacts
(results_singlepod.json / results_multipod.json, produced by
``python -m repro.launch.dryrun --all [--multi-pod] --out ...``), plus the
fabric fusion check: the lowered exchange HLO must contain at most
n_buckets cross-worker collectives (one per leaf before core/fabric.py).

Every check also contributes to ``BENCH_roofline.json`` at the repo root —
the machine-readable perf trajectory (wire bytes, bytes/sample, collective
counts, step-time estimates) tracked across PRs."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_FUSION_CHECK = """
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.compression import get_compressor
    from repro.core.fabric import BucketLayout, wire_nbytes
    from repro.core.jax_compat import make_mesh, set_mesh, shard_map
    from repro.launch.exchange import build_exchange
    from repro.roofline.analysis import collective_count, parse_collectives

    PODS, LAYERS = 4, 8
    mesh = make_mesh((PODS,), ("pod",))
    g = {f"l{i}": {"w": jax.ShapeDtypeStruct((PODS, 256, 64), jnp.float32),
                   "b": jax.ShapeDtypeStruct((PODS, 64), jnp.float32)}
         for i in range(LAYERS)}
    bucket_bytes = 4 * 40_000
    view = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((1,) + s.shape[1:], jnp.float32), g)
    lay = BucketLayout.build(view, bucket_bytes, lead_axes=0)
    rows = {"n_leaves": 2 * LAYERS, "n_buckets": lay.n_buckets}
    for name in ("none", "onebit", "int8", "topk"):
        comp = None if name == "none" else get_compressor(name)
        fn = shard_map(build_exchange(comp, bucket_bytes), mesh=mesh,
                       in_specs=(P("pod"), P("pod")),
                       out_specs=(P("pod"), P("pod")), check_vma=False)
        with set_mesh(mesh):
            c = jax.jit(fn).lower(g, g).compile()
        pc = parse_collectives(c.as_text())
        est = PODS * sum(wire_nbytes(comp, n) for n in lay.bucket_sizes)
        rows[name] = {"collectives": collective_count(c.as_text()),
                      "hlo_bytes": sum(pc["bytes"].values()),
                      "fabric_bytes": est}
    print("FUSION " + json.dumps(rows))
"""


def _run_on_host_devices(script: str):
    """Run ``script`` in a child on 4 forced host CPU devices.  These are
    lowering checks: the child never needs the chip, and this process has
    already touched JAX (benchmarks/common.py), so it may hold the chip."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, timeout=560)


def check_fusion():
    """Lower the bucketed exchange on 4 forced host devices (subprocess:
    this process must keep the single real device) and emit the
    collective-count / wire-byte evidence."""
    out = _run_on_host_devices(_FUSION_CHECK)
    if out.returncode != 0:
        emit("roofline/fusion", 0.0, "error=" + out.stderr[-200:].replace(
            "\n", " ").replace(",", ";"))
        return None
    line = [l for l in out.stdout.splitlines() if l.startswith("FUSION ")][0]
    rows = json.loads(line[len("FUSION "):])
    n_leaves, n_buckets = rows.pop("n_leaves"), rows.pop("n_buckets")
    for name, r in rows.items():
        ok = r["collectives"] <= n_buckets
        ratio = rows["none"]["hlo_bytes"] / max(r["hlo_bytes"], 1)
        emit(f"roofline/fusion/{name}", float(r["collectives"]),
             f"n_leaves={n_leaves};n_buckets={n_buckets};"
             f"collectives={r['collectives']};fused={ok};"
             f"hlo_bytes={r['hlo_bytes']};fabric_bytes={r['fabric_bytes']};"
             f"compression_x={ratio:.1f}")
    return {"n_leaves": n_leaves, "n_buckets": n_buckets,
            "compressors": rows}


_ZERO1_CHECK = """
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import strategies as ST
    from repro.core.comm import ShardComm
    from repro.core.fabric import BucketLayout, Fabric
    from repro.core.jax_compat import make_mesh, set_mesh, shard_map
    from repro.optim import adam
    from repro.roofline.analysis import (exchange_wire_bytes,
                                         opt_state_bytes, parse_collectives)
    from repro.train.loop import zero1_opt_template

    PODS, LAYERS = 4, 8
    mesh = make_mesh((PODS,), ("pod",))
    params = {f"l{i}": {"w": jax.ShapeDtypeStruct((256, 64), jnp.float32),
                        "b": jax.ShapeDtypeStruct((64,), jnp.float32)}
              for i in range(LAYERS)}
    bucket_bytes = 4 * 40_000
    lay = BucketLayout.build(params, bucket_bytes, lead_axes=0)
    opt = adam(1e-3)
    opt_state = zero1_opt_template(params, opt, PODS, bucket_bytes)
    strat = ST.sync_zero1(bucket_bytes=bucket_bytes)
    comm = ShardComm("pod", PODS)

    def body(p, g, s):
        p, s, _, _ = strat.update(p, g, s, {}, jnp.zeros((), jnp.int32),
                                  opt, comm)
        return p, s

    rep = jax.tree.map(lambda _: P(), params)
    ssp = jax.tree.map(lambda _: P("pod"), opt_state)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(rep, rep, ssp), out_specs=(rep, ssp),
                   check_vma=False)
    with set_mesh(mesh):
        c = jax.jit(fn).lower(params, params, opt_state).compile()
    pc = parse_collectives(c.as_text())
    n = sum(x.size for x in jax.tree.leaves(params))
    shard_elems = sum(x.size for x in jax.tree.leaves(opt_state)) // PODS
    rows = {"n_buckets": lay.n_buckets,
            "counts": pc["counts"],
            "dense_state_bytes": opt_state_bytes(n, opt.state_floats),
            "zero1_state_bytes": 4 * shard_elems,
            "zero1_model_bytes": opt_state_bytes(n, opt.state_floats,
                                                 PODS, partitioned=True),
            "wire_dense": exchange_wire_bytes(4 * n, PODS),
            "wire_zero1": exchange_wire_bytes(4 * n, PODS, partitioned=True)}
    print("ZERO1 " + json.dumps(rows))
"""


def check_zero1():
    """Lower the partitioned (ZeRO-1) exchange on 4 forced host devices and
    emit the reduce-scatter/all-gather counts + the ~W per-worker
    optimizer-state shrink."""
    out = _run_on_host_devices(_ZERO1_CHECK)
    if out.returncode != 0:
        emit("roofline/zero1", 0.0, "error=" + out.stderr[-200:].replace(
            "\n", " ").replace(",", ";"))
        return None
    line = [l for l in out.stdout.splitlines() if l.startswith("ZERO1 ")][0]
    rows = json.loads(line[len("ZERO1 "):])
    counts = rows["counts"]
    ok = (0 < counts["reduce-scatter"] <= rows["n_buckets"]
          and 0 < counts["all-gather"] <= rows["n_buckets"]
          and counts["all-reduce"] == 0)
    shrink = rows["dense_state_bytes"] / max(rows["zero1_state_bytes"], 1)
    emit("roofline/zero1", float(counts["reduce-scatter"]),
         f"n_buckets={rows['n_buckets']};rs={counts['reduce-scatter']};"
         f"ag={counts['all-gather']};ar={counts['all-reduce']};"
         f"partitioned={ok};state_shrink_x={shrink:.2f};"
         f"model_shrink_x={rows['dense_state_bytes']/max(rows['zero1_model_bytes'],1):.2f};"
         f"wire_parity={rows['wire_zero1'] == rows['wire_dense']}")
    rows["ok"] = ok
    rows["state_shrink_x"] = shrink
    return rows


_PRECISION_CHECK = """
    import json, re
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import strategies as ST
    from repro.core.comm import ShardComm
    from repro.core.fabric import BucketLayout, Fabric
    from repro.core.jax_compat import make_mesh, set_mesh, shard_map
    from repro.core.precision import get_policy
    from repro.optim import adam
    from repro.roofline.analysis import parse_collectives
    from repro.train.loop import zero1_opt_template

    PODS, LAYERS = 4, 8
    mesh = make_mesh((PODS,), ("pod",))
    bucket_bytes = 4 * 40_000

    def lower(policy_name):
        pol = get_policy(policy_name)
        pdt = pol.param_dt
        params = {f"l{i}": {"w": jax.ShapeDtypeStruct((256, 64), pdt),
                            "b": jax.ShapeDtypeStruct((64,), pdt)}
                  for i in range(LAYERS)}
        opt = adam(1e-3)
        opt_state = zero1_opt_template(params, opt, PODS, bucket_bytes,
                                       policy=None if pol.is_noop else pol)
        strat = ST.sync_zero1(bucket_bytes=bucket_bytes, policy=pol)
        comm = ShardComm("pod", PODS)

        def body(p, g, s):
            p, s, _, _ = strat.update(p, g, s, {}, jnp.zeros((), jnp.int32),
                                      adam(1e-3), comm)
            return p, s

        rep = jax.tree.map(lambda _: P(), params)
        ssp = jax.tree.map(lambda _: P("pod"), opt_state)
        fn = shard_map(body, mesh=mesh,
                       in_specs=(rep, rep, ssp), out_specs=(rep, ssp),
                       check_vma=False)
        with set_mesh(mesh):
            c = jax.jit(fn).lower(params, params, opt_state).compile()
        txt = c.as_text()
        pc = parse_collectives(txt)
        f32_rs = sum(1 for l in txt.splitlines()
                     if "reduce-scatter(" in l
                     and re.search(r"=\\s*f32\\[", l))
        fab = Fabric(comm, bucket_bytes, wire_dtype=pol.wire_dt)
        lay = BucketLayout.build(params, bucket_bytes, lead_axes=0)
        return {"hlo_bytes": pc["bytes"], "counts": pc["counts"],
                "f32_reduce_scatters": f32_rs,
                "fabric_wire_bytes": fab.flat_bytes(lay)}

    rows = {"f32": lower("f32"), "bf16": lower("bf16")}
    print("PRECISION " + json.dumps(rows))
"""


def check_precision():
    """Lower the ZeRO-1 exchange under the f32 and bf16 policies and emit
    the wire-shrink evidence: the bf16 reduce-scatter/all-gather ship ~2x
    fewer bytes and no f32 reduce-scatter survives in the HLO."""
    out = _run_on_host_devices(_PRECISION_CHECK)
    if out.returncode != 0:
        emit("roofline/precision", 0.0, "error=" + out.stderr[-200:].replace(
            "\n", " ").replace(",", ";"))
        return None
    line = [l for l in out.stdout.splitlines()
            if l.startswith("PRECISION ")][0]
    rows = json.loads(line[len("PRECISION "):])
    f32, bf16 = rows["f32"], rows["bf16"]
    shrink = f32["fabric_wire_bytes"] / max(bf16["fabric_wire_bytes"], 1)
    ok = (shrink > 1.99 and bf16["f32_reduce_scatters"] == 0
          and bf16["counts"]["reduce-scatter"] == 0
          and bf16["counts"]["all-to-all"] > 0)
    emit("roofline/precision", shrink,
         f"wire_shrink_x={shrink:.2f};ok={ok};"
         f"f32_rs_in_bf16_hlo={bf16['f32_reduce_scatters']};"
         f"bf16_rs={bf16['counts']['reduce-scatter']};"
         f"bf16_a2a={bf16['counts']['all-to-all']};"
         f"ag_bytes_f32={f32['hlo_bytes']['all-gather']};"
         f"ag_bytes_bf16={bf16['hlo_bytes']['all-gather']}")
    rows["ok"] = ok
    rows["wire_shrink_x"] = shrink
    return rows


_ACCUM_CHECK = """
    import json
    import jax, jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro.core.comm import ShardComm
    from repro.core.fabric import BucketLayout, Fabric
    from repro.core.jax_compat import make_mesh, set_mesh, shard_map
    from repro.optim import adam
    from repro.roofline.analysis import parse_collectives, wire_bytes_per_sample
    from repro.train.loop import zero1_opt_template

    PODS, LAYERS, B = 4, 8, 8  # B = per-pod samples per microbatch
    mesh = make_mesh((PODS,), ("pod",))
    bucket_bytes = 4 * 40_000
    params = {f"l{i}": {"w": jnp.zeros((256, 64)), "b": jnp.zeros((64,))}
              for i in range(LAYERS)}
    lay = BucketLayout.build(params, bucket_bytes, lead_axes=0)
    comm = ShardComm("pod", PODS)
    opt = adam(1e-3)
    opt_state = zero1_opt_template(params, opt, PODS, bucket_bytes)

    def loss_fn(p, mb):
        # toy but differentiable-in-every-leaf loss with a real batch dep
        s = sum(jnp.vdot(l, l) for l in jax.tree.leaves(p))
        return s * jnp.mean(mb ** 2)

    def accum(fab, p, batch, k, play=None):
        la = fab.layout(p)
        def micro(carry, mb):
            acc, ls = carry
            l, g = jax.value_and_grad(loss_fn)(p, mb)
            return (fab.accumulate(acc, g, la, play=play), ls + l), None
        (acc, ls), _ = lax.scan(
            micro, (fab.init_accum(la, play), jnp.zeros(())), batch)
        return [a / k for a in acc], ls / k

    def lower(path, k):
        fab = Fabric(comm, bucket_bytes)
        if path == "dense":
            def body(p, batch):
                acc, _ = accum(fab, p, batch, k)
                g, _, _ = fab.exchange_accumulated(acc, lay)
                return jax.tree.map(lambda x, gg: x - 0.1 * gg, p, g)
            specs = (jax.tree.map(lambda _: P(), params), P(None, "pod"))
            outs = jax.tree.map(lambda _: P(), params)
            args = (params, jnp.zeros((k, PODS * B, 16)))
        else:
            play = fab.partitioned_layout(params)
            def body(p, batch, s):
                acc, _ = accum(fab, p, batch, k, play=play)
                g_sh, _ = fab.exchange_partitioned_accumulated(acc, play)
                p_sh, s = opt.update(g_sh, s, fab.shard_params(p, play), 0)
                return fab.unpartition(p_sh, play), s
            ssp = jax.tree.map(lambda _: P("pod"), opt_state)
            specs = (jax.tree.map(lambda _: P(), params), P(None, "pod"), ssp)
            outs = (jax.tree.map(lambda _: P(), params), ssp)
            args = (params, jnp.zeros((k, PODS * B, 16)), opt_state)
        fn = shard_map(body, mesh=mesh,
                       in_specs=specs, out_specs=outs, check_vma=False)
        with set_mesh(mesh):
            c = jax.jit(fn).lower(*args).compile()
        pc = parse_collectives(c.as_text())
        n = sum(x.size for x in jax.tree.leaves(params))
        return {"counts": pc["counts"],
                "hlo_bytes": sum(pc["bytes"].values()),
                "wire_bytes_per_sample": wire_bytes_per_sample(
                    4 * n, PODS, B, accum_steps=k)}

    rows = {"n_buckets": lay.n_buckets,
            "dense": {k: lower("dense", k) for k in (1, 4)},
            "zero1": {k: lower("zero1", k) for k in (1, 4)}}
    print("ACCUM " + json.dumps(rows))
"""


def check_accum():
    """Lower the microbatched boundary step (k=1 vs k=4) on both the dense
    sync and ZeRO-1 paths and emit the accumulation proof: wire bytes per
    SAMPLE shrink by exactly accum_steps while the step HLO still carries
    one exchange's worth of collectives (≤ n_buckets, the fused-Fabric
    bound) per boundary — the scan body is collective-free."""
    out = _run_on_host_devices(_ACCUM_CHECK)
    if out.returncode != 0:
        emit("roofline/accum", 0.0, "error=" + out.stderr[-200:].replace(
            "\n", " ").replace(",", ";"))
        return None
    line = [l for l in out.stdout.splitlines() if l.startswith("ACCUM ")][0]
    rows = json.loads(line[len("ACCUM "):])
    nb = rows["n_buckets"]
    oks = {}
    for path in ("dense", "zero1"):
        r1, r4 = rows[path]["1"], rows[path]["4"]
        ratio = r1["wire_bytes_per_sample"] / r4["wire_bytes_per_sample"]
        c1, c4 = r1["counts"], r4["counts"]
        exchange_ops = (c4["all-reduce"] if path == "dense"
                        else max(c4["reduce-scatter"], c4["all-gather"]))
        ok = (abs(ratio - 4.0) < 1e-9          # 4x fewer bytes per sample
              and c1 == c4                     # collectives don't scale in k
              and r1["hlo_bytes"] == r4["hlo_bytes"]  # nor do wire bytes
              and 0 < exchange_ops <= nb)      # one fused exchange/boundary
        oks[path] = ok
        emit(f"roofline/accum/{path}", ratio,
             f"n_buckets={nb};bytes_per_sample_x={ratio:.1f};ok={ok};"
             f"k4_counts=" + "/".join(f"{k}:{v}" for k, v in c4.items()
                                      if v) + ";"
             f"hlo_bytes_k1={r1['hlo_bytes']};hlo_bytes_k4={r4['hlo_bytes']}")
    rows["ok"] = all(oks.values())
    return rows


def run():
    report = {
        "fusion": check_fusion(),
        "zero1": check_zero1(),
        "precision": check_precision(),
        "accum": check_accum(),
        "dryrun": {},
    }
    for fname, mesh in (("results_singlepod.json", "16x16"),
                        ("results_multipod.json", "2x16x16")):
        path = os.path.join(ROOT, fname)
        if not os.path.exists(path):
            emit(f"roofline/{mesh}", 0.0, "missing=run repro.launch.dryrun --all")
            continue
        rows = json.load(open(path))
        ok = [r for r in rows if r["status"] == "ok"]
        for r in ok:
            ro = r["roofline"]
            # step-time estimate: the binding roofline term
            step_s = max(ro["compute_s"], ro["memory_s"],
                         ro["collective_s"])
            report["dryrun"].setdefault(mesh, []).append({
                "arch": r["arch"], "shape": r["shape"],
                "step_time_s_est": step_s, "dominant": ro["dominant"],
                "collective_bytes": ro["collective_bytes"],
                "collective_counts": ro["collective_counts"],
                "peak_per_device_gb": r["memory"]["peak_per_device_gb"],
                "accum_steps": r.get("accum_steps", 1),
            })
            emit(f"roofline/{mesh}/{r['arch']}/{r['shape']}",
                 ro["compute_s"] * 1e6,
                 f"dominant={ro['dominant']};compute_ms={ro['compute_s']*1e3:.2f};"
                 f"memory_ms={ro['memory_s']*1e3:.2f};"
                 f"collective_ms={ro['collective_s']*1e3:.2f};"
                 f"useful_flop_ratio={ro['useful_flops_ratio']:.2f};"
                 f"gb_per_device={r['memory']['peak_per_device_gb']:.2f}")
        nskip = sum(1 for r in rows if r["status"] == "skip")
        nerr = sum(1 for r in rows if r["status"] == "error")
        emit(f"roofline/{mesh}/summary", 0.0,
             f"ok={len(ok)};skip={nskip};error={nerr}")
    out = os.path.join(ROOT, "BENCH_roofline.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    emit("roofline/json", 0.0, f"wrote={os.path.basename(out)}")
    return report


if __name__ == "__main__":
    run()
