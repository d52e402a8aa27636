"""Cross-pod gradient exchange with the paper's §2.2.4 compression — the
loosely-coupled-tier program of the hierarchical deployment (DESIGN.md §2).

Each pod runs its own (single-pod) train step; this SEPARATE program then
synchronizes gradients across pods.  The exchange itself is a thin wrapper
over the bucketed ``Fabric`` (core/fabric.py): per-pod grads are flattened
into flat f32 buckets, 1-bit/int8/top-k encoded with error feedback, and
ONE packed uint8 buffer per bucket is all-gathered over "pod" — the same
code path the in-step exchange (train/loop.py) uses.  Grads carry a
leading pod dim (stacked), sharded P("pod", <intra-pod spec>).

(The fused form — compression inside the train step via partial-manual
shard_map — trips an XLA SPMD partitioner CHECK in 0.8.2; the two-program
structure is also how multi-pod deployments actually launch.)

    PYTHONPATH=src python -m repro.launch.exchange --arch gemma3-1b
"""

import argparse
import os
import sys

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import jax_compat as compat
from repro.core.comm import ShardComm
from repro.core.compression import get_compressor
from repro.core.fabric import DEFAULT_BUCKET_BYTES, Fabric
from repro.launch.mesh import ICI_BW, make_production_mesh
from repro.launch.specs import model_sds, param_shardings_sds
from repro.roofline.analysis import parse_collectives


def force_host_devices(n: int = 512):
    """Give the CLI enough forced host devices for the multi-pod mesh.

    Called from ``main()`` ONLY (before the first jax computation touches
    the backend) — an import-time mutation of ``XLA_FLAGS`` used to leak
    512 host devices into every test or tool importing ``build_exchange``."""
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={n}")


def build_exchange(compressor, bucket_bytes: int = DEFAULT_BUCKET_BYTES):
    """(grads stacked (P, ...), residual (P, ...)) → (avg grads, residual).

    Runs inside shard_map over "pod"; delegates to ``Fabric.exchange``:
    at most one collective per bucket (an all-gather of packed bytes when
    compressed, an all-reduce of the flat f32 bucket otherwise)."""

    def per_pod(g_loc, r_loc):
        comm = ShardComm("pod", compat.axis_size("pod"))
        fab = Fabric(comm, bucket_bytes)
        g, new_r, _ = fab.exchange(g_loc, r_loc, compressor)
        return g, new_r

    return per_pod


def lower_exchange(arch: str, compressor_name: str,
                   bucket_bytes: int = DEFAULT_BUCKET_BYTES):
    from repro.launch.specs import resolve_config

    mesh = make_production_mesh(multi_pod=True)
    cfg = resolve_config(arch, "train_4k")
    params_sds = model_sds(cfg)
    intra = param_shardings_sds(params_sds, mesh, cfg.sharding_mode)

    def stack(sds):
        return jax.ShapeDtypeStruct((2,) + sds.shape, jnp.float32)

    def stack_sh(sh):
        return NamedSharding(mesh, P(*(("pod",) + tuple(sh.spec))))

    g_sds = jax.tree.map(stack, params_sds)
    g_sh = jax.tree.map(stack_sh, intra)

    comp = None if compressor_name == "none" else get_compressor(compressor_name)
    fn = build_exchange(comp, bucket_bytes)
    smapped = compat.shard_map(
        fn, mesh=mesh,
        in_specs=(P("pod"), P("pod")), out_specs=(P("pod"), P("pod")),
        check_vma=False)
    with compat.set_mesh(mesh):
        compiled = jax.jit(smapped).lower(g_sds, g_sds).compile()
    pc = parse_collectives(compiled.as_text())
    total = sum(pc["bytes"].values())
    return total, pc


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--bucket-mib", type=float, default=4.0)
    args = ap.parse_args()
    from repro.configs import get_config, list_configs
    try:
        get_config(args.arch)
    except KeyError:
        print(f"unknown arch {args.arch!r}; valid names: "
              + ", ".join(sorted(list_configs())), file=sys.stderr)
        raise SystemExit(2)
    bucket_bytes = int(args.bucket_mib * 2**20)
    base = None
    for name in ("none", "int8", "onebit", "topk"):
        total, pc = lower_exchange(args.arch, name, bucket_bytes)
        if base is None:
            base = total
        ncoll = sum(pc["counts"].values())
        print(f"{args.arch} cross-pod exchange [{name:6s}]: "
              f"{total/2**20:9.1f} MiB on the wire in {ncoll} collectives "
              f"({base/max(total,1):5.1f}× vs uncompressed)  "
              f"→ {total/ICI_BW*1e3:7.2f} ms at pod-link bw")


if __name__ == "__main__":
    main()
