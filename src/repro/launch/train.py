"""End-to-end trainer CLI.

W model replicas, stacked on one device, under any spectrum strategy and
optional compression — the paper's experimental rig (DESIGN.md §3).

Two size cuts:
  * ``--reduced``: the CPU-test preset, which cuts widths as well as depth;
  * ``--num-layers N``: depth only.  Every width stays as published, which
    is how a published model fits one chip (e.g. qwen2-1.5b at 4 of 28
    layers).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --reduced \
      --strategy gossip --workers 4 --steps 200 --compressor onebit
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
      --num-layers 4 --workers 1 --precision bf16 --steps 3
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax

from repro.checkpoint import save_checkpoint
from repro.configs import get_config, list_configs
from repro.core.comm import LocalComm
from repro.core.compression import get_compressor
from repro.core.precision import POLICIES, apply_policy, get_policy
from repro.core.strategies import REGISTRY, get_strategy
from repro.data.pipeline import DataConfig, bayes_entropy, prefetch_batches
from repro.launch.compile_cache import (attention_paths, compile_count,
                                        use_compile_cache)
from repro.models import transformer as T
from repro.optim import adam, sgd, warmup_cosine
from repro.train.loop import (init_train_state, make_loss_fn,
                              make_replica_train_step)


def build_argparser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant of the arch")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="cut depth only: keep every published width and "
                         "run this many layers (a whole number of the "
                         "arch's super-blocks)")
    ap.add_argument("--strategy", default="sync", choices=sorted(REGISTRY))
    ap.add_argument("--zero-stage", type=int, default=0,
                    choices=[0, 1, 2, 3],
                    help="ZeRO partitioning stage (shorthand for "
                         "--strategy sync_zero{N}): 1 shards optimizer "
                         "state, 2 also reduce-scatters per-microbatch "
                         "gradients into a 1/W accumulator, 3 also shards "
                         "the parameters (gathered per step)")
    ap.add_argument("--compressor", default="none",
                    choices=["none", "onebit", "int8", "topk"])
    ap.add_argument("--precision", default="f32", choices=sorted(POLICIES),
                    help="precision policy (core/precision.py): f32 | "
                         "bf16 (bf16 compute/wire, f32 master, dynamic "
                         "loss scaling) | bf16-pure")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--steps", type=int, default=100,
                    help="OPTIMIZER steps (accumulation boundaries)")
    ap.add_argument("--batch-per-worker", type=int, default=4)
    ap.add_argument("--accum-steps", type=int, default=1,
                    help="microbatches accumulated per optimizer step "
                         "(DESIGN.md §8): the exchange fires once per "
                         "boundary, so wire bytes per sample shrink by "
                         "this factor; effective global batch = workers x "
                         "batch-per-worker x accum-steps")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="batches kept in flight by the double-buffered "
                         "device prefetch (1 = synchronous)")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adam", choices=["adam", "sgd"])
    ap.add_argument("--fused-adam", action="store_true",
                    help="route the Adam update through the fused Pallas "
                         "kernel (one VMEM pass per flat bucket — pairs "
                         "with the ZeRO-1 shard-bucket update boundary)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", default=None, choices=["auto"],
                    help="auto: resume from the latest VALID checkpoint in "
                         "--ckpt-dir (corrupt/partial steps are verified "
                         "against the per-leaf checksums and skipped); "
                         "exit 2 with a one-line message when the dir has "
                         "no valid step")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--out", default=None,
                    help="JSON metrics file: one record per logged step, "
                         "with the executables built so far (compiles) "
                         "and the attention layers traced so far by path "
                         "(attention_paths: flash or dense)")
    return ap


def strategy_from_args(args, policy=None):
    comp = None
    if args.compressor != "none":
        comp = get_compressor(args.compressor) if args.compressor != "topk" \
            else get_compressor("topk", ratio=0.01)
    kw = {}
    if args.strategy in ("sync", "ssp", "downpour"):
        kw["compressor"] = comp
    if args.strategy == "sync_dgc":
        if comp is None:
            print("sync_dgc needs --compressor (onebit | int8 | topk)",
                  file=sys.stderr)
            raise SystemExit(2)
        kw["compressor"] = comp
    if policy is not None:
        kw["policy"] = policy
    return get_strategy(args.strategy, **kw)


def resume_auto(ckpt_dir, state, strategy, comm, policy, strategy_name):
    """Restore the newest valid checkpoint into ``state`` (in place).

    Builds the restore template as a mirror of the save tree below (replica-0
    params [+ master], shard-bucket opt state / ZeRO-3 param shards for the
    sync_zero* strategies) and re-shards across worker counts when the save
    recorded a partition spec.  Returns the restored step; exits 2 when the
    dir holds no valid step or the checkpoint doesn't fit this run."""
    import jax.numpy as jnp

    from repro.checkpoint import (latest_valid_step, read_meta,
                                  restore_checkpoint)
    step0 = latest_valid_step(ckpt_dir)
    if step0 is None:
        print(f"--resume auto: no valid checkpoint step in {ckpt_dir!r}",
              file=sys.stderr)
        raise SystemExit(2)
    owns = getattr(strategy, "owns_params", False)
    full = strategy.gather_params(state["params"], comm) if owns \
        else state["params"]
    template = {"params": comm.replica(full, 0), "step": state["step"]}
    if policy is not None and "master" in state:
        template["master"] = comm.replica(state["master"], 0)
    if strategy_name.startswith("sync_zero"):
        template["opt_state"] = state["opt_state"]
        if owns:
            template["param_shards"] = state["params"]
    has_part = str(step0) in read_meta(ckpt_dir).get("partitions", {})
    try:
        restored = restore_checkpoint(ckpt_dir, step0, template,
                                      repartition=has_part)
    except (KeyError, ValueError) as e:
        print(f"--resume auto: checkpoint step {step0} does not match this "
              f"run's strategy/layout ({e})", file=sys.stderr)
        raise SystemExit(2)
    if owns:
        state["params"] = jax.tree.map(jnp.asarray, restored["param_shards"])
    else:
        state["params"] = comm.replicate(restored["params"])
    if "master" in template:
        state["master"] = comm.replicate(restored["master"])
    if "opt_state" in template:
        state["opt_state"] = jax.tree.map(jnp.asarray, restored["opt_state"])
    state["step"] = jnp.asarray(restored["step"], jnp.int32)
    return int(restored["step"])


def main(argv=None):
    use_compile_cache()
    compile_count()  # count from here: the --out records carry the total
    attention_paths()  # and which attention path each traced layer took
    args = build_argparser().parse_args(argv)
    try:
        cfg = get_config(args.arch)
    except KeyError:
        print(f"unknown arch {args.arch!r}; valid names: "
              + ", ".join(sorted(list_configs())), file=sys.stderr)
        raise SystemExit(2)
    if args.reduced:
        cfg = cfg.reduced()
    published_layers = cfg.num_layers
    if args.num_layers is not None:
        try:
            cfg = cfg.with_depth(args.num_layers)
        except ValueError as e:
            print(f"--num-layers: {e}", file=sys.stderr)
            raise SystemExit(2)
    if args.zero_stage:
        if args.strategy not in ("sync", f"sync_zero{args.zero_stage}"):
            print(f"--zero-stage {args.zero_stage} conflicts with "
                  f"--strategy {args.strategy}", file=sys.stderr)
            raise SystemExit(2)
        args.strategy = f"sync_zero{args.zero_stage}"
    if cfg.is_encoder_decoder or cfg.modality is not None:
        raise SystemExit("trainer CLI supports decoder-only text archs; "
                         "see examples/ for enc-dec and multimodal")
    policy = get_policy(args.precision)
    if policy.is_noop:
        policy = None  # f32: the bitwise pre-precision path
    else:
        cfg = apply_policy(cfg, policy)

    comm = LocalComm(args.workers)
    strategy = strategy_from_args(args, policy)
    sched = warmup_cosine(args.lr, warmup=max(1, args.steps // 20),
                          total_steps=args.steps)
    opt = (adam(sched, fused=args.fused_adam) if args.optimizer == "adam"
           else sgd(sched))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      batch_per_worker=args.batch_per_worker, seed=args.seed)

    key = jax.random.PRNGKey(args.seed)
    params = comm.replicate(T.init_model(key, cfg))
    state = init_train_state(params, opt, strategy, comm, policy=policy)

    loss_fn_single = make_loss_fn(cfg, remat=False)

    def loss_fn(p, toks):
        return loss_fn_single(p, {"tokens": toks, "labels": toks})

    step_fn = make_replica_train_step(loss_fn, opt, strategy, comm,
                                      policy=policy,
                                      accum_steps=args.accum_steps)

    n_params = sum(x.size for x in jax.tree.leaves(params)) // args.workers
    # global-batch accounting: one optimizer step consumes accum_steps
    # microbatches of workers x batch_per_worker samples each, but ships
    # the wire bytes of ONE exchange
    samples_per_step = args.workers * args.batch_per_worker * args.accum_steps
    print(f"arch={cfg.name} layers={cfg.num_layers}/{published_layers} "
          f"params={n_params:,} strategy={strategy.name} "
          f"precision={args.precision} workers={args.workers} "
          f"accum_steps={args.accum_steps} "
          f"global_batch={samples_per_step} "
          f"prefetch_depth={args.prefetch_depth} "
          f"entropy_floor={bayes_entropy(dcfg):.3f}")

    start_step = 0
    if args.resume:
        if not args.ckpt_dir:
            print("--resume auto requires --ckpt-dir", file=sys.stderr)
            raise SystemExit(2)
        start_step = resume_auto(args.ckpt_dir, state, strategy, comm,
                                 policy, args.strategy)
        print(f"resumed from step {start_step} ({args.ckpt_dir})")

    history = []
    t0 = time.time()
    for t, batches in prefetch_batches(dcfg, args.workers, args.steps,
                                       accum_steps=args.accum_steps,
                                       depth=args.prefetch_depth):
        if t < start_step:
            # identical data stream to an uninterrupted run: boundaries
            # before the restored step are consumed, not trained on
            continue
        state, m = step_fn(state, batches)
        if t % args.log_every == 0 or t == args.steps - 1:
            rec = {"step": t, "loss": float(m["loss"]),
                   "divergence": float(m["replica_divergence"]),
                   "wire_bytes": float(m["wire_bytes"]),
                   "wire_bytes_per_sample":
                       float(m["wire_bytes"]) / samples_per_step,
                   "elapsed_s": round(time.time() - t0, 2),
                   "compiles": compile_count(),
                   "attention_paths": attention_paths()}
            if "loss_scale" in m:
                rec["loss_scale"] = float(m["loss_scale"])
            history.append(rec)
            print(f"step {t:5d} loss {rec['loss']:.4f} "
                  f"div {rec['divergence']:.2e} wireB {rec['wire_bytes']:.0f}"
                  f" wireB/sample {rec['wire_bytes_per_sample']:.1f}")

    if args.ckpt_dir:
        # ZeRO-3 keeps only shard buckets in the state: gather the full
        # tree so the checkpoint stays worker-count-portable
        full_params = strategy.gather_params(state["params"], comm) \
            if getattr(strategy, "owns_params", False) else state["params"]
        tree = {"params": comm.replica(full_params, 0),
                "step": state["step"]}
        kw = {}
        if policy is not None:
            kw["precision"] = policy.spec()
            if "master" in state:  # dense f32 master rides the checkpoint
                tree["master"] = comm.replica(state["master"], 0)
        if args.strategy.startswith("sync_zero"):
            # shard-bucket opt state (incl. any f32 master / ZeRO-3 param
            # shards) + the partition spec, so a restore can re-shard to
            # another W
            from repro.core.fabric import Fabric
            tree["opt_state"] = state["opt_state"]
            if getattr(strategy, "owns_params", False):
                tree["param_shards"] = state["params"]
            kw["partition"] = Fabric(comm).partitioned_layout(
                full_params).spec()
        save_checkpoint(args.ckpt_dir, args.steps, tree, **kw)
        print(f"checkpoint saved to {args.ckpt_dir}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(history, f, indent=1)
    return history


if __name__ == "__main__":
    main()
