"""Smoke run of the main paths on a TPU, at qwen2-1.5b's published widths.

    python chip_smoke.py            # one chip: train, train compressed, serve
    python chip_smoke.py --chips 4  # four chips: ZeRO-1 against plain sync

Every phase runs in this one process, through the entry points a user
calls, on qwen2-1.5b (d_model 1536, 12 query / 2 KV heads of 128, d_ff
8960, vocab 151,936) cut in depth only.  Weights are random, from a seed.

One chip:
  * train: ``repro.launch.train.main`` at 4 of 28 layers, bf16 policy.
    Every loss is finite and step 0 is within LOSS0_TOL of ln(vocab).
  * train, compressed: the same CLI with ``--compressor onebit
    --fused-adam`` and two replicas, at the one layer that fits 16 GB next
    to the replicas' f32 state and error-feedback residuals.  The 1-bit
    encode and the Adam update run as compiled Pallas kernels.
  * serve: ``PagedDecodeEngine`` on the 4-layer model with bf16 pages
    answers requests of a few hundred prompt tokens and 32 new tokens.
    One decode step's logits through the Pallas paged-attention kernel
    agree with the jnp gather path within SERVE_TOL.

Four chips (``--chips 4``): ``launch.specs.build_train_step`` on a
(pod=4, data=1, model=1) mesh, run with ``zero_stage=1`` and as plain sync
on the same batches and seed.  The losses agree within ZERO1_TOL and each
device holds a quarter of the ZeRO-1 optimizer state.

The last line printed is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Off a TPU, or with the kernels in interpret mode, the script prints no
result and exits 2.  A failed check raises and exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen2-1.5b"
SEED = 0
# random init gives near-uniform logits: the tied embedding's rows have
# variance 1/vocab, so the logits' variance is d_model/vocab ≈ 0.01 and
# the expected step-0 loss is ln(vocab) + 0.005
LOSS0_TOL = 0.1
# kernel vs gather decode logits, max |Δ| over max |logit|: both paths
# read the same bf16 pages and differ only in where the attention output
# is rounded to bf16 (2^-8 relative).  That rounding passes through four
# residual layers and the bf16 vocabulary projection; 5e-2 is about ten
# such roundings, while a wrong page, head or mask moves the logits by
# their own size.
SERVE_TOL = 5e-2
# ZeRO-1 vs plain sync, |Δloss| / loss per step.  Both run the same f32
# forward on the same shards; only the order of the gradient reduction
# (reduce-scatter vs all-reduce) differs, a few f32 ulps per gradient.
ZERO1_TOL = 1e-4

TRAIN = ["--num-layers", "4", "--workers", "1", "--precision", "bf16",
         "--batch-per-worker", "2", "--seq-len", "1024"]
TRAIN_COMPRESSED = ["--num-layers", "1", "--workers", "2",
                    "--compressor", "onebit", "--fused-adam",
                    "--batch-per-worker", "1", "--seq-len", "512"]


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def log(msg: str) -> None:
    print(msg, flush=True)


def run_train(name: str, argv, vocab: int, steps: int = 3):
    """The trainer CLI for ``steps`` optimizer steps; returns the losses."""
    from repro.launch import train

    t0 = time.perf_counter()
    hist = train.main(["--arch", ARCH, "--steps", str(steps),
                       "--log-every", "1", "--seed", str(SEED), *argv])
    losses = [r["loss"] for r in hist]
    check(len(losses) == steps, f"{name}: {len(losses)} of {steps} steps")
    check(all(math.isfinite(x) for x in losses),
          f"{name}: non-finite loss {losses}")
    check(abs(losses[0] - math.log(vocab)) < LOSS0_TOL,
          f"{name}: step-0 loss {losses[0]} is not near ln({vocab})")
    log(f"[{name}] ok: losses {losses} "
        f"(wall {time.perf_counter() - t0:.1f} s, compile included)")
    return losses


def run_serve(cfg, *, n_requests: int = 4, prompt_len: int = 300,
              new_tokens: int = 32, max_seq: int = 512):
    """Paged serving with bf16 pages through the Pallas decode kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T
    from repro.serve.engine import PagedDecodeEngine, Request

    t0 = time.perf_counter()
    params = T.init_model(jax.random.PRNGKey(SEED), cfg)
    eng = PagedDecodeEngine(params, cfg, batch_slots=n_requests,
                            max_seq=max_seq, cache_dtype=jnp.bfloat16)
    check(eng.use_kernel, "serve: the paged engine did not pick the kernel")
    rng = np.random.default_rng(SEED)
    for rid in range(n_requests):
        plen = prompt_len + 16 * rid  # ragged: the slots end pages apart
        eng.submit(Request(rid, rng.integers(0, cfg.vocab_size, plen,
                                             dtype=np.int32), new_tokens))
    while not all(p == "decode" for p in eng.phase):
        check(eng.steps < 100, "serve: prompts not ingested in 100 steps")
        eng.step()

    # one decode step of every slot, kernel path against gather path, at
    # the positions the engine's next step writes (pages allocated first)
    for i in range(eng.b):
        check(eng.kv.ensure(i, int(eng.pos[i]) + 1), "serve: out of pages")
    tok = jnp.asarray([eng.slot[i].generated[-1] for i in range(eng.b)],
                      jnp.int32)
    pos = jnp.asarray(eng.pos, jnp.int32)
    tables = jnp.asarray(eng.kv.tables)

    def decode(use_kernel):
        fn = jax.jit(lambda p, t, q, c, bt: T.decode_step_paged(
            p, cfg, t, q, c, bt, use_kernel=use_kernel))
        return np.asarray(fn(params, tok, pos, eng.cache, tables)[0])

    lk, lg = decode(True), decode(False)
    check(np.isfinite(lk).all(), "serve: non-finite kernel logits")
    err = float(np.max(np.abs(lk - lg)) / np.max(np.abs(lg)))
    check(err <= SERVE_TOL, f"serve: kernel vs gather logits differ by "
          f"{err:.3e} of max |logit| (tolerance {SERVE_TOL})")

    done = eng.run()
    check(len(done) == n_requests and all(r.done for r in done),
          "serve: not every request finished")
    check(all(len(r.generated) == new_tokens for r in done),
          "serve: a request stopped short of its new tokens")
    eng.kv.allocator.check()
    check(eng.kv.allocator.num_allocated == 0, "serve: pages leaked")
    log(f"[serve] ok: {n_requests} requests x {new_tokens} new tokens, "
        f"prompts {prompt_len}-{prompt_len + 16 * (n_requests - 1)}, "
        f"{eng.steps} engine steps; kernel vs gather logits "
        f"max|d|/max|logit| = {err:.3e} "
        f"(wall {time.perf_counter() - t0:.1f} s, compile included)")
    return err


def run_zero1_vs_sync(cfg, mesh, shape, steps: int = 3):
    """A few steps of the production step with ZeRO-1 and as plain sync,
    same seed and batches; returns both loss lists."""
    import jax
    import jax.numpy as jnp

    from repro.core.jax_compat import set_mesh
    from repro.data.pipeline import DataConfig, global_batch
    from repro.launch.specs import build_train_step
    from repro.models import transformer as T

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                      batch_per_worker=1, seed=SEED)
    pods = dict(mesh.shape)["pod"]
    losses = {}
    for zero_stage in (1, 0):
        t0 = time.perf_counter()
        step, (state_sds, _), (state_sh, batch_sh), donate = \
            build_train_step(cfg, shape, mesh, zero_stage=zero_stage)
        params = T.init_model(jax.random.PRNGKey(SEED), cfg)
        # the optimizer state starts at zero on both paths
        rest = {k: v for k, v in state_sds.items() if k != "params"}
        rest = jax.jit(lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), rest),
            out_shardings={k: state_sh[k] for k in rest})()
        state = {"params": jax.device_put(params, state_sh["params"]),
                 **rest}
        del params
        if zero_stage:
            for leaf in jax.tree.leaves(state["opt_state"]):
                shards = leaf.addressable_shards
                check(len(shards) == pods and all(
                    s.data.shape[0] * pods == leaf.shape[0] for s in shards),
                    f"ZeRO-1 state leaf {leaf.shape} is not split "
                    f"{pods} ways: {[s.data.shape for s in shards]}")
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     donate_argnums=donate)
        out = []
        with set_mesh(mesh):
            for t in range(steps):
                toks = global_batch(dcfg, t, shape.global_batch)
                batch = jax.device_put({"tokens": toks, "labels": toks},
                                       batch_sh)
                state, loss = fn(state, batch)
                out.append(float(loss))
        check(all(math.isfinite(x) for x in out),
              f"zero_stage={zero_stage}: non-finite loss {out}")
        losses[zero_stage] = out
        del state
        log(f"[zero_stage={zero_stage}] losses {out} "
            f"(wall {time.perf_counter() - t0:.1f} s, compile included)")
    rel = max(abs(a - b) / abs(b) for a, b in zip(losses[1], losses[0]))
    check(rel <= ZERO1_TOL, f"ZeRO-1 vs sync losses differ by {rel:.3e} "
          f"relative (tolerance {ZERO1_TOL})")
    log(f"[zero1 vs sync] ok: max |dloss|/loss = {rel:.3e} over {steps} "
        f"steps; optimizer state split {pods} ways")
    return losses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the four-chip ZeRO-1 vs sync phase")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository source at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()

    import jax

    from repro.kernels.ops import default_interpret
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devs)}; jax {jax.__version__}; compile cache {cache}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    if default_interpret():
        print("chip_smoke: Pallas kernels would run in interpret mode on "
              f"backend {jax.default_backend()!r}", file=sys.stderr)
        return 2
    check(len(devs) >= args.chips,
          f"--chips {args.chips} but only {len(devs)} devices")

    from repro.configs import get_config
    cfg = get_config(ARCH).with_depth(4)
    if args.chips == 4:
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import ShapeSpec
        mesh = make_mesh((4, 1, 1), ("pod", "data", "model"))
        run_zero1_vs_sync(cfg, mesh, ShapeSpec("smoke_512", 512, 8, "train"))
    else:
        run_train("train", TRAIN, cfg.vocab_size)
        run_train("train, compressed", TRAIN_COMPRESSED, cfg.vocab_size)
        from repro.core.precision import apply_policy, get_policy
        run_serve(apply_policy(cfg, get_policy("bf16")))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
