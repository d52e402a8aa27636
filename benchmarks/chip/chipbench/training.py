"""Training cells: the production step from ``launch.specs.build_train_step``
on a (chips, 1, 1) mesh, fed by ``data.pipeline.global_batch``.

Set-up builds the state on the device from the seed in one call, compiles
the step, and drives that same step through its first three steps on
batches 0, 1, 2 (rows that all differ).  It reads the first gradient from
the optimizer's state after step 1 (m = (1 - b1)·g) and the change of the
float32 master after step 3.  The window then continues the same step
from batch 3 for ``--seconds``.  Once the window has closed and the state
is freed, the plain reference runs the same three steps, and three numbers
are compared, each by the worst leaf:

* ``loss_gap``: |loss − reference| / reference, over the three steps;
* ``grad_gap``: |‖g‖ − ‖g_ref‖| / max(‖g_ref‖, median leaf ‖g_ref‖);
* ``change_gap``: the same for the change of each leaf over three steps,
  leaving out leaves whose reference gradient is under a thousandth of
  the median leaf's (a key bias under softmax: Adam moves it by
  round-off alone).

The program's trace-time counters (``/repro/...``) are taken over the
build of the step.  A traced run (``--trace 1``) profiles ``trace_steps``
steps from 40 % into the window; after the window it compiles the step it
ran once more, outside the window and set-up, and hands the compiled text
to the trace reduction, which joins device ops to the program's phases
and scopes by instruction name.
"""

from __future__ import annotations

import gc
import math
import statistics
import time
from collections import deque

import numpy as np

from chipbench import reference, traffic, weights

CHECK_STEPS = 3
ZERO_GRAD_FRACTION = 1e-3

COUNTER_PREFIX = "/repro/"
_COUNTERS = {}
_LISTENING = []


def _count(name, value, **kw):
    if name.startswith(COUNTER_PREFIX):
        _COUNTERS[name] = _COUNTERS.get(name, 0) + value


def program_counters() -> dict:
    """The program's trace-time counters so far in this process: every
    ``jax.monitoring`` scalar whose name starts with ``/repro/``, summed by
    name.  The first call registers the listener; take differences
    (``counters_since``)."""
    import jax

    if not _LISTENING:
        _LISTENING.append(True)
        jax.monitoring.register_scalar_listener(_count)
    return dict(_COUNTERS)


def counters_since(before: dict) -> dict:
    """The counters that moved since ``before``, by how much."""
    now = program_counters()
    return {k: v - before.get(k, 0) for k, v in sorted(now.items())
            if v != before.get(k, 0)}


def data_step0(seed: int) -> int:
    """The seed picks where in the job's token stream the run starts: a
    traced step index, so one compiled feed serves every seed."""
    return traffic.small_seed(seed) % (1 << 30)


def reference_batches(ctx) -> list:
    job, dims = ctx.mix, ctx.dims
    d = job["data"]
    return [reference.train_tokens(
        d["seed"], data_step0(ctx.seed) + s, ctx.chips,
        job["batch_per_chip"], job["seq_len"], dims["vocab"],
        d["structure"], d["a"], d["b"]) for s in range(CHECK_STEPS)]


def gaps(prog: dict, ref: dict, exclude=()) -> float:
    """Worst leaf's |prog − ref| / max(ref, median leaf ref)."""
    keys = [k for k in ref if k not in exclude]
    med = statistics.median(ref[k] for k in keys)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def zero_grad_leaves(grad_norms: dict) -> list:
    med = statistics.median(grad_norms.values())
    return sorted(k for k, v in grad_norms.items()
                  if v < ZERO_GRAD_FRACTION * med)


def compare(prog: dict, ref: dict) -> dict:
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    return {
        "loss_gap": loss_gap,
        "grad_gap": gaps(prog["grad_norms"], ref["grad_norms"]),
        "change_gap": gaps(prog["change_norms"], ref["change_norms"],
                           exclude=zero_grad_leaves(ref["grad_norms"])),
    }


class Trainer:
    """The compiled step with its state, and the feed of the window."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from repro.core.fabric import DEFAULT_BUCKET_BYTES, BucketLayout
        from repro.core.precision import get_policy, init_scale_state
        from repro.data.pipeline import DataConfig
        from repro.launch.specs import ShapeSpec, build_train_step
        from repro.optim.optimizers import adam
        from repro.train.loop import zero1_opt_template

        job, dims, seed, fam = ctx.mix, ctx.dims, ctx.seed, ctx.family
        self.chips, self.bpc, self.seq = ctx.chips, job["batch_per_chip"], \
            job["seq_len"]
        self.batch = self.bpc * self.chips
        zero = job["zero_stage"]
        opt = job["optimizer"]
        cfg = fam.program_config(ctx.conf, dims)
        self.mesh = Mesh(np.array(ctx.devices).reshape(self.chips, 1, 1),
                         ("pod", "data", "model"))
        step, (state_sds, batch_sds), (state_sh, self.batch_sh), donate = \
            build_train_step(cfg, ShapeSpec(ctx.workload, self.seq,
                                            self.batch, "train"),
                             self.mesh, precision=ctx.conf["precision"],
                             zero_stage=zero)
        want = weights.tree_paths(fam.shapes(dims))
        got = weights.tree_paths(state_sds["params"])
        if want != got:
            raise ValueError(f"the program's parameter tree differs from "
                             f"the benchmark's: {want} vs {got}")
        policy = get_policy(ctx.conf["precision"])
        pdt = jax.tree.leaves(state_sds["params"])[0].dtype

        self.wkey = weights.key(seed)

        def make_state(wkey):
            master = weights.init(fam, wkey, dims, jnp.float32)
            st = {"params": jax.tree.map(lambda x: x.astype(pdt), master),
                  "comm_state": {}, "step": jnp.zeros((), jnp.int32)}
            if zero:
                st["opt_state"] = zero1_opt_template(
                    master, adam(opt["lr"]), self.chips, policy=policy)
            else:
                st["opt_state"] = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype),
                    state_sds["opt_state"])
                if "master" in state_sds:
                    st["master"] = master
            if "loss_scale" in state_sds:
                st["loss_scale"] = init_scale_state(policy)
            return st

        if jax.tree.structure(jax.eval_shape(make_state, self.wkey)) != \
                jax.tree.structure(state_sds):
            raise ValueError("the benchmark's state does not match the "
                             "program's state template")
        self.state = jax.jit(make_state, out_shardings=state_sh)(self.wkey)
        self.fn = jax.jit(step, in_shardings=(state_sh, self.batch_sh),
                          donate_argnums=donate)
        d = job["data"]
        self.first_step = data_step0(seed)
        self.dcfg = DataConfig(vocab_size=dims["vocab"], seq_len=self.seq,
                               batch_per_worker=self.bpc,
                               structure=d["structure"], a=d["a"], b=d["b"],
                               seed=d["seed"])
        lay = BucketLayout.build(state_sds["params"], DEFAULT_BUCKET_BYTES,
                                 lead_axes=0)
        b1 = opt["b1"]

        sharded_master = zero and policy.keeps_master

        def master_of(st):
            if sharded_master:
                return lay.debucketize(st["opt_state"]["master"], cast=False)
            return st.get("master", st["params"])

        def m_of(st):
            if sharded_master:
                return lay.debucketize(st["opt_state"]["opt"]["m"],
                                       cast=False)
            if zero:
                return lay.debucketize(st["opt_state"]["m"], cast=False)
            return st["opt_state"]["m"]

        def norms(tree):
            return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32)))), tree)

        self.grad_readout = jax.jit(
            lambda st: norms(jax.tree.map(lambda m: m / (1 - b1), m_of(st))))
        self.change_readout = jax.jit(lambda st, k: norms(jax.tree.map(
            jnp.subtract, master_of(st), weights.init(fam, k, dims,
                                                      jnp.float32))))

    def feed(self, t: int):
        import jax

        from repro.data.pipeline import global_batch

        toks = global_batch(self.dcfg, self.first_step + t, self.batch)
        return jax.device_put({"tokens": toks, "labels": toks}, self.batch_sh)

    def step(self, batch):
        from repro.core.jax_compat import set_mesh

        with set_mesh(self.mesh):
            self.state, loss = self.fn(self.state, batch)
        return loss

    def step_text(self, batch) -> str:
        """The compiled text of the step that ran, lowered again from its
        state and a batch of the window's shape."""
        from repro.core.jax_compat import set_mesh

        with set_mesh(self.mesh):
            return self.fn.lower(self.state, batch).compile().as_text()


def check_steps(tr) -> dict:
    """The first three steps through the window's own call and feed, with
    the readings the reference is compared with."""
    import jax

    prog = {"losses": []}
    for t in range(CHECK_STEPS):
        prog["losses"].append(tr.step(tr.feed(t)))
        if t == 0:
            g = tr.grad_readout(tr.state)
    c = tr.change_readout(tr.state, tr.wkey)
    prog["losses"] = [float(x) for x in prog["losses"]]
    prog["grad_norms"] = {k: float(v) for k, v in reference.flat(g).items()}
    prog["change_norms"] = {k: float(v) for k, v in
                            reference.flat(c).items()}
    del g, c
    jax.block_until_ready(tr.state)
    return prog


def run(ctx) -> dict:
    import jax

    from repro.launch.compile_cache import compile_count

    before = program_counters()
    tr = Trainer(ctx)
    prog = check_steps(tr)
    counters = counters_since(before)
    n_compiles = compile_count()
    per_step_tokens = tr.batch * tr.seq
    per_step = ctx.family.train_flops_per_token(ctx.dims, tr.seq) \
        * per_step_tokens
    batch_s, losses, pending = [], [], deque()
    traced = None
    t = CHECK_STEPS
    t_w0 = time.perf_counter()
    setup_s = time.time() - ctx.t_process
    while time.perf_counter() - t_w0 < ctx.seconds:
        if ctx.trace and traced is None and \
                time.perf_counter() - t_w0 >= 0.4 * ctx.seconds:
            traced, t = _traced_steps(ctx, tr, t, batch_s, losses)
            continue
        with jax.profiler.TraceAnnotation("bench.batch"):
            h0 = time.perf_counter()
            b = tr.feed(t)
            batch_s.append(time.perf_counter() - h0)
        with jax.profiler.TraceAnnotation("bench.step"):
            loss = tr.step(b)
        losses.append(loss)
        pending.append(loss)
        if len(pending) > 2:
            with jax.profiler.TraceAnnotation("bench.sync"):
                pending.popleft().block_until_ready()
        t += 1
    jax.block_until_ready(tr.state)
    window = time.perf_counter() - t_w0
    steps = t - CHECK_STEPS
    in_window = compile_count() - n_compiles
    losses = [float(x) for x in losses]
    failed = sum(not math.isfinite(x) for x in losses)
    peak = ctx.memory_peak()
    ctx.log(f"window {window:.3f} s, {steps} steps, {in_window} compiles in "
            f"the window, last loss {losses[-1] if losses else None}; "
            f"program counters {counters}")
    step_text = tr.step_text(tr.feed(t)) if ctx.trace else None
    bpc, seq = tr.bpc, tr.seq
    del tr
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference.train_steps(ctx.family, ctx.seed, ctx.dims,
                                reference_batches(ctx), ctx.mix["optimizer"],
                                devices=ctx.devices)
    ctx.log(f"reference {time.perf_counter() - t_ref:.1f} s; losses "
            f"{prog['losses']} vs {ref['losses']}")
    got = compare(prog, ref)
    checks = {k: (v, ctx.limits[k]) for k, v in got.items()}
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())
    return {
        "correct": correct, "attempted": steps, "failed": failed,
        "memory_peak_bytes": int(peak), "checks": checks,
        "e2e": {"setup_s": setup_s,
                "train_tokens_per_s": steps * per_step_tokens / window},
        "window_s": window, "steps": steps, "batch_s": batch_s,
        "flops_per_step": per_step, "chips": ctx.chips, "peaks": ctx.peaks,
        "traced_steps": traced, "compiles_in_window": in_window,
        "counters": counters, "dims": ctx.dims, "batch_per_chip": bpc,
        "seq_len": seq,
        "trace": _reduce(ctx, step_text) if ctx.trace else None,
    }


def _traced_steps(ctx, tr, t, batch_s, losses):
    """``trace_steps`` steps under the profiler, inside ``bench.window``,
    after the device has drained."""
    import shutil

    import jax

    jax.block_until_ready(tr.state)
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(ctx.trace_dir))
    n = ctx.mix["trace_steps"]
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(n):
            with jax.profiler.TraceAnnotation("bench.batch"):
                h0 = time.perf_counter()
                b = tr.feed(t)
                batch_s.append(time.perf_counter() - h0)
            with jax.profiler.TraceAnnotation("bench.step"):
                losses.append(tr.step(b))
            t += 1
        with jax.profiler.TraceAnnotation("bench.sync"):
            jax.block_until_ready(tr.state)
    jax.profiler.stop_trace()
    return n, t


def _reduce(ctx, step_text) -> dict:
    import shutil

    from chipbench.trace_reduce import load_xplane, reduce_trace

    red = reduce_trace(load_xplane(str(ctx.trace_dir)), step_text=step_text)
    per_step = 1e3 / ctx.mix["trace_steps"]
    ctx.log(f"traced: busy {red['busy_s'] * per_step:.3f} ms a step; by "
            f"phase (ms a step) " + ", ".join(
                f"{k} {v * per_step:.3f}" for k, v in
                sorted(red["phase_s"].items(), key=lambda kv: -kv[1])))
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    return red
