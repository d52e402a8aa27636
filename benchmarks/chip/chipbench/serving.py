"""Serving cells: ``serve.engine.PagedDecodeEngine`` with bf16 weights and
bf16 pages, the Pallas paged kernel on, fed by an open-loop schedule.

Set-up makes the weights on the device from the seed in one call, builds
the engine, and warms its one prefill-chunk shape and one decode shape
with a request of its own.  The schedule then starts: warm-up arrivals
for ``warmup_s``, then the window's arrivals for ``--seconds``.  Each
request is timed from when it was due.  After the window closes no
request arrives, and the engine runs on until every request due in the
window has its first token (at most ``drain_s`` more); one that has none
by then has failed.

``correct``: no request failed, and over a sample of finished requests
drawn from the seed (the longest among them), the widest gap by which a
served token's logit lies below the plain reference's best logit at that
position is within its limit.  The reference runs once the engine and
its weights are freed.
"""

from __future__ import annotations

import gc
import shutil
import time

import numpy as np

from chipbench import flops, reference, traffic, weights

SAMPLE_TOKENS = 300
SAMPLE_MAX = 8


class StepLog:
    """What each engine step computed, read from the engine's host state
    around the step (no device read)."""

    def __init__(self, eng):
        self.eng = eng

    def before(self):
        e = self.eng
        return ({id(r): (r, int(e.prompt_cursor[i]), e.phase[i],
                         len(r.generated))
                 for i, r in enumerate(e.slot) if r is not None},
                list(e.queue))

    def after(self, snap):
        """(prefill positions, prefill rows, decode contexts)."""
        e = self.eng
        slots, queued = snap
        now = {id(r): i for i, r in enumerate(e.slot) if r is not None}
        positions, rows, ctx = [], 0, []
        reqs = dict(slots)
        for r in queued:
            if id(r) not in reqs:
                reqs[id(r)] = (r, 0, "queued", len(r.generated))
        for key, (r, cur0, phase0, ngen0) in reqs.items():
            if phase0 == "queued" and key not in now and not r.done:
                continue  # still waiting
            if phase0 in ("queued", "prefill"):
                if key in now:
                    cur1 = int(e.prompt_cursor[now[key]])
                else:  # finished inside this step
                    cur1 = len(r.prompt)
                if cur1 > cur0:
                    positions.extend(range(cur0, cur1))
                    rows += 1
            ngen1 = len(r.generated)
            first = 1 if (phase0 in ("queued", "prefill")
                          and ngen1 > ngen0) else 0
            if ngen1 - ngen0 - first >= 1:
                # decoded once this step; its context includes the new token
                ctx.append(len(r.prompt) + ngen1 - 1)
        return positions, rows, ctx


def run(ctx) -> dict:
    rec, served, wkey, make = serve(ctx)
    t_ref = time.perf_counter()
    params = make(wkey)
    gaps = reference.served_gaps(ctx.family, params, ctx.dims, served,
                                 ctx.mix["max_seq"])
    del params
    token_gap = float(max(g.max() for g in gaps["gaps"]))
    ctx.log(f"reference {time.perf_counter() - t_ref:.1f} s over "
            f"{len(served)} requests, {sum(len(g) for _, g in served)} "
            f"served tokens; widest gap {token_gap}")
    rec["checks"] = {"token_gap": (token_gap, ctx.limits["token_gap"])}
    rec["correct"] = rec["failed"] == 0 and \
        token_gap <= ctx.limits["token_gap"]
    return rec


def serve(ctx):
    """The window; returns its record, the sampled (prompt, served tokens),
    and what remakes the weights.  The engine and its weights are freed."""
    import jax
    import jax.numpy as jnp

    from repro.core.precision import apply_policy, get_policy
    from repro.models import transformer as T
    from repro.launch.compile_cache import compile_count
    from repro.serve.engine import PagedDecodeEngine, Request

    mix, dims, seed, fam = ctx.mix, ctx.dims, ctx.seed, ctx.family
    cfg = apply_policy(fam.program_config(ctx.conf, dims),
                       get_policy(ctx.conf["precision"]))
    wdt = jnp.dtype(cfg.param_dtype)
    want = weights.tree_paths(fam.shapes(dims))
    got = weights.tree_paths(jax.eval_shape(
        lambda: T.init_model(jax.random.PRNGKey(0), cfg)))
    if want != got:
        raise ValueError(f"the program's parameter tree differs from the "
                         f"benchmark's: {want} vs {got}")
    wkey = weights.key(seed)
    make = jax.jit(lambda k: weights.init(fam, k, dims, wdt))
    params = make(wkey)
    eng = PagedDecodeEngine(params, cfg, batch_slots=mix["batch_slots"],
                            max_seq=mix["max_seq"],
                            cache_dtype=jnp.dtype(mix["kv_dtype"]),
                            use_kernel=None)
    if ctx.devices[0].platform == "tpu" and not eng.use_kernel:
        raise RuntimeError("the paged engine did not pick the Pallas kernel")
    # warm the one prefill-chunk shape and the one decode shape
    warm = Request(-1, np.arange(eng.chunk + 1, dtype=np.int32) % dims[
        "vocab"], 2)
    eng.submit(warm)
    eng.run()
    eng.finished.clear()
    sched = traffic.serve_schedule(mix, seed, ctx.seconds, dims["vocab"])

    log = StepLog(eng)
    reqs, due, admitted = [], {}, {}
    step_t, flops_rec = [], []
    trace_state = {"on": False, "done": not ctx.trace, "note": None}
    late = []
    warm_s, seconds = float(mix["warmup_s"]), float(ctx.seconds)
    trace_at, trace_len = 0.4 * seconds, float(mix["trace_seconds"])
    n_compiles = None
    slow = []  # (seconds, when after the window opened) of the slowest steps
    i = 0
    t_base = time.perf_counter()
    w0, w1 = t_base + warm_s, t_base + warm_s + seconds
    setup_s = None
    window_reqs = []
    while True:
        now = time.perf_counter()
        if setup_s is None and now >= w0:
            setup_s = time.time() - ctx.t_process
            n_compiles = compile_count()
        if not trace_state["done"]:
            _trace_switch(ctx, trace_state, now, w0 + trace_at,
                          w0 + trace_at + trace_len)
        with jax.profiler.TraceAnnotation("bench.arrivals"):
            while i < len(sched) and t_base + sched[i].due <= now:
                a = sched[i]
                r = Request(a.rid, a.prompt, a.max_new_tokens)
                eng.submit(r)
                due[a.rid] = t_base + a.due
                late.append(now - due[a.rid])
                reqs.append(r)
                if a.in_window:
                    window_reqs.append(r)
                i += 1
        if now >= w1 and (i == len(sched) and all(
                r.token_times for r in window_reqs)
                or now >= w1 + mix["drain_s"]):
            break
        if eng.queue or any(p != "idle" for p in eng.phase):
            queued = list(eng.queue)
            snap = log.before() if trace_state["on"] else None
            t_s = time.perf_counter()
            with jax.profiler.TraceAnnotation("engine.step"):
                eng.step()
            t_e = time.perf_counter()
            if w0 <= t_s < w1:
                slow.append((t_e - t_s, t_s - w0))
            if queued:
                left = {id(r) for r in queued} - {id(r) for r in eng.queue}
                for r in queued:
                    if id(r) in left:
                        admitted[r.rid] = t_s
            if snap is not None:
                step_t.append(t_e - t_s)
                flops_rec.append(log.after(snap))
        else:
            nxt = t_base + sched[i].due if i < len(sched) else w1
            time.sleep(max(0.0, min(nxt, w1) - time.perf_counter()))
    if trace_state["on"]:
        _trace_switch(ctx, trace_state, float("inf"), 0, 0)
    in_window = compile_count() - (n_compiles or 0)

    peak = ctx.memory_peak()
    ttft, failed = [], 0
    for r in window_reqs:
        if r.token_times:
            ttft.append(r.token_times[0] - due[r.rid])
        else:
            failed += 1
    itl, n_tok = [], 0
    for r in reqs:
        tt = r.token_times
        n_tok += sum(w0 <= x < w1 for x in tt)
        itl.extend(b - a for a, b in zip(tt, tt[1:]) if w0 <= b < w1)
    admit = [admitted[r.rid] - due[r.rid] for r in window_reqs
             if r.rid in admitted]
    ctx.log(f"{len(window_reqs)} requests due in the window, {failed} "
            f"without a first token; {len(itl)} gaps; {eng.steps} engine "
            f"steps; generator lateness p99 "
            f"{traffic.percentile(late, 99) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms; {in_window} compiles after the "
            f"window opened; slowest steps (s, at s) "
            f"{[(round(a, 3), round(b, 1)) for a, b in sorted(slow)[-3:]]}")

    sample = _sample([r for r in reqs if r.done], seed)
    served = [(np.asarray(r.prompt), list(r.generated)) for r in sample]
    del eng, params, log
    gc.collect()
    trace = None
    if ctx.trace:
        from chipbench.trace_reduce import load_xplane, reduce_trace
        trace = reduce_trace(load_xplane(str(ctx.trace_dir)))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    kv_bytes = jnp.dtype(mix["kv_dtype"]).itemsize
    return {
        "attempted": len(window_reqs), "failed": failed,
        "memory_peak_bytes": int(peak),
        "e2e": {"setup_s": setup_s,
                "ttft_p90_ms": traffic.percentile(ttft, 90) * 1e3
                if ttft else float("nan"),
                "itl_p95_ms": traffic.percentile(itl, 95) * 1e3,
                "serve_tokens_per_s": n_tok / seconds},
        "admit_wait_s": admit, "late_s": late, "slow_steps": sorted(slow)[-5:],
        "compiles_in_window": in_window, "peaks": ctx.peaks, "chips": ctx.chips,
        "dims": dims, "traced_step_s": step_t,
        "traced_model_flops": sum(
            fam.prefill_flops(dims, pos, rows)
            + (fam.decode_flops(dims, c) if c else 0.0)
            for pos, rows, c in flops_rec),
        "traced_paged_attention": [
            flops.paged_attention_cost(dims, c, kv_bytes, wdt.itemsize)
            for _, _, c in flops_rec if c],
        "trace": trace,
    }, served, wkey, make


def _sample(done, seed):
    """The longest finished request, then others drawn from the seed until
    the sample holds ``SAMPLE_TOKENS`` served tokens."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r.rid)
    longest = max(done, key=lambda r: (len(r.generated), -r.rid))
    rest = [r for r in done if r is not longest]
    order = traffic.rng(seed, 9).permutation(len(rest))
    out = [longest]
    for j in order:
        if sum(len(r.generated) for r in out) >= SAMPLE_TOKENS \
                or len(out) >= SAMPLE_MAX:
            break
        out.append(rest[j])
    return out


def _trace_switch(ctx, st, now, start, stop):
    import jax

    if not st["on"] and now >= start:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(ctx.trace_dir), profiler_options=opts)
        st["note"] = jax.profiler.TraceAnnotation("bench.window")
        st["note"].__enter__()
        st["on"] = True
    elif st["on"] and now >= stop:
        st["note"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        st["on"], st["done"] = False, True
