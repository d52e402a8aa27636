"""Rig builders: the small jax programs whose traced/compiled artifacts
the lint rules (repro.analysis.rules) check.

Three cost tiers, matched to what each contract actually depends on:

  * **exchange rigs** — shard_map of ``strategy.update`` over a
    ``ShardComm`` with the config's (reduced-scale) parameter tree:
    per (config × strategy × precision).  These need
    ``--xla_force_host_platform_device_count`` ≥ ``workers``; the lint
    CLI (launch/lint.py) and the subprocess tests set it before
    importing jax.
  * **loop rigs** (donation / retrace) and **eager rigs**
    (state-aliasing, fused-dispatch) — LocalComm stacked-replica
    programs on a tiny synthetic problem: the contracts they prove live
    in the train-step machinery and the strategy code, not the model,
    so they are evaluated once per (strategy × precision × accum) and
    shared across configs.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis import rules
from repro.core import compression as C
from repro.core import strategies as ST
from repro.core.comm import LocalComm, ShardComm
from repro.core.fabric import BucketLayout, Fabric
from repro.core.jax_compat import make_mesh, set_mesh, shard_map
from repro.core.precision import PrecisionPolicy, cast_floats, get_policy
from repro.optim import sgd
from repro.train.loop import (
    init_train_state,
    jit_cache_size,
    make_replica_train_step,
    zero1_opt_template,
    zero3_param_template,
)

WORKERS = 4  # mesh/replica width of every rig


def rig_policy(precision: str) -> Optional[PrecisionPolicy]:
    """'f32' rides the policy-less production path (the f32 policy is a
    proven bitwise no-op, and passing None matches how launch/specs
    builds the step)."""
    pol = get_policy(precision)
    return None if pol.is_noop else pol


def build_strategy(name: str, policy: Optional[PrecisionPolicy],
                   bucket_bytes: int) -> ST.Strategy:
    kw = dict(bucket_bytes=bucket_bytes, policy=policy)
    if name == "sync_dgc":
        kw["compressor"] = C.get_compressor("topk", ratio=0.25)
    return ST.get_strategy(name, **kw)


def param_sds(cfg, policy: Optional[PrecisionPolicy]):
    """Reduced-scale parameter ShapeDtypeStructs for a config, float
    leaves at the policy's param dtype (what the production sharded step
    hands the strategy)."""
    from repro.launch.specs import model_sds

    sds = model_sds(cfg.reduced() if hasattr(cfg, "reduced") else cfg)
    if policy is None:
        return sds
    dt = policy.param_dt

    def cast(s):
        if jnp.issubdtype(s.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(s.shape, dt)
        return s

    return jax.tree.map(cast, sds)


def pick_bucket_bytes(tree, target_buckets: int = 6) -> int:
    """Bucket size giving a handful of buckets at rig scale, so the
    ≤ n_buckets budgets are exercised with n_buckets > 1 while the HLO
    stays small."""
    total = sum(math.prod(s.shape) for s in jax.tree.leaves(tree))
    return max(4 * 2000, 4 * -(-total // target_buckets))


# ---------------------------------------------------------------------------
# exchange rig — compiled HLO + jaxpr of one strategy.update on a mesh
# ---------------------------------------------------------------------------
def exchange_artifacts(params, strategy_name: str, precision: str,
                       workers: int = WORKERS,
                       bucket_bytes: Optional[int] = None) -> dict:
    """Lower ``strategy.update`` (traced step counter, so schedule gates
    become lax.cond) under shard_map over a ``workers``-wide 'pod' axis.

    Returns the artifacts every HLO/jaxpr rule consumes:
    ``hlo`` text, ``jaxpr``, the bucket ``layout``, the fabric
    ``contract`` for the strategy's declared wire profile, and the
    ``strategy`` itself."""
    pol = rig_policy(precision)
    if bucket_bytes is None:
        bucket_bytes = pick_bucket_bytes(params)
    strat = build_strategy(strategy_name, pol, bucket_bytes)
    owns_params = getattr(strat, "owns_params", False)
    comm = ShardComm("pod", workers)
    mesh = make_mesh((workers,), ("pod",))
    opt = sgd(0.1)
    rep = jax.tree.map(lambda _: P(), params)
    if owns_params:
        # ZeRO-3: the train state's params are flat shard buckets (the
        # production zero3_param_template shapes), sharded over the pod
        # axis; the dense tree only appears as the gradient input.
        p_state = zero3_param_template(params, workers, bucket_bytes)
        p_spec = jax.tree.map(lambda _: P("pod"), p_state)
    else:
        p_state, p_spec = params, rep
    if strat.init_opt is not None:
        # stage-3 f32 param shards double as the master: no policy split
        opt_state = zero1_opt_template(params, opt, workers, bucket_bytes,
                                       policy=None if owns_params else pol)
        opt_spec = jax.tree.map(lambda _: P("pod"), opt_state)
    else:
        opt_state = jax.eval_shape(opt.init, params)
        opt_spec = jax.tree.map(lambda _: P(), opt_state)
    cstate = jax.eval_shape(lambda p: strat.init(p, comm), params)
    t_sds = jax.ShapeDtypeStruct((), jnp.int32)

    def body(p, g, s, c, t):
        p2, s2, c2, _ = strat.update(p, g, s, c, t, opt, comm)
        return p2, s2, c2

    crep = jax.tree.map(lambda _: P(), cstate)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(p_spec, rep, opt_spec, crep, P()),
                   out_specs=(p_spec, opt_spec, crep),
                   check_vma=False)
    args = (p_state, params, opt_state, cstate, t_sds)
    jaxpr = jax.make_jaxpr(fn)(*args)
    with set_mesh(mesh):
        hlo = jax.jit(fn).lower(*args).compile().as_text()
    fab = Fabric(comm, bucket_bytes,
                 wire_dtype=pol.wire_dt if pol is not None else None)
    lay = BucketLayout.build(params, bucket_bytes, lead_axes=0)
    contract = fab.collective_contract(lay, strat.wire_profile,
                                       events=strat.wire_events)
    return {"hlo": hlo, "jaxpr": jaxpr, "layout": lay,
            "contract": contract, "strategy": strat,
            "narrow_wire": pol is not None and pol.narrow_wire,
            "bucket_bytes": bucket_bytes}


# ---------------------------------------------------------------------------
# loop rig — donation aliasing + retrace on the replica train step
# ---------------------------------------------------------------------------
def _tiny_problem(workers: int, accum: int, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    kw, kx, ky = jax.random.split(key, 3)
    params = {"w": jax.random.normal(kw, (8, 16), jnp.float32),
              "b": jnp.zeros((16,), jnp.float32)}
    lead = (accum, workers) if accum > 1 else (workers,)
    batch = {"x": jax.random.normal(kx, lead + (4, 8), jnp.float32),
             "y": jax.random.normal(ky, lead + (4, 16), jnp.float32)}

    def loss_fn(p, b):
        pred = b["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - b["y"]) ** 2)

    return params, batch, loss_fn


def _state_nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def loop_artifacts(strategy_name: str, precision: str, accum: int,
                   workers: int = WORKERS, steps: int = 3) -> dict:
    """Build the production replica train step (jitted, donated) on a
    tiny synthetic problem; compile it for the donation proof, then run
    ``steps`` boundaries for the retrace proof.

    These contracts live in train/loop.py + the strategy, not the model,
    so one evaluation covers every config."""
    pol = rig_policy(precision)
    comm = LocalComm(workers)
    opt = sgd(0.05)
    base, batch, loss_fn = _tiny_problem(workers, accum)
    params = comm.replicate(base)
    if pol is not None:
        params = cast_floats(params, pol.param_dt)
    strat = build_strategy(strategy_name, pol, bucket_bytes=4 * 256)
    state = init_train_state(params, opt, strat, comm, policy=pol)
    step = make_replica_train_step(loss_fn, opt, strat, comm, policy=pol,
                                   accum_steps=accum,
                                   bucket_bytes=4 * 256)
    donated_bytes = _state_nbytes(state)
    compiled = step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    alias_bytes = int(getattr(mem, "alias_size_in_bytes", 0) or 0)
    cache_sizes = []
    for _ in range(steps):
        state, _ = step(state, batch)
        cache_sizes.append(jit_cache_size(step))
    return {"alias_bytes": alias_bytes, "donated_bytes": donated_bytes,
            "cache_sizes": cache_sizes,
            "hlo": compiled.as_text()}


# ---------------------------------------------------------------------------
# tp rig — tensor-parallel activation combines on a 'model' mesh
# ---------------------------------------------------------------------------
TP_DEGREE = 2


def tp_artifacts(precision: str, tp_degree: int = TP_DEGREE) -> dict:
    """Lower one TP training step (forward + backward + replicated-grad
    finalize) of a tiny ``tp_degree``-split transformer under shard_map
    over a 'model' mesh.  The TP combine contract lives entirely in
    models/layers.py + models/tensor_parallel.py — not the config or the
    strategy — so one rig per precision covers every lint cell.

    Returns the compiled ``hlo``, the op->count ``contract`` (activation
    combines from ``tp_collective_contract`` plus the finalize_grads
    bucket budget) and ``tp_degree``."""
    import dataclasses

    from repro.configs.base import get_config
    from repro.models import tensor_parallel as TP
    from repro.models import transformer as T

    pol = rig_policy(precision)
    cfg = dataclasses.replace(
        get_config("qwen2-1.5b").reduced(),
        num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
        head_dim=16, d_ff=64, vocab_size=64, tp_degree=tp_degree)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    if pol is not None:
        params = cast_floats(params, pol.param_dt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    targets = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                 cfg.vocab_size)
    shards = TP.tp_split_params(params, tp_degree)

    def loss_of(p):
        logits, _ = T.forward(p, cfg, tokens)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                             axis=-1))

    def rank_step(sh):
        p = jax.tree.map(lambda v: v[0], sh)
        with TP.tp_context(tp_degree):
            loss, grads = jax.value_and_grad(loss_of)(p)
            grads = TP.current_tp().finalize_grads(grads)
        loss = jax.lax.pmean(loss, "model")
        return loss, jax.tree.map(lambda v: v[None], grads)

    mesh = make_mesh((tp_degree,), ("model",))
    spec = jax.tree.map(lambda _: P("model"), shards)
    fn = shard_map(rank_step, mesh=mesh,
                   in_specs=(spec,), out_specs=(P(), spec),
                   check_vma=False)
    with set_mesh(mesh):
        hlo = jax.jit(fn).lower(shards).compile().as_text()
    act = jax.ShapeDtypeStruct(
        (2, 8, cfg.d_model), jnp.float32 if pol is None else pol.param_dt)
    contract = dict(TP.tp_collective_contract(cfg, act))
    # finalize_grads ships the replicated-leaf grads as one bucketed
    # all-sum on the same fabric — extend the combine budget by its
    # bucket count.
    rep, _ = TP._partition_replicated(
        jax.tree.map(lambda v: v[0], shards), "stack")
    fab = Fabric(ShardComm("model", tp_degree))
    contract["all-reduce"] = (contract.get("all-reduce", 0)
                              + fab.layout(rep).n_buckets)
    return {"hlo": hlo, "contract": contract, "tp_degree": tp_degree}


# ---------------------------------------------------------------------------
# eager rig — comm_state mutation detector
# ---------------------------------------------------------------------------
def state_aliasing_artifacts(strategy_name: str, precision: str,
                             workers: int = WORKERS) -> dict:
    """Run ``strategy.update`` eagerly on concrete arrays at several
    schedule phases (t hitting and missing sync boundaries) and snapshot
    the input comm_state around every call — any structural diff is an
    in-place mutation of the caller's tree."""
    pol = rig_policy(precision)
    comm = LocalComm(workers)
    opt = sgd(0.05)
    base, _, _ = _tiny_problem(workers, accum=1)
    params = comm.replicate(base)
    if pol is not None:
        params = cast_floats(params, pol.param_dt)
    strat = build_strategy(strategy_name, pol, bucket_bytes=4 * 256)
    grads = jax.tree.map(lambda p: jnp.ones_like(p) * 0.01, params)
    if getattr(strat, "owns_params", False):
        # ZeRO-3 state params are shard buckets; grads stay dense
        params = strat.init_params(params, comm)
    if strat.init_opt is not None:
        opt_state = strat.init_opt(params, opt, comm)
    else:
        opt_state = opt.init(params)
    cstate = strat.init(params, comm)
    snaps = []
    for t in range(max(2, strat.sync_every)):
        before = rules.tree_snapshot(cstate)
        _, opt_state, new_c, _ = strat.update(
            params, grads, opt_state, cstate, t, opt, comm)
        snaps.append((before, rules.tree_snapshot(cstate)))
        cstate = new_c
    return {"snapshots": snaps}


# ---------------------------------------------------------------------------
# eager rig — fused compressed dispatch (pallas_call, no jnp codec)
# ---------------------------------------------------------------------------
def fused_artifacts(params, precision: str, workers: int = WORKERS,
                    bucket_bytes: Optional[int] = None,
                    fused: bool = True) -> dict:
    """Trace the compressed ``Fabric.exchange_dgc`` (the sync_dgc wire)
    on stacked replicas, counting jnp codec entries while tracing: the
    fused path must dispatch ``pallas_call`` and never touch the jnp
    pack/codec fallback."""
    pol = rig_policy(precision)
    if bucket_bytes is None:
        bucket_bytes = pick_bucket_bytes(params)
    comp = C.get_compressor("topk", ratio=0.25)
    fab = Fabric(LocalComm(workers), bucket_bytes,
                 wire_dtype=pol.wire_dt if pol is not None else None,
                 fused=fused)
    calls = {"n": 0}
    orig_fallback = fab._bucket_mean_compressed

    def counting_fallback(target, compressor):
        # the jnp codec dispatch point: the fused path must never enter
        # the per-bucket compress→pack fallback.  (compressor.compress
        # alone is NOT a reliable probe — wire accounting
        # (compression.packed_nbytes) eval_shapes it for metrics without
        # shipping anything.)
        calls["n"] += 1
        return orig_fallback(target, compressor)

    fab._bucket_mean_compressed = counting_fallback
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((workers,) + s.shape, s.dtype),
        params)
    dgc = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
        {"velocity": stacked, "residual": stacked})

    def ex(g, st):
        out, new_st, _ = fab.exchange_dgc(g, st, comp)
        return out, new_st

    jaxpr = jax.make_jaxpr(ex)(stacked, dgc)
    return {"jaxpr_text": str(jaxpr), "codec_calls": calls["n"]}


# ---------------------------------------------------------------------------
# elastic rig: the demoted-tier resync path (DESIGN.md §13)
# ---------------------------------------------------------------------------
def elastic_artifacts(workers: int = WORKERS, resync_every: int = 4) -> dict:
    """Trace ONLY the demoted-tier resync of ``launch/elastic.py`` over a
    ShardComm with a TRACED boundary counter and participation mask — the
    jaxpr the ``elastic-demotion-gated`` rule walks.

    The masked boundary exchange itself is intentionally UNGATED (it
    fires every boundary); the contract is that the resync's consensus
    pull — the only collective a demoted worker's recovery adds — sits
    under ``lax.cond``.  ``make_jaxpr(axis_env=...)`` keeps the rig
    device-free: the rule is jaxpr-level, no mesh compile needed."""
    from repro.launch.elastic import demoted_resync

    comm = ShardComm("pod", workers)
    fab = Fabric(comm, 4 * 64)
    params = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32),
              "b": jax.ShapeDtypeStruct((16,), jnp.float32)}

    def body(p, mask, t):
        out, _ = demoted_resync(fab, p, mask, t, resync_every)
        return out

    jaxpr = jax.make_jaxpr(body, axis_env=[("pod", workers)])(
        params, jax.ShapeDtypeStruct((workers,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32))
    return {"jaxpr": jaxpr, "resync_every": resync_every}
