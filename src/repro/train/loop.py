"""Training loops.

Two entry points mirroring the Comm duality (DESIGN.md §3):

  * ``make_replica_train_step`` — the *strategy simulator*: W model replicas
    stacked on axis 0 (LocalComm layout), per-worker data shards, any
    spectrum strategy.  Runs on one device; used by tests, convergence
    benchmarks, and the examples.  This is the paper's experimental rig.

  * ``make_sharded_train_step`` — the production path: one global model,
    pjit-sharded over (pod, data, model); the strategy runs across the
    ``pod`` (or ``data``) axis via shard_map + ShardComm.  ``sync`` here is
    plain global data parallelism (the paper's point 1), which is also what
    the multi-pod dry-run lowers.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import jax_compat as compat
from repro.core import precision as PR
from repro.core.comm import Comm, HierComm, LocalComm, ShardComm
from repro.core.fabric import (BucketLayout, DEFAULT_BUCKET_BYTES, Fabric,
                               PartitionedLayout)
from repro.core.precision import PrecisionPolicy
from repro.core.scopes import FORWARD, OPTIMIZER, scoped
from repro.core.strategies import Strategy
from repro.models import transformer as T
from repro.optim.optimizers import Optimizer, state_template
from repro.train.losses import lm_loss


def init_train_state(params, optimizer: Optimizer, strategy: Strategy,
                     comm: Comm, policy: Optional[PrecisionPolicy] = None):
    # ZeRO-3: the strategy owns the PARAMETER layout too — the dense init
    # params are sharded into 1/W flat f32 buckets up front (recording the
    # PartitionedLayout inside the strategy) and everything downstream
    # (optimizer state, comm state) is built over the shards
    if getattr(strategy, "owns_params", False):
        params = strategy.init_params(params, comm)
    # strategies that own the optimizer-state layout (ZeRO-1 shard buckets)
    # build it themselves; everyone else gets the dense param-shaped state
    init_opt = getattr(strategy, "init_opt", None)
    opt_state = (init_opt(params, optimizer, comm) if init_opt is not None
                 else optimizer.init(params))
    state = {
        "params": params,
        "opt_state": opt_state,
        "comm_state": strategy.init(params, comm),
        "step": jnp.zeros((), jnp.int32),
    }
    if policy is not None and not policy.is_noop:
        if policy.uses_scaling:
            state["loss_scale"] = PR.init_scale_state(policy)
        if policy.keeps_master and not getattr(strategy, "owns_master",
                                               False):
            # dense strategies: the wider master copy lives in the train
            # state (the ZeRO-1 strategy keeps its own 1/W master shards
            # inside opt_state instead — never both)
            state["master"] = policy.cast_to_master(params)
    return state


# ---------------------------------------------------------------------------
# replica simulator (LocalComm stacked layout)
# ---------------------------------------------------------------------------
def make_replica_train_step(loss_fn, optimizer: Optimizer, strategy: Strategy,
                            comm: LocalComm, jit: bool = True,
                            policy: Optional[PrecisionPolicy] = None,
                            accum_steps: int = 1,
                            bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                            donate: bool = True):
    """loss_fn(params, batch) -> scalar, defined for ONE replica.

    The returned step takes stacked state (leading dim W on every leaf of
    params/opt_state) and per-worker batches (leading dim W), and is jitted
    with ``donate_argnums=(0,)`` (``donate=False`` opts out): the consumed
    train state aliases the produced one, so params / optimizer state /
    master / accumulator buffers are updated in place instead of
    re-allocated every step.  Callers must not touch a donated input state
    after stepping — re-step from a state you intend to keep only with
    ``donate=False``.

    ``accum_steps > 1`` turns the step into a MICROBATCHED boundary step
    (DESIGN.md §8): batches carry a leading ``(accum_steps, W, ...)`` axis,
    a ``lax.scan`` accumulates per-microbatch gradients directly into the
    Fabric's flat f32 buckets (one flatten per microbatch, no per-microbatch
    tree unflatten), and the strategy — hence the exchange, and with it any
    compression / error-feedback state — runs ONCE per boundary on the
    microbatch-mean gradients.  ``state["step"]`` counts optimizer steps
    (boundaries), so ``sync_every``-style schedules of local-step
    strategies (``Strategy.exchange_at_boundary=False``) are unchanged by
    accumulation.  Wire bytes per sample shrink by ``accum_steps``.

    With a non-trivial precision ``policy`` (core/precision.py) the step
    becomes cast-params → forward (scaled loss) → unscale → skip-or-apply:
    the strategy/optimizer pipeline runs on the widest copy available (the
    f32 master for dense strategies, the working params for the ZeRO-1
    strategy whose master rides its opt-state shard), the fabric ships
    wire-dtype buckets, and a step with non-finite gradients leaves
    params, optimizer state and comm state untouched while the dynamic
    loss scale backs off.  Under accumulation the finite check and the
    skip decision apply to the whole boundary.  ``policy=None`` (or the
    f32 policy) takes the exact pre-precision code path — bit-for-bit
    identical (the gradient of each microbatch is accumulated in f32 in
    the same order a per-microbatch reference would sum trees)."""
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    def _jit(fn):
        if not jit:
            return fn
        return jax.jit(fn, donate_argnums=(0,) if donate else ())

    def accum_grads(src, batches, vgrad_fn):
        """scan over the leading microbatch axis, accumulating gradients
        into flat f32 buckets — zero collectives in here; the boundary
        exchange consumes the SUM (callers divide by accum_steps, and by
        the loss scale, exactly once)."""
        # the accumulator is purely local: it only needs the replica-axis
        # layout, which a two-tier HierComm delegates to its inner comm
        # (both tiers declare the same lead_axes)
        acc_comm = comm.inner if isinstance(comm, HierComm) else comm
        fab = Fabric(acc_comm, bucket_bytes)
        lay = fab.layout(src)

        def micro(carry, mb):
            acc, loss_sum = carry
            loss, grads = vgrad_fn(src, mb)
            return (fab.accumulate(acc, grads, lay),
                    loss_sum + jnp.mean(loss)), None

        (acc, loss_sum), _ = lax.scan(
            micro, (fab.init_accum(lay), jnp.zeros((), jnp.float32)),
            batches)
        return acc, lay, loss_sum

    owns_params = getattr(strategy, "owns_params", False)
    part_accum = accum_steps > 1 and getattr(strategy, "partitioned_accum",
                                             False)

    def accum_grads_part(full, batches, vgrad_fn):
        """ZeRO-2/3 microbatch accumulation (DESIGN.md §12): every
        microbatch's gradients are reduce-scatter-meaned and ONLY the
        local 1/W shard accumulates (``Fabric.accumulate_partitioned``) —
        the full gradient tree is never resident across microbatches.
        The RS is a cross-worker collective, so it runs on the outer comm
        (not the HierComm inner tier).  Returns (summed shard buckets,
        summed per-replica-mean loss, RS wire bytes, RS events); callers
        divide the shards ONCE at the boundary."""
        fab = Fabric(comm, bucket_bytes)
        play = fab.partitioned_layout(full)

        def micro(carry, mb):
            acc, loss_sum, wire, ev = carry
            loss, grads = vgrad_fn(full, mb)
            acc, m = fab.accumulate_partitioned(acc, grads, play)
            return (acc, loss_sum + jnp.mean(loss), wire + m["wire_bytes"],
                    ev + m["comm_events"]), None

        (acc, loss_sum, wire, ev), _ = lax.scan(
            micro, (fab.init_accum_partitioned(play),
                    jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32)), batches)
        return acc, loss_sum, wire, ev

    if policy is None or policy.is_noop:
        grad_fn = jax.vmap(jax.value_and_grad(scoped(FORWARD)(loss_fn)))

        def step(state, batches):
            src = state["params"]
            # ZeRO-3: params live as 1/W shard buckets — gather the full
            # tree (per-bucket all-gather) for forward/backward only; it
            # is a temporary of the step, never part of the train state
            fwd = strategy.gather_params(src, comm) if owns_params else src
            boundary_wire = None
            if accum_steps == 1:
                loss, grads = grad_fn(fwd, batches)
                mean_loss = jnp.mean(loss)
                params, opt_state, comm_state, metrics = strategy.update(
                    src, grads, state["opt_state"],
                    state["comm_state"], state["step"], optimizer, comm)
            elif part_accum:
                acc, loss_sum, wire, ev = accum_grads_part(fwd, batches,
                                                           grad_fn)
                g_shards = [a / accum_steps for a in acc]
                mean_loss = loss_sum / accum_steps
                params, opt_state, comm_state, metrics = \
                    strategy.update_partitioned(
                        src, g_shards, state["opt_state"],
                        state["comm_state"], state["step"], optimizer, comm)
                boundary_wire = (wire, ev)
            else:
                acc, lay, loss_sum = accum_grads(fwd, batches, grad_fn)
                grads = lay.debucketize([a / accum_steps for a in acc])
                mean_loss = loss_sum / accum_steps
                params, opt_state, comm_state, metrics = strategy.update(
                    src, grads, state["opt_state"],
                    state["comm_state"], state["step"], optimizer, comm)
            new_state = {"params": params, "opt_state": opt_state,
                         "comm_state": comm_state, "step": state["step"] + 1}
            metrics = dict(metrics)
            if boundary_wire is not None:  # charge the per-microbatch RS
                metrics["wire_bytes"] = metrics["wire_bytes"] \
                    + boundary_wire[0]
                metrics["comm_events"] = metrics["comm_events"] \
                    + boundary_wire[1]
            metrics["loss"] = mean_loss
            with jax.named_scope(OPTIMIZER):  # reads the updated replicas
                metrics["replica_divergence"] = _stack_divergence(
                    strategy.gather_params(params, comm) if owns_params
                    else params)
            return new_state, metrics

        return _jit(step)

    def step(state, batches):
        sstate = state.get("loss_scale")
        scale = sstate["scale"] if sstate is not None else 1.0
        src = state.get("master", state["params"])
        fwd = strategy.gather_params(src, comm) if owns_params else src

        @scoped(FORWARD)
        def scaled_loss(p_src, batch):
            # cast-params: forward consumes the param-dtype image of the
            # (possibly wider) source-of-truth copy
            return loss_fn(policy.cast_to_param(p_src), batch) * scale

        vgrad = jax.vmap(jax.value_and_grad(scaled_loss), in_axes=(0, 0))
        boundary_wire = None
        if accum_steps == 1:
            loss, grads = vgrad(fwd, batches)
            with jax.named_scope(OPTIMIZER):
                grads = PR.unscale_grads(grads, scale)
            mean_loss = jnp.mean(loss)
        elif part_accum:
            acc, loss_sum, wire, ev = accum_grads_part(fwd, batches, vgrad)
            # shard-space boundary: one division for microbatch mean AND
            # unscale, then straight into the partitioned update
            grads = [a / (accum_steps * scale) for a in acc]
            mean_loss = loss_sum / accum_steps
            boundary_wire = (wire, ev)
        else:
            acc, lay, loss_sum = accum_grads(fwd, batches, vgrad)
            # one division at the boundary: microbatch mean AND unscale
            # (the accumulator keeps f32 — cast=False — so the boundary
            # gradients are at least as wide as the legacy per-step path)
            grads = lay.debucketize([a / (accum_steps * scale) for a in acc],
                                    cast=False)
            mean_loss = loss_sum / accum_steps
        with jax.named_scope(OPTIMIZER):  # update, loss scale, skip-or-apply
            finite = PR.tree_finite(grads) if sstate is not None \
                else jnp.asarray(True)
            if boundary_wire is not None:
                new_src, opt_state, comm_state, metrics = \
                    strategy.update_partitioned(
                        src, grads, state["opt_state"], state["comm_state"],
                        state["step"], optimizer, comm)
            else:
                new_src, opt_state, comm_state, metrics = strategy.update(
                    src, grads, state["opt_state"], state["comm_state"],
                    state["step"], optimizer, comm)
            if sstate is not None:  # skip-or-apply
                new_src = PR.select_tree(finite, new_src, src)
                opt_state = PR.select_tree(finite, opt_state,
                                           state["opt_state"])
                comm_state = PR.select_tree(finite, comm_state,
                                            state["comm_state"])
            new_state = {"opt_state": opt_state, "comm_state": comm_state,
                         "step": state["step"] + 1}
            if "master" in state:
                new_state["master"] = new_src
                new_state["params"] = policy.cast_to_param(new_src)
            else:
                new_state["params"] = new_src
            if sstate is not None:
                new_state["loss_scale"] = PR.next_scale_state(policy, sstate,
                                                              finite)
        metrics = dict(metrics)
        if boundary_wire is not None:
            metrics["wire_bytes"] = metrics["wire_bytes"] + boundary_wire[0]
            metrics["comm_events"] = metrics["comm_events"] \
                + boundary_wire[1]
        metrics["loss"] = mean_loss / scale
        with jax.named_scope(OPTIMIZER):  # reads the updated replicas
            metrics["replica_divergence"] = _stack_divergence(
                strategy.gather_params(new_state["params"], comm)
                if owns_params else new_state["params"])
        if sstate is not None:
            metrics["loss_scale"] = sstate["scale"]
            metrics["overflow"] = 1.0 - finite.astype(jnp.float32)
        return new_state, metrics

    return _jit(step)


def jit_cache_size(step_fn) -> int:
    """Compiled-variant count of a jitted step fn — the probe behind the
    retrace-detector lint rule (repro.analysis.rules.retrace): exactly 1
    in steady state; every growth is a silent recompilation in the
    training loop.  Returns -1 when the callable exposes no cache
    accounting (``jit=False``, or a jax without ``_cache_size``)."""
    probe = getattr(step_fn, "_cache_size", None)
    return int(probe()) if callable(probe) else -1


def _stack_divergence(params):
    """Max |w_i − w_0| over replicas — the model-consistency measure of §3."""

    def per_leaf(x):
        return jnp.max(jnp.abs(x - x[0:1])) if x.ndim > 0 and x.shape[0] > 1 \
            else jnp.zeros((), x.dtype)

    leaves = [per_leaf(x).astype(jnp.float32) for x in jax.tree.leaves(params)]
    return jnp.max(jnp.stack(leaves)) if leaves else jnp.zeros(())


# ---------------------------------------------------------------------------
# production (sharded) train step — also the dry-run target
# ---------------------------------------------------------------------------
def make_loss_fn(cfg, remat: bool = True):
    def loss_fn(params, batch):
        memory = None
        if cfg.is_encoder_decoder:
            memory = T.encode(params, cfg, embeds=batch["source_embeds"])
        logits, aux = T.forward(
            params, cfg,
            tokens=batch.get("tokens"),
            embeds=batch.get("embeds"),
            memory=memory,
            remat=remat)
        return lm_loss(logits, batch["labels"], aux)
    return loss_fn


def zero1_opt_template(params, optimizer: Optimizer, n_parts: int,
                       bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                       policy: Optional[PrecisionPolicy] = None):
    """GLOBAL optimizer state for the partitioned production path: one
    padded flat f32 bucket per state leaf, to be sharded ``P("pod")`` over
    the data-parallel axis (per-device footprint 1/W).  Accepts arrays or
    ShapeDtypeStructs; returns the same flavour.

    Under a master-keeping policy the template grows the f32 master
    buckets: ``{"opt": <inner>, "master": [...]}`` — matching
    ``sync_zero1(policy=...)``'s opt-state layout.  A template built from
    real arrays materializes the master FROM the params (zeros would
    silently reset the model on the first step); use
    ``zero1_master_buckets`` to fill a ShapeDtypeStruct template."""
    play = PartitionedLayout.build(
        BucketLayout.build(params, bucket_bytes, lead_axes=0), n_parts)
    sds = [jax.ShapeDtypeStruct((p,), jnp.float32)
           for p in play.padded_sizes]
    template = state_template(optimizer, sds)
    keeps_master = policy is not None and policy.keeps_master
    if keeps_master:
        template = {"opt": template, "master": list(sds)}
    if all(isinstance(x, jax.ShapeDtypeStruct)
           for x in jax.tree.leaves(params)):
        return template
    zeros = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jnp.zeros(s.shape, s.dtype), t)
    if keeps_master:  # master comes FROM the params, never from zeros
        return {"opt": zeros(template["opt"]),
                "master": zero1_master_buckets(params, n_parts,
                                               bucket_bytes)}
    return zeros(template)


def zero1_master_buckets(params, n_parts: int,
                         bucket_bytes: int = DEFAULT_BUCKET_BYTES):
    """The f32 master in GLOBAL (padded flat bucket) form, initialized
    from the params — what the "master" entry of the production ZeRO-1
    opt state must hold before the first step."""
    lay = BucketLayout.build(params, bucket_bytes, lead_axes=0)
    play = PartitionedLayout.build(lay, n_parts)
    buckets = lay.bucketize(params)
    return [b if b.shape[-1] == p else
            jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, p - b.shape[-1])])
            for b, p in zip(buckets, play.padded_sizes)]


def zero3_param_template(params, n_parts: int,
                         bucket_bytes: int = DEFAULT_BUCKET_BYTES):
    """GLOBAL parameter state for the ZeRO-3 production path: one padded
    flat f32 bucket per param bucket, to be sharded ``P("pod")`` over the
    data-parallel axis — per-device footprint 1/W of the f32 model, and
    the ONLY param-shaped thing in the train state (the full tree exists
    only as a step temporary after the per-bucket all-gather).  The f32
    buckets double as the precision master under a master-keeping policy.
    Accepts arrays or ShapeDtypeStructs; returns the same flavour (arrays
    are filled FROM the params — zeros would reset the model)."""
    play = PartitionedLayout.build(
        BucketLayout.build(params, bucket_bytes, lead_axes=0), n_parts)
    if all(isinstance(x, jax.ShapeDtypeStruct)
           for x in jax.tree.leaves(params)):
        return [jax.ShapeDtypeStruct((p,), jnp.float32)
                for p in play.padded_sizes]
    return zero1_master_buckets(params, n_parts, bucket_bytes)


def make_sharded_train_step(cfg, optimizer: Optimizer,
                            strategy: Optional[Strategy] = None,
                            comm: Optional[Comm] = None,
                            remat: bool = True,
                            pod_compressor=None,
                            partition_grads: bool = False,
                            bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                            policy: Optional[PrecisionPolicy] = None,
                            accum_steps: int = 1,
                            zero_stage: int = 0,
                            param_template=None):
    """Global-model train step.  With ``strategy=None`` this is pure
    synchronous data parallelism (gradients all-reduced by XLA across the
    batch sharding) — the paper's spectrum point 1 and the dry-run target.
    With a strategy + ShardComm, the gradient transform runs across the
    named axis (used by the hierarchical pod-level strategies).

    ``pod_compressor``: the paper's §2.2.4 technique as a first-class
    production feature — gradients are synced *completely* inside each pod
    (fast ICI, spectrum pt. 1) but the CROSS-POD hop (slow DCN, the paper's
    loosely-coupled tier) ships the COMPRESSED payload.  The exchange is
    the bucketed ``Fabric`` (core/fabric.py): per-pod gradients are
    flattened into flat f32 buckets, 1-bit/int8/top-k encoded with error
    feedback, and ONE packed byte buffer per bucket is all-gathered over
    "pod" — at most n_buckets collectives in the lowered HLO where the old
    per-leaf path emitted one (or more) per parameter.

    ``partition_grads`` (ZeRO-1): gradients are reduce-SCATTERED over the
    "pod" axis instead of all-reduced; each pod updates its 1/W parameter
    shard against 1/W of the optimizer state (``state["opt_state"]`` must
    be the flat shard buckets from ``zero1_opt_template``, sharded
    ``P("pod")``) and the updated shards are all-gathered back.  Same wire
    bytes as the all-reduce, O(W) less optimizer-state memory per device.
    Mutually exclusive with ``pod_compressor`` and ``strategy``.

    ``zero_stage`` generalizes it (``partition_grads=True`` ≡ stage 1):
    stage 2 reduce-scatters every MICROBATCH's gradients into a 1/W
    shard-bucket accumulator (the full gradient never materializes across
    microbatches); stage 3 additionally shards the PARAMETERS —
    ``state["params"]`` must be the flat f32 shard buckets from
    ``zero3_param_template`` (sharded ``P("pod")``), ``param_template``
    must carry the full model's arrays/ShapeDtypeStructs, and each step
    all-gathers the wire-dtype param image as a boundary temporary.

    ``accum_steps > 1`` (DESIGN.md §8): the batch carries a leading
    ``accum_steps`` axis and the step becomes a microbatched BOUNDARY
    step.  On the restructured paths (plain sync, ZeRO-1, pod compressor)
    a ``lax.scan`` inside the "pod" shard_map accumulates per-microbatch
    per-pod gradients directly into the Fabric's flat f32 buckets — the
    scan body issues ZERO cross-pod collectives — and exactly one
    exchange's worth of collectives (≤ n_buckets all-reduces, or one
    reduce-scatter + all-gather pair per bucket on the ZeRO-1 path) fires
    per boundary, so wire bytes per sample shrink by ``accum_steps``.
    Compression / error-feedback state advances once per boundary.  The
    legacy strategy-over-ShardComm path falls back to tree-space
    accumulation (strategy semantics preserved; no HLO fusion claim)."""

    loss_fn = make_loss_fn(cfg, remat=remat)
    if partition_grads:  # legacy spelling of the first ZeRO stage
        zero_stage = max(zero_stage, 1)
    if zero_stage not in (0, 1, 2, 3):
        raise ValueError(f"zero_stage must be 0..3, got {zero_stage}")
    if zero_stage and (pod_compressor is not None or strategy is not None):
        raise ValueError("partition_grads composes with the plain sync "
                         "path only (no pod_compressor / strategy)")
    if zero_stage >= 3 and param_template is None:
        raise ValueError("zero_stage=3 needs param_template (the FULL "
                         "model's arrays or ShapeDtypeStructs) to rebuild "
                         "the shard-bucket layout inside the step")
    partition_grads = zero_stage >= 1
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if policy is not None and policy.is_noop:
        policy = None  # f32 policy: take the pre-precision path bit-for-bit
    scaling = policy is not None and policy.uses_scaling
    keeps_master = policy is not None and policy.keeps_master
    wire = policy.wire_dt if policy is not None else None

    def value_and_grad(params, batch, scale):
        """cast-params → forward → scaled loss (the backward runs against
        the scaled objective; callers unscale in f32)."""
        @scoped(FORWARD)
        def lfn(p):
            p = policy.cast_to_param(p) if policy is not None else p
            loss = loss_fn(p, batch)
            return loss * scale if scaling else loss
        return jax.value_and_grad(lfn)(params)

    def sync_grads(params, batch, scale):
        return value_and_grad(params, batch, scale)

    def accum_buckets(params, batch, scale, fab, lay, play=None):
        """``lax.scan`` over the leading microbatch axis of ``batch``,
        accumulating per-microbatch gradients directly into flat f32
        buckets (padded shard layout when ``play`` is given).  The scan
        body issues NO collective; the boundary divides ONCE by
        ``accum_steps`` (and the loss scale) before the single exchange.
        Returns (mean_buckets, mean_scaled_loss)."""

        def micro(carry, mb):
            acc, loss_sum = carry
            loss, grads = value_and_grad(params, mb, scale)
            return (fab.accumulate(acc, grads, lay, play=play),
                    loss_sum + loss), None

        (acc, loss_sum), _ = lax.scan(
            micro, (fab.init_accum(lay, play), jnp.zeros((), jnp.float32)),
            batch)
        denom = accum_steps * (scale if scaling else 1.0)
        return [a / denom for a in acc], loss_sum / accum_steps

    def tree_accum_grads(params, batch, scale):
        """Tree-space microbatch accumulation for the strategy-over-
        ShardComm path (the strategy owns its own exchange; no bucket-
        fusion claim here).  Returns (mean_scaled_loss, mean_grads)."""

        def micro(carry, mb):
            acc, loss_sum = carry
            loss, grads = value_and_grad(params, mb, scale)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                               acc, grads)
            return (acc, loss_sum + loss), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (acc, loss_sum), _ = lax.scan(
            micro, (zeros, jnp.zeros((), jnp.float32)), batch)
        denom = accum_steps * (scale if scaling else 1.0)
        return (loss_sum / accum_steps,
                jax.tree.map(lambda a: a / denom, acc))

    def sync_fabric_accum_body(params, batch, scale):
        """Microbatched plain-sync boundary step: shard_map over the batch
        axes, scan-accumulate each shard's gradients into flat buckets,
        then ONE fused all-mean per bucket at the boundary — the HLO
        carries at most n_buckets cross-worker collectives per boundary
        regardless of accum_steps (proven in bench_roofline/check_accum
        and tests/test_accum.py).

        Like the ZeRO-1 production body above, the shard_map declares
        replicated (P()) param specs, so on the full-manual
        lowering (DESIGN.md §7) model-axis sharding is gathered at the
        body boundary — the same memory tradeoff the partition_grads path
        already makes; accum_steps=1 keeps the pjit auto-sharded path
        untouched."""
        from jax.sharding import PartitionSpec as P

        mesh = compat.get_abstract_mesh()
        sizes = dict(mesh.shape) if mesh is not None else {}
        axes = tuple(a for a in ("pod", "data") if a in sizes)
        if not axes:  # no batch axis to exchange over (single device)
            return tree_accum_grads(params, batch, scale)
        w = 1
        for a in axes:
            w *= sizes[a]
        axis_name = axes if len(axes) > 1 else axes[0]

        def per_shard(params, batch, scale):
            fab = Fabric(ShardComm(axis_name, w), bucket_bytes,
                         wire_dtype=wire)
            lay = fab.layout(params)
            acc, loss = accum_buckets(params, batch, scale, fab, lay)
            grads, _, _ = fab.exchange_accumulated(acc, lay)
            return jax.lax.pmean(loss, axis_name), grads

        batch_specs = jax.tree.map(lambda _: P(None, axes), batch)
        rep = jax.tree.map(lambda _: P(), params)
        return compat.shard_map(
            per_shard, mesh=mesh,
            in_specs=(rep, batch_specs, P()),
            out_specs=(P(), rep), check_vma=False,
        )(params, batch, scale)

    def pod_fabric_grads(params, batch, residual, scale):
        from jax.sharding import PartitionSpec as P

        mesh = compat.get_abstract_mesh()
        npods = dict(mesh.shape).get("pod", 1)

        def per_pod(params, batch, residual, scale):
            fab = Fabric(ShardComm("pod", npods), bucket_bytes,
                         wire_dtype=wire)
            if accum_steps == 1:
                loss, grads = value_and_grad(params, batch, scale)
                if scaling:
                    with jax.named_scope(OPTIMIZER):
                        grads = PR.unscale_grads(grads, scale)
                grads, new_r, _ = fab.exchange(grads, residual,
                                               pod_compressor)
            else:
                # boundary-only compression: the error-feedback residual
                # sees ONE exchange of the microbatch-mean gradients
                lay = fab.layout(params)
                acc, loss = accum_buckets(params, batch, scale, fab, lay)
                grads, new_r, _ = fab.exchange_accumulated(
                    acc, lay, residual, pod_compressor)
            return jax.lax.pmean(loss, "pod"), grads, new_r

        bspec = P("pod") if accum_steps == 1 else P(None, "pod")
        batch_specs = jax.tree.map(lambda _: bspec, batch)
        rep = jax.tree.map(lambda _: P(), params)
        rep_r = jax.tree.map(lambda _: P(), residual)
        return compat.shard_map(
            per_pod, mesh=mesh,
            in_specs=(rep, batch_specs, rep_r, P()),
            out_specs=(P(), rep, rep_r), check_vma=False,
        )(params, batch, residual, scale)

    def zero1_step_body(params, batch, opt_state, t, scale):
        """shard_map body over "pod": grads → reduce-scatter → shard update
        → all-gather, one RS + one AG per bucket, NO full all-reduce of
        gradients (the loss mean is the only scalar psum).  Under a
        master-keeping policy the f32 master shards live in
        ``opt_state["master"]`` (1/W per device) and the all-gather ships
        the wire-dtype image of the updated master.  With ``accum_steps >
        1`` the scan accumulates straight into the PADDED shard-bucket
        layout, so the boundary reduce-scatter consumes the accumulator
        with no re-pad — still one RS + one AG per bucket per boundary.

        ``zero_stage=2`` changes ONLY the accumulation: each microbatch's
        gradients are reduce-scattered as they arrive and the accumulator
        holds 1/W shard buckets (the full gradient is never resident),
        trading accum_steps× the RS traffic for a W× smaller accumulator
        — the wire-vs-memory axis the launch planner searches."""
        from jax.sharding import PartitionSpec as P

        mesh = compat.get_abstract_mesh()
        npods = dict(mesh.shape).get("pod", 1)

        def per_pod(params, batch, opt_state, t, scale):
            fab = Fabric(ShardComm("pod", npods), bucket_bytes,
                         wire_dtype=wire)
            play = fab.partitioned_layout(params)
            if accum_steps == 1:
                loss, grads = value_and_grad(params, batch, scale)
                if scaling:
                    with jax.named_scope(OPTIMIZER):
                        grads = PR.unscale_grads(grads, scale)
                g_shards, _ = fab.exchange_partitioned(grads, play)
            elif zero_stage >= 2:
                def micro(carry, mb):
                    acc, loss_sum = carry
                    loss, grads = value_and_grad(params, mb, scale)
                    acc, _ = fab.accumulate_partitioned(acc, grads, play)
                    return (acc, loss_sum + loss), None

                (acc, loss_sum), _ = lax.scan(
                    micro, (fab.init_accum_partitioned(play),
                            jnp.zeros((), jnp.float32)), batch)
                denom = accum_steps * (scale if scaling else 1.0)
                g_shards = [a / denom for a in acc]
                loss = loss_sum / accum_steps
            else:
                acc, loss = accum_buckets(params, batch, scale, fab,
                                          play.layout, play=play)
                g_shards, _ = fab.exchange_partitioned_accumulated(acc, play)
            # every pod must take the same skip decision: the finite check
            # runs on this pod's reduced shards, pmin'ed across pods
            with jax.named_scope(OPTIMIZER):
                ok = PR.tree_finite(g_shards).astype(jnp.float32) \
                    if scaling else jnp.ones((), jnp.float32)
                ok = jax.lax.pmin(ok, "pod") if scaling else ok
            if keeps_master:
                inner, p_shards = opt_state["opt"], opt_state["master"]
            else:
                inner, p_shards = opt_state, fab.shard_params(params, play)
            p_shards, inner = optimizer.update(g_shards, inner, p_shards, t)
            new_params = fab.unpartition(p_shards, play)
            new_opt = {"opt": inner, "master": p_shards} if keeps_master \
                else inner
            return (jax.lax.pmean(loss, "pod"), new_params, new_opt, ok)

        bspec = P("pod") if accum_steps == 1 else P(None, "pod")
        batch_specs = jax.tree.map(lambda _: bspec, batch)
        rep = jax.tree.map(lambda _: P(), params)
        shard_specs = jax.tree.map(lambda _: P("pod"), opt_state)
        return compat.shard_map(
            per_pod, mesh=mesh,
            in_specs=(rep, batch_specs, shard_specs, P(), P()),
            out_specs=(P(), rep, shard_specs, P()), check_vma=False,
        )(params, batch, opt_state, t, scale)

    def zero3_step_body(p_shards, batch, opt_state, t, scale):
        """ZeRO-3 shard_map body over "pod": the train state holds ONLY
        flat f32 param shard buckets (``zero3_param_template``, sharded
        ``P("pod")`` — 1/W of the f32 model per device, doubling as the
        precision master) plus the matching shard-bucket optimizer state.
        Each boundary: per-bucket all-gather of the wire-dtype param image
        (``unpartition``) → forward/backward on the full model →
        reduce-scatter of the gradients → elementwise shard update.  The
        full parameter tree is a TEMPORARY of the step, never part of the
        state, so ``step_state_peak_bytes`` sheds the dense param term —
        the W× shrink the roofline's ``opt_state_bytes(partitioned=True)``
        already models for optimizer state, now applied to params too."""
        from jax.sharding import PartitionSpec as P

        mesh = compat.get_abstract_mesh()
        npods = dict(mesh.shape).get("pod", 1)
        play = PartitionedLayout.build(
            BucketLayout.build(param_template, bucket_bytes, lead_axes=0),
            npods)

        def per_pod(p_shards, batch, opt_state, t, scale):
            fab = Fabric(ShardComm("pod", npods), bucket_bytes,
                         wire_dtype=wire)
            params = fab.unpartition(p_shards, play)
            if accum_steps == 1:
                loss, grads = value_and_grad(params, batch, scale)
                if scaling:
                    with jax.named_scope(OPTIMIZER):
                        grads = PR.unscale_grads(grads, scale)
                g_shards, _ = fab.exchange_partitioned(grads, play)
            else:
                def micro(carry, mb):
                    acc, loss_sum = carry
                    loss, grads = value_and_grad(params, mb, scale)
                    acc, _ = fab.accumulate_partitioned(acc, grads, play)
                    return (acc, loss_sum + loss), None

                (acc, loss_sum), _ = lax.scan(
                    micro, (fab.init_accum_partitioned(play),
                            jnp.zeros((), jnp.float32)), batch)
                denom = accum_steps * (scale if scaling else 1.0)
                g_shards = [a / denom for a in acc]
                loss = loss_sum / accum_steps
            with jax.named_scope(OPTIMIZER):
                ok = PR.tree_finite(g_shards).astype(jnp.float32) \
                    if scaling else jnp.ones((), jnp.float32)
                ok = jax.lax.pmin(ok, "pod") if scaling else ok
            new_shards, new_opt = optimizer.update(g_shards, opt_state,
                                                   p_shards, t)
            return (jax.lax.pmean(loss, "pod"), new_shards, new_opt, ok)

        bspec = P("pod") if accum_steps == 1 else P(None, "pod")
        batch_specs = jax.tree.map(lambda _: bspec, batch)
        p_specs = jax.tree.map(lambda _: P("pod"), p_shards)
        o_specs = jax.tree.map(lambda _: P("pod"), opt_state)
        return compat.shard_map(
            per_pod, mesh=mesh,
            in_specs=(p_specs, batch_specs, o_specs, P(), P()),
            out_specs=(P(), p_specs, o_specs, P()), check_vma=False,
        )(p_shards, batch, opt_state, t, scale)

    def step(state, batch):
        sstate = state.get("loss_scale")
        scale = sstate["scale"] if scaling else jnp.ones((), jnp.float32)
        if partition_grads:
            body = zero3_step_body if zero_stage >= 3 else zero1_step_body
            loss, params, opt_state, ok = body(
                state["params"], batch, state["opt_state"], state["step"],
                scale)
            with jax.named_scope(OPTIMIZER):  # the loss-scale work
                finite = ok > 0.5
                if scaling:  # skip-or-apply
                    params = PR.select_tree(finite, params, state["params"])
                    opt_state = PR.select_tree(finite, opt_state,
                                               state["opt_state"])
                    loss = loss / scale
                new_state = {"params": params, "opt_state": opt_state,
                             "comm_state": state["comm_state"],
                             "step": state["step"] + 1}
                if scaling:
                    new_state["loss_scale"] = PR.next_scale_state(
                        policy, sstate, finite)
            return new_state, loss
        # dense paths: the f32 master (when the policy keeps one) lives in
        # state["master"] and is the source of truth — forward casts it to
        # the param dtype inside value_and_grad, the optimizer/strategy
        # update runs on it in full precision, and state["params"] is its
        # param-dtype image
        src = state.get("master", state["params"])
        if pod_compressor is not None:
            loss, grads, new_res = pod_fabric_grads(
                src, batch, state["comm_state"]["residual"], scale)
            comm_state = {"residual": new_res}
        elif accum_steps > 1 and strategy is None:
            loss, grads = sync_fabric_accum_body(src, batch, scale)
            comm_state = state["comm_state"]
        elif accum_steps > 1:
            loss, grads = tree_accum_grads(src, batch, scale)
            comm_state = state["comm_state"]
        else:
            loss, grads = sync_grads(src, batch, scale)
            if scaling:
                with jax.named_scope(OPTIMIZER):
                    grads = PR.unscale_grads(grads, scale)
            comm_state = state["comm_state"]
        with jax.named_scope(OPTIMIZER):  # update, loss scale, skip-or-apply
            finite = PR.tree_finite(grads) if scaling else jnp.asarray(True)
            if strategy is not None:
                new_src, opt_state, comm_state, _ = strategy.update(
                    src, grads, state["opt_state"],
                    comm_state, state["step"], optimizer, comm)
            else:
                new_src, opt_state = optimizer.update(
                    grads, state["opt_state"], src, state["step"])
            if scaling:  # skip-or-apply
                new_src = PR.select_tree(finite, new_src, src)
                opt_state = PR.select_tree(finite, opt_state,
                                           state["opt_state"])
                comm_state = PR.select_tree(finite, comm_state,
                                            state["comm_state"])
                loss = loss / scale
            new_state = {"opt_state": opt_state, "comm_state": comm_state,
                         "step": state["step"] + 1}
            if "master" in state:
                new_state["master"] = new_src
                new_state["params"] = policy.cast_to_param(new_src)
            else:
                new_state["params"] = new_src
            if scaling:
                new_state["loss_scale"] = PR.next_scale_state(policy, sstate,
                                                              finite)
        return new_state, loss

    return step
