"""Bucketed flat-buffer exchange fabric (DESIGN.md §3).

The paper's tensor-moving interface (``Comm``) decouples strategies from
transport, but a naive realization still issues one collective per
parameter leaf — hundreds of tiny transfers for a real model.  Following
the fusion argument of cuDNN/DLL (many small ops → few large ops), the
``Fabric`` flattens a gradient pytree into size-capped flat f32 *buckets*
and drives every ``Comm`` primitive once per bucket:

    tree (n_leaves) --bucketize--> [b0, b1, ...] (n_buckets ≤ n_leaves)
                     --collective / compress+pack → wire--> ...
                     --debucketize--> tree

Compression (1-bit / int8 / top-k with error feedback) runs on the flat
buffer, and the wire format is genuinely packed: every wire component is
serialized into ONE uint8 buffer per bucket (8 signs/byte, bf16 scales,
uint16 top-k indices), so a compressed exchange is a single all-gather of
bytes per bucket — no per-leaf metadata soup.  ``wire_nbytes`` reports the
exact size of that buffer (it is derived from the same packing code via
``jax.eval_shape``), so strategy metrics match the bytes on the wire by
construction.

Replica safety: ``comm.lead_axes`` leading axes (worker stacking in the
LocalComm simulator, pods×workers in the hierarchy) are preserved through
flattening and the per-replica compression is vmapped over them — a
compression block never mixes values from two replicas.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.comm import Comm, ShardComm
from repro.core.compression import (Compressor, _from_bytes,  # noqa: F401
                                    _narrow_wire, _pack, _to_bytes, _unpack,
                                    pack_signs, packed_nbytes, unpack_signs)
from repro.core.scopes import EXCHANGE, scoped

DEFAULT_BUCKET_BYTES = 4 << 20  # 4 MiB of f32 per bucket


def _prod(shape):
    n = 1
    for s in shape:
        n *= s
    return n


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class BucketLayout:
    """Static description of tree ↔ flat-bucket correspondence.

    Leaves are assigned greedily, in tree order, to f32 buckets holding at
    most ``bucket_bytes`` (a leaf larger than the cap gets its own
    bucket — leaves are never split).  ``lead_shape`` is the common shape
    of the leading replica axes; offsets/sizes are in trailing elements."""

    treedef: Any
    lead_shape: tuple
    shapes: tuple  # per-leaf trailing shape
    dtypes: tuple  # per-leaf original dtype
    sizes: tuple  # per-leaf trailing element count
    bucket_of: tuple  # leaf index -> bucket index
    offsets: tuple  # leaf offset inside its bucket (elements)
    bucket_sizes: tuple  # elements per bucket
    bucket_bytes: int

    @property
    def n_leaves(self) -> int:
        return len(self.sizes)

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_sizes)

    @property
    def total_elements(self) -> int:
        return sum(self.bucket_sizes)

    @staticmethod
    def build(tree, bucket_bytes: int = DEFAULT_BUCKET_BYTES,
              lead_axes: int = 0) -> "BucketLayout":
        leaves, treedef = jax.tree.flatten(tree)
        lead_shape = tuple(leaves[0].shape[:lead_axes]) if leaves else ()
        for x in leaves:
            if tuple(x.shape[:lead_axes]) != lead_shape:
                raise ValueError(
                    f"inconsistent replica axes: {x.shape[:lead_axes]} vs "
                    f"{lead_shape} (lead_axes={lead_axes})")
        shapes = tuple(tuple(x.shape[lead_axes:]) for x in leaves)
        dtypes = tuple(x.dtype for x in leaves)
        sizes = tuple(_prod(s) for s in shapes)
        cap = max(1, bucket_bytes // 4)  # elements of f32
        bucket_of, offsets, bucket_sizes = [], [], []
        cur = -1  # no open bucket
        for sz in sizes:
            if cur < 0 or (bucket_sizes[cur] > 0
                           and bucket_sizes[cur] + sz > cap):
                bucket_sizes.append(0)
                cur += 1
            bucket_of.append(cur)
            offsets.append(bucket_sizes[cur])
            bucket_sizes[cur] += sz
        return BucketLayout(treedef, lead_shape, shapes, dtypes, sizes,
                            tuple(bucket_of), tuple(offsets),
                            tuple(bucket_sizes), bucket_bytes)

    # -- tree <-> buckets ---------------------------------------------------
    def bucketize(self, tree):
        """Tree → list of f32 buckets of shape lead_shape + (n_b,)."""
        leaves = jax.tree.leaves(tree)
        flats = [x.astype(jnp.float32).reshape(self.lead_shape + (-1,))
                 for x in leaves]
        out = []
        for b in range(self.n_buckets):
            segs = [flats[i] for i in range(self.n_leaves)
                    if self.bucket_of[i] == b]
            out.append(segs[0] if len(segs) == 1
                       else jnp.concatenate(segs, axis=-1))
        return out

    def debucketize(self, buckets, cast: bool = True):
        """Buckets → tree (cast back to original leaf dtypes unless
        ``cast=False``, which keeps f32 — used for residual state)."""
        leaves = []
        for i in range(self.n_leaves):
            b = buckets[self.bucket_of[i]]
            seg = lax.slice_in_dim(b, self.offsets[i],
                                   self.offsets[i] + self.sizes[i],
                                   axis=b.ndim - 1)
            seg = seg.reshape(self.lead_shape + self.shapes[i])
            leaves.append(seg.astype(self.dtypes[i]) if cast else seg)
        return jax.tree.unflatten(self.treedef, leaves)


# ---------------------------------------------------------------------------
# partitioned (ZeRO-1) layout: every bucket padded to a multiple of W
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PartitionedLayout:
    """BucketLayout + the ZeRO-1 partition: each flat f32 bucket is
    zero-padded to a multiple of ``n_parts`` and worker w owns chunk w.

    Per-worker footprint of anything kept in shard form (optimizer state,
    master shards) is ``sum(shard_sizes)`` ≈ ``total_elements / n_parts``
    instead of ``total_elements`` — the O(W) memory lever.  The wire cost
    of one partitioned exchange (reduce-scatter + all-gather) equals the
    ring all-reduce of the dense path."""

    layout: BucketLayout
    n_parts: int
    padded_sizes: tuple  # per-bucket elements after padding

    @staticmethod
    def build(layout: BucketLayout, n_parts: int) -> "PartitionedLayout":
        """THE padding rule (single definition): each bucket rounds up to
        the next multiple of ``n_parts`` — runtime shard shapes and the
        global opt-state template must agree element-for-element."""
        padded = tuple(-(-n // n_parts) * n_parts
                       for n in layout.bucket_sizes)
        return PartitionedLayout(layout, n_parts, padded)

    @property
    def shard_sizes(self) -> tuple:
        return tuple(p // self.n_parts for p in self.padded_sizes)

    def spec(self) -> dict:
        """JSON-able partition description for checkpoint re-sharding."""
        return {"n_parts": self.n_parts,
                "bucket_sizes": list(self.layout.bucket_sizes)}

    def with_parts(self, n_parts: int) -> "PartitionedLayout":
        """Re-pad the SAME bucket layout for a different worker count —
        the elastic-resize primitive (launch/elastic.py): bucket contents
        (``layout.bucket_sizes``) are invariant across a W → W′
        transition, only the per-bucket padding and chunk width change."""
        return PartitionedLayout.build(self.layout, n_parts)


# ---------------------------------------------------------------------------
# wire accounting (codec itself lives in core/compression.py)
# ---------------------------------------------------------------------------
def wire_nbytes(compressor: Optional[Compressor], n: int,
                wire_dtype=jnp.float32) -> int:
    """Exact packed-wire size (bytes) to ship ``n`` f32 elements once.

    Derived from the actual packing code via eval_shape
    (``compression.packed_nbytes``), so it equals the size of the uint8
    buffer a ShardComm exchange really gathers.  An uncompressed exchange
    ships raw ``wire_dtype`` buckets (2 bytes/elem under the bf16
    policy); compressors own their packed format and ignore
    ``wire_dtype``."""
    if compressor is None or compressor.name == "none":
        return jnp.dtype(wire_dtype).itemsize * n
    return packed_nbytes(compressor, n)


# ---------------------------------------------------------------------------
# fabric
# ---------------------------------------------------------------------------
class Fabric:
    """Bucket-fused tensor moving over a ``Comm``.

    Every public op issues at most ONE collective per bucket (and exactly
    one all-gather of packed bytes per bucket on the compressed ShardComm
    path).  Residual / DGC state stays param-shaped f32 trees, so existing
    checkpoint and sharding-spec machinery is untouched.  Every public
    exchange traces under the ``train.exchange`` scope (core/scopes.py)."""

    def __init__(self, comm: Comm, bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 wire_dtype=None, fused: bool = True):
        self.comm = comm
        self.bucket_bytes = bucket_bytes
        # dtype of the UNCOMPRESSED wire (PrecisionPolicy.wire_dtype):
        # buckets are rounded to it before every collective.  f32 (the
        # default) leaves every path bit-for-bit unchanged.
        self.wire_dtype = (jnp.dtype(wire_dtype) if wire_dtype is not None
                           else jnp.dtype(jnp.float32))
        # dispatch compressed exchanges through the fused Pallas
        # encode+error-feedback kernels when the compressor has one
        # (Compressor.fused_encode) — bitwise identical to the jnp path
        self.fused = fused

    def _wire_cast(self, buckets):
        """Round flat f32 buckets to the wire dtype.  On the stacked
        simulator the rounded values are upcast back to f32 so the axis
        reduction accumulates in f32 (the reference semantics of a bf16
        wire with f32 ring accumulation); a ShardComm ships the narrow
        buffer itself and the TPU reduction accumulates on-chip."""
        if self.wire_dtype == jnp.float32:
            return buckets
        narrowed = [b.astype(self.wire_dtype) for b in buckets]
        if isinstance(self.comm, ShardComm):
            return narrowed
        return [b.astype(jnp.float32) for b in narrowed]

    def layout(self, tree) -> BucketLayout:
        return BucketLayout.build(tree, self.bucket_bytes,
                                  self.comm.lead_axes)

    # -- plain (uncompressed) fused collectives -----------------------------
    @property
    def _narrow_sharded(self) -> bool:
        """Narrow wire on a per-shard realization: XLA convert-promotes a
        bf16 all-reduce/reduce-scatter/all-gather back to an f32 wire, so
        every narrow ShardComm op must be expressed in promotion-proof
        form (all-to-all of narrow chunks + local f32 accumulate, and
        bitcast-uint16 gathers/permutes)."""
        return (self.wire_dtype.itemsize == 2
                and isinstance(self.comm, ShardComm))

    def _bitcast_u16(self, buckets):
        return [lax.bitcast_convert_type(b.astype(self.wire_dtype),
                                         jnp.uint16) for b in buckets]

    def _reduce_narrow_sharded(self, buckets, mean: bool):
        """All-reduce(-mean) semantics per flat bucket with a provably
        narrow wire: pad to a multiple of W, ship the narrowed chunks with
        ONE all-to-all (ring bytes of a reduce-scatter), accumulate the W
        received chunks locally in f32, and all-gather the reduced shard's
        bitcast-uint16 wire image back (ring bytes of an all-gather).
        RS + AG move exactly the bytes of the all-reduce they replace."""
        w = self.comm.size
        out = []
        for b in buckets:
            n = b.shape[-1]
            p = -(-n // w) * w
            bb = b if n == p else jnp.pad(
                b, [(0, 0)] * (b.ndim - 1) + [(0, p - n)])
            (stacked,) = self.comm.gather_chunks(
                [bb.astype(self.wire_dtype)])
            red = jnp.sum(stacked.astype(jnp.float32), axis=0)
            if mean:
                red = red / w
            (full,) = self.comm.all_gather(self._bitcast_u16([red]),
                                           tiled=True)
            full = lax.bitcast_convert_type(full, self.wire_dtype)
            out.append(lax.slice_in_dim(full.astype(jnp.float32), 0, n,
                                        axis=full.ndim - 1))
        return out

    @scoped(EXCHANGE)
    def all_mean(self, tree):
        return self._reduce(tree, mean=True)

    @scoped(EXCHANGE)
    def all_sum(self, tree):
        return self._reduce(tree, mean=False)

    def _reduce(self, tree, mean: bool):
        lay = self.layout(tree)
        if lay.n_leaves == 0:
            return tree
        gb = lay.bucketize(tree)
        if self._narrow_sharded:
            return lay.debucketize(self._reduce_narrow_sharded(gb, mean))
        op = self.comm.all_mean if mean else self.comm.all_sum
        return lay.debucketize(op(self._wire_cast(gb)))

    @scoped(EXCHANGE)
    def ppermute(self, tree, shift: int = 1):
        lay = self.layout(tree)
        if lay.n_leaves == 0:
            return tree
        gb = lay.bucketize(tree)
        if self._narrow_sharded:  # pure data movement: permute the bytes
            out = self.comm.ppermute(self._bitcast_u16(gb), shift)
            out = [lax.bitcast_convert_type(b, self.wire_dtype)
                   for b in out]
            return lay.debucketize(out)
        return lay.debucketize(self.comm.ppermute(self._wire_cast(gb),
                                                  shift))

    # -- wire accounting ----------------------------------------------------
    def flat_bytes(self, tree_or_layout) -> float:
        """Uncompressed wire-dtype bytes to ship the tree once (all
        replicas) — halves under a bf16 wire."""
        lay = tree_or_layout if isinstance(tree_or_layout, BucketLayout) \
            else self.layout(tree_or_layout)
        return float(self.wire_dtype.itemsize * lay.total_elements
                     * _prod(lay.lead_shape))

    def wire_bytes(self, tree_or_layout, compressor=None) -> float:
        """Packed bytes to ship the tree once (all replicas)."""
        lay = tree_or_layout if isinstance(tree_or_layout, BucketLayout) \
            else self.layout(tree_or_layout)
        per = sum(wire_nbytes(compressor, n, self.wire_dtype)
                  for n in lay.bucket_sizes)
        return float(per * _prod(lay.lead_shape))

    def metrics(self, nbytes, events=1.0):
        ev = jnp.asarray(events, jnp.float32)
        return {"wire_bytes": jnp.asarray(nbytes, jnp.float32) * ev,
                "comm_events": ev}

    def collective_contract(self, tree_or_layout, profile: str,
                            events: int = 1) -> dict:
        """Expected HLO collective budget for ONE exchange of the tree —
        the introspection hook ``repro.analysis`` lints compiled programs
        against.  Maps collective op name -> max instruction count; ops
        absent from the mapping must not appear at all (scalar control
        traffic is budgeted separately by the rules).

        ``profile`` names the wire shape a strategy declares
        (``Strategy.wire_profile``):

          dense        all-reduce(-mean/-sum) of the full tree
          partitioned  ZeRO-1/2/3 reduce-scatter + all-gather per bucket
          compressed   packed uint8 all-gather per bucket (codec wire)
          ring         neighbour ppermute, ``events`` hops per exchange
          tp           tensor parallelism: one dense all-reduce of the
                       layer activation per row-parallel combine
                       (attention out-projection + MLP down-projection);
                       ``events`` counts the combines in the compiled
                       program (forward AND backward — the column-split
                       input grads all-reduce too)
          none         no wire traffic at all
        """
        lay = (tree_or_layout
               if isinstance(tree_or_layout, BucketLayout)
               else self.layout(tree_or_layout))
        nb = lay.n_buckets
        narrow = self._narrow_sharded
        if profile == "none":
            return {}
        if profile == "compressed":
            # packed bytes ride one all-gather per bucket at every width
            return {"all-gather": nb}
        if profile == "dense":
            if narrow:  # a2a decomposition + bitcast-u16 gather-back
                return {"all-to-all": nb, "all-gather": nb}
            return {"all-reduce": nb}
        if profile == "partitioned":
            if narrow:
                return {"all-to-all": nb, "all-gather": nb}
            return {"reduce-scatter": nb, "all-gather": nb}
        if profile == "ring":
            return {"collective-permute": int(events) * nb}
        if profile == "tp":
            if narrow:
                return {"all-to-all": int(events) * nb,
                        "all-gather": int(events) * nb}
            return {"all-reduce": int(events) * nb}
        raise ValueError(f"unknown wire profile {profile!r}")

    # -- compression plumbing ----------------------------------------------
    def _vmap_replicas(self, fn):
        for _ in range(self.comm.lead_axes):
            fn = jax.vmap(fn)
        return fn

    def _self_decode(self, target, compressor):
        """Per-replica compress → pack → unpack → decode of a flat bucket.

        The pack/unpack roundtrip is included on purpose: the simulator
        then sees exactly the wire numerics (bf16 scales etc.) that the
        sharded realization ships."""

        def one(t):
            wire, meta = compressor.compress(t)
            arrs, widen = _narrow_wire(compressor.name, wire)
            buf, specs = _pack(arrs)
            return compressor.decompress(_w(widen, buf, specs), meta,
                                         t.shape, jnp.float32)

        def _w(widen, buf, specs):
            return widen(_unpack(buf, specs))

        return self._vmap_replicas(one)(target)

    def _bucket_mean_compressed(self, target, compressor):
        """(mean of per-replica decodes, own decode) for one flat bucket.

        ShardComm: ONE all-gather of the packed byte buffer, then decode
        every peer locally.  LocalComm: decode per replica (vmapped), then
        one axis-mean — numerically identical."""
        if isinstance(self.comm, ShardComm):
            def enc(t):
                wire, meta = compressor.compress(t)
                arrs, widen = _narrow_wire(compressor.name, wire)
                buf, specs = _pack(arrs)
                dec = lambda bb: compressor.decompress(  # noqa: E731
                    widen(_unpack(bb, specs)), meta, t.shape, jnp.float32)
                (gathered,) = self.comm.all_gather([buf])
                decs = [dec(gathered[i]) for i in range(self.comm.size)]
                return sum(decs) / self.comm.size, dec(buf)

            return enc(target)
        dec_self = self._self_decode(target, compressor)
        (mean,) = self.comm.all_mean([dec_self])
        return mean, dec_self

    def _bucket_ef_round(self, g, r, compressor):
        """One full compressed error-feedback round for a flat bucket:
        (mean of per-replica decodes, own decode, new residual).

        Fused path (the default): ``compressor.fused_encode`` runs the
        whole encode — t = g + r, narrow wire arrays, residual update —
        as ONE Pallas kernel pass; the packed byte buffer shipped is
        byte-identical to the jnp path's, so both realizations stay
        bitwise equal (tests/test_fused_compression.py)."""
        fe = compressor.fused_encode if self.fused else None
        if fe is None:
            t = g + r
            mean, dec_self = self._bucket_mean_compressed(t, compressor)
            return mean, dec_self, t - dec_self
        arrs, widen, new_r = fe(g, r)
        n = g.shape[-1]

        def dec(a):  # one replica's narrow arrays → decoded flat bucket
            return compressor.decompress(widen(a), None, (n,), jnp.float32)

        if isinstance(self.comm, ShardComm):
            buf, specs = _pack(arrs)
            (gathered,) = self.comm.all_gather([buf])
            decs = [dec(_unpack(gathered[i], specs))
                    for i in range(self.comm.size)]
            return sum(decs) / self.comm.size, dec(arrs), new_r
        dec_self = self._vmap_replicas(dec)(arrs)
        (mean,) = self.comm.all_mean([dec_self])
        return mean, dec_self, new_r

    # -- flat-bucket gradient accumulation ----------------------------------
    # The microbatched train step (train/loop.py, DESIGN.md §8) keeps its
    # gradient accumulator in BUCKET space: one flatten per microbatch
    # (``accumulate``), no per-microbatch unflatten, and the boundary
    # exchange consumes the accumulated buckets directly
    # (``exchange_accumulated`` / ``exchange_partitioned_accumulated``) —
    # compression, error feedback and the collective all compose at the
    # boundary only.

    def init_accum(self, lay: BucketLayout,
                   play: Optional[PartitionedLayout] = None):
        """Zeroed flat f32 accumulator buckets (padded when ``play`` is
        given, so the boundary reduce-scatter needs no re-pad)."""
        sizes = play.padded_sizes if play is not None else lay.bucket_sizes
        return [jnp.zeros(lay.lead_shape + (n,), jnp.float32) for n in sizes]

    def accumulate(self, acc, tree, lay: BucketLayout,
                   play: Optional[PartitionedLayout] = None):
        """acc + bucketize(tree): ONE flatten, elementwise adds — a scan
        over microbatches carries only these buckets.  Under
        ``donate_argnums`` the adds are in-place buffer reuse."""
        gb = lay.bucketize(tree)
        if play is not None:
            gb = self._pad_buckets(gb, play)
        return [a + g for a, g in zip(acc, gb)]

    # ZeRO-2 (gradient sharding): the accumulator itself lives in the
    # PartitionedLayout — every microbatch's gradient is reduce-scattered
    # and only the local 1/W shard accumulates, so the full gradient is
    # never resident.  The trade: one RS per bucket per MICROBATCH (vs one
    # per boundary for ZeRO-1) against a W× smaller accumulator — exactly
    # the wire-vs-memory axis the launch planner costs.

    def init_accum_partitioned(self, play: PartitionedLayout):
        """Zeroed 1/W shard-bucket f32 accumulator (ZeRO-2)."""
        lead = play.layout.lead_shape
        return [jnp.zeros(lead + (n,), jnp.float32)
                for n in play.shard_sizes]

    def accumulate_partitioned(self, acc, tree, play: PartitionedLayout):
        """acc + reduce_scatter_mean(tree): the shard-space microbatch
        add of ZeRO-2.  Accumulates per-microbatch cross-worker MEANS, so
        the boundary divides by accum_steps only.  Returns
        (shard_buckets, metrics); the metrics charge the RS half of the
        partitioned exchange (the boundary all-gather is charged by
        ``unpartition``'s caller)."""
        gb = self._pad_buckets(play.layout.bucketize(tree), play)
        shards, _ = self.exchange_partitioned_accumulated(gb, play)
        return ([a + s for a, s in zip(acc, shards)],
                self.metrics(self.flat_bytes(play.layout) / 2.0))

    # -- fused exchanges ----------------------------------------------------
    @scoped(EXCHANGE)
    def exchange(self, grads, residual=None, compressor=None, events=1.0):
        """Fused all-mean of ``grads`` with optional compression + error
        feedback.  Returns (mean_tree, new_residual_tree, metrics)."""
        lay = self.layout(grads)
        return self.exchange_accumulated(lay.bucketize(grads), lay,
                                         residual=residual,
                                         compressor=compressor, events=events)

    @scoped(EXCHANGE)
    def exchange_accumulated(self, buckets, lay: BucketLayout, residual=None,
                             compressor=None, events=1.0):
        """The exchange of ``exchange`` starting from flat f32 buckets
        (e.g. a microbatch accumulator) instead of a tree.  Exactly one
        collective per bucket fires here — the microbatch loop that built
        ``buckets`` issued none.  Returns (mean_tree, new_residual_tree,
        metrics)."""
        if compressor is None or compressor.name == "none":
            out = (self._reduce_narrow_sharded(buckets, mean=True)
                   if self._narrow_sharded
                   else self.comm.all_mean(self._wire_cast(buckets)))
            return (lay.debucketize(out), residual,
                    self.metrics(self.flat_bytes(lay), events))
        rb = lay.bucketize(residual)
        g_out, r_out = [], []
        for g, r in zip(buckets, rb):
            mean, _, new_r = self._bucket_ef_round(g, r, compressor)
            g_out.append(mean)
            r_out.append(new_r)
        return (lay.debucketize(g_out),
                lay.debucketize(r_out, cast=False),
                self.metrics(self.wire_bytes(lay, compressor), events))

    @scoped(EXCHANGE)
    def exchange_dgc(self, grads, state, compressor, momentum: float = 0.9,
                     events=1.0):
        """Fused all-mean with DGC momentum correction (Lin et al. [54]):
        velocity accumulates into the residual before top-k, and whatever
        was sent leaves both accumulators.  ``state`` = {"velocity",
        "residual"} param-shaped f32 trees."""
        lay = self.layout(grads)
        gb = lay.bucketize(grads)
        ub = lay.bucketize(state["velocity"])
        rb = lay.bucketize(state["residual"])
        g_out, u_out, r_out = [], [], []
        for g, u, r in zip(gb, ub, rb):
            u1 = momentum * u + g
            mean, sent, new_r = self._bucket_ef_round(u1, r, compressor)
            mask = (sent != 0).astype(jnp.float32)
            g_out.append(mean)
            u_out.append(u1 * (1 - mask))
            r_out.append(new_r)
        new_state = {"velocity": lay.debucketize(u_out, cast=False),
                     "residual": lay.debucketize(r_out, cast=False)}
        return (lay.debucketize(g_out), new_state,
                self.metrics(self.wire_bytes(lay, compressor), events))

    # -- partitioned (ZeRO-1) exchange --------------------------------------
    def partitioned_layout(self, tree) -> PartitionedLayout:
        return PartitionedLayout.build(self.layout(tree), self.comm.size)

    def _pad_buckets(self, buckets, play: PartitionedLayout):
        out = []
        for b, p in zip(buckets, play.padded_sizes):
            n = b.shape[-1]
            out.append(b if n == p else jnp.pad(
                b, [(0, 0)] * (b.ndim - 1) + [(0, p - n)]))
        return out

    @scoped(EXCHANGE)
    def shard_params(self, tree, play: Optional[PartitionedLayout] = None):
        """This worker's 1/W shard of each (replicated) flat f32 bucket —
        a local slice, no collective.  Feeds ``Optimizer.init``/``update``
        with shard buckets; the optimizer state built from them is the
        ZeRO-1 sharded state."""
        play = play or self.partitioned_layout(tree)
        buckets = self._pad_buckets(play.layout.bucketize(tree), play)
        return self.comm.shard_chunk(buckets)

    @scoped(EXCHANGE)
    def exchange_partitioned(self, grads,
                             play: Optional[PartitionedLayout] = None,
                             events=1.0):
        """Fused reduce-scatter mean: every worker receives ONLY its own
        1/W shard of the cross-worker mean gradient — one reduce-scatter
        per bucket.  Returns (shard_buckets, metrics).  Together with the
        all-gather in ``unpartition`` this ships the same ring bytes as the
        dense all-reduce of ``exchange`` (2·N·(W−1)/W per worker)."""
        play = play or self.partitioned_layout(grads)
        gb = self._pad_buckets(play.layout.bucketize(grads), play)
        return self.exchange_partitioned_accumulated(gb, play, events=events)

    @scoped(EXCHANGE)
    def exchange_partitioned_accumulated(self, buckets,
                                         play: PartitionedLayout,
                                         events=1.0):
        """``exchange_partitioned`` starting from PADDED flat f32 buckets
        (the microbatch accumulator built with ``init_accum(lay, play)`` /
        ``accumulate(..., play=play)``): one reduce-scatter per bucket at
        the boundary, nothing per microbatch.  Returns (shard_buckets,
        metrics)."""
        gb = buckets
        if self._narrow_sharded:
            # narrow wire with f32 ring accumulation, HLO-provably: the
            # reduction is decomposed into ONE all-to-all of the narrowed
            # chunks per bucket (identical ring bytes to a reduce-scatter)
            # plus a local f32 accumulate — a bf16 reduce-scatter would be
            # silently convert-promoted back to an f32 wire by XLA.
            narrowed = [b.astype(self.wire_dtype) for b in gb]
            stacked = self.comm.gather_chunks(narrowed)  # (W, C) per bucket
            shards = [jnp.sum(s.astype(jnp.float32), axis=0)
                      / self.comm.size for s in stacked]
        else:
            # f32 wire (or the stacked simulator, whose _wire_cast already
            # rounds to the wire dtype and upcasts so the axis reduction
            # accumulates in f32 — same semantics as the a2a path)
            shards = self.comm.reduce_scatter(self._wire_cast(gb), mean=True)
            if self.wire_dtype != jnp.float32:
                shards = [s.astype(jnp.float32) for s in shards]
        return shards, self.metrics(self.flat_bytes(play.layout), events)

    @scoped(EXCHANGE)
    def unpartition(self, shards, play: PartitionedLayout):
        """All-gather updated shards back into the full tree — one tiled
        all-gather per bucket (of ``wire_dtype`` buffers: the gathered
        params are the wire-dtype image of the f32 master shards), padding
        sliced away, leaf dtypes restored."""
        shards = self._wire_cast(shards)
        if self._narrow_sharded:
            # pin the narrow wire THROUGH the gather: XLA convert-promotes
            # a bf16 all-gather back to an f32 one, so gather the bitcast
            # uint16 image instead — dtype-exact data movement, the same
            # trick as the packed uint8 compressed wire
            full = self.comm.all_gather(self._bitcast_u16(shards),
                                        tiled=True)
            full = [lax.bitcast_convert_type(b, self.wire_dtype)
                    for b in full]
        else:
            full = self.comm.all_gather(shards, tiled=True)
        full = [lax.slice_in_dim(b, 0, n, axis=b.ndim - 1)
                for b, n in zip(full, play.layout.bucket_sizes)]
        return play.layout.debucketize(full)

    @scoped(EXCHANGE)
    def compress(self, grads, residual, compressor):
        """Error-feedback compression WITHOUT a collective (for strategies
        that buffer/accumulate before communicating, e.g. SSP/Downpour).
        Returns (g_hat_tree, new_residual_tree, packed_bytes_one_send)."""
        lay = self.layout(grads)
        if compressor is None or compressor.name == "none":
            return grads, residual, self.flat_bytes(lay)
        gb = lay.bucketize(grads)
        rb = lay.bucketize(residual)
        g_out, r_out = [], []
        for g, r in zip(gb, rb):
            fe = compressor.fused_encode if self.fused else None
            if fe is None:
                t = g + r
                dec = self._self_decode(t, compressor)
                g_out.append(dec)
                r_out.append(t - dec)
                continue
            arrs, widen, new_r = fe(g, r)
            n = g.shape[-1]
            dec = self._vmap_replicas(
                lambda a, widen=widen, n=n: compressor.decompress(
                    widen(a), None, (n,), jnp.float32))(arrs)
            g_out.append(dec)
            r_out.append(new_r)
        return (lay.debucketize(g_out),
                lay.debucketize(r_out, cast=False),
                self.wire_bytes(lay, compressor))
