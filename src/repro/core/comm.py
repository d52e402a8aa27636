"""The FAST "tensor-moving interface" (paper §4), adapted to JAX/TPU.

The paper's central design move is to decouple *parallel coordination* from
*node-level execution* behind a general-purpose tensor-moving interface.
Here that interface is ``Comm``: strategies (core/strategies.py) are written
against it and run unchanged in two realizations:

  * ``LocalComm``  — every worker's tensors are stacked on a leading axis W.
    Collectives are axis reductions / rolls.  Used for CPU tests,
    convergence benchmarks, and vmap-based simulation of large worker
    counts.  Deterministic and single-device.

  * ``ShardComm``  — inside ``shard_map`` over a named mesh axis;
    tensors are per-worker shards and collectives lower to real TPU
    ICI/DCN collectives (psum / ppermute / all-gather).  Used by the
    production launcher.

This dual realization is exactly the paper's portability argument: the
strategy code (the science) is independent of the transport.  The actual
tensor moving — bucketing, flat-buffer fusion, wire packing — lives one
level up in ``core/fabric.py`` (DESIGN.md §3), which drives these
primitives once per *bucket* instead of once per parameter leaf.

``lead_axes`` tells the fabric how many leading replica axes the stacked
layout carries (0 for ShardComm shards, 1 for plain LocalComm, 2 for the
pods×workers hierarchy) so flattening never mixes replicas into one
compression block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


class Comm:
    """Abstract tensor-moving interface."""

    size: int
    lead_axes: int = 0  # leading replica axes in the tensor layout

    def all_mean(self, tree):
        raise NotImplementedError

    def all_sum(self, tree):
        raise NotImplementedError

    def ppermute(self, tree, shift: int = 1):
        """Ring shift: worker w receives worker (w - shift) % W's value."""
        raise NotImplementedError

    def all_gather(self, tree, tiled: bool = False):
        """Gather every worker's value.

        ``tiled=False``: stacked on a NEW leading axis of size W (the
        fabric's packed wire path; only meaningful for per-shard
        realizations — the stacked simulator already sees every replica).
        ``tiled=True``: concatenated along the LAST axis — the inverse of
        ``reduce_scatter``, used by the partitioned (ZeRO-1) exchange."""
        raise NotImplementedError

    def reduce_scatter(self, tree, mean: bool = False):
        """Cross-worker sum (or mean), scattered: worker w keeps only its
        own chunk w of the last axis, which must divide by W.  The ZeRO-1
        primitive: reduce_scatter + shard update + all_gather(tiled=True)
        moves the same ring bytes as one all-reduce."""
        raise NotImplementedError

    def shard_chunk(self, tree):
        """Worker w's own 1/W chunk of the last axis of a REPLICATED tree
        (a local slice — no communication)."""
        raise NotImplementedError

    def worker_index(self, like=None):
        """Per-worker index in [0, W), broadcastable against local tensors."""
        raise NotImplementedError


class LocalComm(Comm):
    """Stacked-replica realization: leaves carry a worker dim at ``axis``.

    ``lead_axes`` (defaults to ``axis + 1``) is the total count of leading
    replica axes in the layout — e.g. the hierarchical (P, W, ...) layout
    has lead_axes=2 for BOTH tier comms, while each tier reduces over its
    own ``axis``."""

    def __init__(self, size: int, axis: int = 0, lead_axes: int | None = None):
        self.size = size
        self.axis = axis
        self.lead_axes = axis + 1 if lead_axes is None else lead_axes

    def all_mean(self, tree):
        ax = self.axis
        return jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.mean(x, axis=ax, keepdims=True),
                                       x.shape), tree)

    def all_sum(self, tree):
        ax = self.axis
        return jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.sum(x, axis=ax, keepdims=True),
                                       x.shape), tree)

    def ppermute(self, tree, shift: int = 1):
        return jax.tree.map(lambda x: jnp.roll(x, shift, axis=self.axis), tree)

    def all_gather(self, tree, tiled: bool = False):
        if not tiled:
            raise NotImplementedError(
                "stacked LocalComm already sees every replica; only the "
                "tiled (last-axis concat) gather is defined")
        ax, w = self.axis, self.size

        def one(x):
            y = jnp.moveaxis(x, ax, -2)  # (..., W, C): shards in rank order
            flat = y.reshape(y.shape[:-2] + (w * x.shape[-1],))
            return jnp.broadcast_to(jnp.expand_dims(flat, ax),
                                    x.shape[:-1] + (w * x.shape[-1],))

        return jax.tree.map(one, tree)

    def reduce_scatter(self, tree, mean: bool = False):
        ax, w = self.axis, self.size

        def one(x):
            red = jnp.mean(x, axis=ax) if mean else jnp.sum(x, axis=ax)
            c = x.shape[-1] // w
            chunks = red.reshape(red.shape[:-1] + (w, c))
            return jnp.moveaxis(chunks, -2, ax)  # worker w gets chunk w

        return jax.tree.map(one, tree)

    def shard_chunk(self, tree):
        """Worker w's own 1/W chunk of the last axis of a REPLICATED tree
        (no communication: the local slice of a value every worker holds)."""
        ax, w = self.axis, self.size

        def one(x):
            c = x.shape[-1] // w
            chunks = x.reshape(x.shape[:-1] + (w, c))
            idx = jax.lax.broadcasted_iota(
                jnp.int32, chunks.shape[:-2] + (1, c), ax)
            return jnp.take_along_axis(chunks, idx, axis=-2).reshape(
                x.shape[:-1] + (c,))

        return jax.tree.map(one, tree)

    def worker_index(self, like=None):
        return jnp.arange(self.size).reshape(
            (1,) * self.axis + (self.size,))

    # helpers for stacked layout -------------------------------------------
    def replicate(self, tree):
        """Broadcast a single-replica pytree to the stacked layout."""
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.size,) + jnp.shape(x)).copy(), tree)

    def replica(self, tree, w: int):
        return jax.tree.map(lambda x: x[w], tree)


class ShardComm(Comm):
    """shard_map realization over one (or more) named mesh axes."""

    lead_axes = 0

    def __init__(self, axis_name, size: int):
        self.axis_name = axis_name
        self.size = size

    def all_mean(self, tree):
        return jax.tree.map(lambda x: jax.lax.pmean(x, self.axis_name), tree)

    def all_sum(self, tree):
        return jax.tree.map(lambda x: jax.lax.psum(x, self.axis_name), tree)

    def ppermute(self, tree, shift: int = 1):
        n = self.size
        perm = [((i - shift) % n, i) for i in range(n)]
        return jax.tree.map(
            lambda x: jax.lax.ppermute(x, self.axis_name, perm), tree)

    def all_gather(self, tree, tiled: bool = False):
        return jax.tree.map(
            lambda x: jax.lax.all_gather(
                x, self.axis_name,
                axis=x.ndim - 1 if tiled else 0, tiled=tiled), tree)

    def reduce_scatter(self, tree, mean: bool = False):
        def one(x):
            y = jax.lax.psum_scatter(x, self.axis_name,
                                     scatter_dimension=x.ndim - 1, tiled=True)
            return y / self.size if mean else y

        return jax.tree.map(one, tree)

    def gather_chunks(self, tree):
        """The data movement of a reduce-scatter WITHOUT the reduction:
        worker w receives every peer's chunk w of the last axis (which
        must divide by W), stacked on a NEW leading axis (W, ..., C).
        One all-to-all per leaf — identical ring bytes to
        ``reduce_scatter`` — leaving the ACCUMULATION dtype to the
        caller.  This is how the fabric realizes a narrow (bf16) wire
        with f32 accumulation: the wire op carries only the narrow
        chunks (core/fabric.py::exchange_partitioned).  Where 128
        divides C the wire moves (W, ..., C/128, 128): the same bytes,
        and a (W, C) all-to-all of a large bucket compiles for minutes on
        a TPU."""
        def one(x):
            c = x.shape[-1] // self.size
            y = x.reshape(x.shape[:-1] + (self.size, c))
            y = jnp.moveaxis(y, -2, 0)  # (W, ..., C): piece w -> worker w
            if c % 128:
                return jax.lax.all_to_all(y, self.axis_name, split_axis=0,
                                          concat_axis=0)
            y = y.reshape(y.shape[:-1] + (c // 128, 128))
            y = jax.lax.all_to_all(y, self.axis_name, split_axis=0,
                                   concat_axis=0)
            return y.reshape(y.shape[:-2] + (c,))

        return jax.tree.map(one, tree)

    def shard_chunk(self, tree):
        """This shard's 1/W chunk of the last axis of a replicated tree."""
        i = jax.lax.axis_index(self.axis_name)

        def one(x):
            c = x.shape[-1] // self.size
            return jax.lax.dynamic_slice_in_dim(x, i * c, c, axis=x.ndim - 1)

        return jax.tree.map(one, tree)

    def worker_index(self, like=None):
        return jax.lax.axis_index(self.axis_name)


class HierComm:
    """Two-tier comm: ``inner`` (fast fabric, e.g. intra-pod ICI) and
    ``outer`` (slow fabric, e.g. pod-to-pod DCN).  The beyond-paper
    hierarchical strategy composes a complete strategy on ``inner`` with a
    partial one on ``outer`` (DESIGN.md §2)."""

    def __init__(self, inner: Comm, outer: Comm):
        self.inner = inner
        self.outer = outer
        self.size = inner.size * outer.size


class LocalHierComm(HierComm):
    """Stacked layout (P, W, ...): axis 0 = pods (outer), axis 1 = workers.

    Both tier comms declare lead_axes=2 — a compression block must never
    mix values across pods OR workers, whichever tier is communicating."""

    def __init__(self, pods: int, workers: int):
        super().__init__(LocalComm(workers, axis=1, lead_axes=2),
                         LocalComm(pods, axis=0, lead_axes=2))
