"""Grouped matrix multiplication Pallas TPU kernels, forward and backward,
for expert layers: rows sorted by expert, each group of rows times its own
expert's matrix, with work that follows the rows each group really has.

* ``expert_gmm``: out[rows of group i] = lhs[rows of group i] @ rhs[i]
  (or @ rhs[i].T), for the groups that ``rhs`` holds: the first
  ``rhs.shape[0]`` groups of ``group_sizes``.  Grid (n tiles, m tiles
  of those groups, k tiles); the middle axis is as long as the rows of
  those groups need (a traced grid size), so rows of later groups (the
  assignments to experts held elsewhere) cost nothing and are left
  unwritten.  A tile that straddles two groups is visited once for each,
  and each visit stores only its own group's rows.
* ``expert_tgmm``: the weight gradient, out[i] = lhs[rows of i].T @
  grad[rows of i], (groups, k, n); a group with no rows gets zeros.

The tiles' bookkeeping (which group and which m tile each grid step
works on) is ``make_group_metadata`` of ``jax``'s megablox kernels, whose
kernels these follow; these carry names (``pallas_call(name=...)``) that a
device trace can count.  Operands keep their dtype (bf16 feeds the MXU as
bf16) and accumulate in f32 VMEM scratch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

LANES = 128
NN = (((1,), (0,)), ((), ()))  # a @ b
NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _tile(dim: int, cap: int = 1024) -> int:
    """The largest multiple of 128 up to ``cap`` that divides ``dim``; the
    whole dim where 128 does not divide it."""
    if dim % LANES:
        return dim
    return max(t for t in range(LANES, min(cap, dim) + 1, LANES)
               if dim % t == 0)


def _rows_tile(m: int) -> int:
    return next((t for t in (512, 256, 128) if m % t == 0), 128)


def _group_rows(meta, grid_id, tm, shape):
    """Mask of the rows of this grid step's m tile that belong to its
    group."""
    offsets, group_ids, m_tile_ids = meta
    g = group_ids[grid_id]
    rows = m_tile_ids[grid_id] * tm + lax.broadcasted_iota(jnp.int32,
                                                           shape, 0)
    return jnp.logical_and(rows >= offsets[g], rows < offsets[g + 1])


def _gmm_kernel(meta, offset, lhs_ref, rhs_ref, out_ref, acc_ref, *, tm,
                k_tiles, transpose_rhs):
    grid_id, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += lax.dot_general(
        lhs_ref[...], rhs_ref[...], NT if transpose_rhs else NN,
        preferred_element_type=jnp.float32)

    @pl.when(k_i == k_tiles - 1)
    def _store():
        mask = _group_rows(meta, grid_id, tm, acc_ref.shape)
        out_ref[...] = lax.select(mask, acc_ref[...],
                                  out_ref[...].astype(jnp.float32)
                                  ).astype(out_ref.dtype)


def _gmm(lhs, rhs, group_sizes, *, transpose_rhs, out_dtype, interpret):
    m, k = lhs.shape
    groups = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = _rows_tile(m), _tile(k), _tile(n)
    k_tiles = k // tk
    meta, active = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=groups,
        visit_empty_groups=False)

    def lhs_map(n_i, grid_id, k_i, meta, offset):
        return meta[2][grid_id], k_i

    def rhs_map(n_i, grid_id, k_i, meta, offset):
        g = meta[1][grid_id] - offset[0]
        return (g, n_i, k_i) if transpose_rhs else (g, k_i, n_i)

    def out_map(n_i, grid_id, k_i, meta, offset):
        return meta[2][grid_id], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, k_tiles=k_tiles,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, active, k_tiles),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="expert_gmm",
    )(meta, jnp.zeros((1,), jnp.int32), lhs, rhs)


def _tgmm_kernel(meta, offset, lhs_ref, rhs_ref, out_ref, acc_ref, *, tm):
    grid_id = pl.program_id(2)
    offsets, group_ids, _ = meta
    g = group_ids[grid_id]
    last = pl.num_programs(2) - 1
    prev = group_ids[jnp.maximum(grid_id - 1, 0)]
    nxt = group_ids[jnp.minimum(grid_id + 1, last)]

    @pl.when(jnp.logical_or(grid_id == 0, prev != g))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets[g + 1] > offsets[g])
    def _accumulate():
        x = lhs_ref[...]
        y = rhs_ref[...]
        x = lax.select(_group_rows(meta, grid_id, tm, x.shape),
                       x.astype(jnp.float32), jnp.zeros(x.shape, jnp.float32))
        y = lax.select(_group_rows(meta, grid_id, tm, y.shape),
                       y.astype(jnp.float32), jnp.zeros(y.shape, jnp.float32))
        acc_ref[...] += lax.dot(x.swapaxes(0, 1).astype(lhs_ref.dtype),
                                y.astype(rhs_ref.dtype),
                                preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_or(grid_id == last, nxt != g))
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm(lhs, rhs, group_sizes, *, groups, out_dtype, interpret):
    """(groups, k, n): lhs (m, k) and rhs (m, n) contracted over the rows
    of each group."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tm, tk, tn = _rows_tile(m), _tile(k), _tile(n)
    meta, active = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=groups,
        visit_empty_groups=True)

    def lhs_map(n_i, k_i, grid_id, meta, offset):
        return meta[2][grid_id], k_i

    def rhs_map(n_i, k_i, grid_id, meta, offset):
        return meta[2][grid_id], n_i

    def out_map(n_i, k_i, grid_id, meta, offset):
        return meta[1][grid_id] - offset[0], k_i, n_i

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            grid=(n // tn, k // tk, active),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="expert_tgmm",
    )(meta, jnp.zeros((1,), jnp.int32), lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, transpose_rhs=False,
                out_dtype=lhs.dtype, interpret=interpret)


def _grouped_fwd(lhs, rhs, group_sizes, interpret):
    return _grouped(lhs, rhs, group_sizes, interpret), \
        (lhs, rhs, group_sizes)


def _grouped_bwd(interpret, res, g):
    lhs, rhs, group_sizes = res
    d_lhs = _gmm(g, rhs, group_sizes, transpose_rhs=True,
                 out_dtype=lhs.dtype, interpret=interpret)
    d_rhs = _tgmm(lhs, g, group_sizes, groups=rhs.shape[0],
                  out_dtype=rhs.dtype, interpret=interpret)
    return d_lhs, d_rhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *,
                   interpret: Optional[bool] = None):
    """lhs (m, k) rows sorted by group; rhs (g, k, n); group_sizes (G,)
    int32 with G ≥ g, the rows of each group in order → (m, n) in lhs's
    dtype, differentiable in lhs and rhs.  The first g groups are
    computed; rows after them are unspecified (and get unspecified
    gradients)."""
    from repro.kernels.ops import default_interpret
    if interpret is None:  # resolved per call, so the jit's cache keys on it
        interpret = default_interpret()
    return _grouped_matmul(lhs, rhs, group_sizes.astype(jnp.int32),
                           interpret=interpret)


# a jit of its own keeps the kernels' instruction names free of the
# caller's transforms (jvp, transpose): the device trace shows them by name
@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_matmul(lhs, rhs, group_sizes, *, interpret):
    m = lhs.shape[0]
    pad = -m % LANES
    if pad:  # rows past the groups: never computed
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    return _grouped(lhs, rhs, group_sizes, interpret)[:m]
