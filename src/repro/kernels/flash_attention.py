"""Flash attention Pallas TPU kernels (blocked online softmax), forward and
backward.

q, k, v are (B, H|KV, L, D).  Every pass walks blocks of (block_q, D)
queries against blocks of (block_k, D) keys and keeps the (block_q,
block_k) scores, probabilities, dP and dS in VMEM: nothing of size L×L
reaches HBM.

* ``flash_attention_fwd``: grid (B, H, q blocks, kv blocks), the kv axis
  innermost and sequential, with f32 accumulators (acc, row max m, row sum
  l) in VMEM scratch.  Besides the output it writes the f32 log-sum-exp of
  every row, the one residual the backward needs.
* ``flash_attention_dq``: the same grid; recomputes each score block from
  q, k and the log-sum-exp and accumulates dQ over the kv blocks.
* ``flash_attention_dkv``: grid (B, KV, kv blocks, G, q blocks); works on
  the transposed score block (keys in rows, queries in lanes, so the
  per-query log-sum-exp and ``di`` broadcast over rows) and accumulates dK
  and dV over the q blocks of all G query heads that share the KV head.

GQA rides the BlockSpec index maps: query head ``h`` reads KV head
``h // G``, so the KV heads are never repeated in HBM.  Causal and sliding
window masks are applied inside a block only where the block crosses the
mask's edge; blocks the mask hides entirely are *skipped* via ``@pl.when``,
and under a causal mask their index maps repeat the last live block, so the
pipeline fetches nothing for them.  Under a causal sliding window the
inner grid axis walks only the band of blocks the window can reach (from
the first block it reaches, as many steps as the widest band), and the
kernels are named ``flash_attention_window_fwd`` / ``_dq`` / ``_dkv``, so
that a trace tells them from the plain causal calls.

Precision: bf16 inputs feed the MXU as bf16 with f32 accumulation; the
softmax statistics, the log-sum-exp and dS stay f32, and the probabilities
(and dS) are cast to the input dtype only as matmul operands.  f32 inputs
stay f32 throughout.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
LANES = 128
NT = (((1,), (1,)), ((), ()))  # a @ b.T


class _Spec(NamedTuple):
    causal: bool
    window: int
    block_q: int
    block_k: int
    kv_len: int  # keys past this (padding) are masked
    interpret: bool


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _block(length: int, d: int) -> int:
    """Block size for a sequence of ``length`` and head size ``d``: the
    largest of 512, 256, 128 that divides the length rounded up to 128,
    capped so the f32 score blocks and accumulators fit VMEM."""
    cap = 512 if d <= 128 else 256 if d <= 256 else 128
    padded = _round_up(length, LANES)
    return next(b for b in (512, 256, 128) if b <= cap and padded % b == 0)


def _live(spec: _Spec, q_start, k_start):
    """Whether any (query, key) pair of the block is visible."""
    live = jnp.bool_(True)
    if spec.causal:
        live = k_start <= q_start + spec.block_q - 1
    if spec.window > 0:
        live = jnp.logical_and(
            live, q_start - (k_start + spec.block_k - 1) < spec.window)
    return live


def _whole(spec: _Spec, q_start, k_start):
    """Whether every (query, key) pair of the block is visible, so the block
    needs no mask."""
    whole = k_start + spec.block_k <= spec.kv_len
    if spec.causal:
        whole = jnp.logical_and(whole, k_start + spec.block_k - 1 <= q_start)
    if spec.window > 0:
        whole = jnp.logical_and(
            whole, q_start + spec.block_q - 1 - k_start < spec.window)
    return whole


def _visible(spec: _Spec, q_start, k_start, shape, keys_in_rows=False):
    rows = lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = lax.broadcasted_iota(jnp.int32, shape, 1)
    ii, jj = (cols, rows) if keys_in_rows else (rows, cols)
    ii, jj = q_start + ii, k_start + jj
    mask = jj < spec.kv_len
    if spec.causal:
        mask &= jj <= ii
    if spec.window > 0:
        mask &= (ii - jj) < spec.window
    return mask


def _each_live_block(spec: _Spec, q_start, k_start, body, in_range=None):
    """Run ``body(masked)`` on a live block (and ``in_range``, where
    given): unmasked where the whole block is visible, masked where it
    crosses the mask's edge."""
    live = _live(spec, q_start, k_start)
    if in_range is not None:
        live = jnp.logical_and(live, in_range)
    whole = _whole(spec, q_start, k_start)
    pl.when(jnp.logical_and(live, whole))(lambda: body(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(whole)))(lambda: body(True))


def _banded(spec: _Spec) -> bool:
    return spec.causal and spec.window > 0


def _kv_block(spec: _Spec, qi, ki):
    """kv block of step ``ki`` of q block ``qi``: in a band, counted from
    the first block the window reaches."""
    if not _banded(spec):
        return ki
    return jnp.maximum(qi * spec.block_q - spec.window + 1, 0) \
        // spec.block_k + ki


def _q_block(spec: _Spec, ki, qi):
    """q block of step ``qi`` of kv block ``ki``: in a band, counted from
    the block of the diagonal."""
    if not _banded(spec):
        return qi
    return (ki * spec.block_k) // spec.block_q + qi


def _bands(spec: _Spec, nq: int, nk: int) -> tuple:
    """Steps of the inner grid axis: (kv steps a q block, q steps a kv
    block); the widest band under a window, else every block."""
    if not _banded(spec):
        return nk, nq
    bq, bk, w = spec.block_q, spec.block_k, spec.window
    kv = max(min((q * bq + bq - 1) // bk, nk - 1)
             - max(q * bq - w + 1, 0) // bk + 1 for q in range(nq))
    qs = max(min((k * bk + bk - 1 + w - 1) // bq, nq - 1)
             - (k * bk) // bq + 1 for k in range(nk))
    return kv, qs


def _name(spec: _Spec, kernel: str) -> str:
    return f"flash_attention_window_{kernel}" if _banded(spec) \
        else f"flash_attention_{kernel}"


def _last_live_kv(spec: _Spec, qi, ki):
    """kv block to fetch at step ``ki`` of q block ``qi``: under a causal
    mask the blocks past the diagonal repeat the last live one."""
    if not spec.causal:
        return ki
    return jnp.minimum(_kv_block(spec, qi, ki),
                       (qi * spec.block_q + spec.block_q - 1)
                       // spec.block_k)


def _first_live_q(spec: _Spec, ki, qi, nq: int):
    """q block to fetch at step ``qi`` of kv block ``ki``: under a causal
    mask the blocks before the diagonal repeat the first live one; in a
    band the steps past the window (or the last block) repeat the last."""
    if not spec.causal:
        return qi
    if _banded(spec):
        last = (ki * spec.block_k + spec.block_k - 1 + spec.window - 1) \
            // spec.block_q
        return jnp.minimum(_q_block(spec, ki, qi), jnp.minimum(last, nq - 1))
    return jnp.maximum(qi, (ki * spec.block_k) // spec.block_q)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, spec: _Spec, scale: float):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * spec.block_q
    k_start = _kv_block(spec, qi, ki) * spec.block_k

    def body(masked: bool):
        s = lax.dot_general(q_ref[...], k_ref[...], NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            mask = _visible(spec, q_start, k_start, s.shape)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, LANES), every lane the same
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        if masked:  # a row that sees no key of the block yet
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[...]
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _each_live_block(spec, q_start, k_start, body)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # a row that sees no key at all
        o_ref[...] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        lse = m_ref[...] + jnp.log(l)  # (bq, LANES)
        lse_ref[...] = lse.T[:1]  # (1, bq): queries in lanes


def _fwd(q, k, v, spec: _Spec):
    b, h, lq, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    bq, bk = spec.block_q, spec.block_k
    grid = (b, h, lq // bq, _bands(spec, lq // bq, k.shape[2] // bk)[0])

    def q_map(bi, hi, qi, ki):
        return bi, hi, qi, 0

    def kv_map(bi, hi, qi, ki):
        return bi, hi // g, _last_live_kv(spec, qi, ki), 0

    def lse_map(bi, hi, qi, ki):
        return bi, hi, 0, qi

    return pl.pallas_call(
        functools.partial(_fwd_kernel, spec=spec, scale=d ** -0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, d), q_map),
            pl.BlockSpec((None, None, bk, d), kv_map),
            pl.BlockSpec((None, None, bk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, d), q_map),
            pl.BlockSpec((None, None, 1, bq), lse_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, lq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),      # acc
            pltpu.VMEM((bq, LANES), jnp.float32),  # running max m
            pltpu.VMEM((bq, LANES), jnp.float32),  # running sum l
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=spec.interpret,
        name=_name(spec, "fwd"),
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               acc_ref, *, spec: _Spec, scale: float):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * spec.block_q
    k_start = _kv_block(spec, qi, ki) * spec.block_k

    def body(masked: bool):
        k = k_ref[...]
        s = lax.dot_general(q_ref[...], k, NT,
                            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        if masked:
            p = jnp.where(_visible(spec, q_start, k_start, s.shape), p, 0.0)
        dp = lax.dot_general(do_ref[...], v_ref[...], NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(di_ref[0], -1)) * scale
        acc_ref[...] += jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)

    _each_live_block(spec, q_start, k_start, body)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dq(q, k, v, do, lse, di, spec: _Spec):
    b, h, lq, d = q.shape
    g = h // k.shape[1]
    bq, bk = spec.block_q, spec.block_k
    grid = (b, h, lq // bq, _bands(spec, lq // bq, k.shape[2] // bk)[0])

    def q_map(bi, hi, qi, ki):
        return bi, hi, qi, 0

    def kv_map(bi, hi, qi, ki):
        return bi, hi // g, _last_live_kv(spec, qi, ki), 0

    def row_map(bi, hi, qi, ki):
        return bi, hi, 0, qi

    q_spec = pl.BlockSpec((None, None, bq, d), q_map)
    kv_spec = pl.BlockSpec((None, None, bk, d), kv_map)
    row_spec = pl.BlockSpec((None, None, 1, bq), row_map)
    return pl.pallas_call(
        functools.partial(_dq_kernel, spec=spec, scale=d ** -0.5),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=spec.interpret,
        name=_name(spec, "dq"),
    )(q, k, v, do, lse, di)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, spec: _Spec, scale: float, nq: int):
    ki, gi, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when(jnp.logical_and(gi == 0, qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = _q_block(spec, ki, qi) * spec.block_q
    k_start = ki * spec.block_k

    def body(masked: bool):
        q, do = q_ref[...], do_ref[...]
        # transposed scores: keys in rows, queries in lanes
        s = lax.dot_general(k_ref[...], q, NT,
                            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[...])
        if masked:
            p = jnp.where(_visible(spec, q_start, k_start, s.shape,
                                   keys_in_rows=True), p, 0.0)
        dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dp = lax.dot_general(v_ref[...], do, NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...]) * scale
        dk_acc[...] += jnp.dot(ds.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    # a band's steps past the last q block (the fetch repeats it) see none
    _each_live_block(spec, q_start, k_start, body,
                     in_range=_q_block(spec, ki, qi) < nq
                     if _banded(spec) else None)

    @pl.when(jnp.logical_and(gi == pl.num_programs(3) - 1,
                             qi == pl.num_programs(4) - 1))
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _dkv(q, k, v, do, lse, di, spec: _Spec):
    b, h, lq, d = q.shape
    kvh, lk = k.shape[1], k.shape[2]
    g = h // kvh
    bq, bk = spec.block_q, spec.block_k
    nq = lq // bq
    grid = (b, kvh, lk // bk, g, _bands(spec, nq, lk // bk)[1])

    def q_map(bi, kv, ki, gi, qi):
        return bi, kv * g + gi, _first_live_q(spec, ki, qi, nq), 0

    def row_map(bi, kv, ki, gi, qi):
        return bi, kv * g + gi, 0, _first_live_q(spec, ki, qi, nq)

    def kv_map(bi, kv, ki, gi, qi):
        return bi, kv, ki, 0

    q_spec = pl.BlockSpec((None, None, bq, d), q_map)
    kv_spec = pl.BlockSpec((None, None, bk, d), kv_map)
    row_spec = pl.BlockSpec((None, None, 1, bq), row_map)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, spec=spec, scale=d ** -0.5, nq=nq),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=spec.interpret,
        name=_name(spec, "dkv"),
    )(q, k, v, do, lse, di)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend(q, k, v, spec: _Spec):
    return _fwd(q, k, v, spec)[0]


def _attend_fwd(q, k, v, spec: _Spec):
    o, lse = _fwd(q, k, v, spec)
    return o, (q, k, v, o, lse)


def _attend_bwd(spec: _Spec, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = di[:, :, None, :]  # (B, H, 1, L), like lse
    dq = _dq(q, k, v, do, lse, di, spec)
    dk, dv = _dkv(q, k, v, do, lse, di, spec)
    return dq, dk, dv


_attend.defvjp(_attend_fwd, _attend_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = -1,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q: (B, H, L, D); k, v: (B, KV, Lk, D) with H a multiple of KV →
    (B, H, L, D), differentiable.  Blocks default to ``_block(L, D)``; the
    sequences are zero-padded to block multiples and the padded keys
    masked."""
    from repro.kernels.ops import default_interpret
    if interpret is None:  # resolved per call, so the jit's cache keys on it
        interpret = default_interpret()
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads do not group over "
                         f"{k.shape[1]} KV heads")
    return _flash_attention(q, k, v, causal=causal, window=window,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)


# a jit of its own keeps the kernels' instruction names free of the
# caller's transforms (jvp, transpose): the device trace shows them by name
@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def _flash_attention(q, k, v, *, causal, window, block_q, block_k,
                     interpret):
    l, d = q.shape[2:]
    lk = k.shape[2]
    block_q = block_q or _block(l, d)
    block_k = block_k or _block(lk, d)
    pad_q = _round_up(l, block_q) - l
    pad_k = _round_up(lk, block_k) - lk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    spec = _Spec(causal, window, block_q, block_k, lk, interpret)
    return _attend(q, k, v, spec)[:, :, :l]
