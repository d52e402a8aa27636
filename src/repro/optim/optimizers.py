"""In-house optimizers (no optax dependency).

SGD / momentum / Adam, plus the staleness-aware variant the paper's §3
discussion calls for: delay-compensated SGD (Zheng et al., cited as [41]),
which first-order-corrects a stale gradient toward the current weights.

Shard-aware by construction (ZeRO-1, core/strategies.py::sync_zero1):
every ``init``/``update`` here is a pure elementwise ``jax.tree.map``, so
the same optimizer runs unchanged on the fabric's flat f32 *shard buckets*
(a list of 1/W chunks) — state built from shards IS the partitioned
optimizer state, at 1/W of the dense per-worker footprint.  ``t`` (Adam
bias correction) and the learning-rate schedule are replicated scalars, so
shard updates agree exactly with the dense update on the same elements.

Precision (core/precision.py, DESIGN.md §4): every update runs in f32
against the (possibly wider "master") params it is handed — gradients and
params are upcast, the arithmetic is f32, and only the final result is
cast back to the incoming param dtype.  For f32 params this is the
identical op sequence (bitwise-tested); for bf16 working params the
f32 master shards of the ZeRO-1 path flow through unchanged.
``state_floats`` on each Optimizer records how many f32 state values it
keeps per parameter (roofline memory accounting — a kept master copy adds
``master_floats`` on top, see roofline/analysis.py::opt_state_bytes), and
``state_template`` builds an allocation-free, dtype-exact state skeleton
for checkpoint re-sharding.

``adam(..., fused=True)`` routes the elementwise update chain through the
Pallas kernel in kernels/fused_adam.py (one VMEM pass per tile instead of
10+ HLO ops; ref/interpret fallback on CPU) — parity-tested against the
pure-JAX path in tests/test_kernels.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.scopes import OPTIMIZER, scoped


# ---------------------------------------------------------------------------
# learning-rate schedules
# ---------------------------------------------------------------------------
def constant_schedule(lr):
    return lambda t: jnp.asarray(lr, jnp.float32)


def cosine_schedule(lr, total_steps, final_frac=0.1):
    def f(t):
        frac = jnp.clip(t / max(1, total_steps), 0.0, 1.0)
        c = 0.5 * (1 + jnp.cos(jnp.pi * frac))
        return lr * (final_frac + (1 - final_frac) * c)
    return f


def warmup_cosine(lr, warmup, total_steps, final_frac=0.1):
    cos = cosine_schedule(lr, total_steps - warmup, final_frac)
    def f(t):
        w = jnp.minimum(1.0, (t + 1) / max(1, warmup))
        return jnp.where(t < warmup, lr * w, cos(t - warmup))
    return f


# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Optimizer:
    init: Callable  # params -> opt_state
    update: Callable  # (grads, opt_state, params, t) -> (new_params, opt_state)
    state_floats: int = 0  # f32 state values kept per parameter element

    def __post_init__(self):
        # every update, by any strategy or step body, traces under the
        # optimizer's phase scope (core/scopes.py)
        if not getattr(self.update, "_optimizer_scoped", False):
            update = scoped(OPTIMIZER)(self.update)
            update._optimizer_scoped = True
            object.__setattr__(self, "update", update)


def state_template(opt: Optimizer, params):
    """Shape/dtype skeleton of ``opt.init(params)`` with NO allocation.

    Works on ShapeDtypeStruct trees as well as real arrays — builds the
    dry-run state specs (launch/specs.py) and the global ZeRO-1
    shard-state template (train/loop.py::zero1_opt_template) without
    materializing a dense state.  Dtype-aware: the skeleton's dtypes are
    exactly what ``init`` would allocate for the given params."""
    return jax.eval_shape(opt.init, params)


def _as_sched(lr):
    return lr if callable(lr) else constant_schedule(lr)


def _f32(x):
    return x.astype(jnp.float32)


def sgd(lr, weight_decay: float = 0.0) -> Optimizer:
    lr = _as_sched(lr)

    def init(params):
        return {}

    def update(grads, state, params, t):
        step = lr(t)

        def one(p, g):
            return (_f32(p) - step * (_f32(g) + weight_decay * _f32(p))
                    ).astype(p.dtype)

        return jax.tree.map(one, params, grads), state

    return Optimizer(init, update, state_floats=0)


def momentum(lr, beta: float = 0.9, nesterov: bool = False,
             weight_decay: float = 0.0) -> Optimizer:
    lr = _as_sched(lr)

    def init(params):
        return {"m": jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)}

    def update(grads, state, params, t):
        step = lr(t)
        m = jax.tree.map(lambda m_, g: beta * m_ + _f32(g),
                         state["m"], grads)
        if nesterov:
            upd = jax.tree.map(lambda m_, g: beta * m_ + _f32(g), m, grads)
        else:
            upd = m

        def one(p, u):
            return (_f32(p) - step * (u + weight_decay * _f32(p))
                    ).astype(p.dtype)

        return jax.tree.map(one, params, upd), {"m": m}

    return Optimizer(init, update, state_floats=1)


def adam(lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
         weight_decay: float = 0.0, fused: bool = False) -> Optimizer:
    """``fused=True`` runs the (p, m, v) read-modify-write chain through
    the Pallas kernel (kernels/fused_adam.py) leaf-by-leaf on the
    flattened view.  The kernel carries no weight-decay term, so fusion is
    only offered for ``weight_decay=0``."""
    lr = _as_sched(lr)
    if fused and weight_decay:
        raise ValueError("fused adam does not implement weight_decay; "
                         "use fused=False")

    def init(params):
        def z(p):
            return jnp.zeros_like(p, jnp.float32)
        return {"m": jax.tree.map(z, params), "v": jax.tree.map(z, params)}

    def update(grads, state, params, t):
        tt = t.astype(jnp.float32) + 1.0 if hasattr(t, "astype") else float(t) + 1.0
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * _f32(g),
                         state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(_f32(g)),
                         state["v"], grads)
        mh = jax.tree.map(lambda m_: m_ / (1 - b1 ** tt), m)
        vh = jax.tree.map(lambda v_: v_ / (1 - b2 ** tt), v)
        step = lr(t)

        def one(p, m_, v_):
            return (_f32(p) - step * (m_ / (jnp.sqrt(v_) + eps)
                                      + weight_decay * _f32(p))
                    ).astype(p.dtype)

        return jax.tree.map(one, params, mh, vh), {"m": m, "v": v}

    def update_fused(grads, state, params, t):
        from repro.kernels import ops

        step = lr(t)
        tt = t.astype(jnp.float32) + 1.0 if hasattr(t, "astype") else float(t) + 1.0
        ps, tdef = jax.tree.flatten(params)
        gs = jax.tree.leaves(grads)
        ms = jax.tree.leaves(state["m"])
        vs = jax.tree.leaves(state["v"])
        new_p, new_m, new_v = [], [], []
        for p, g, m_, v_ in zip(ps, gs, ms, vs):
            p1, m1, v1 = ops.fused_adam(
                p.reshape(-1), _f32(g).reshape(-1), m_.reshape(-1),
                v_.reshape(-1), step, tt, b1=b1, b2=b2, eps=eps)
            new_p.append(p1.reshape(p.shape))
            new_m.append(m1.reshape(m_.shape))
            new_v.append(v1.reshape(v_.shape))
        return (jax.tree.unflatten(tdef, new_p),
                {"m": jax.tree.unflatten(tdef, new_m),
                 "v": jax.tree.unflatten(tdef, new_v)})

    return Optimizer(init, update_fused if fused else update, state_floats=2)


def delay_compensated_sgd(lr, lam: float = 0.04) -> Optimizer:
    """DC-ASGD (Zheng et al. 2016): g̃ = g + λ · g ⊙ g ⊙ (w − w_bak).

    ``w_bak`` is the weight snapshot the gradient was computed against;
    the optimizer state carries it and the *caller* (an async strategy)
    refreshes it via ``state["w_bak"]`` when it ships a gradient.
    """
    lr = _as_sched(lr)

    def init(params):
        return {"w_bak": jax.tree.map(lambda p: p.astype(jnp.float32), params)}

    def update(grads, state, params, t):
        step = lr(t)

        def comp(p, g, wb):
            gf = _f32(g)
            corr = gf + lam * gf * gf * (_f32(p) - wb)
            return (_f32(p) - step * corr).astype(p.dtype)

        new = jax.tree.map(comp, params, grads, state["w_bak"])
        new_bak = jax.tree.map(lambda p: p.astype(jnp.float32), new)
        return new, {"w_bak": new_bak}

    return Optimizer(init, update, state_floats=1)
