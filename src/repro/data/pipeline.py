"""Deterministic synthetic data pipeline.

The paper's data parallelism partitions the dataset among workers
(§2: "distributing partitions of training data among workers").  This
pipeline gives every (worker, step) a *disjoint, reproducible* shard with
no host I/O: batches are generated on device from a folded PRNG key.

The token stream is learnable, not uniform noise: with probability
``structure`` the next token is the affine successor  x' = (a·x + b) mod V,
else uniform.  A model that learns the successor reaches
H ≈ s·log V·(1−s)… well below log V — so convergence benchmarks
(benchmarks/bench_strategies.py) have signal to distinguish strategies,
which is exactly what the paper's §3 experiments need.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scopes import DATA_BATCH


@dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    batch_per_worker: int
    structure: float = 0.9  # P(next = successor)
    a: int = 31
    b: int = 7
    seed: int = 0
    # tokens are drawn from [0, active_vocab): a small active set makes the
    # task learnable within a few hundred steps at large model/vocab scale
    # (the embedding table only needs active_vocab live rows)
    active_vocab: int = 0  # 0 ⇒ full vocab

    @property
    def v_act(self) -> int:
        return self.active_vocab or self.vocab_size


def _successor(x, cfg: DataConfig):
    return (cfg.a * x + cfg.b) % cfg.v_act


def _sample_batch(cfg: DataConfig, worker, step):
    """Traceable core of ``sample_batch`` (worker/step may be traced)."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(cfg.seed), worker), step)
    k0, k1, k2 = jax.random.split(key, 3)
    b, l, v = cfg.batch_per_worker, cfg.seq_len, cfg.v_act
    start = jax.random.randint(k0, (b,), 0, v)
    noise = jax.random.randint(k1, (b, l), 0, v)
    coin = jax.random.bernoulli(k2, cfg.structure, (b, l))

    def step_fn(x, inputs):
        nz, cn = inputs
        nxt = jnp.where(cn, _successor(x, cfg), nz)
        return nxt, nxt

    _, toks = jax.lax.scan(step_fn, start,
                           (noise.swapaxes(0, 1), coin.swapaxes(0, 1)))
    return toks.swapaxes(0, 1).astype(jnp.int32)  # (b, l)


@partial(jax.jit, static_argnames=("cfg",))
def sample_batch(cfg: DataConfig, worker, step):
    """(batch_per_worker, seq_len) int32, deterministic in (seed, worker,
    step).  Jitted ONCE per (hashable, frozen) config: ``worker`` and
    ``step`` are traced operands, so per-step synthesis neither retraces
    nor re-dispatches op-by-op — and its dispatch is async, which is what
    lets ``prefetch_batches`` synthesize batch t+1 while step t runs."""
    return _sample_batch(cfg, worker, step)


@partial(jax.jit, static_argnames=("cfg", "n_workers"))
def worker_batches(cfg: DataConfig, n_workers: int, step):
    """Stacked (W, batch_per_worker, seq_len) — LocalComm layout.  One
    trace per (cfg, W); the per-worker streams are vmapped, not looped."""
    return jax.vmap(lambda w: _sample_batch(cfg, w, step))(
        jnp.arange(n_workers))


@partial(jax.jit, static_argnames=("cfg", "n_workers", "accum_steps"))
def microbatch_stack(cfg: DataConfig, n_workers: int, opt_step,
                     accum_steps: int):
    """(accum_steps, W, batch_per_worker, seq_len): the microbatch input of
    one accumulation boundary (train/loop.py, DESIGN.md §8).

    Microbatch j of optimizer step T draws the data of plain step
    ``T*accum_steps + j`` — the token stream is IDENTICAL to running
    ``accum_steps`` unaccumulated steps, which is what the equivalence
    sweep in tests/test_accum.py relies on."""
    steps = opt_step * accum_steps + jnp.arange(accum_steps)
    return jax.vmap(lambda s: jax.vmap(
        lambda w: _sample_batch(cfg, w, s))(jnp.arange(n_workers)))(steps)


def global_batch(cfg: DataConfig, step: int, global_batch_size: int):
    """One flat global batch (production path); workers' shards concatenated."""
    with jax.profiler.TraceAnnotation(DATA_BATCH):
        n = global_batch_size // cfg.batch_per_worker
        ws = worker_batches(cfg, n, step)
        return ws.reshape(global_batch_size, cfg.seq_len)


def prefetch_batches(cfg: DataConfig, n_workers: int, steps: int,
                     accum_steps: int = 1, depth: int = 2):
    """Double-buffered device prefetch: yields ``(t, batch)`` for ``steps``
    optimizer steps, keeping up to ``depth`` batches in flight.

    Batch synthesis is a jitted on-device program whose dispatch is async,
    so enqueueing batch t+1 BEFORE the consumer blocks on step t's result
    overlaps host-side synthesis/dispatch with device compute — the
    classic double buffer at ``depth=2``.  ``jax.device_put`` makes the
    device placement explicit (and covers host-resident arrays if a
    caller swaps in a host pipeline).  ``depth=1`` degrades to the old
    synchronous order."""
    depth = max(1, depth)
    q: deque = deque()

    def synth(t):
        with jax.profiler.TraceAnnotation(DATA_BATCH):
            if accum_steps > 1:
                b = microbatch_stack(cfg, n_workers, t, accum_steps)
            else:
                b = worker_batches(cfg, n_workers, t)
            return jax.device_put(b)

    for t in range(steps):
        q.append((t, synth(t)))
        while len(q) >= depth:
            yield q.popleft()
    while q:
        yield q.popleft()


def bayes_entropy(cfg: DataConfig) -> float:
    """Entropy of the generating process (loss floor for a perfect model)."""
    s, v = cfg.structure, cfg.v_act
    # next ~ s·δ(successor) + (1−s)·uniform; the successor bucket gets s+(1−s)/V
    p_succ = s + (1 - s) / v
    p_other = (1 - s) / v
    return float(-(p_succ * np.log(p_succ) + (v - 1) * p_other * np.log(p_other)))
