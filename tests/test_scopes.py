"""The training step's phase scopes (core/scopes.py) reach the compiled
program on every training path, and cover nearly all of it.

Each case compiles a tiny step (the loss built with ``remat=True``) and
reads the ``op_name`` of its instructions from ``compiled.as_text()``: the
forward scope, the backward and the recompute that autodiff labels from
it, and the optimizer scope appear on every path, the exchange scope on
the sharded ZeRO-1 path, and under 5 % of the ops that run are left
without a phase.  A refactor that moves work out of its chokepoint fails
here before a profile misattributes it."""

import collections
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHARDED = """
    import sys
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.core.jax_compat import set_mesh
    from repro.launch.specs import ShapeSpec, build_train_step

    chips, zero, out = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    cfg = get_config("qwen2-1.5b").reduced()
    mesh = Mesh(np.array(jax.devices()[:chips]).reshape(chips, 1, 1),
                ("pod", "data", "model"))
    step, (state, batch), (state_sh, batch_sh), donate = build_train_step(
        cfg, ShapeSpec("t", 32, 2 * chips, "train"), mesh,
        precision="bf16", zero_stage=zero)
    with set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=(state_sh, batch_sh),
                           donate_argnums=donate).lower(state, batch).compile()
    open(out, "w").write(compiled.as_text())
"""


def _sharded_hlo(tmp_path, chips, zero):
    """The production step (``launch.specs.build_train_step``, the path
    the chip benchmark drives) on ``chips`` host devices, in a process of
    its own so that it can have more than one."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + f" --xla_force_host_platform_device_count={chips}")
    out = tmp_path / "step.hlo"
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(SHARDED),
                        str(chips), str(zero), str(out)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return out.read_text()


def _replica_hlo(precision):
    """The replica simulator's step: two replicas under ``sync``."""
    from repro.configs import get_config
    from repro.core.comm import LocalComm
    from repro.core.precision import apply_policy, get_policy
    from repro.core.strategies import get_strategy
    from repro.models import transformer as T
    from repro.optim import adam
    from repro.train.loop import (init_train_state, make_loss_fn,
                                  make_replica_train_step)

    cfg = get_config("qwen2-1.5b").reduced()
    policy = get_policy(precision)
    policy = None if policy.is_noop else policy
    if policy is not None:
        cfg = apply_policy(cfg, policy)
    comm, strategy, opt = LocalComm(2), get_strategy("sync"), adam(1e-3)
    state = init_train_state(
        comm.replicate(T.init_model(jax.random.PRNGKey(0), cfg)), opt,
        strategy, comm, policy=policy)
    loss = make_loss_fn(cfg, remat=True)
    step = make_replica_train_step(
        lambda p, t: loss(p, {"tokens": t, "labels": t}), opt, strategy,
        comm, policy=policy)
    return step.lower(state, jnp.zeros((2, 2, 32), jnp.int32)).compile() \
        .as_text()


@pytest.mark.parametrize("path", ["replica-f32", "replica-bf16",
                                  "sharded-zero0", "sharded-zero1"])
def test_phase_scopes_reach_every_training_path(tmp_path, path):
    if path.startswith("replica"):
        text = _replica_hlo(path.split("-")[1])
    else:
        zero = int(path[-1])
        text = _sharded_hlo(tmp_path, 4 if zero else 1, zero)
    names = re.findall(r'op_name="([^"]*)"', text)
    want = {"forward": f"jvp({scopes.FORWARD})",
            "backward": f"transpose(jvp({scopes.FORWARD}))",
            "recompute": "rematted_computation",
            "optimizer": scopes.OPTIMIZER}
    if path == "sharded-zero1":
        want["exchange"] = scopes.EXCHANGE
    for phase, mark in want.items():
        assert any(mark in n and scopes.phase_of(n) == phase
                   for n in names), f"no {phase} ({mark}) in the {path} step"
    ops = scopes.phases(text)
    count = collections.Counter(ops.values())
    assert set(want) <= set(count), count
    assert count[scopes.UNSCOPED] < 0.05 * len(ops), count
    # the matrix products by their own scope: the backward does about
    # twice the forward's work, the recompute repeats part of the forward
    flops = scopes.phase_flops(text)
    assert 0 < flops["recompute"] < flops["forward"], flops
    assert 1.5 * flops["forward"] < flops["backward"] \
        < 2.5 * flops["forward"], flops


def test_phase_of_and_scoped():
    assert scopes.phase_of("jit(step)/jvp(train.forward)/dot_general") \
        == "forward"
    assert scopes.phase_of(
        "jit(step)/transpose(jvp(train.forward))/while/body/dot_general") \
        == "backward"
    assert scopes.phase_of(
        "jit(step)/transpose(jvp(train.forward))/while/body/closed_call/"
        "checkpoint/rematted_computation/dot_general") == "recompute"
    assert scopes.phase_of("jit(step)/train.optimizer/train.exchange/"
                           "all-gather") == "exchange"
    assert scopes.phase_of("jit(step)/train.optimizer/mul") == "optimizer"
    assert scopes.phase_of("jit(step)/add") == scopes.UNSCOPED

    @scopes.scoped(scopes.OPTIMIZER)
    def f(x):
        return x * 2.0

    text = jax.jit(f).lower(np.ones(3, np.float32)).as_text(
        debug_info=True)
    assert scopes.OPTIMIZER in text


def test_phase_flops_counts_each_product_by_its_scope_and_its_loop():
    """A product in a scanned loop counts once a trip, under its own
    scope, whatever fusion or unrolling the compiler chose."""
    def f(x, w, a, b):
        with jax.named_scope(scopes.FORWARD):
            y, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x,
                                None, length=3)
        with jax.named_scope(scopes.OPTIMIZER):
            z = a @ b
        return y, z

    args = (np.ones((8, 16), np.float32), np.ones((16, 16), np.float32),
            np.ones((4, 8), np.float32), np.ones((8, 2), np.float32))
    text = jax.jit(f).lower(*args).compile().as_text()
    assert scopes.phase_flops(text) == {"forward": 3 * 2 * 8 * 16 * 16,
                                        "optimizer": 2 * 4 * 8 * 2}


# A batched product as XLA's TPU backend writes it: the batch dimensions
# (2 and 3) are the window's spatial dimensions, and the input's dilation
# leaves holes, so each output reads one tap of the 2x3 window, and the
# product is 2·b·f·i·(2·3) FLOPs, not six times that.
_CONV = """
HloModule m

ENTRY %main (p0: f32[2,3,5,4], p1: f32[2,5,3,6]) -> f32[2,3,4,6] {
  %p0 = f32[2,3,5,4]{3,2,1,0} parameter(0)
  %p1 = f32[2,5,3,6]{3,2,1,0} parameter(1)
  ROOT %convolution.1 = f32[2,3,4,6]{3,2,1,0} convolution(%p0, %p1), \
window={size=2x3 stride=1x2 lhs_dilate=2x3}, dim_labels=01fb_0i1o->01bf, \
metadata={op_name="jit(step)/transpose(jvp(train.forward))/dot_general"}
}
"""


def test_phase_flops_counts_the_taps_a_convolution_computes():
    assert scopes._taps(2, 2, 2, 1, 0, 2, 1) == 2  # one tap an output
    assert scopes.phase_flops(_CONV) == {"backward": 2 * 4 * 6 * 5 * 2 * 3}
