"""The expert layer's compact-row share (``moe_compact_share.train``) on
synthetic traces, and the phase join of an op that runs inside a branch
of the layer's choice between compact and spill rows, from a step
compiled on the CPU."""

import re

import jax
import jax.numpy as jnp
import pytest

import _paths  # noqa: F401
from chipbench import spec, trace_reduce

NAME = "moe_compact_share.train"


def _read(scope_s):
    return spec.metric_reader(NAME)({"traced_steps": 4},
                                    {"scope_s": scope_s})


@pytest.mark.parametrize("scope_s,share", [
    ({"moe.experts": 0.3, "moe.compact": 0.2}, 100.0),
    ({"moe.experts": 0.5, "moe.compact": 0.3, "moe.spill": 0.1}, 75.0),
    ({"moe.experts": 0.5, "moe.spill": 0.4}, 0.0),
    ({"moe.experts": 0.5, "train.forward": 0.9}, None),
    ({}, None),
])
def test_compact_share(scope_s, share):
    """Compact time alone reads 100, both scopes a share, spill alone 0;
    neither scope (a program without the choice, as before it) reads
    nothing, and raises nothing."""
    assert _read(scope_s) == (share if share is None
                              else pytest.approx(share))
    assert spec.metric_reader(NAME)({}, None) is None


def test_a_backward_op_in_a_branch_joins_both_scopes():
    """A step's loss over one expert layer holding 2 of 8 experts, 2048
    tokens top-2 (its rows hold 2048 of the 4096 assignments): an op of
    the backward's compact branch is joined to the backward phase and to
    both ``moe.experts`` and ``moe.compact``, not to ``moe.spill``; the
    reader then reads 100."""
    from repro.configs.base import ModelConfig
    from repro.core import scopes
    from repro.models import layers as L

    cfg = ModelConfig(d_model=64, num_experts=8, top_k=2, moe_d_ff=32,
                      expert_pad_to=1).with_expert_share(2, 0)
    p = L.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2048, 64))

    def loss(p, x):
        with jax.named_scope(scopes.FORWARD):
            return jnp.sum(L.moe(p, cfg, x)[0] ** 2)

    text = jax.jit(jax.grad(loss)).lower(p, x).compile().as_text()
    comps, _ = scopes._parse(text)
    branches = {c for instrs in comps.values() for i in instrs.values()
                if i["opcode"] == "conditional"
                for kind, c in i["callees"] if kind == "branch"}
    phases = scopes.phases(text)
    op = next(name for c in branches for name, i in comps[c].items()
              if phases.get(name) == "backward"
              and "moe.compact" in re.split(r"[/()]", i["op"]))
    trace = {"devices": {"0": [[f"%{op} = f32[8]{{0}} fusion()", 0, 10]]},
             "host": []}
    r = trace_reduce.reduce_trace(trace, step_text=text)
    assert r["phase_s"] == {"backward": pytest.approx(10e-9)}
    assert {"moe.experts", "moe.compact"} <= set(r["scope_s"])
    assert "moe.spill" not in r["scope_s"]
    assert spec.metric_reader(NAME)({"traced_steps": 1}, r) == 100.0
