"""The plain reference against the program at a tiny size on the CPU: the
forward pass, prefill then paged decode through the engine's gather path,
the training loss and gradient, and the token stream."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
import tiny
from chipbench import reference, spec, traffic, weights


def _setup(tied):
    from repro.configs import get_config

    tiny.register()
    name = "chipbench-tiny" if tied else "chipbench-tiny-untied"
    cfg = get_config(name)
    fam = spec.family({"family": "qwen2"})
    dims = fam.dims({"config": dict(tiny.TINY, tie_word_embeddings=tied)})
    params = weights.init(fam, weights.key(3), dims, jnp.float32)
    return fam, cfg, dims, params


@pytest.mark.parametrize("tied", [True, False])
def test_forward_matches_the_program(tied):
    from repro.models import transformer as T

    fam, cfg, dims, params = _setup(tied)
    toks = np.asarray(traffic.rng(1, 0).integers(0, 256, 40), np.int32)
    prog, _ = T.forward(params, cfg, tokens=jnp.asarray(toks)[None])
    ref = reference.sequence_logits(fam, params, toks, dims)
    np.testing.assert_allclose(np.asarray(prog[0]), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # a wrong reference is caught: drop the query bias
    bad = dict(params, stack={"0": dict(params["stack"]["0"], attn=dict(
        params["stack"]["0"]["attn"],
        bq=jnp.zeros_like(params["stack"]["0"]["attn"]["bq"])))})
    off = reference.sequence_logits(fam, bad, toks, dims)
    assert float(jnp.max(jnp.abs(off - ref))) > 1e-2


def test_prefill_then_paged_decode_agree_with_the_reference():
    from repro.serve.engine import PagedDecodeEngine, Request

    fam, cfg, dims, params = _setup(False)
    eng = PagedDecodeEngine(params, cfg, batch_slots=3, max_seq=96,
                            cache_dtype=jnp.float32, use_kernel=False)
    r = traffic.rng(2, 0)
    reqs = [Request(i, r.integers(0, 256, n).astype(np.int32), 9)
            for i, n in enumerate((5, 40, 70))]
    for q in reqs:
        eng.submit(q)
    eng.run()
    served = [(q.prompt, q.generated) for q in reqs]
    assert all(len(g) == 9 for _, g in served)
    gaps = reference.served_gaps(fam, params, dims, served, 96)["gaps"]
    assert max(float(g.max()) for g in gaps) < 1e-4
    # a token changed where it is produced is far below the best
    p, g = served[1]
    g = list(g)
    g[4] = (g[4] + 1) % 256
    bad = reference.served_gaps(fam, params, dims, [(p, g)], 96)["gaps"][0]
    assert float(bad.max()) > 0.05


def test_training_loss_and_gradient_match_the_program():
    from repro.train.loop import make_loss_fn

    fam, cfg, dims, params = _setup(True)
    cfg = dataclasses.replace(cfg, remat=False)
    toks = traffic.rng(3, 0).integers(0, 256, (2, 24)).astype(np.int32)
    loss_fn = make_loss_fn(cfg, remat=False)
    pl, pg = jax.value_and_grad(loss_fn)(params, {"tokens": toks,
                                                  "labels": toks})
    rl = []
    rg = jax.tree.map(jnp.zeros_like, params)
    for row in toks:
        l_, g_ = jax.value_and_grad(reference.row_loss, argnums=1)(
            fam, params, jnp.asarray(row), reference._hashable(dims))
        rl.append(float(l_))
        rg = jax.tree.map(lambda a, b: a + b / len(toks), rg, g_)
    assert float(pl) == pytest.approx(np.mean(rl), rel=1e-5)
    for k, a in reference.flat(pg).items():
        b = reference.flat(rg)[k]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-6, err_msg=k)


def test_token_stream_matches_the_pipeline():
    from repro.data.pipeline import DataConfig, global_batch

    d = tiny.TRAIN["data"]
    dcfg = DataConfig(vocab_size=256, seq_len=32, batch_per_worker=2,
                      structure=d["structure"], a=d["a"], b=d["b"], seed=5)
    for step in (0, 123456789):
        prog = np.asarray(global_batch(dcfg, step, 6))
        ref = reference.train_tokens(5, step, 3, 2, 32, 256, d["structure"],
                                     d["a"], d["b"])
        np.testing.assert_array_equal(prog, ref)
