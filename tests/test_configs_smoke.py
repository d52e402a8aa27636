"""Per-architecture smoke tests: a REDUCED variant of each assigned family
(≤2 super-blocks, d_model ≤ 512, ≤4 experts) runs one forward pass and one
train step on CPU; output shapes asserted, no NaNs."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_configs
from repro.models import transformer as T
from repro.optim import adam
from repro.train.loop import make_loss_fn

ALL_ARCHS = [
    "gemma3-1b", "deepseek-67b", "seamless-m4t-medium", "xlstm-125m",
    "qwen2.5-14b", "qwen2-moe-a2.7b", "granite-moe-1b-a400m", "pixtral-12b",
    "jamba-1.5-large-398b", "qwen2-1.5b",
]

B, L = 2, 16


def _batch(cfg, key):
    batch = {"labels": jax.random.randint(key, (B, L), 0, cfg.vocab_size)}
    if cfg.modality == "vision":
        batch["embeds"] = jax.random.normal(key, (B, L, cfg.d_model),
                                            jnp.float32) * 0.02
    else:
        batch["tokens"] = jax.random.randint(key, (B, L), 0, cfg.vocab_size)
    if cfg.is_encoder_decoder:
        batch["source_embeds"] = jax.random.normal(
            key, (B, cfg.encoder_seq_len, cfg.d_model), jnp.float32) * 0.02
    return batch


def test_all_archs_registered():
    assert set(ALL_ARCHS) <= set(list_configs())


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_with_depth_cuts_depth_only(arch):
    """The depth cut keeps every field but num_layers, and takes one
    whole super-block (the layer pattern's period) at the least."""
    cfg = get_config(arch)
    period = len(cfg.superblock()[0])
    cut = cfg.with_depth(period)
    assert cut.num_layers == period
    assert dataclasses.replace(cut, num_layers=cfg.num_layers) == cfg
    for bad in (0, cfg.num_layers + period) + ((period + 1,) if period > 1
                                               else ()):
        with pytest.raises(ValueError, match="num_layers"):
            cfg.with_depth(bad)


def test_trainer_num_layers_flag(capsys):
    """--num-layers keeps the widths and prints the cut; a depth that is
    not a whole number of super-blocks exits 2."""
    from repro.launch.train import main
    main(["--arch", "qwen2-1.5b", "--reduced", "--num-layers", "1",
          "--workers", "1", "--steps", "1", "--seq-len", "8",
          "--batch-per-worker", "1"])
    assert "layers=1/2" in capsys.readouterr().out
    with pytest.raises(SystemExit) as e:
        main(["--arch", "jamba-1.5-large-398b", "--num-layers", "4",
              "--steps", "1"])
    assert e.value.code == 2
    assert "multiple of 8" in capsys.readouterr().err


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    without it the cache goes to the fixed in-checkout directory."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    calls = []
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.use_compile_cache() == str(
            compile_cache.DEFAULT_DIR)
        assert calls == [("jax_compilation_cache_dir",
                          str(compile_cache.DEFAULT_DIR))]
        root = compile_cache.DEFAULT_DIR.parent
        assert (root / "src" / "repro" / "launch").is_dir()
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
        assert compile_cache.use_compile_cache() == str(tmp_path / env_dir)
        assert calls == []
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_limits(arch):
    cfg = get_config(arch).reduced()
    specs, repeat = cfg.superblock()
    assert repeat <= 2
    assert cfg.d_model <= 512
    assert cfg.num_experts <= 4


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward(arch, rng):
    cfg = get_config(arch).reduced()
    params = T.init_model(rng, cfg)
    batch = _batch(cfg, rng)
    memory = None
    if cfg.is_encoder_decoder:
        memory = T.encode(params, cfg, embeds=batch["source_embeds"])
        assert memory.shape == (B, cfg.encoder_seq_len, cfg.d_model)
    logits, aux = T.forward(params, cfg, tokens=batch.get("tokens"),
                            embeds=batch.get("embeds"), memory=memory)
    assert logits.shape == (B, L, cfg.vocab_size)
    assert not jnp.isnan(logits).any()
    assert not jnp.isnan(aux)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step(arch, rng):
    cfg = get_config(arch).reduced()
    params = T.init_model(rng, cfg)
    opt = adam(1e-3)
    loss_fn = make_loss_fn(cfg, remat=False)
    batch = _batch(cfg, rng)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt_state = opt.update(grads, opt_state, params, 0)
        return params, opt_state, loss

    p1, _, loss1 = step(params, opt.init(params))
    assert jnp.isfinite(loss1)
    # loss roughly log(V) at init for uniform predictions
    assert float(loss1) < jnp.log(cfg.vocab_size) * 2 + 1
    moved = jax.tree.map(lambda a, b: jnp.any(a != b), params, p1)
    assert any(bool(x) for x in jax.tree.leaves(moved))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_decode_step(arch, rng):
    cfg = get_config(arch).reduced()
    params = T.init_model(rng, cfg)
    memory = None
    if cfg.is_encoder_decoder:
        memory = T.encode(params, cfg, embeds=jax.random.normal(
            rng, (B, cfg.encoder_seq_len, cfg.d_model)) * 0.02)
    cache = T.init_cache(cfg, B, 32)
    tok = jax.random.randint(rng, (B,), 0, cfg.vocab_size)
    for pos in range(3):
        logits, cache = T.decode_step(params, cfg, token=tok,
                                      pos=jnp.int32(pos), cache=cache,
                                      memory=memory)
        assert logits.shape == (B, cfg.vocab_size)
        assert not jnp.isnan(logits).any()
        tok = jnp.argmax(logits, -1).astype(jnp.int32)


@pytest.mark.parametrize("arch", ["gemma3-1b", "qwen2-moe-a2.7b",
                                  "xlstm-125m", "jamba-1.5-large-398b",
                                  "seamless-m4t-medium", "pixtral-12b"])
def test_prefill_matches_forward(arch, rng):
    cfg = get_config(arch).reduced()
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no drops
    params = T.init_model(rng, cfg)
    toks = jax.random.randint(rng, (B, L), 0, cfg.vocab_size)
    embeds = None
    memory = None
    if cfg.modality == "vision":
        embeds = jax.random.normal(rng, (B, L, cfg.d_model)) * 0.02
    if cfg.is_encoder_decoder:
        memory = T.encode(params, cfg, embeds=jax.random.normal(
            rng, (B, 8, cfg.d_model)) * 0.02)
    ref, _ = T.forward(params, cfg, tokens=None if embeds is not None else toks,
                       embeds=embeds, memory=memory)
    pf, cache = T.prefill(params, cfg,
                          tokens=None if embeds is not None else toks[:, :L - 1],
                          embeds=embeds[:, :L - 1] if embeds is not None else None,
                          memory=memory)
    assert jnp.allclose(pf, ref[:, :L - 1], rtol=5e-4, atol=5e-4)

    def pad(x):
        if x.ndim >= 3 and x.shape[2] == L - 1:
            w = [(0, 0)] * x.ndim
            w[2] = (0, 1)
            return jnp.pad(x, w)
        return x

    cache = jax.tree.map(pad, cache)
    lg, _ = T.decode_step(
        params, cfg,
        token=toks[:, L - 1] if embeds is None else None,
        embeds=embeds[:, L - 1:L] if embeds is not None else None,
        pos=jnp.int32(L - 1), cache=cache, memory=memory)
    assert float(jnp.max(jnp.abs(lg - ref[:, L - 1]))) < 5e-3


def test_param_counts_match_published():
    """Analytic N must land on the published model sizes."""
    expected = {
        "gemma3-1b": (0.9e9, 1.1e9),
        "deepseek-67b": (66e9, 69e9),
        "qwen2.5-14b": (14e9, 15.5e9),
        "qwen2-1.5b": (1.4e9, 1.7e9),
        "pixtral-12b": (12e9, 12.6e9),
        "jamba-1.5-large-398b": (390e9, 405e9),
        "qwen2-moe-a2.7b": (14e9, 14.6e9),
        "granite-moe-1b-a400m": (1.2e9, 1.45e9),
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)


def test_active_params():
    assert 2.4e9 <= get_config("qwen2-moe-a2.7b").active_param_count() <= 3.0e9
    assert 0.35e9 <= get_config("granite-moe-1b-a400m").active_param_count() <= 0.5e9
    assert 90e9 <= get_config("jamba-1.5-large-398b").active_param_count() <= 96e9
