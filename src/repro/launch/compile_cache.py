"""Where JAX keeps its persistent compilation cache, how many programs the
process has compiled, and which attention path its traces took."""

from __future__ import annotations

import os
import pathlib

import jax

# the checkout's root (src/repro/launch/ → three levels up); gitignored
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of what a later run
    must find again, so it never comes from a temp name, a pid or the
    time.  Call before the first compile; later calls change nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


_COMPILES = []


def compile_count() -> int:
    """Executables built so far in this process: each compiled, or read
    from the persistent cache; a call whose executable is already in
    memory builds none.  The first call registers the listener
    (``jax.monitoring``) and returns 0, so call it once before the builds
    that should count and take differences."""
    if not _COMPILES:
        _COMPILES.append(0)
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: _COMPILES.append(secs)
            if "backend_compile" in name else None)
    return len(_COMPILES) - 1


ATTENTION_PATH = "/repro/attention_path/"
_PATHS = {}


def record_attention_path(path: str, layers: int) -> None:
    """Note, at trace time, that ``layers`` attention layers took ``path``
    (``flash``: the Pallas kernel; ``dense``: the jnp path)."""
    jax.monitoring.record_scalar(ATTENTION_PATH + path, layers)


def attention_paths() -> dict:
    """Attention layers traced so far in this process, by path: a trace of
    a scanned stack counts each of its layers.  Like ``compile_count``,
    the first call registers the listener and returns zeros."""
    if not _PATHS:
        _PATHS.update(flash=0, dense=0)

        def count(name, value, **kw):
            if name.startswith(ATTENTION_PATH):
                path = name[len(ATTENTION_PATH):]
                _PATHS[path] = _PATHS.get(path, 0) + int(value)

        jax.monitoring.register_scalar_listener(count)
    return dict(_PATHS)
