"""What this process is attached to: backend, device identity, compile cache.

The one place the library asks "am I on a TPU?" — every layout/kernel
selection (learner.build_kwargs, fused.py, ops/histogram.py, boosting.py)
and every bisect script reads :func:`on_tpu`, so the sandbox AOT
pre-flight (tests/test_aot_tpu.py) can stand in for the chip by replacing
this one function. Call it as ``runtime.on_tpu()`` (module attribute), never
through a ``from``-import, or the replacement is not seen.
"""
from __future__ import annotations

import os

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def device_identity() -> dict:
    """The device as JAX reports it; stamped on every measured result."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, nothing is set
    in code. Otherwise ``<checkout>/.jax_cache`` next to this package
    (git-ignored) — a fixed path, because the path is part of the cache key.
    JAX's own floor (programs that took >= 1 s to compile) decides what is
    written.

    The cache key holds the programs' metadata: JAX leaves it out by
    default, and an executable loaded under another source's key carries
    that source's ``op_name``s — the ``lgbtpu/<phase>`` scopes a device
    trace is read by (``obs.PHASES``) would be those of whatever commit
    filled the cache first.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
