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

from .obs import host_phase, monotonic, telemetry

_started = False    # the runtime_start record is written: all a later call reads


def start() -> None:
    """Bring the XLA backend up under its own name: one ``runtime_start``
    record a process, host phase ``lgbtpu/runtime_start``. The first of this
    function, :func:`on_tpu` and :func:`device_identity` to run times its
    ``jax.devices()`` (on a TPU the runtime's start: the client, the chips);
    every later call reads one flag. ``ops/split.py`` calls it while the
    package imports, ahead of the device scalars of its default arguments,
    which would bring the backend up unnamed. ``backend_was_up`` says
    whether something had done so already (a caller that touched jax's
    devices before importing the package; ``None`` where this jax does not
    say): ``runtime_start_s`` then reads ~0 and times nothing."""
    global _started
    if _started:
        return
    _started = True
    try:
        from jax._src import xla_bridge
        was_up = bool(xla_bridge.backends_are_initialized())
    except Exception:
        was_up = None
    t0 = monotonic()
    with host_phase("lgbtpu/runtime_start"):
        devs = jax.devices()
    telemetry.record("runtime_start",
                     runtime_start_s=monotonic() - t0, asked_s=t0,
                     backend_was_up=was_up, platform=devs[0].platform,
                     device_kind=devs[0].device_kind, device_count=len(devs))


def on_tpu() -> bool:
    start()
    return jax.default_backend() == "tpu"


def device_identity() -> dict:
    """The device as JAX reports it; stamped on every measured result."""
    start()
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
