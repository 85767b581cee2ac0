"""Persistent XLA compilation cache.

The reference pays "model load time" once per process (onnxruntime session
build, ``onnxrt_backend.py:228``); our equivalent startup cost is XLA
compilation — tens of seconds per shape bucket on TPU. JAX can persist
compiled executables to disk keyed by (HLO, backend, flags); enabling it
turns every warm restart, bench subprocess, and supervised-server respawn
into a cache hit instead of a recompile.

Where the cache lives is the deployment's call, made the way JAX itself
takes it: ``JAX_COMPILATION_CACHE_DIR``. With it set, this module
configures no directory at all. Without it the cache goes to one fixed
path inside the checkout, ``<repo>/.jax_cache`` — the path is part of
what makes an entry findable again, so it is never a home directory, a
temporary name, a pid or a time. Opt out with ``LUMEN_COMPILE_CACHE=0``.
"""

from __future__ import annotations

import logging
import os
import threading

logger = logging.getLogger(__name__)

#: the cache's place when the environment names none: ``<repo>/.jax_cache``
#: (git-ignored), next to the ``lumen_tpu`` package directory.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_listener_lock = threading.Lock()
_listener_installed = False


def _on_jax_event(name: str, secs: float, **kwargs) -> None:  # noqa: ARG001
    """jax.monitoring duration listener: every backend compile lands on
    the capacity-telemetry rings as a count + a duration observation —
    the recompile-storm signal continuous batching needs (a healthy warm
    server shows ~0 compiles/window; a shape-churning caller shows a
    rising windowed rate at seconds per compile)."""
    if not name.endswith("backend_compile_duration"):
        return
    from . import telemetry
    from ..utils.metrics import metrics

    # metrics.count tees into the rolling window itself, so the windowed
    # `xla_compiles` rate comes for free with the cumulative counter;
    # only the duration histogram is telemetry-direct (a metrics.observe
    # would fabricate an "xla_compile_ms" row in the per-task table).
    metrics.count("xla_compiles")
    telemetry.observe("xla_compile_ms", secs * 1e3)


def install_compile_listener() -> bool:
    """Register the XLA compile-event hook (idempotent; returns whether
    the hook is live). Called from :func:`enable_persistent_cache` — the
    one place this repo configures JAX's compilation machinery."""
    global _listener_installed
    from jax import monitoring

    with _listener_lock:
        if _listener_installed:
            return True
        monitoring.register_event_duration_secs_listener(_on_jax_event)
        _listener_installed = True
    logger.info("XLA compile events feeding capacity telemetry")
    return True


def enable_persistent_cache() -> str | None:
    """Turn JAX's persistent compilation cache on (see the module
    docstring for where it lives).

    Idempotent; safe to call before or after backend init (the cache is
    consulted per compile). Returns the directory in force, or None when
    disabled.
    """
    # Compile events feed telemetry whether or not the disk cache is on:
    # the recompile-storm detector must not vanish with LUMEN_COMPILE_CACHE=0.
    install_compile_listener()
    if os.environ.get("LUMEN_COMPILE_CACHE") == "0":
        return None
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir:
        # JAX read the variable itself at import; leave its setting alone.
        logger.info("persistent XLA compile cache at %s (JAX_COMPILATION_CACHE_DIR)", cache_dir)
        return cache_dir
    # JAX's own gating (min compile time 1s by default) keeps ms-scale
    # programs out of the cache; every real model bucket qualifies.
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    logger.info("persistent XLA compile cache at %s", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
