"""Persistent XLA compilation cache, placed from outside.

The device encode programs cost tens of seconds to compile per shape
for the TPU (the 32-lane fused filter+deflate program ~80 s), so a
cold process is mostly compiling; compiled executables persist on disk
and reload in milliseconds. The directory is part of the cache key, so
it never moves:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own handling keeps the
  cache there; this module sets no directory in code.
- unset: the config key ``jax.compilation-cache-dir`` if the operator
  gave one (engages on any backend), else ``<checkout>/.jax_cache`` on
  the TPU backend only — CPU AOT entries reload across machines with
  mismatched vector-feature sets (XLA warns of SIGILL), so a CPU
  backend stays uncached unless someone asked.

Every compile is cached, however small: a second start on a warm
directory then compiles nothing at all.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

log = logging.getLogger("omero_ms_pixel_buffer_tpu.jax_cache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

#: where the cache lives once an enable call ENGAGED it (jax's cache
#: dir is process-global: the first engagement wins)
_enabled_path: Optional[str] = None
#: the unconfigured default was evaluated and declined (non-TPU
#: backend) — cached so per-batch calls stay one branch; it must not
#: block a later configured opt-in in the same process
_default_declined = False
#: the last configured dir that lost to an earlier engagement
_ignored: Optional[str] = None


def enable_persistent_cache(configured: Optional[str] = None) -> None:
    """Idempotent; call before the first device compile. ``configured``
    is the operator's ``jax.compilation-cache-dir``, honoured only
    when ``JAX_COMPILATION_CACHE_DIR`` is unset."""
    global _enabled_path, _default_declined, _ignored
    if _enabled_path is not None:
        if configured and configured not in (_enabled_path, _ignored):
            _ignored = configured  # say it once, not once per batch
            log.warning(
                "persistent compile cache already at %r; ignoring %r",
                _enabled_path, configured,
            )
        return
    if _default_declined and not configured:
        return
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = os.environ.get(ENV_VAR)
    if path:
        _ignored = configured  # the variable wins, by design
    else:
        if not configured and jax.default_backend() != "tpu":
            _default_declined = True
            return
        path = configured or DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # jax latches the cache at its first compile; re-point it so a dir
    # engaged after a jit already ran (a warm process) still takes
    compilation_cache.reset_cache()
    _enabled_path = path
    log.info("persistent compile cache at %s", path)


def enabled_path() -> Optional[str]:
    """Where the cache lives, or None when it never engaged."""
    return _enabled_path
