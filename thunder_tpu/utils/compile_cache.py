"""Persistent XLA compilation cache: where it lives, and whether it is on.

The reference pays its (much smaller) torch.compile cost per process; on TPU
the whole-step XLA compile is tens of seconds, so thunder_tpu persists
compiled executables across processes via jax's compilation cache. This
layer only skips the XLA *backend* compile; the compile service's artifact
store (whole-step and region executables) is what removes retrace +
relowering too — see docs/compilation.md.

The directory is placed from outside the program:

  JAX_COMPILATION_CACHE_DIR — jax reads it itself; where it is set, this
                              module names no directory of its own
  (unset)                   — ``.tt_cache/xla`` at the root of the checkout: a
                              fixed path, because a cache that moves between
                              runs never hits

``cache_root()`` is the one rule both this cache and the artifact store's
default directory follow. Unplaced, it is enabled lazily at the first tt.jit
compile and only on a TPU backend (XLA:CPU executables are machine-specific
and compile in seconds); naming JAX_COMPILATION_CACHE_DIR turns it on
anywhere, from import. TT_NO_COMPILE_CACHE=1 disables it.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_enabled: bool | None = None


def cache_root() -> str:
    """Root of everything the program caches on disk:
    $JAX_COMPILATION_CACHE_DIR, else the checkout's ``.tt_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(_CHECKOUT, ".tt_cache")


def enable_persistent_cache() -> bool:
    """Turn jax's persistent compilation cache on for this process.
    Idempotent; returns whether the cache is active."""
    global _enabled
    if _enabled is not None:
        return _enabled
    import jax

    if os.environ.get("TT_NO_COMPILE_CACHE") == "1":
        jax.config.update("jax_enable_compilation_cache", False)
        _enabled = False
        return False
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # runs at the first tt.jit compile (not package import), so the
        # backend reflects any jax.config.update("jax_platforms") the
        # caller did after importing jax
        if jax.default_backend() == "cpu":
            _enabled = False
            return False
        jax.config.update("jax_compilation_cache_dir", os.path.join(cache_root(), "xla"))
    # cache everything: whole-step programs are always worth persisting,
    # and small traces cost nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _enabled = True
    return True


def cache_dir() -> str | None:
    """The XLA cache directory in use, or None while the cache is off."""
    import jax

    return jax.config.jax_compilation_cache_dir if _enabled else None


if os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.environ.get("TT_NO_COMPILE_CACHE") == "1":
    # decided by the environment alone, so decided at import (neither branch
    # touches a backend): jax has a placed cache on from its own import, and
    # with its default one-second threshold still in force until the first
    # tt.jit, whether a model's init programs were cached would hang on
    # whether each compile took 0.9 or 1.1 s — and a second run would add
    # entries the first one skipped
    enable_persistent_cache()
