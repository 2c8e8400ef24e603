"""AOT whole-step executable cache — now a thin compat shim over the
content-addressed artifact store (thunder_tpu/compile_service/store.py).

The public surface (``enabled``/``cache_dir``/``step_key``/``module_digest``/
``load_keyed``/``save_keyed``) and the legacy ``aot.*`` counters are
unchanged; the storage layer is not:

* entries live in the store's content-addressed layout (per-key directory,
  ``manifest.json`` with a sha256 recorded at publish time) and the digest
  is verified BEFORE any ``pickle`` deserialization — the old flat-file
  format deserialized unvalidated bytes;
* legacy flat ``<base>-<digest>.aot`` files are never deserialized: they
  carry no publish-time digest, so they are swept with a ``stale-key``
  event (one recompile re-publishes them in the verified format);
* cross-process concurrency (racing publishes, torn reads, GC) is the
  store's contract, not this module's.

Controlled by:
  TT_ARTIFACT_DIR — the compile service store root (enables on ANY backend)
  TT_AOT_CACHE_DIR — legacy alias for the same directory
  TT_NO_AOT_CACHE=1 / TT_NO_ARTIFACT_STORE=1 — disable
Default-on only on non-CPU backends when no directory is named (CPU
executables are machine-specific and compile in seconds anyway); the default
directory is ``artifacts`` under the compile-cache root
(utils/compile_cache.py).
"""
from __future__ import annotations

import glob
import hashlib
import os

from ..compile_service import store as _cs
from ..observability import metrics as _obs_metrics


def enabled() -> bool:
    return _cs.store_enabled()


def cache_dir() -> str:
    d = _cs.store_dir()
    os.makedirs(d, exist_ok=True)
    return d


def source_digest() -> str:
    """sha256 over the package's .py sources (``store.code_fingerprint``,
    which every key of the store embeds)."""
    return _cs.code_fingerprint()


def _spec(tree) -> str:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    parts = [str(treedef)]
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None:
            parts.append(f"{shape}:{dtype}")
        else:
            parts.append(f"py:{type(leaf).__name__}:{leaf!r}")
    return "|".join(parts)


def step_key(*, inputs, extra: str = "") -> str:
    """Cache key for a compiled step called with `inputs` (a pytree of
    arrays/python scalars)."""
    import jax

    h = hashlib.sha256()
    h.update(source_digest().encode())
    h.update(jax.__version__.encode())
    h.update(jax.devices()[0].device_kind.encode())
    h.update(str(len(jax.devices())).encode())
    h.update(_spec(inputs).encode())
    h.update(extra.encode())
    return h.hexdigest()


def module_digest(module) -> str:
    """Digest of a Module's *computation*: the tree structure (child names +
    class names + parameter/buffer names) and every distinct forward's
    source. Editing a forward must invalidate AOT warm starts — the package
    source_digest() only covers thunder_tpu's own files, so a user model
    edit would otherwise run a stale executable with no signal at all."""
    import inspect

    h = hashlib.sha256()
    for name, mod in module.named_modules():
        cls = type(mod)
        h.update(f"{name}:{cls.__module__}.{cls.__qualname__}".encode())
        h.update(("|".join(sorted(getattr(mod, "_parameters", {}))) + ";"
                  + "|".join(sorted(getattr(mod, "_buffers", {})))).encode())
        fwd = getattr(cls, "forward", None)
        if fwd is not None:
            try:
                h.update(inspect.getsource(fwd).encode())
            except (OSError, TypeError):  # builtins / REPL-defined: best effort
                h.update(repr(fwd.__code__.co_code).encode()
                         if hasattr(fwd, "__code__") else b"?")
    return h.hexdigest()


def _store() -> _cs.ArtifactStore:
    return _cs.get_store(cache_dir())


def _store_key(base_key: str, digest: str) -> str:
    return _cs.artifact_key(kind="step", base_key=base_key, digest=digest[:16])


def _sweep_legacy(base_key: str) -> int:
    """Evict legacy flat-file entries for ``base_key`` (pre-store format:
    no publish-time digest, so they are never deserialized — the
    unvalidated-pickle fix). Returns the number swept."""
    stale = glob.glob(os.path.join(cache_dir(), f"{base_key}*.aot"))
    for p in stale:
        _obs_metrics.record_cache("aot", "evict", key=base_key[:12], why="stale-key")
        try:
            os.unlink(p)
        except OSError:
            pass
    return len(stale)


def load(key: str):
    """Deserialize a cached executable; None on miss or any failure.

    Read-only on miss (like the pre-store implementation): the legacy
    unkeyed probe must never sweep digest-keyed entries sharing the base
    key — only load_keyed, which knows the expected digest, may evict."""
    st = _store()
    k = _store_key(key, "")
    if st.contains(k):
        loaded = st.get_executable(k)
        if loaded is not None:
            _obs_metrics.record_cache("aot", "hit", key=key[:12])
            return loaded
        st.record_miss(k, kind="step")
        _obs_metrics.record_cache("aot", "evict", key=key[:12], why="corrupt")
        return None
    st.record_miss(k, kind="step")
    _obs_metrics.record_cache("aot", "miss", key=key[:12])
    return None


def load_keyed(base_key: str, digest: str):
    """Lookup keyed by (inputs/config base key, model-code digest).

    Returns ``(compiled_or_None, outcome)`` with outcome in:
      "hit"    — exact entry digest-verified and deserialized
      "stale"  — an entry exists for these inputs but under a DIFFERENT
                 model digest (the forward was edited), or only in the
                 unverifiable legacy format: evicted, cold trace
      "miss"   — nothing cached for these inputs
      "corrupt"— exact entry failed verification/deserialization: evicted
    """
    st = _store()
    key = _store_key(base_key, digest)
    if st.contains(key):
        loaded = st.get_executable(key)
        if loaded is not None:
            _obs_metrics.record_cache("aot", "hit", key=base_key[:12])
            return loaded, "hit"
        # digest mismatch or undeserializable: the store evicted it and a
        # cold compile follows — a store miss, same as plain absence
        st.record_miss(key, kind="step")
        _obs_metrics.record_cache("aot", "evict", key=base_key[:12], why="corrupt")
        return None, "corrupt"
    # same inputs/config under a different model digest: never run it; evict
    # so the store doesn't accumulate one entry per edit
    n_stale = 0
    for m in st.find(kind="step", base_key=base_key):
        if m.get("meta", {}).get("digest") != digest[:16]:
            st.evict(m["key"], why="stale-key")
            _obs_metrics.record_cache("aot", "evict", key=base_key[:12],
                                      why="stale-key")
            n_stale += 1
    n_stale += _sweep_legacy(base_key)
    # either way the store served nothing and a cold compile follows — that
    # must show in stats()["misses"] (bench's artifact_misses_warm) and as a
    # compile_artifact_miss event, same as a region-lookup miss
    st.record_miss(key, kind="step")
    if n_stale:
        return None, "stale"
    _obs_metrics.record_cache("aot", "miss", key=base_key[:12])
    return None, "miss"


def save(key: str, compiled) -> bool:
    """Serialize a jax Compiled to the store (atomic publish)."""
    return save_keyed(key, "", compiled)


def save_keyed(base_key: str, digest: str, compiled) -> bool:
    """Digest-keyed save (counterpart of load_keyed)."""
    st = _store()
    key = _store_key(base_key, digest)
    ok = st.put_executable(key, compiled, kind="step",
                           meta={"base_key": base_key, "digest": digest[:16]})
    if ok:
        # size comes from the manifest (one small json read) — re-reading
        # and re-hashing a multi-MB payload just to log its size would tax
        # every compile even with the bus disabled
        m = st.manifest(key)
        if m is not None and m.get("bytes") is not None:
            _obs_metrics.record_executable_size("aot", m["bytes"],
                                                entry=base_key[:28])
    return ok
