"""Content-addressed compile-artifact store: publish once, hit everywhere.

Layout (under ``store_dir()``)::

    <root>/<key[:2]>/<key>/
        artifact.bin     # opaque payload (e.g. pickled serialized executable)
        manifest.json    # {"key", "kind", "sha256", "bytes", "created",
                         #  "env": {...}, "meta": {...}}

``key`` is a sha256 hex digest over the artifact's full identity
(``artifact_key``): canonical trace text, transform stack, mesh/sharding
spec, jax/jaxlib versions, device kind/count, this package's own sources
(``code_fingerprint``), and input avals. Anything
that could change the compiled program changes the key — a hit can never
run a stale program.

Concurrency contract:

* **reads are lock-free**: a reader sees either no directory or a fully
  published one (``os.replace`` is atomic); ``artifact.bin`` is digest-
  verified against the manifest BEFORE any deserialization — the fix for
  the old aot_cache's unvalidated ``pickle.load`` — and a mismatch evicts
  the entry with a ``stale-key`` event instead of raising;
* **publishes converge**: each publisher stages into its own tmp dir and
  installs with one atomic ``os.replace`` — two processes racing the same
  key end with exactly one entry (the second ``replace`` fails ENOTEMPTY
  and the loser discards its tmp dir; content-addressed keys make either
  winner correct). A best-effort ``O_CREAT|O_EXCL`` lock file (with
  stale-lock reclaim) lets a publisher that sees the winner's finished
  entry skip re-serializing, but correctness never depends on it;
* **GC never deletes an artifact published after the scan started** and
  keeps the most recently used K entries (last-K by manifest/access time).

The store keeps plain process-local counters (hits/misses/evicts/
publishes) unconditionally — cheap ints, readable by callers without the
observability bus — and ALSO records ``artifact.*`` counters plus
``compile_artifact_hit/miss/evict`` events when the bus is enabled.
"""
from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import os
import pickle
import shutil
import tempfile
import threading
import time
from typing import Any, Iterable, Optional

from ..observability import events as _obs
from ..observability import metrics as _obs_metrics

_MANIFEST = "manifest.json"
_PAYLOAD = "artifact.bin"
_LOCK_STALE_S = 120.0


# -- enablement / location ---------------------------------------------------

def store_dir() -> str:
    """Store root: TT_ARTIFACT_DIR, else the legacy TT_AOT_CACHE_DIR (the
    aot shim's entries live in the same store), else ``artifacts`` under the
    compile-cache root (utils/compile_cache.py: $JAX_COMPILATION_CACHE_DIR,
    else the checkout's ``.tt_cache``)."""
    from ..utils.compile_cache import cache_root

    return (os.environ.get("TT_ARTIFACT_DIR")
            or os.environ.get("TT_AOT_CACHE_DIR")
            or os.path.join(cache_root(), "artifacts"))


def store_enabled() -> bool:
    """The store is on when a directory is named explicitly (ANY backend);
    in the default directory only on a non-CPU backend, where XLA:CPU
    executables are machine-specific and cheap to rebuild."""
    if (os.environ.get("TT_NO_ARTIFACT_STORE") == "1"
            or os.environ.get("TT_NO_AOT_CACHE") == "1"):
        return False
    if os.environ.get("TT_ARTIFACT_DIR") or os.environ.get("TT_AOT_CACHE_DIR"):
        return True
    import jax

    return jax.default_backend() != "cpu"


@functools.lru_cache(maxsize=None)
def code_fingerprint() -> str:
    """sha256 over this package's .py sources, once a process — a code change
    invalidates every cached executable (stale programs must never run
    silently). A trace names a program's symbols, not what they lower to: a
    Pallas kernel's body, an executor's lowering and a pass are this code.
    Files are named by their path inside the package, so two checkouts of one
    commit agree wherever they sit on disk."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d != "__pycache__" and not d.startswith("."))
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                p = os.path.join(dirpath, fn)
                h.update(os.path.relpath(p, root).encode())
                with open(p, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()


def environment_fingerprint() -> dict:
    """The environment fields every key embeds: a serialized executable is
    only valid for the jax/jaxlib version and device kind that built it, and
    for the version of this package that lowered the trace."""
    env = {"jax": "?", "jaxlib": "?", "device_kind": "?", "n_devices": 0,
           "code": code_fingerprint()}
    try:
        import jax

        env["jax"] = jax.__version__
        try:
            import jaxlib

            env["jaxlib"] = getattr(jaxlib, "__version__", "?")
        except Exception:
            pass
        devs = jax.devices()
        env["device_kind"] = devs[0].device_kind
        env["n_devices"] = len(devs)
    except Exception:
        pass
    return env


def artifact_key(**fields: Any) -> str:
    """sha256 over sorted (name, value) field pairs + the environment
    fingerprint. Values are stringified; callers pass deterministic reprs
    (canonical trace text, transform-stack reprs, aval specs)."""
    h = hashlib.sha256()
    for k, v in sorted(environment_fingerprint().items()):
        h.update(f"env.{k}={v}\n".encode())
    for k in sorted(fields):
        h.update(f"{k}=".encode())
        v = fields[k]
        h.update((v if isinstance(v, bytes) else str(v).encode()))
        h.update(b"\n")
    return h.hexdigest()


# -- the store ---------------------------------------------------------------

class ArtifactStore:
    """One directory of content-addressed artifacts (see module docstring)."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self._lock = threading.Lock()
        # process-local traffic counters, kept unconditionally (callers and
        # tests read them without enabling the bus)
        self.hits = 0
        self.misses = 0
        self.evicts = 0
        self.publishes = 0

    # -- paths --
    def _entry_dir(self, key: str) -> str:
        return os.path.join(self.root, key[:2], key)

    def _manifest_path(self, key: str) -> str:
        return os.path.join(self._entry_dir(key), _MANIFEST)

    # -- read (lock-free) --
    def get_bytes(self, key: str, *, record: bool = True) -> Optional[tuple[bytes, dict]]:
        """(payload, manifest) for ``key``; None on miss. Corrupt or
        digest-mismatched entries are evicted (``stale-key`` event) and
        read as a miss — a torn or tampered artifact must never reach a
        deserializer."""
        entry = self._entry_dir(key)
        mpath = os.path.join(entry, _MANIFEST)
        try:
            with open(mpath) as f:
                manifest = json.load(f)
            with open(os.path.join(entry, _PAYLOAD), "rb") as f:
                payload = f.read()
        except (OSError, json.JSONDecodeError) as e:
            if (os.path.isdir(entry)
                    and isinstance(e, (FileNotFoundError, json.JSONDecodeError))):
                # the directory exists but a piece is missing or the manifest
                # is torn: genuinely corrupt, evict it. Other OSErrors
                # (EMFILE, transient EACCES on a network FS) must NOT evict a
                # valid fleet-shared artifact — read as a plain miss instead
                self._evict(key, why="corrupt")
            elif record:
                self._record("miss", key=key[:12])
            return None
        digest = hashlib.sha256(payload).hexdigest()
        if digest != manifest.get("sha256"):
            self._evict(key, why="stale-key")
            return None
        if record:
            self._record("hit", key=key[:12], kind=manifest.get("kind"),
                         bytes=len(payload))
        # access time drives keep-last-K GC ordering (best-effort)
        with contextlib.suppress(OSError):
            os.utime(mpath)
        return payload, manifest

    def contains(self, key: str) -> bool:
        return os.path.isfile(self._manifest_path(key))

    def manifest(self, key: str) -> Optional[dict]:
        """The entry's manifest alone (no payload read, no digest check) —
        for cheap metadata like the recorded byte size."""
        try:
            with open(self._manifest_path(key)) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            return None

    # -- publish (locked) --
    @contextlib.contextmanager
    def _publish_lock(self, key: str):
        """Best-effort cross-process publish lock; yields whether this
        process owns it. A non-owner still publishes (atomic ``os.replace``
        guarantees convergence, and the winner may have crashed) — the lock
        only serves the contains() re-check that skips duplicate work when
        the winner already finished. A crashed publisher's lock is reclaimed
        after _LOCK_STALE_S."""
        os.makedirs(self.root, exist_ok=True)
        lock_path = os.path.join(self.root, f".lock.{key}")
        fd = None
        try:
            try:
                fd = os.open(lock_path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                os.write(fd, str(os.getpid()).encode())
            except FileExistsError:
                try:
                    if time.time() - os.path.getmtime(lock_path) > _LOCK_STALE_S:
                        os.unlink(lock_path)  # stale: reclaim on next attempt
                except OSError:
                    pass
                yield False
                return
            yield True
        finally:
            if fd is not None:
                os.close(fd)
                with contextlib.suppress(OSError):
                    os.unlink(lock_path)

    def put_bytes(self, key: str, payload: bytes, *, kind: str = "artifact",
                  meta: Optional[dict] = None) -> bool:
        """Atomically publish ``payload`` under ``key``. Returns True when
        the key is present afterwards (whether this process or a racing one
        published it). Never raises on IO failure — a failed publish only
        costs the next process a recompile."""
        if self.contains(key):
            return True
        manifest = {
            "key": key,
            "kind": kind,
            "sha256": hashlib.sha256(payload).hexdigest(),
            "bytes": len(payload),
            "created": time.time(),
            "env": environment_fingerprint(),
            "meta": dict(meta or {}),
        }
        final = self._entry_dir(key)
        try:
            with self._publish_lock(key):
                if self.contains(key):
                    return True
                parent = os.path.dirname(final)
                os.makedirs(parent, exist_ok=True)
                tmp = tempfile.mkdtemp(prefix=f".tmp.{key[:12]}.", dir=self.root)
                try:
                    with open(os.path.join(tmp, _PAYLOAD), "wb") as f:
                        f.write(payload)
                    with open(os.path.join(tmp, _MANIFEST), "w") as f:
                        json.dump(manifest, f, sort_keys=True)
                    # single atomic publish: readers see nothing or all of it
                    os.replace(tmp, final)
                except OSError:
                    # a racing publisher (lockless loser path) or a full disk:
                    # converged if the entry exists now
                    shutil.rmtree(tmp, ignore_errors=True)
                    return self.contains(key)
        except OSError:
            return self.contains(key)
        with self._lock:
            self.publishes += 1
        if _obs.enabled():
            _obs_metrics.record_artifact("publish", key=key[:12], kind=kind,
                                         bytes=len(payload))
        return True

    # -- executables (serialize_executable payloads) --
    def put_executable(self, key: str, compiled, *, kind: str = "step",
                       meta: Optional[dict] = None) -> bool:
        """Serialize a jax ``Compiled`` and publish it; False when the
        backend cannot serialize it or its trees do not pickle."""
        import jax
        from jax.experimental import serialize_executable as se

        try:
            # with the number of devices it runs on: deserialize_and_load
            # otherwise loads it across every device of the process, and a
            # one-device program then fails at its first call on any host
            # that has more ("Expected args ... to have N shards")
            n_devices = len(compiled.runtime_executable().local_devices())
            payload = pickle.dumps(se.serialize(compiled) + (n_devices,))
        except (ValueError, TypeError, AttributeError, pickle.PicklingError,
                jax.errors.JaxRuntimeError):
            return False
        return self.put_bytes(key, payload, kind=kind, meta=meta)

    def get_executable(self, key: str, *, record: bool = True):
        """Deserialize a cached executable; None on miss/corruption. The
        payload digest was verified by ``get_bytes`` before this unpickles
        anything."""
        got = self.get_bytes(key, record=record)
        if got is None:
            return None
        payload, _ = got
        try:
            import jax
            from jax.experimental import serialize_executable as se

            serialized, in_tree, out_tree, n_devices = pickle.loads(payload)
            return se.deserialize_and_load(
                serialized, in_tree, out_tree,
                execution_devices=jax.devices()[:n_devices])
        except Exception:
            # digest-valid but undeserializable here (other machine/ABI):
            # evict so the directory doesn't accumulate unusable entries
            self._evict(key, why="corrupt")
            return None

    # -- maintenance --
    def entries(self) -> list[dict]:
        """All manifests (unordered); unreadable entries are skipped."""
        out = []
        if not os.path.isdir(self.root):
            return out
        for shard in sorted(os.listdir(self.root)):
            sdir = os.path.join(self.root, shard)
            # shards are exactly key[:2] — two hex chars. Anything else is a
            # co-tenant (the `xla/` backend cache, .tmp/.lock debris, obs
            # dumps), not store state: never scan, flag, or GC it.
            if (len(shard) != 2 or any(c not in "0123456789abcdef" for c in shard)
                    or not os.path.isdir(sdir)):
                continue
            for key in sorted(os.listdir(sdir)):
                mpath = os.path.join(sdir, key, _MANIFEST)
                try:
                    with open(mpath) as f:
                        m = json.load(f)
                    m["_atime"] = os.path.getmtime(mpath)
                    m["_path"] = os.path.join(sdir, key)
                    out.append(m)
                except (OSError, json.JSONDecodeError):
                    out.append({"key": key, "kind": "?", "_path":
                                os.path.join(sdir, key), "_invalid": True})
        return out

    def find(self, *, kind: Optional[str] = None, **meta_filters) -> Iterable[dict]:
        for m in self.entries():
            if m.get("_invalid"):
                continue
            if kind is not None and m.get("kind") != kind:
                continue
            mm = m.get("meta", {})
            if all(mm.get(k) == v for k, v in meta_filters.items()):
                yield m

    def validate(self, key: str) -> tuple[bool, list[str]]:
        """Manifest-vs-payload integrity of one entry (no deserialization)."""
        entry = self._entry_dir(key)
        problems: list[str] = []
        mpath = os.path.join(entry, _MANIFEST)
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            return False, [f"manifest unreadable: {e}"]
        ppath = os.path.join(entry, _PAYLOAD)
        try:
            with open(ppath, "rb") as f:
                payload = f.read()
        except OSError:
            return False, ["artifact.bin missing"]
        if len(payload) != manifest.get("bytes"):
            problems.append(f"size mismatch: {len(payload)} != {manifest.get('bytes')}")
        if hashlib.sha256(payload).hexdigest() != manifest.get("sha256"):
            problems.append("sha256 mismatch")
        return not problems, problems

    def evict(self, key: str, *, why: str = "evicted") -> bool:
        return self._evict(key, why=why)

    def _evict(self, key: str, *, why: str) -> bool:
        entry = self._entry_dir(key)
        try:
            # rename-aside first so a concurrent reader can't see a half-
            # deleted entry as a valid one (the CheckpointManager idiom)
            doomed = tempfile.mkdtemp(prefix=f".tmp.evict.{key[:12]}.",
                                      dir=self.root)
            os.rmdir(doomed)  # os.replace needs the target absent (non-empty dirs fail)
            os.replace(entry, doomed)
            shutil.rmtree(doomed, ignore_errors=True)
        except OSError:
            return False
        with self._lock:
            self.evicts += 1
        if _obs.enabled():
            _obs_metrics.record_artifact("evict", key=key[:12], why=why)
            if why == "stale-key":
                _obs_metrics.record_recompile(_obs_metrics.REASON_STALE_KEY,
                                              key=key[:12])
        return True

    def gc(self, keep: Optional[int] = None, *, _scan_start: Optional[float] = None) -> int:
        """Keep the ``keep`` most recently used entries; delete the rest.
        Entries published AFTER the scan started are never deleted (a
        racing publisher's fresh artifact must survive a concurrent GC).
        Returns the number of entries removed."""
        if keep is None:
            keep = int(os.environ.get("TT_ARTIFACT_KEEP", "64"))
        scan_start = time.time() if _scan_start is None else _scan_start
        ents = [m for m in self.entries() if not m.get("_invalid")]
        ents.sort(key=lambda m: m.get("_atime", 0.0), reverse=True)
        removed = 0
        for m in ents[keep:]:
            if m.get("created", 0.0) >= scan_start:
                continue  # published after the scan started: off-limits
            if self._evict(m["key"], why="gc"):
                removed += 1
        # invalid (torn) entries are always garbage
        for m in self.entries():
            if m.get("_invalid"):
                path = m["_path"]
                shutil.rmtree(path, ignore_errors=True)
                if os.path.exists(path):  # a stray file, not a dir
                    with contextlib.suppress(OSError):
                        os.unlink(path)
                if not os.path.exists(path):
                    removed += 1
        return removed

    def record_miss(self, key: str, *, kind: str = "artifact") -> None:
        """Count a lookup that found no usable entry — for callers (the aot
        shim) that probe with ``contains()`` instead of ``get_bytes()``, so
        their misses still reach ``stats()`` and ``compile_artifact_miss``."""
        self._record("miss", key=key[:12], kind=kind)

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evicts": self.evicts, "publishes": self.publishes}

    def _record(self, outcome: str, **attrs) -> None:
        with self._lock:
            if outcome == "hit":
                self.hits += 1
            elif outcome == "miss":
                self.misses += 1
        if _obs.enabled():
            _obs_metrics.record_artifact(outcome, **attrs)


# -- process-global store ----------------------------------------------------

_STORE: Optional[ArtifactStore] = None
_STORE_LOCK = threading.Lock()


def get_store(root: Optional[str] = None) -> ArtifactStore:
    """The process store (rebuilt when the resolved root changes — tests
    repoint TT_ARTIFACT_DIR between cases)."""
    global _STORE
    want = os.path.abspath(root or store_dir())
    with _STORE_LOCK:
        if _STORE is None or _STORE.root != want:
            _STORE = ArtifactStore(want)
        return _STORE
