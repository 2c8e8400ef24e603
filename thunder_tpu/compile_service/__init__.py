"""compile_service: every compiled artifact in the system, owned in one place.

Three pillars (ROADMAP #3 — kill the cold start):

* **Content-addressed artifact store** (`store.py`) — compiled executables
  (whole-step programs, fusion-region executables) are keyed by a sha256
  over everything that could change the program (canonical trace text,
  transform stack, mesh/sharding spec, jax/jaxlib version, device kind,
  this package's sources, input avals) and published atomically (tmp dir + ``os.replace`` + a
  sha256 ``manifest.json`` — the CheckpointManager pattern at artifact
  scale). Reads are lock-free and digest-verified BEFORE any ``pickle``
  deserialization; publishes serialize under a best-effort lock file.
  ``utils/aot_cache.py`` and ``utils/compile_cache.py`` are thin compat
  shims over this store.

* **Parallel region compilation** (`parallel_compile.py`) — after
  ``transform_for_execution`` forms fusion regions, independent regions
  lower + XLA-compile concurrently on a worker pool (instead of serially
  at first dispatch), joined by the region registry
  ``observability/profiler.py`` already maintains. Warm stores serve
  region executables straight from disk.

* **Bucketed lowering** (`buckets.py`) — ONE declared power-of-two,
  page-size-aligned ``BucketLadder`` shared by the serving engine's
  prompt buckets and the trainer's shape guards, so one stored artifact
  serves a (batch, seq) range and steady-state recompiles stay at zero
  across mixed lengths.

Environment knobs (see docs/compilation.md):

  TT_ARTIFACT_DIR         store root (enables the store on ANY backend,
                          including CPU)
  TT_NO_ARTIFACT_STORE=1  disable the store entirely
  TT_PARALLEL_COMPILE     0/1 force parallel region compilation off/on
                          (default: on exactly when the store is enabled)
  TT_COMPILE_WORKERS      worker-pool width (default: min(8, regions))
  TT_ARTIFACT_KEEP        keep-last-K GC retention (default 64)
"""
from __future__ import annotations

from .buckets import BucketLadder, pad_to_bucket  # noqa: F401
from .parallel_compile import (  # noqa: F401
    maybe_prewarm,
    parallel_compile_enabled,
    prewarm_regions,
)
from .store import (  # noqa: F401
    ArtifactStore,
    artifact_key,
    environment_fingerprint,
    get_store,
    store_dir,
    store_enabled,
)
