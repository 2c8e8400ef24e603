"""Multi-process (multi-controller SPMD) bring-up + a local CPU harness.

The paper's lineage (GSPMD, Xu et al. 2021) assumes the multi-controller
model: N identical processes, each owning a slice of the devices, every one
running the SAME program over global arrays. ``initialize()`` wires
``jax.distributed.initialize`` for that world — on TPU pods the runtime
autodetects everything; on CPU (tests, laptops) it selects the gloo
cross-process collective implementation so a real 2-process mesh exists to
test against, not just the in-process 8-device simulation.

Two consumers:

* production entry points call ``initialize()`` once before building a
  mesh (``make_mesh`` already spans all global devices);
* ``LocalCluster`` spawns an N-process cluster of workers on THIS machine
  (subprocess + env wiring + free-port coordinator) so the distributed
  fault-tolerance paths — sharded checkpoints, psum'd guards, host death —
  are driven by real cross-process tests (tests/test_multiprocess.py),
  not trusted.

Also here: ``barrier()`` and ``kv_agree()`` over the distributed runtime's
key-value store. These are HOST-level coordination (no devices involved),
so they are safe from checkpoint writer threads where a device collective
could deadlock against an in-flight step.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Optional

# env wiring shared by LocalCluster (writer) and initialize() (reader);
# TT_MP_PROC is also read by robustness/faults.py for host-scoped faults
ENV_COORD = "TT_MP_COORD"
ENV_NPROCS = "TT_MP_NPROCS"
ENV_PROC = "TT_MP_PROC"
ENV_LOCAL_DEVICES = "TT_MP_LOCAL_DEVICES"

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None, *,
               cpu_collectives: str = "gloo") -> bool:
    """Join (or skip joining) a multi-process jax cluster. Args fall back to
    the TT_MP_* env vars LocalCluster sets; with neither, this is a no-op
    single-process run (returns False). Idempotent: a second call returns
    whether the cluster spans >1 process.

    Must run before any jax computation: the CPU collective implementation
    (gloo) has to be selected before the backend initializes."""
    global _initialized
    import jax

    if _initialized:
        return jax.process_count() > 1
    if coordinator_address is None:
        coordinator_address = os.environ.get(ENV_COORD)
    if num_processes is None and os.environ.get(ENV_NPROCS):
        num_processes = int(os.environ[ENV_NPROCS])
    if process_id is None and os.environ.get(ENV_PROC):
        process_id = int(os.environ[ENV_PROC])
    if coordinator_address is None:
        # not a multi-process launch (TPU pod autodetection still applies
        # when jax.distributed.initialize() is called with no args by the
        # operator; we only auto-wire the explicit/env path here)
        return False
    if num_processes is None or process_id is None:
        raise ValueError(
            "multiprocess.initialize needs num_processes and process_id "
            "(or the TT_MP_NPROCS / TT_MP_PROC env vars) alongside the "
            "coordinator address")
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if "cpu" in platforms or not platforms:
        jax.config.update("jax_cpu_collectives_implementation", cpu_collectives)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _initialized = True
    return jax.process_count() > 1


def process_index() -> int:
    """This host's index; 0 when jax is uninitialized (cheap, import-safe)."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


def process_count() -> int:
    try:
        import jax

        return int(jax.process_count())
    except Exception:
        return 1


def coordinator_client():
    """The distributed runtime's KV-store client, or None outside a
    multi-process run. Host-level coordination only — no device work."""
    try:
        from jax._src import distributed

        return distributed.global_state.client
    except Exception:
        return None


def barrier(name: str, *, timeout_s: float = 60.0) -> None:
    """Cross-host barrier over the coordination service (NOT a device
    collective: safe from writer threads). No-op single-process."""
    client = coordinator_client()
    if client is None:
        return
    client.wait_at_barrier(name, int(timeout_s * 1000))


def kv_set(key: str, value: str) -> None:
    client = coordinator_client()
    if client is not None:
        client.key_value_set(key, value)


def kv_get(key: str, *, timeout_s: float = 60.0) -> str:
    client = coordinator_client()
    if client is None:
        raise RuntimeError("kv_get outside a multi-process run")
    return client.blocking_key_value_get(key, int(timeout_s * 1000))


def kv_delete(key: str) -> None:
    """Best-effort delete (retiring a superseded published snapshot —
    observability/fleet.py); a missing key or an old runtime without
    delete support is fine."""
    client = coordinator_client()
    if client is not None:
        try:
            client.key_value_delete(key)
        except Exception:
            pass


def kv_dir(prefix: str) -> list[tuple[str, str]]:
    """Every (key, value) currently published under ``prefix`` (full key
    paths, the runtime's dir-get). Empty outside a multi-process run — and
    on a runtime hiccup, so pollers (fleet snapshot collection) degrade to
    their local view instead of raising mid-scrape."""
    client = coordinator_client()
    if client is None:
        return []
    try:
        return list(client.key_value_dir_get(prefix))
    except Exception:
        return []


def kv_agree(tag: str, value: str, *, timeout_s: float = 60.0) -> dict[int, str]:
    """Publish this host's ``value`` under ``tag`` and collect every host's.
    Returns {process_index: value}; raises TimeoutError (from the runtime)
    when a peer never reports — the caller turns that into a reason-coded
    error instead of hanging in a later collective. Single-process: {0: value}.

    ``timeout_s`` bounds the WHOLE collection (one shared deadline, not a
    per-peer budget): callers size it to grace windows, and N dead peers
    must not multiply the wait by N."""
    client = coordinator_client()
    n = process_count()
    if client is None or n <= 1:
        return {0: value}
    me = process_index()
    client.key_value_set(f"tt_agree/{tag}/{me}", value)
    deadline = time.monotonic() + timeout_s
    out = {}
    for p in range(n):
        left_ms = max(1, int((deadline - time.monotonic()) * 1000))
        out[p] = client.blocking_key_value_get(f"tt_agree/{tag}/{p}", left_ms)
    return out


# ---------------------------------------------------------------------------
# local CPU cluster harness
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# marker prefix workers use to hand structured results back to the harness
RECORD_PREFIX = "TTMP "

# prelude injected before every worker body: joins the cluster and gives the
# worker `emit(**fields)` for structured results. This module is loaded
# STANDALONE (by file path, stdlib-only at module level) so the cluster
# joins before `import thunder_tpu` — the package import runs jax
# computations, and jax.distributed.initialize must come first.
_WORKER_PRELUDE = """\
import importlib.util as _ilu
import json as _json
import os as _os
import sys as _sys

_sys.path.insert(0, {repo_root!r})
_os.environ.setdefault("JAX_PLATFORMS", "cpu")
_spec = _ilu.spec_from_file_location("_tt_multiprocess", {mp_path!r})
_mp = _ilu.module_from_spec(_spec)
_sys.modules["_tt_multiprocess"] = _mp  # dataclasses resolve via sys.modules
_spec.loader.exec_module(_mp)
_mp.initialize()


def emit(**fields):
    print({prefix!r} + _json.dumps(fields), flush=True)

"""


@dataclass
class ProcResult:
    """One worker's outcome: exit code, raw streams, and the structured
    records it ``emit()``-ed (TTMP-prefixed JSON lines)."""

    proc: int
    returncode: int
    stdout: str
    stderr: str
    timed_out: bool = False
    records: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.returncode == 0 and not self.timed_out


class LocalCluster:
    """CPU harness: spawn an N-process local jax cluster of CPU workers
    running one worker source. The workers are pinned to JAX_PLATFORMS=cpu
    with virtual host devices — a chip belongs to one process, so this is not
    a way to run on chips.

        cluster = LocalCluster(nprocs=2)
        results = cluster.run(WORKER_SRC, env={"TT_FAULT": "die@3:host=1"})

    Each worker gets: TT_MP_* env wiring to a fresh free-port coordinator,
    JAX_PLATFORMS=cpu, ``local_devices`` virtual CPU devices, the repo on
    sys.path, and an ``emit(**fields)`` helper whose JSON lines come back
    parsed in ``ProcResult.records``. ``run`` may be called repeatedly —
    each call is a fresh cluster (fresh port), which is exactly the
    kill-one-host-then-restart-everything shape."""

    def __init__(self, nprocs: int = 2, *, local_devices: int = 1,
                 timeout_s: float = 300.0, repo_root: Optional[str] = None):
        if nprocs < 1:
            raise ValueError("nprocs must be >= 1")
        self.nprocs = nprocs
        self.local_devices = local_devices
        self.timeout_s = timeout_s
        self.repo_root = repo_root or os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    def _env(self, proc: int, port: int, extra: Optional[dict]) -> dict:
        env = dict(os.environ)
        env.update({
            ENV_COORD: f"127.0.0.1:{port}",
            ENV_NPROCS: str(self.nprocs),
            ENV_PROC: str(proc),
            ENV_LOCAL_DEVICES: str(self.local_devices),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": (env.get("XLA_FLAGS", "") +
                          f" --xla_force_host_platform_device_count={self.local_devices}"),
            "PYTHONPATH": self.repo_root + os.pathsep + env.get("PYTHONPATH", ""),
        })
        # per-proc overrides: {"TT_FAULT": {...by proc...}} via callable or
        # plain values shared by every proc
        for k, v in (extra or {}).items():
            v = v(proc) if callable(v) else v
            if v is None:
                env.pop(k, None)
            else:
                env[k] = str(v)
        return env

    def run(self, worker_source: str, *, env: Optional[dict] = None,
            timeout_s: Optional[float] = None) -> list[ProcResult]:
        timeout_s = self.timeout_s if timeout_s is None else timeout_s
        port = free_port()
        prelude = _WORKER_PRELUDE.format(repo_root=self.repo_root,
                                         mp_path=os.path.abspath(__file__),
                                         prefix=RECORD_PREFIX)
        with tempfile.NamedTemporaryFile("w", suffix="_tt_worker.py",
                                         delete=False) as f:
            f.write(prelude + worker_source)
            script = f.name
        procs = []
        try:
            for p in range(self.nprocs):
                procs.append(subprocess.Popen(
                    [sys.executable, script],
                    env=self._env(p, port, env),
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True, cwd=self.repo_root))
            deadline = time.monotonic() + timeout_s
            results = []
            for p, proc in enumerate(procs):
                left = max(0.1, deadline - time.monotonic())
                timed_out = False
                try:
                    out, err = proc.communicate(timeout=left)
                except subprocess.TimeoutExpired:
                    timed_out = True
                    proc.kill()
                    out, err = proc.communicate()
                results.append(ProcResult(
                    proc=p, returncode=proc.returncode, stdout=out or "",
                    stderr=err or "", timed_out=timed_out,
                    records=self._parse(out or "")))
            return results
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.kill()
            try:
                os.unlink(script)
            except OSError:
                pass

    @staticmethod
    def _parse(stdout: str) -> list:
        records = []
        for line in stdout.splitlines():
            if line.startswith(RECORD_PREFIX):
                try:
                    records.append(json.loads(line[len(RECORD_PREFIX):]))
                except json.JSONDecodeError:
                    pass
        return records
