"""Persistent XLA compilation cache (utils/compile_cache.py; BASELINE.json
secondary metric — warm processes must skip the cold whole-step compile)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SNIPPET = """
import json, time, jax, jax.numpy as jnp
dir_updates = []
_update = jax.config.update
def update(name, value):
    if name == "jax_compilation_cache_dir":
        dir_updates.append(value)
    return _update(name, value)
jax.config.update = update
import thunder_tpu as tt
from jax._src import xla_bridge
from thunder_tpu.utils import compile_cache
imported_backends = list(xla_bridge._backends)
def f(a, b):
    return tt.ops.ltorch.sum(tt.ops.ltorch.matmul(a, b))
t0 = time.perf_counter()
float(tt.jit(f)(jnp.ones((64, 64)), jnp.ones((64, 64))))
print(json.dumps({"dir": compile_cache.cache_dir(), "t": time.perf_counter() - t0,
                  "config_dir": jax.config.jax_compilation_cache_dir,
                  "dir_updates": dir_updates,
                  "imported_backends": imported_backends}))
"""


def _run(env_extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    out = subprocess.run([sys.executable, "-c", _SNIPPET], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_placed_from_outside_populates_and_hits(tmp_path):
    """JAX_COMPILATION_CACHE_DIR places the cache: jax reads it itself, the
    code names no directory, and a second process adds no entry."""
    cache = str(tmp_path / "xla-cache")
    r1 = _run({"JAX_COMPILATION_CACHE_DIR": cache})
    assert r1["dir"] == cache and r1["config_dir"] == cache
    assert r1["dir_updates"] == [], "the code set a cache directory of its own"
    assert r1["imported_backends"] == [], "importing the package took a device"
    entries = os.listdir(cache)
    assert entries, "first process wrote no cache entries"
    r2 = _run({"JAX_COMPILATION_CACHE_DIR": cache})
    assert r2["dir"] == cache
    # no new compilation artifacts needed beyond what process 1 wrote
    assert set(os.listdir(cache)) == set(entries)


def test_cache_disabled_by_env(tmp_path):
    cache = str(tmp_path / "xla-cache-off")
    r = _run({"JAX_COMPILATION_CACHE_DIR": cache, "TT_NO_COMPILE_CACHE": "1"})
    assert r["dir"] is None
    assert not os.path.exists(cache)


def test_cache_defaults_off_on_cpu_backend():
    # the test env runs JAX_PLATFORMS=cpu: with no directory placed the cache
    # must stay off (XLA:CPU AOT load warnings + cheap compiles)
    if "cpu" not in os.environ.get("JAX_PLATFORMS", "").lower():
        pytest.skip("only meaningful under a cpu backend env")
    r = _run({})
    assert r["dir"] is None and r["config_dir"] is None


def test_unplaced_cache_is_one_fixed_path_in_the_checkout(monkeypatch):
    """No JAX_COMPILATION_CACHE_DIR: where the cache is on by default (a TPU
    backend, stood in for here) the one directory the code names is
    .tt_cache/xla at the root of the checkout — no HOME, pid or time in it."""
    import jax

    from thunder_tpu.utils import compile_cache

    updates = {}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("TT_NO_COMPILE_CACHE", raising=False)
    monkeypatch.setattr(compile_cache, "_enabled", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    assert compile_cache.enable_persistent_cache()
    assert compile_cache.cache_root() == os.path.join(REPO, ".tt_cache")
    assert updates["jax_compilation_cache_dir"] == os.path.join(REPO, ".tt_cache", "xla")


def test_artifact_store_default_follows_the_cache_root(monkeypatch, tmp_path):
    from thunder_tpu.compile_service import store

    monkeypatch.delenv("TT_ARTIFACT_DIR", raising=False)
    monkeypatch.delenv("TT_AOT_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert store.store_dir() == os.path.join(REPO, ".tt_cache", "artifacts")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert store.store_dir() == str(tmp_path / "artifacts")
    monkeypatch.setenv("TT_ARTIFACT_DIR", str(tmp_path / "elsewhere"))
    assert store.store_dir() == str(tmp_path / "elsewhere")


# -- AOT executable cache (utils/aot_cache.py) --

_AOT_SNIPPET = """
import time, json
import jax.numpy as jnp, numpy as np
import thunder_tpu as tt
from thunder_tpu import optim
from thunder_tpu.models.litgpt import Config, GPTForCausalLM
from thunder_tpu.training import TrainStep
cfg = Config.from_name("tiny")
rng = np.random.RandomState(0)
idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 32)), jnp.int32)
tgt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 32)), jnp.int32)
step = TrainStep(GPTForCausalLM(cfg), optim.AdamW(lr=1e-4))
losses = [float(step(idx, tgt)) for _ in range(3)]
from thunder_tpu.training import _CompiledWithFallback
print(json.dumps({"losses": losses,
                  "aot": isinstance(step._jitted, _CompiledWithFallback)}))
"""


def test_aot_cache_cross_process_parity(tmp_path):
    """Warm process deserializes the whole-step executable and produces
    bit-identical losses (the warm-compile path must not change numerics)."""
    aot = str(tmp_path / "aot")
    env = {"TT_AOT_CACHE_DIR": aot}
    out1 = subprocess.run([sys.executable, "-c", _AOT_SNIPPET],
                          env={**os.environ, "PYTHONPATH": REPO, **env},
                          capture_output=True, text=True, timeout=600)
    assert out1.returncode == 0, out1.stderr[-2000:]
    r1 = json.loads(out1.stdout.strip().splitlines()[-1])
    assert r1["aot"], "cold process did not engage the AOT save path"
    assert os.listdir(aot), "cold process wrote no AOT entries"
    out2 = subprocess.run([sys.executable, "-c", _AOT_SNIPPET],
                          env={**os.environ, "PYTHONPATH": REPO, **env},
                          capture_output=True, text=True, timeout=600)
    assert out2.returncode == 0, out2.stderr[-2000:]
    r2 = json.loads(out2.stdout.strip().splitlines()[-1])
    assert r2["losses"] == r1["losses"], "warm AOT start changed numerics"


def test_aot_cache_stale_source_invalidates(tmp_path, monkeypatch):
    from thunder_tpu.utils import aot_cache

    from thunder_tpu.compile_service import store

    monkeypatch.setattr(store, "code_fingerprint", lambda: "digest-a")
    k1 = aot_cache.step_key(inputs=(1, 2), extra="x")
    monkeypatch.setattr(store, "code_fingerprint", lambda: "digest-b")
    k2 = aot_cache.step_key(inputs=(1, 2), extra="x")
    assert k1 != k2


def test_aot_cache_default_off_on_cpu(monkeypatch):
    if "cpu" not in os.environ.get("JAX_PLATFORMS", "").lower():
        pytest.skip("only meaningful under a cpu backend env")
    from thunder_tpu.utils import aot_cache

    monkeypatch.delenv("TT_AOT_CACHE_DIR", raising=False)
    assert not aot_cache.enabled()
