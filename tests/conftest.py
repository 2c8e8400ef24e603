"""Test configuration: virtual 8-device CPU mesh, float64 oracle enabled.

Mirrors the reference's distributed test strategy (SURVEY.md §4): the
reference spawns real NCCL processes (thunder/tests/distributed/helper.py:146);
on the jax stack a virtual CPU mesh via --xla_force_host_platform_device_count
covers multi-device semantics in-process."""
import os

# TT_ONCHIP=1 keeps the machine's own platform for the on-chip tests
# (tests/test_onchip.py); default is the virtual 8-device CPU mesh.
_ONCHIP = os.environ.get("TT_ONCHIP") == "1"

if not _ONCHIP:
    os.environ["JAX_PLATFORMS"] = "cpu"  # read by jax at import, below
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = flags + " --xla_force_host_platform_device_count=8"
    if "xla_backend_optimization_level" not in flags:
        # tier-1 on CPU is compile-bound (thousands of tiny jits on one
        # core): backend opt level 1 cuts wall time ~20% with the failure
        # set byte-identical to the default level. Level 0 is NOT safe —
        # it breaks cross-program bit-equality (guarded-vs-unguarded step
        # trajectories). Subprocess tests (quickstarts, the multiprocess
        # harness) inherit this via os.environ.
        flags = flags + " --xla_backend_optimization_level=1"
    os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

if not _ONCHIP:
    jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(1234)


@pytest.fixture
def pallas_claims(monkeypatch):
    """Interpreted claims: the checkers of the kernels that claim only on the
    chip (paged, grouped MLP, ring flash, int8 and fp8 linear) claim here too,
    and their kernels run in Pallas interpret mode. Patches the one predicate
    they all ask; undone when the test ends."""
    from thunder_tpu.executors import pallasex

    monkeypatch.setattr(pallasex, "_claims_on_platform", lambda: True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (deselected by the tier-1 run)")
    config.addinivalue_line(
        "markers",
        "fault: fault-injection test (exercises TT_FAULT recovery paths; "
        "filter with -m fault / -m 'not fault')")
    config.addinivalue_line(
        "markers",
        "serve: serving-engine test (continuous batching + paged KV cache; "
        "runs under JAX_PLATFORMS=cpu interpret mode in tier-1; filter with "
        "-m serve / -m 'not serve')")
    config.addinivalue_line(
        "markers",
        "telemetry: live-telemetry test (streaming percentiles, metrics "
        "exporter, SLO monitors; filter with -m telemetry / "
        "-m 'not telemetry')")
    config.addinivalue_line(
        "markers",
        "analysis: static-analysis test (trace verifier, pass-interposed "
        "checking, alias/donation safety, memory budgeting; filter with "
        "-m analysis / -m 'not analysis')")
    config.addinivalue_line(
        "markers",
        "compile: compile-service test (content-addressed artifact store, "
        "parallel region compilation, bucketed lowering, warm-start smoke; "
        "filter with -m compile / -m 'not compile')")
    config.addinivalue_line(
        "markers",
        "dist: multi-process distributed test (subprocess-spawned 2-process "
        "CPU cluster via jax.distributed + gloo; these also carry `slow` so "
        "tier-1 stays fast — run with -m dist)")
    config.addinivalue_line(
        "markers",
        "perf: performance-lever correctness test (overlap cache keys, "
        "bucketed grad-sync bit-identity, fused fp8 kernel parity, int8 "
        "decode token-identity; filter with -m perf / -m 'not perf')")
    config.addinivalue_line(
        "markers",
        "moe: mixture-of-experts test (grouped-dispatch bit-identity, "
        "capacity/drop semantics, EP×DP mesh wiring, moe.* telemetry; "
        "filter with -m moe / -m 'not moe')")
    config.addinivalue_line(
        "markers",
        "longctx: long-context test (streaming ring-flash identity, GQA "
        "ring attention, 32k paged serving; the genuinely long-T runs also "
        "carry `slow`; filter with -m longctx / -m 'not longctx')")


def pytest_collection_modifyitems(config, items):
    # TT_TEST_ORDER_SEED=<int> runs the suite in a seeded random order to
    # flush out cross-test global-state leaks (registry/cache pollution).
    seed = os.environ.get("TT_TEST_ORDER_SEED")
    if seed:
        import random

        random.Random(int(seed)).shuffle(items)
