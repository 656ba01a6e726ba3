"""Test config: force an 8-device virtual CPU mesh so sharding/collective
paths run multi-device without TPU hardware (SURVEY.md §4 implication:
multi-node-without-a-cluster testing, reference lightgbm/vw local[*] suites).
Must run before jax import.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The persistent compilation cache (JAX_COMPILATION_CACHE_DIR,
# mmlspark_tpu.utils.compile_cache) stays off here: tests must compile
# what they test, and tests/test_aot_tpu_compile.py's described-device
# executables can be written to a cache but not read back without a chip.

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# the benchmark's own tests (benchmarks/tests) are collected by the thin
# tests/test_bench_*.py modules; they import `lib`, `reducers`,
# `with_shelved` and each other by bare name, as their own conftest
# arranges when they are run from their directory
sys.path += [os.path.join(ROOT, "benchmarks"),
             os.path.join(ROOT, "benchmarks", "tests")]

import jax

# the env var above only counts if jax was not imported before this file
# (a plugin, a -p option); the config knob still wins as long as no
# backend has been initialized
jax.config.update("jax_platforms", "cpu")
assert jax.default_backend() == "cpu", "tests must run on the virtual CPU mesh"

import numpy as np
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--graftsan", action="store_true", default=False,
        help="run the whole session under the tools/graftsan runtime "
             "concurrency sanitizer (same as GRAFTSAN=1); every test "
             "gets an end-of-test audit and fails on unsuppressed "
             "S-findings")


def _graftsan_requested(config) -> bool:
    return bool(config.getoption("--graftsan")
                or os.environ.get("GRAFTSAN", "") not in ("", "0"))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running / wall-clock-sensitive; excluded from the "
        "tier-1 gate (-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic seeded fault-injection tests (utils.faults); "
        "fast and tier-1 — chaos here means reproducible, not flaky")
    if _graftsan_requested(config):
        import tools.graftsan as graftsan

        graftsan.install()


# benchmarks/tests is the benchmark's and only a `benchmark` PR may edit
# it; what fails there for a reason such a PR has to repair is marked
# from this side, by name and with the reason, not skipped and not edited
_KNOWN_XFAIL = {
    "test_bench_program_spans.py::test_check_takes_the_merged_manifest":
        "compares the shelved trace_lower_s entry's three cells with ALL "
        "cells of BENCHMARK.json, which has had a fourth since PR 26 "
        "(PERF.md section 7 (a)); only a `benchmark` PR may edit it",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for tail, reason in _KNOWN_XFAIL.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(reason=reason))


def pytest_unconfigure(config):
    if _graftsan_requested(config):
        import tools.graftsan as graftsan

        graftsan.uninstall()


# thread-name prefixes owned by serving/batching/training infrastructure;
# a test that returns while one of these is still alive has leaked a
# server, batcher, or training-guard watchdog (a later test inherits its
# port contention / fault plan / telemetry noise).  Only non-daemon
# threads fail the test outright: daemon pool threads
# (ThreadPoolExecutor) park harmlessly.
_INFRA_PREFIXES = ("serve-", "serving-", "continuous-batcher", "stream-",
                   "train-guard", "flow-", "dist-")


@pytest.fixture(autouse=True)
def _end_of_test_checks(request):
    """One ordered teardown for the per-test invariants.  The graftsan
    audit MUST run before the thread-leak check: a leaked flow worker
    usually means a leaked credit, and the sanitizer's S301 names the
    stage and construction site where the generic leak message can only
    list thread names."""
    import threading
    import time

    graftsan = None
    mark = 0
    if _graftsan_requested(request.config):
        import tools.graftsan as graftsan

        mark = graftsan.begin_test()
    before = {t.ident for t in threading.enumerate()}
    yield
    if graftsan is not None:
        found = graftsan.finish_test(mark)
        if found:
            pytest.fail(
                "graftsan: unsuppressed finding(s):\n" +
                "\n".join(f.render() for f in found))
    deadline = time.monotonic() + 2.0  # grace: stop() joins may lag
    while time.monotonic() < deadline:
        leaked = [
            t for t in threading.enumerate()
            if t.ident not in before and t.is_alive() and not t.daemon
            and t.name.startswith(_INFRA_PREFIXES)
        ]
        if not leaked:
            return
        time.sleep(0.05)
    pytest.fail(
        f"test leaked non-daemon infra threads: "
        f"{[t.name for t in leaked]} — call .stop() on every "
        "WorkerServer/ServingServer/ContinuousBatcher/TrainingGuard "
        "the test starts")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def bench_trace_lib():
    """benchmarks/lib/trace.py by path: the benchmark reducers' own trace
    reader and matcher, for tests that hold the program to them."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_trace_lib",
        os.path.join(ROOT, "benchmarks", "lib", "trace.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # its dataclass looks itself up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def small_table():
    from mmlspark_tpu import Table

    rng = np.random.default_rng(0)
    return Table(
        {
            "features": rng.normal(size=(20, 4)).astype(np.float32),
            "label": rng.integers(0, 2, size=20),
            "text": [f"row {i}" for i in range(20)],
            "value": rng.normal(size=20),
        }
    )
