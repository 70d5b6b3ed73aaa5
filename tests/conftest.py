"""Test configuration: force an 8-device virtual CPU mesh.

This gives every test real multi-device sharding semantics without TPUs —
the thing the reference never had (SURVEY.md §4: "no simulated cluster").
Tests run on the CPU backend whatever the machine has; the chip is
exercised by chip_smoke.py, not by pytest.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# The tests' programs are tiny and run for milliseconds: what they cost is
# their compile, so LLVM builds them unoptimised (the HLO passes, and so every
# compiled text a test reads, are the same: tools/same_program.py prints the
# same digests either way). Servers and workers that tests spawn inherit the
# flag with the device count.
if "xla_backend_optimization_level" not in flags:
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags.strip()
# No persistent compilation cache under pytest, in this process or in the
# servers and workers tests spawn (they inherit the variable): ModelManager
# places the cache inside the checkout, and a test run must neither write
# there nor depend on what an earlier run compiled. XLA:CPU also warns that
# executables it reads back were built for other machine features ("could
# lead to SIGILL"). Where the cache lives has its own test
# (tests/test_config.py); the chip run exercises the cache for real.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


# Nearly all of the suite's time is XLA:CPU compiles, and the driver runs it
# on six workers (`-n 6 --dist loadfile`: a worker takes the next module when
# it has run out). What the run takes is then the busiest worker's time, so
# the modules go longest first and the cheap ones fill the gaps at the end.
# The seconds are those of the last whole run somebody measured:
# `python tools/test_budget.py <junit.xml> --write` rewrites the file, and a
# module it does not know goes first of all (a new file is presumed costly
# until it is measured).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pytest_collection_modifyitems(items):
    import json

    with open(os.path.join(_REPO_ROOT, "tests", "module_seconds.json")) as f:
        seconds = json.load(f)
    # list.sort is stable: order inside a module is untouched
    items.sort(key=lambda it: -seconds.get(
        os.path.relpath(str(it.fspath), _REPO_ROOT).replace(os.sep, "/"),
        float("inf")))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multichip: needs the forced 8-device CPU mesh (tp>1 engine tests); "
        "re-executed in a subprocess with XLA_FLAGS="
        "--xla_force_host_platform_device_count=8 when this process somehow "
        "initialized jax with fewer devices",
    )
    config.addinivalue_line(
        "markers",
        "multiproc: spawns REAL worker processes (separate jax CPU "
        "runtimes + an HTTP hop) via localai_tpu.testing.multihost — the "
        "2-process simulated cluster the ISSUE 13 span-transfer and "
        "discovery tests run against; tier-1 on CPU like multichip",
    )


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {devs}"
    return devs


# multichip marker/fixture (ISSUE 7 satellite): tp=2/tp=4 engine tests need
# a multi-device mesh. This conftest already forces 8 virtual CPU devices,
# so the fixture normally just hands back the device count and the test runs
# inline (a tier-1 pass dot, thread-leak guard included). The subprocess
# fallback covers the environments where that forcing loses — jax already
# initialized before this conftest ran, or an externally-set XLA_FLAGS:
# the marked tests of the requesting module are re-executed once
# in a child pytest with the flag forced (same idiom as the
# affinity-stability subprocess test in test_cluster.py), and the parent
# test reports the child's verdict.
_MULTICHIP_MODULE_RESULT: dict = {}


@pytest.fixture
def multichip(request):
    n = jax.device_count()
    if n >= 8 or os.environ.get("LOCALAI_MULTICHIP_CHILD") == "1":
        return n
    import subprocess
    import sys

    mod = str(request.node.fspath)
    if mod not in _MULTICHIP_MODULE_RESULT:
        kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
                if "xla_force_host_platform_device_count" not in f]
        env = {
            **os.environ,
            "XLA_FLAGS": " ".join(
                kept + ["--xla_force_host_platform_device_count=8"]),
            "JAX_PLATFORMS": "cpu",
            "LOCALAI_MULTICHIP_CHILD": "1",
        }
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-m", "multichip",
             "-p", "no:cacheprovider", mod],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        _MULTICHIP_MODULE_RESULT[mod] = (
            proc.returncode, proc.stdout[-4000:] + proc.stderr[-4000:]
        )
    rc, out = _MULTICHIP_MODULE_RESULT[mod]
    if rc != 0:
        pytest.fail(
            f"multichip subprocess re-run of {mod} failed (rc={rc}):\n{out}"
        )
    pytest.skip("passed in the 8-device subprocess re-run")


# multiproc fixture (ISSUE 13 satellite): one REAL prefill-role worker
# process (own jax CPU runtime, tiny paged model "mh") shared across the
# session — the remote end of the 2-process span-transfer/discovery tests.
# Boot cost (~a tiny-model load) is paid once; tests must treat the worker
# as shared state (assert deltas, use distinct prompts).
@pytest.fixture(scope="session")
def multiproc_worker(tmp_path_factory):
    from localai_tpu.testing import multihost

    d = tmp_path_factory.mktemp("mh-models")
    multihost.write_tiny_model_yaml(str(d))
    worker = multihost.spawn_worker(str(d), role="prefill")
    yield worker
    worker.stop()


# Thread-leak guard (ISSUE 4 satellite): the supervisor restart path is
# exactly where stray engine threads would hide — a reloaded model whose
# predecessor's loop/drain thread never exited would double-dispatch into
# the same devices. After every test MODULE, any thread with one of these
# names that did NOT exist when the module started must be gone. Module
# granularity (not per-test) because module-scoped fixtures load engines
# LAZILY — a server fixture's model loads during the first request, so its
# engine threads legitimately appear mid-test and live until the fixture's
# module teardown; that teardown runs before this guard's check.
#
# The watch list lives in tools/lint/threads.py (ISSUE 15): the lint
# thread-root discovery and this guard share ONE source, and a drift test
# in tests/test_lint.py fails when a new threading.Thread site is covered
# by neither the guard nor the documented exemption list there.
import sys as _sys

if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)

from tools.lint.threads import (  # noqa: E402
    GUARDED_THREAD_PREFIXES as _GUARDED_THREAD_PREFIXES,
)


def _guarded_threads():
    import threading

    return {
        t for t in threading.enumerate()
        if t.is_alive() and t.name.startswith(_GUARDED_THREAD_PREFIXES)
    }


@pytest.fixture(scope="module", autouse=True)
def _no_thread_leaks():
    import time

    before = _guarded_threads()
    yield
    # Grace window: stop()/shutdown() signal their threads but some exit on
    # their next wait() tick (watchdog interval, drain join).
    deadline = time.monotonic() + 10.0
    leaked = _guarded_threads() - before
    while leaked and time.monotonic() < deadline:
        time.sleep(0.05)
        leaked = _guarded_threads() - before
    assert not leaked, (
        "threads leaked past module teardown (engine not stopped / manager "
        "not shut down?): " + ", ".join(sorted(t.name for t in leaked))
    )
