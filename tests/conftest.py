"""Test fixtures: force an 8-device virtual CPU platform BEFORE jax loads.

Mirrors the reference's multiprocess-on-localhost distributed test strategy
(/root/reference/python/paddle/fluid/tests/unittests/test_dist_base.py:943)
with XLA's virtual-device simulation instead of spawning ranks.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# CPU-oracle testing wants exact fp32 matmuls; on TPU the framework default
# follows FLAGS_tpu_matmul_precision (bf16-pass default, like cublas TF32 in
# the reference).
jax.config.update("jax_default_matmul_precision", "highest")

import fnmatch  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# ---------------------------------------------------------------------
# runtime lockdep (analysis/sanitizer.py): when FLAGS_lockdep is set
# (env or flag), every Lock/RLock/Condition constructed by repo code
# from here on is instrumented — per-thread acquisition stacks, an
# observed order graph, and an error on the first AB/BA inversion.
# Installed at conftest import so locks created at test-module import
# time are covered too.
from paddle_tpu.framework.flags import flag_value  # noqa: E402

_LOCKDEP = bool(flag_value("FLAGS_lockdep"))
if _LOCKDEP:
    from paddle_tpu.analysis import sanitizer as _sanitizer
    _sanitizer.install()


@pytest.fixture(autouse=True)
def _lockdep_guard(request):
    """Fail any test on whose watch lockdep observed a NEW inversion
    (even one swallowed by a try/except in product code). Long holds
    are reported in the final sanitizer report, not per-test — wall
    time under a debugger or a loaded CI box is not a correctness
    signal."""
    if not _LOCKDEP:
        yield
        return
    before = len(_sanitizer.report()["inversions"])
    yield
    fresh = _sanitizer.report()["inversions"][before:]
    if fresh:
        notes = "; ".join(i["note"] for i in fresh)
        pytest.fail(f"lockdep observed {len(fresh)} lock-order "
                    f"inversion(s) during this test: {notes}",
                    pytrace=False)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if _LOCKDEP:
        rep = _sanitizer.report()
        terminalreporter.write_line(
            f"lockdep: {rep['acquires']} instrumented acquires, "
            f"{len(rep['classes'])} lock classes, "
            f"{len(rep['edges'])} order-graph sources, "
            f"{len(rep['inversions'])} inversions, "
            f"{len(rep['long_holds'])} long holds")


# ---------------------------------------------------------------------
# thread-leak guard: a test that exits leaving live threads it started
# fails with the offending names. Non-daemon leftovers would hang the
# interpreter at exit; leaked daemon *server/worker loops* (names our
# own code assigns) keep mutating shared state under later tests.
# Generic daemon "Thread-N" helpers are given a grace period but not
# failed — executor pools and stdlib internals park threads legally.
_LEAK_ALLOWLIST = (
    # intentional long-lived singletons, started once per process
    "pytest-watcher*",
    "ThreadPoolExecutor-*",       # parked pool workers are reused
    "asyncio_*",
    "paddle-metrics-exporter",    # process-wide registry exporter
)
_LOOP_NAME_PATTERNS = (
    # named loops from our own serving/observability/elastic stack:
    # these are servers — a test that starts one must stop it
    "fleet-supervisor-*", "fleet-worker-*", "engine-*", "router-*",
    "autoscaler-*", "watchdog-*", "canary-*", "chaos-*", "slo-*",
    "wedge-*", "breaker-*", "paddle-*", "goodput-*", "drain-*",
)


def _match(name, patterns):
    return any(fnmatch.fnmatch(name, p) for p in patterns)


@pytest.fixture(autouse=True)
def _thread_leak_guard(request):
    before = {t.ident for t in threading.enumerate()}
    yield
    def bad_threads():
        # Only threads that would fail the test: non-daemon, or named
        # like a serving/engine loop.  Plain transient daemon threads
        # are forgiven immediately — no grace wait — so the guard adds
        # no latency to the overwhelmingly common clean case.
        return [t for t in threading.enumerate()
                if t.is_alive() and t.ident not in before
                and not _match(t.name, _LEAK_ALLOWLIST)
                and (not t.daemon or _match(t.name, _LOOP_NAME_PATTERNS))]
    bad = bad_threads()
    deadline = time.monotonic() + 1.5
    while bad and time.monotonic() < deadline:
        time.sleep(0.02)                 # grace: loops finishing shutdown
        bad = bad_threads()
    if bad:
        names = ", ".join(f"{t.name}{'' if t.daemon else ' (non-daemon)'}"
                          for t in bad)
        pytest.fail(f"test leaked {len(bad)} live thread(s): {names} "
                    f"— stop/join servers and loops you start "
                    f"(or allowlist an intentional singleton in "
                    f"tests/conftest.py)", pytrace=False)


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh_utils import set_global_mesh
    paddle.seed(0)
    set_global_mesh(None)
    yield
    set_global_mesh(None)
