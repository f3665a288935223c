"""Test configuration: run every test on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness (tp/pp/
dp/ep) is validated on XLA's host-platform virtual devices instead — the
fake-backend test strategy the reference lacked entirely (SURVEY §4: "no
automated tests in the reference").
"""

import os

# Must be set before jax initializes its backends.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert jax.device_count() == 8, f"expected 8 virtual CPU devices, got {jax.devices()}"

# -- the benchmark's own tests ride along -------------------------------------
# perfbench/tests (readers, rooflines, the spec loader, the trace parser)
# guard what the driver measures with and live beside it, so a run that
# names this directory collects that one too. ``pytest tests/test_x.py``
# stays what it was.

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_PERFBENCH_TESTS = os.path.join(
    os.path.dirname(_TESTS_DIR), "perfbench", "tests")


def pytest_configure(config):
    named = {os.path.abspath(a.split("::")[0]) for a in config.args}
    if _TESTS_DIR in named and _PERFBENCH_TESTS not in named:
        config.args.append(_PERFBENCH_TESTS)


# -- per-test timeout fallback ----------------------------------------------
# pytest-timeout (wired via pyproject [tool.pytest.ini_options]) is the real
# implementation when installed; this container does not ship it, so a
# minimal SIGALRM fallback enforces the same contract: a regressed hang
# fails ONE test fast (default 300 s, tighter via @pytest.mark.timeout(N))
# instead of eating the whole 1,470 s tier-1 budget.

try:
    import pytest_timeout  # noqa: F401
    _HAVE_PYTEST_TIMEOUT = True
except ImportError:
    _HAVE_PYTEST_TIMEOUT = False

if not _HAVE_PYTEST_TIMEOUT:
    import signal
    import threading

    import pytest

    _DEFAULT_TIMEOUT_S = 300.0

    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_call(item):
        marker = item.get_closest_marker("timeout")
        limit = (float(marker.args[0]) if marker and marker.args
                 else _DEFAULT_TIMEOUT_S)
        # Only the call phase is timed (fixture setup legitimately pays XLA
        # compile time); SIGALRM needs the main thread, like pytest-timeout's
        # signal method.
        if (limit <= 0 or not hasattr(signal, "SIGALRM")
                or threading.current_thread() is not threading.main_thread()):
            yield
            return

        def _on_alarm(signum, frame):
            raise TimeoutError(
                f"test exceeded {limit:.0f}s (conftest SIGALRM fallback; "
                "install pytest-timeout for stack dumps)")

        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.setitimer(signal.ITIMER_REAL, limit)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, old)
