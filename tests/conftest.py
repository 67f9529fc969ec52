import os
import signal
import subprocess
import sys
import threading
import types
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


# ---- hypothesis shim -------------------------------------------------------
# Property tests use hypothesis, which is a dev extra.  In a clean env the
# suite must still collect and run: install a stub module whose @given turns
# each property test into a zero-arg skipper, so only the property tests are
# skipped and everything else runs.
try:
    import hypothesis  # noqa: F401
except ImportError:
    def _given_stub(*_a, **_k):
        def deco(fn):
            def skipper():
                pytest.skip("hypothesis not installed (property test)")
            skipper.__name__ = fn.__name__
            skipper.__doc__ = fn.__doc__
            return skipper
        return deco

    def _settings_stub(*_a, **_k):
        return lambda fn: fn

    def _strategy_stub(*_a, **_k):
        return None

    _st = types.ModuleType("hypothesis.strategies")
    for _name in ("integers", "floats", "booleans", "lists", "tuples", "text",
                  "sampled_from", "just", "one_of", "data", "composite"):
        setattr(_st, _name, _strategy_stub)
    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given_stub
    _hyp.settings = _settings_stub
    _hyp.strategies = _st
    _hyp.HealthCheck = types.SimpleNamespace(all=lambda: [])
    _hyp.assume = lambda *a, **k: True
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st

# ---- per-test timeout ------------------------------------------------------
# pytest-timeout is not available in hermetic containers, so the harness is
# hand-rolled: every test gets a SIGALRM-based wall-clock budget (default
# REPRO_TEST_TIMEOUT_S, override per test with @pytest.mark.timeout(N)) so a
# hung transport quiesce or deadlocked FIFO fails fast with a stack instead
# of wedging CI.  SIGALRM interrupts the main thread only — exactly where
# pytest runs test bodies; proxy worker threads are daemons and die with it.
_DEFAULT_TEST_TIMEOUT_S = float(os.environ.get("REPRO_TEST_TIMEOUT_S", "300"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("timeout")
    limit = float(marker.args[0]) if marker and marker.args \
        else _DEFAULT_TEST_TIMEOUT_S
    if (limit <= 0 or not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        return (yield)

    def on_alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded the {limit:.0f}s per-test timeout")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


# NOTE: no XLA_FLAGS here — smoke tests must see 1 device (assignment rule).
# Multi-device tests run via run_distributed() subprocesses.


def run_distributed(script: str, n_devices: int = 8, timeout: int = 900):
    """Run a python snippet in a subprocess with N fake CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"distributed subprocess failed:\nSTDOUT:\n{proc.stdout[-4000:]}"
            f"\nSTDERR:\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture(scope="session")
def dist_runner():
    return run_distributed
