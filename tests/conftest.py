"""Test harness config: tests run on the CPU, on an 8-device virtual mesh.

JAX_PLATFORMS and the forced host device count are set before jax is
imported, so no test ever reaches for an accelerator (the chip is
chip_smoke.py's business, through the builder's chip tool). pytest's
capture plugin has already swapped fd 1/2 by the time conftest imports, so
this is done in-process rather than by re-exec'ing with a scrubbed env.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ---- thread-leak detector -------------------------------------------------
# Watchdog/resolver/auditor restarts must never leak loops into later tests
# silently: product threads are daemons by contract (the process may exit
# under them), so any NON-daemon thread that outlives the test that started
# it is a harness bug — it would also hang the pytest process at exit.

import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

_THREAD_BASELINE: "set[int] | None" = None


def _leaked_nondaemon(baseline: "set[int]", grace_s: float = 2.0) -> list:
    """Live non-daemon threads not in ``baseline``, after letting
    shutdown-in-progress threads finish for up to ``grace_s``."""
    def live():
        return [t for t in threading.enumerate()
                if not t.daemon and t.is_alive()
                and t.ident not in baseline
                and t is not threading.main_thread()]
    leaked = live()
    deadline = time.time() + grace_s
    while leaked and time.time() < deadline:
        time.sleep(0.05)
        leaked = live()
    return leaked


@pytest.fixture(autouse=True)
def _no_thread_leaks():
    global _THREAD_BASELINE
    if _THREAD_BASELINE is None:  # session baseline: pytest's own threads
        _THREAD_BASELINE = {t.ident for t in threading.enumerate()
                            if not t.daemon}
    baseline = set(_THREAD_BASELINE)
    yield
    leaked = _leaked_nondaemon(baseline)
    if leaked:
        # absorb into the baseline so ONE leak fails ONE test, not every
        # test that follows it
        _THREAD_BASELINE.update(t.ident for t in leaked)
        pytest.fail(
            "non-daemon thread(s) leaked past the test: "
            + ", ".join(f"{t.name} ({t.ident})" for t in leaked),
            pytrace=False)
