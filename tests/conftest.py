"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax import.

Multi-chip hardware is not available in CI; sharding tests run over
``--xla_force_host_platform_device_count=8`` on the CPU backend exactly as
the driver's ``dryrun_multichip`` does.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import faulthandler  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _deadlock_watchdog():
    """Env-gated faulthandler deadlock watchdog (ISSUE 14).

    A lock-discipline regression that slips past the static lint shows
    up at runtime as a silent deadlock — and tier-1 then burns its
    whole 870 s timeout with no diagnostics. With
    ``NORNICDB_TEST_WATCHDOG_S=<seconds>`` set, any single test
    exceeding the budget dumps ALL thread stacks to stderr (the lock
    holder is in the dump) and, unless
    ``NORNICDB_TEST_WATCHDOG_EXIT=0``, exits the process so the run
    fails fast instead of hanging. Off by default: the timer is armed
    per test and cancelled on teardown, costing nothing when the env
    is unset."""
    budget = os.environ.get("NORNICDB_TEST_WATCHDOG_S")
    if not budget:
        yield
        return
    exit_on_dump = os.environ.get(
        "NORNICDB_TEST_WATCHDOG_EXIT", "1") != "0"
    faulthandler.dump_traceback_later(
        float(budget), exit=exit_on_dump)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def benchmark_state_put_back():
    """A rehearsed run of the benchmark (``benchmark/run.py --rehearse``,
    in process) sets the deployment's environment and the trace buffer's
    capacity, and leaves the admission controller with the waits it
    observed and with limits read from that environment (``admission.cfg``
    caches them for the process); neither the run nor the tests that
    follow on this worker may inherit another's."""
    from nornicdb_tpu import admission
    from nornicdb_tpu.obs import tracing

    env = dict(os.environ)
    capacity = tracing.TRACES.capacity
    # the waits go now; the limits are dropped, NOT re-read (``reload``
    # would cache this moment's environment): the run reads its own
    admission.CONTROLLER.reset()
    admission._cfg = None
    yield
    tracing.TRACES.capacity = capacity
    for key in set(os.environ) - set(env):
        del os.environ[key]
    os.environ.update(env)
    admission.reload()
