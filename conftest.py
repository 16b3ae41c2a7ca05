"""Repo-root pytest conftest.

Forces tests onto a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware, and makes `boojum_tpu` importable. Must run
before anything imports jax.
"""

import os
import signal
import sys

import pytest

# Force CPU: unit tests never take the chip (python chip_smoke.py does, in
# its own process), and no subprocess a test starts may inherit it.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def pytest_configure(config):
    # Workers that meet a fresh checkout together all build the native
    # resolver into one temporary name, and a loser falls back to the
    # Python resolver for its whole run (and skips the native engine's
    # test). The controller builds it here, before xdist starts a worker.
    from boojum_tpu import native

    native.get_lib()
    # tier-1 runs with -m 'not slow' (ROADMAP.md); register the marker so
    # slow-lane tests don't warn as unknown
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 budget (-m 'not slow')"
    )
    # gateway tests bind loopback sockets (ISSUE 11); they stay in
    # tier-1 by default, but sandboxed runners without socket permits
    # can exclude them wholesale with -m 'not gateway'
    config.addinivalue_line(
        "markers",
        "gateway: binds loopback HTTP sockets (-m 'not gateway' to skip "
        "on sandboxed runners)",
    )
    # multi-process jax.distributed tests (subprocess pairs over a
    # loopback coordinator): slow-lane by construction, selected
    # explicitly by scripts/ci_gate.sh --multihost via -m multihost
    config.addinivalue_line(
        "markers",
        "multihost: spawns jax.distributed subprocess pairs "
        "(ci_gate.sh --multihost runs these)",
    )


# One test may hold its worker this long, then it fails by name and the
# run goes on. The alarm reaches Python code only (a sleep, a lock, a
# socket or subprocess wait): a call into XLA (a compile, a compiled
# program's run) is not interrupted, its test fails when the call returns.
TEST_TIME_LIMIT_S = 420.0


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    def expired(_signum, _frame):
        pytest.fail(
            f"{item.nodeid} ran past the time limit of "
            f"{TEST_TIME_LIMIT_S:g} s",
            pytrace=False,
        )

    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIME_LIMIT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


# The package import applies the one compile-cache rule
# (boojum_tpu/compile_cache.py): XLA:CPU compiles of the big unrolled prover
# graphs take minutes, so they persist under <checkout>/.jax_cache (or where
# JAX_COMPILATION_CACHE_DIR says) and only the first-ever run pays.
import boojum_tpu  # noqa: E402,F401
