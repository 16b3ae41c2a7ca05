"""Repo-root pytest conftest.

Forces tests onto a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware, and makes `boojum_tpu` importable. Must run
before anything imports jax.
"""

import os
import sys

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


def pytest_collection_modifyitems(items):
    # run the AOT artifact tests LAST (stable sort): their subprocess
    # bundle build pays real XLA compiles into a fresh bundle dir every
    # run (the whole point is an isolated cache), which the repo-local
    # persistent cache cannot amortize — if the tier-1 wall-clock budget
    # dies mid-suite, that fixed cost must burn the END of the budget,
    # not starve the alphabetically-later test files
    items.sort(key=lambda it: it.fspath.basename == "test_aot.py")

# The package import applies the one compile-cache rule
# (boojum_tpu/compile_cache.py): XLA:CPU compiles of the big unrolled prover
# graphs take minutes, so they persist under <checkout>/.jax_cache (or where
# JAX_COMPILATION_CACHE_DIR says) and only the first-ever run pays.
import boojum_tpu  # noqa: E402,F401
