"""The one compile-cache rule, for every entry point of this checkout.

If `JAX_COMPILATION_CACHE_DIR` is set, JAX's own reading of it stands and
no code here sets another directory. Otherwise the persistent cache is
`<checkout>/.jax_cache`: a fixed path, because a cache directory that
moves never hits — nothing of the host, the platform, the process or the
time goes into it.
`boojum_tpu/__init__.py`, `bench.py`, `conftest.py`,
`scripts/multihost_worker.py`, `prover/aot.py` and `chip_smoke.py` all
come through `enable()`; the AOT bundle build's scoped redirect
(`prover/aot._redirected_cache`) is the one deliberate exception.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable(min_compile_time_secs: float = 1.0) -> str | None:
    """Apply the rule and the persistence thresholds; return the directory
    in use (None when BOOJUM_TPU_NO_COMPILE_CACHE opts out)."""
    import jax

    if os.environ.get("BOOJUM_TPU_NO_COMPILE_CACHE"):
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_time_secs
    )
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
