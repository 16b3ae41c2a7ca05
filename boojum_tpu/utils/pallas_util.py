"""Shared helpers for Pallas TPU kernels.

The framework enables `jax_enable_x64` globally (the field is 64-bit), which
makes BlockSpec index maps trace as i64 — Mosaic only legalizes i32 index
computations. `imap32` wraps an index map so every returned coordinate is cast
back to int32.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp


_FORCE_XLA = [False]
_LOCAL_OPERANDS = [False]


class force_xla:
    """Context manager pinning dispatchers to the XLA path (used while
    tracing GSPMD-sharded graphs, which pallas_call cannot partition)."""

    def __enter__(self):
        self._prev = _FORCE_XLA[0]
        _FORCE_XLA[0] = True
        return self

    def __exit__(self, *exc):
        _FORCE_XLA[0] = self._prev
        return False


class local_operands:
    """Trace-time marker that the dispatchers below are seeing per-chip
    LOCAL blocks — the bodies of parallel/shard_sweep.py's shard_map
    graphs enter it while they trace, so `pallas_enabled` can skip its
    active-mesh veto (that veto exists for PLAIN jits over mesh-sharded
    operands, which GSPMD cannot hand to a pallas_call). Same idiom as
    `force_xla`; force_xla still wins when both are active."""

    def __enter__(self):
        self._prev = _LOCAL_OPERANDS[0]
        _LOCAL_OPERANDS[0] = True
        return self

    def __exit__(self, *exc):
        _LOCAL_OPERANDS[0] = self._prev
        return False


def pallas_enabled(opt_in_env: str | None = None) -> bool:
    """True when the fused TPU kernels should be used.

    Requires the TPU backend, no active prover mesh (the sharded pipeline
    keeps plain XLA ops so GSPMD can partition them — pallas_call does not
    split under a NamedSharding; shard_map bodies announce their per-chip
    blocks via `local_operands` and keep the kernels), and no
    BOOJUM_TPU_PALLAS=0 override. With `opt_in_env`, additionally requires
    that env var to be "1" (used by kernels that currently trail the XLA
    path and are opt-in)."""
    if opt_in_env is not None and os.environ.get(opt_in_env, "0") != "1":
        return False
    if _FORCE_XLA[0]:
        return False
    from .transfer import env_flag

    if not env_flag("BOOJUM_TPU_PALLAS", True):
        return False
    if jax.default_backend() != "tpu":
        return False
    if _LOCAL_OPERANDS[0]:
        return True
    from ..parallel.sharding import active_mesh

    return active_mesh() is None


def tpu_compiler_params(vmem_limit_bytes: int):
    """The Mosaic compiler parameters carrying a scoped-VMEM limit.
    Shared by the Poseidon2 / limb-sweep / MXU-NTT kernel modules."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes)


def pick_tile(R: int, budget_rows: int) -> int:
    """A legal Mosaic tile for a row axis of R sublane rows: divides R
    (grid = R // tile must cover every output row — a non-divisor would
    silently leave trailing rows unwritten) AND is a multiple of 8 or R
    itself (the sublane block rule). Whole-R blocks are always legal.
    (Shared by the Poseidon2 and limb-sweep kernel families.)"""
    if R <= budget_rows:
        return R
    best = None
    t = 8
    while t <= min(R, budget_rows):
        if R % t == 0:
            best = t
        t *= 2
    if best is None:
        raise ValueError(
            f"no legal tile for R={R} (need R % 8 == 0 when R exceeds the "
            f"VMEM row budget {budget_rows})"
        )
    return best


def _to_i32(v):
    if isinstance(v, int):
        return jnp.int32(v)
    return jax.lax.convert_element_type(v, jnp.int32)


def imap32(fn):
    def wrapped(*args):
        out = fn(*args)
        if not isinstance(out, tuple):
            out = (out,)
        return tuple(_to_i32(v) for v in out)

    return wrapped
