"""The kernel-variant decision (`resolve_variant`, `force_xla`,
`local_operands`, `pallas_enabled`) and shared helpers for Pallas TPU
kernels.

The framework enables `jax_enable_x64` globally (the field is 64-bit), which
makes BlockSpec index maps trace as i64 — Mosaic only legalizes i32 index
computations. `imap32` wraps an index map so every returned coordinate is cast
back to int32.
"""

from __future__ import annotations

import dataclasses
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


@dataclasses.dataclass(frozen=True)
class KernelVariant:
    """Which kernel set a prove dispatches. Resolved in ONE place
    (`resolve_variant`) from what the code observes; `_prove_entry`
    resolves it once a prove and hands it down, `enumerate_kernels`, the
    AOT bundle key and the service cache read the same record.

    representation: "u64" (emulated-uint64 words, plain XLA graphs: the
        CPU default, the tests' reference, every GSPMD and BabyBear
        prove) or "planes" ((lo, hi) u32 limb planes end to end,
        prover/resident.py: the TPU default).
    pallas: the native Pallas/MXU kernels rather than their XLA twins
        (TPU backend, no `force_xla`, not a GSPMD mesh). `pallas_enabled`
        is its trace-time form for the layers below the prover.
    mesh: None, "gspmd" (sequenced rounds, NamedSharding constraints) or
        "shard_map" (fused rounds, per-chip kernels, explicit collectives).
    field: "goldilocks" or "babybear" (field/spec.py)."""

    representation: str
    pallas: bool
    mesh: str | None
    field: str

    @property
    def planes(self) -> bool:
        return self.representation == "planes"

    @property
    def fused(self) -> bool:
        """The fused round graphs (meshless and shard_map); the GSPMD
        rounds stay sequenced: its smaller jits are what GSPMD
        partitions."""
        return self.mesh != "gspmd"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


_ACTIVE = object()


def resolve_variant(mesh=_ACTIVE) -> KernelVariant:
    """THE dispatch decision. Observes the backend, `mesh` (default: the
    mesh `prover_mesh` activated; pass one to ask what `prove(mesh=...)`
    would run without activating it), the active field and `force_xla`,
    plus two overrides: BOOJUM_TPU_MESH_MODE=shard_map|gspmd (unset:
    shard_map on every topology) and BOOJUM_TPU_LIMB_RESIDENT (unset:
    planes on a TPU, u64 elsewhere; =1 runs the plane pipeline on a CPU
    with interpret-mode / XLA limb kernels, which is how the tests reach
    it; =0 keeps u64 words). An unparsable override raises: a typo never
    picks a mode silently."""
    from ..field.spec import active_field
    from .transfer import env_flag_opt

    if mesh is _ACTIVE:
        from ..parallel.sharding import active_mesh

        mesh = active_mesh()
    mode = None
    if mesh is not None:
        mode = os.environ.get("BOOJUM_TPU_MESH_MODE", "").strip().lower()
        mode = {"": "shard_map", "sm": "shard_map"}.get(mode, mode)
        if mode not in ("shard_map", "gspmd"):
            raise ValueError(
                f"BOOJUM_TPU_MESH_MODE={mode!r}: use shard_map or gspmd"
            )
    field = active_field()
    on_tpu = jax.default_backend() == "tpu"
    # GSPMD cannot partition a pallas_call, and the planes have no XLA-
    # partitioned twin; a BabyBear element is one bare u32 lane with no
    # planes to be resident in (its `_bb` kernel set is disjoint)
    native_ok = not _FORCE_XLA[0] and mode != "gspmd"
    explicit = env_flag_opt("BOOJUM_TPU_LIMB_RESIDENT")
    planes = (
        native_ok
        and field == "goldilocks"
        and (on_tpu if explicit is None else explicit)
    )
    return KernelVariant(
        representation="planes" if planes else "u64",
        pallas=native_ok and on_tpu,
        mesh=mode,
        field=field,
    )


def pallas_enabled() -> bool:
    """`resolve_variant().pallas` as the layers that decide at trace time
    see it (Poseidon2 sponges, the NTT dispatcher): additionally False in
    a PLAIN jit over mesh-sharded operands, which GSPMD cannot hand to a
    pallas_call; shard_map bodies announce their per-chip blocks via
    `local_operands` and keep the kernels."""
    if not resolve_variant().pallas:
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
