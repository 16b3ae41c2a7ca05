"""The one proving fixture of tier-1.

Every test file that proves the 2^10 fma acceptance circuit takes circuit,
config, setup and recorded proves from here, so one set of kernel shapes is
compiled once and every other xdist worker finds it in the persistent
compile cache, and every parity test compares against the SAME baseline
prove. State is per process (an xdist worker): `generate_setup` runs once,
each (env, mesh) variant is proved once.

Not a test module and not a plugin: tests import it by name
(`from proving import ...`; pytest puts tests/ on sys.path).
"""

import contextlib
import functools
import os

import pytest

from boojum_tpu.utils import report


def small_config():
    """The smallest honest config: LDE 2, cap 4, 4 queries, final degree
    16 — parity claims are about bytes, not proof strength."""
    from boojum_tpu.prover import ProofConfig

    return ProofConfig(
        fri_lde_factor=2,
        merkle_tree_cap_size=4,
        num_queries=4,
        fri_final_degree=16,
    )


def fma_assembly(log_n=10, seed=0):
    """A full 2^log_n-row fma chain on CSGeometry(8, 0, 6, 4) with one
    public input (`seed` shifts the two start values), synthesized over
    the field that BOOJUM_TPU_FIELD names at the time of the call."""
    return _fma_assembly(log_n, seed, os.environ.get("BOOJUM_TPU_FIELD"))


@functools.lru_cache(maxsize=None)
def _fma_assembly(log_n, seed, _field):
    from boojum_tpu.cs.gates import FmaGate, PublicInputGate
    from boojum_tpu.cs.implementations import ConstraintSystem
    from boojum_tpu.examples import EXAMPLE_GEOMETRY as geom

    cs = ConstraintSystem(geom, 1 << log_n)
    a = cs.alloc_variable_with_value(1 + seed)
    b = cs.alloc_variable_with_value(2 + seed)
    per_row = FmaGate.instance().num_repetitions(geom)
    for _ in range(((1 << log_n) - 8) * per_row):
        a, b = b, FmaGate.fma(cs, a, b, a, 1, 1)
    PublicInputGate.place(cs, b)
    asm = cs.into_assembly()
    assert asm.trace_len == 1 << log_n
    return asm


@functools.lru_cache(maxsize=None)
def small_parts(log_n=10):
    """(assembly, setup, config) of the shared circuit."""
    from boojum_tpu.prover import generate_setup

    asm, config = fma_assembly(log_n), small_config()
    return asm, generate_setup(asm, config), config


def mesh_2x4():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(
        np.array(jax.devices()[:8]).reshape(2, 4), axis_names=("col", "row")
    )


@contextlib.contextmanager
def environ(env):
    """Set the given environment variables for the block."""
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def prove_recorded(label, env=None, mesh=None, parts=None):
    """One fresh prove (never memoised) under `env` with the flight
    recorder on: (proof, ProveReport)."""
    from boojum_tpu.prover import prove

    asm, setup, config = parts or small_parts()
    with environ(env or {}):
        with report.flight_recording(label=label) as rec:
            proof = prove(asm, setup, config, mesh=mesh)
    return proof, report.build_report(rec)


_RUNS = {}


def recorded_prove(label, env, mesh=None):
    """The shared circuit proved under `env` (and `mesh`), once a process
    for each (env, mesh); `recorded_prove("baseline", {})` is the prove
    every parity test compares against."""
    key = (tuple(sorted(env.items())), mesh)
    if key not in _RUNS:
        _RUNS[key] = prove_recorded(label, env, mesh)
    return _RUNS[key]


def baseline():
    return recorded_prove("baseline", {})


# The tests that need a 2^10 prove on the interpret-mode limb kernels
# (BOOJUM_TPU_LIMB_RESIDENT=1, shard_map with it) are
# slow: the program's own jits compile through XLA:CPU's fusion emitters,
# which under jax 0.9.0 run the u32-limb cores for over half an hour a
# kernel (CHANGES.md PR 24). The slow lane sets
# XLA_FLAGS=--xla_cpu_use_fusion_emitters=false; each file says what
# tier-1 keeps of its path.
interpret_e2e = pytest.mark.slow


def checkpoint_stream(rep):
    return [
        (e["seq"], e["round"], e["label"], e["digest"])
        for e in rep["checkpoints"]
    ]
