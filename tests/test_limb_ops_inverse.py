"""The blocked Montgomery inversion (ISSUE 33): `limb_ops.batch_inverse`
and `ext_batch_inverse` on planes against the host's `gl.inv` and against
the u64 `gf.batch_inverse` / `ext_f.batch_inverse`, word for word, at
every kind of plan; and the plan's own multiplication count at the shapes
the four benchmark cells' libraries enumerate."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from boojum_tpu.field import blocked_inverse as bi
from boojum_tpu.field import extension as ext_f
from boojum_tpu.field import gl
from boojum_tpu.field import goldilocks as gf
from boojum_tpu.field import limb_ops as lop
from boojum_tpu.field import limbs

# the prover dispatches these as top-level jits; XLA:CPU's fusion emitters
# run the u32 limb cores for half an hour (tests/test_limb_sweep.py), and
# the Fermat chain compiles in 4 s a shape without LLVM's optimisation
# passes, 10-13 s with them: same integers
_jit = functools.partial(
    jax.jit,
    compiler_options={
        "xla_cpu_use_fusion_emitters": False,
        "xla_backend_optimization_level": 0,
        "xla_llvm_disable_expensive_passes": True,
    },
)
_binv_p = _jit(lop.batch_inverse)
_ext_binv_p = _jit(lop.ext_batch_inverse)

FLOOR = bi._FERMAT_FLOOR

# (shape, what plan it takes): from 2 through the direct-Fermat floor, one,
# two and three levels above it (the cells' 2^14 to 2^19 take two or three), lengths no chain length divides, and the
# batches the prover stacks: 1-D tables, DEEP's (2, N), the lookup's
# (R_args + 1 = 9, n), Keccak's 32 public inputs
SHAPES = [
    ((2,), "direct"),
    ((9, 7), "direct"),
    ((2, FLOOR), "direct"),
    ((FLOOR + 1,), "one level, padded"),
    ((32, 64), "one level"),
    ((9, 1000), "one level, padded"),
    ((2, 1024), "one level"),
    ((2048,), "two levels"),
    ((2, 3001), "two levels, padded"),
    ((9, 4096), "two levels"),
    ((32, 2048), "two levels"),
    ((2, 1 << 14), "two levels"),
    ((1 << 17,), "three levels"),
]


def test_shapes_cover_the_plans():
    def levels(n):
        c = bi.chain_length(n)
        return 0 if c == 1 else 1 + levels(-(-n // c))

    want = {"direct": 0, "one": 1, "two": 2, "three": 3}
    for shape, plan in SHAPES:
        assert levels(shape[-1]) == want[plan.split()[0]], (shape, plan)
        padded = shape[-1] % bi.chain_length(shape[-1]) != 0
        assert padded == ("padded" in plan), (shape, plan)


def _rand(rng, shape):
    return rng.integers(1, gl.P, size=shape, dtype=np.uint64)


def _planes(a):
    lo, hi = limbs.split_np(a)
    return jnp.asarray(lo), jnp.asarray(hi)


def _join(p):
    return limbs.join_np(np.asarray(p[0]), np.asarray(p[1]))


def _host_sample(rng, size, k=64):
    return rng.choice(size, size=min(k, size), replace=False)


@pytest.mark.parametrize("shape", [s for s, _ in SHAPES], ids=str)
def test_batch_inverse_planes(shape):
    rng = np.random.default_rng(abs(hash(shape)) % (1 << 31))
    a = _rand(rng, shape)
    got = _join(_binv_p(_planes(a)))
    # the u64 instance of the same routine, and through it every element
    want = np.asarray(gf.batch_inverse(jnp.asarray(a)))
    assert np.array_equal(got, want)
    assert np.all(np.asarray(gf.mul(jnp.asarray(a), jnp.asarray(got))) == 1)
    # the host's own Fermat inverse, none of the device code
    flat_a, flat_g = a.reshape(-1), got.reshape(-1)
    for i in _host_sample(rng, flat_a.size):
        assert int(flat_g[i]) == gl.inv(int(flat_a[i]))


# the extension's inverse is the same routine on the norms: one shape a plan
@pytest.mark.parametrize(
    "shape", [(9, 7), (FLOOR + 1,), (2, 1024), (9, 4096), (32, 2048),
              (2, 1 << 14)], ids=str,
)
def test_ext_batch_inverse_planes(shape):
    rng = np.random.default_rng(1 + abs(hash(shape)) % (1 << 31))
    c0, c1 = _rand(rng, shape), _rand(rng, shape)
    got = _ext_binv_p((_planes(c0), _planes(c1)))
    g0, g1 = _join(got[0]), _join(got[1])
    w0, w1 = ext_f.batch_inverse((jnp.asarray(c0), jnp.asarray(c1)))
    assert np.array_equal(g0, np.asarray(w0))
    assert np.array_equal(g1, np.asarray(w1))
    # (c0 + c1 w)(g0 + g1 w) = 1 with w^2 = 7, on the host
    f = [x.reshape(-1) for x in (c0, c1, g0, g1)]
    for i in _host_sample(rng, f[0].size, 16):
        x0, x1, y0, y1 = (int(v[i]) for v in f)
        re = gl.add(gl.mul(x0, y0), gl.mul(7, gl.mul(x1, y1)))
        im = gl.add(gl.mul(x0, y1), gl.mul(x1, y0))
        assert (re, im) == (1, 0)


# ---------------------------------------------------------------------------
# The work bound: what `field.batch_inverse_muls` adds for a call
# ---------------------------------------------------------------------------

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
# cell -> (configuration, trace length, public inputs): the traffic fixes
# the trace length (PERF.md section 4) and the circuit the public inputs
CELLS = {
    "sha256-lde8.closed-8k": ("sha256-lde8", 1 << 16, 0),
    "sha256-lde8.closed-1k": ("sha256-lde8", 1 << 14, 0),
    "keccak256-era.closed-2k": ("keccak256-era", 1 << 18, 32),
    "poseidon2-era.closed-tree64k": ("poseidon2-era", 1 << 18, 4),
}


def inversion_shapes(n, L, num_chunks, lookup_args, num_pi, final_degree):
    """The shapes `precompile.enumerate_kernels` gives the inversions of a
    prove ("binv" in the name): per prove the chunk denominators, the
    lookup's, DEEP's two and the public inputs'; once a process the
    domain and one fold table a FRI fold."""
    N = n * L
    per_prove = [(num_chunks, n), (2, N)]
    if lookup_args:
        per_prove.append((lookup_args + 1, n))
    if num_pi:
        per_prove.append((num_pi, N))
    folds = (n // final_degree).bit_length() - 1
    cached = [(N,)] + [(N >> (r + 1),) for r in range(folds)]
    return per_prove, cached


def _cell_shapes(cell):
    name, n, num_pi = CELLS[cell]
    with open(os.path.join(_BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    p, pc = cfg["circuit"]["params"], cfg["proof_config"]
    lookup_args = int(p.get("lookup_args", 0))
    under_copy = int(p["copy_columns"]) + lookup_args * int(
        p.get("lookup_width", 0)
    )
    num_chunks = -(-under_copy // int(p["constraint_degree"]))
    return inversion_shapes(
        n, int(pc["fri_lde_factor"]), num_chunks, lookup_args, num_pi,
        int(pc["fri_final_degree"]),
    )


def test_inversion_shapes_are_the_librarys():
    """`inversion_shapes` against the enumeration itself, on the shared
    2^10 circuit (8 copy columns at degree 4: 2 chunks; one public input;
    no lookups)."""
    from proving import fma_assembly, small_config

    from boojum_tpu.prover import enumerate_kernels

    asm, cfg = fma_assembly(), small_config()
    got = set()
    for s in enumerate_kernels(asm, cfg):
        if "binv" in s.name:
            ref = s.args[0]
            got.add(tuple((ref[0] if isinstance(ref, tuple) else ref).shape))
    per_prove, cached = inversion_shapes(
        asm.trace_len, cfg.fri_lde_factor, 2, 0, 1, cfg.fri_final_degree
    )
    assert got == set(per_prove) | set(cached)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_planned_muls_at_most_four_an_element(cell):
    """Montgomery's trick needs 3 multiplications an element; the plan may
    spend 4 at every shape a prove of the cell inverts, and over the
    tables a process inverts once taken together (a fold table of 32
    points cannot: its one Fermat chain is 125)."""
    per_prove, cached = _cell_shapes(cell)
    for shape in per_prove:
        elements = int(np.prod(shape))
        assert lop.batch_inverse_muls(shape) <= 4 * elements, shape
        assert lop.batch_inverse_muls(shape) >= 3 * elements, shape
    for shape in cached:
        if shape[-1] >= 1 << 13:
            assert lop.batch_inverse_muls(shape) <= 4 * shape[-1], shape
    assert sum(lop.batch_inverse_muls(s) for s in cached) <= 4 * sum(
        s[-1] for s in cached
    )


def test_counted_dispatch_counts_the_plan():
    from boojum_tpu.utils import metrics

    shape = (3, 4096)
    z = jnp.zeros(shape, jnp.uint32)
    reg = metrics.MetricsRegistry()
    token = metrics.install_scoped_registry(reg)
    try:
        assert lop.counted(lambda a: a, (z, z)) == (z, z)
        lop.counted(lambda a: a, ((z, z), (z, z)))
    finally:
        metrics.reset_scoped_registry(token)
    assert reg.counters["field.batch_inverse_muls"] == 2 * (
        lop.batch_inverse_muls(shape)
    )
