"""Compile the main path's kernels for a DESCRIBED TPU v5e, with
`interpret=False`, at the SHA-256 2^16 shapes (60 copy columns + 8 width-4
lookups: 93 witness columns, LDE 8, so 2^19 leaves).

The chip's compiler is installed here and compiles for a chip that is not
attached: what it would refuse on the machine (a slice not aligned to the
tiling, more VMEM or SMEM than a kernel may use) it refuses in this file,
at no chip time. Nothing runs; a compile that passes is not a chip run.

This is the ONLY test file that describes the chip, and it does so inside
a fixture: only one process may load the TPU library, pytest-xdist workers
each import every test file, and a module that touched libtpu at import
would leave the workers with different collections. The whole-oracle
kernels that take minutes (`*:leaf_digests_limbres`, `node_layers_limbres`,
the L=8 sponge at a 256-row tile) belong to
scripts/chip_compile_rehearsal.py, not here.
"""

import os
import sys

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

LOG_N = 16           # SHA-256 8 kB trace
LDE = 8
LEAVES = (1 << LOG_N) * LDE
COPY_COLS = 60


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """An entry compiled for an absent chip is written to the persistent
    cache but cannot be read back: keep it off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _u32(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)


def _pair(one_chip, *shape):
    s = _u32(one_chip, *shape)
    return (s, s)


def _compile(fn, *args):
    txt = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt, "no Mosaic kernel in the compiled module"


def _jit_static(fn, *static):
    """A jitted wrapper fixing a kernel entry point's static arguments, so
    `.lower` takes only the array shapes."""
    return jax.jit(lambda *arrays: fn(*arrays, *static))


# 64 / 128: column blocks of the oracles' leaves; 16: the quotient's
# leaves (2 chunks)
@pytest.mark.parametrize("L", [64, 128, 16])
def test_poseidon2_leaf_sponge(one_chip, L):
    """The leaf sponge over 2^19 leaves of an L-wide column block, at the
    grid step the kernel's one rule picks for that shape."""
    from boojum_tpu.hashes import pallas_poseidon2 as p2

    R = LEAVES // 128
    chunks = L // 8
    v = _u32(one_chip, L, R, 128)
    _compile(
        _jit_static(p2._sponge_planes, chunks, p2.step_rows(chunks, R), False),
        v, v,
    )


def test_poseidon2_node_sponge(one_chip):
    """The first node layer under 2^19 leaves: 2^18 nodes, each one chunk
    (two digests), through the same sponge."""
    from boojum_tpu.hashes import pallas_poseidon2 as p2

    R = LEAVES // 2 // 128
    v = _u32(one_chip, 8, R, 128)
    _compile(_jit_static(p2._sponge_planes, 1, p2.step_rows(1, R), False), v, v)


def test_poseidon2_node_permutation(one_chip):
    """The bare permutation: 2^19 states as (12, 4096, 128) planes."""
    from boojum_tpu.hashes import pallas_poseidon2 as p2

    R = LEAVES // 128
    s = _u32(one_chip, 12, R, 128)
    _compile(_jit_static(p2._permute_planes, p2.step_rows(1, R), False), s, s)


def _mxu_planes(one_chip, lead):
    from boojum_tpu.ntt import mxu_ntt

    ctx = mxu_ntt.get_mxu_ctx(LOG_N)
    return _pair(one_chip, *lead, ctx.R, ctx.C)


def test_mxu_ntt_forward(one_chip):
    from boojum_tpu.ntt import mxu_ntt

    planes = _mxu_planes(one_chip, (COPY_COLS,))
    _compile(
        jax.jit(lambda p: mxu_ntt._fft_planes(p, LOG_N, False)), planes
    )


def test_mxu_ntt_inverse(one_chip):
    from boojum_tpu.ntt import mxu_ntt

    planes = _mxu_planes(one_chip, (COPY_COLS,))
    _compile(
        jax.jit(lambda p: mxu_ntt._ifft_planes(p, LOG_N, False)), planes
    )


def test_mxu_fused_lde(one_chip):
    from boojum_tpu.ntt import mxu_ntt

    coeffs = _mxu_planes(one_chip, (COPY_COLS,))
    scale = _mxu_planes(one_chip, (LDE,))
    _compile(
        jax.jit(lambda c, s: mxu_ntt._lde_planes(c, s, LOG_N, False)),
        coeffs, scale,
    )


def test_fri_fold(one_chip):
    """The first (largest) FRI fold: 2^19 ext values to 2^18."""
    from boojum_tpu.prover import pallas_sweep

    values = (_pair(one_chip, LEAVES), _pair(one_chip, LEAVES))
    table = _u32(one_chip, 4, 1)
    inv_x = _pair(one_chip, LEAVES // 2)
    _compile(
        jax.jit(
            lambda v, t, x: pallas_sweep.fri_fold_planes(
                v, t, x, interpret=False
            )
        ),
        values, table, inv_x,
    )


# the lookup's (R_args + 1, n) and DEEP's (2, N) of this geometry, and the
# Era cell's 32 public inputs over 2^19 points
@pytest.mark.parametrize("shape", [(9, 1 << LOG_N), (2, LEAVES), (32, LEAVES)])
def test_batch_inverse_keeps_the_batch_off_the_lanes(one_chip, shape):
    """The blocked inversion (ISSUE 33) holds one temporary or two the size
    of its input: with the batch axis of a `(B, n)` plane on the 128 lanes,
    as the log-doubling scan it replaced had it, a (9, 2^18) inverse held
    1,030 MiB of temporaries beside 32 MiB of input. No Pallas here: it is
    plain XLA, chain steps over whole (8, 128) tiles."""
    from boojum_tpu.field import limb_ops as lop

    compiled = lop.batch_inverse_jit.lower(_pair(one_chip, *shape)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 2 * mem.argument_size_in_bytes


def test_fused_limb_coset_sweep(one_chip, monkeypatch):
    """`coset_sweep_terms_limbres` of upstream's SHA-256 circuit: gates,
    copy-permutation and the 8 lookup arguments fused over 93 witness and
    106 setup columns — the kernel most likely to meet the VMEM or SMEM
    limit. The spec comes from the program's own enumeration; the 1 kB
    message synthesizes in seconds and has the 8 kB circuit's geometry
    (the sweep closes over structure only), so its row count is scaled
    from 2^14 to the 8 kB trace's 2^16 here. The dispatchers are steered
    to the native set from the test, not through an option of the
    program."""
    from boojum_tpu.examples import build_sha256_bench_circuit
    from boojum_tpu.prover import ProofConfig, enumerate_kernels

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    asm = build_sha256_bench_circuit(1024).into_assembly()
    n = asm.trace_len
    assert n == 1 << 14
    cfg = ProofConfig(
        fri_lde_factor=LDE, merkle_tree_cap_size=16, num_queries=50,
        pow_bits=0, fri_final_degree=16,
    )
    (spec,) = [
        s for s in enumerate_kernels(asm, cfg)
        if s.name == "coset_sweep_terms_limbres"
    ]
    scale = (1 << LOG_N) // n

    def place(x):
        if not isinstance(x, jax.ShapeDtypeStruct):
            return x
        shape = tuple(d * scale if d % n == 0 else d for d in x.shape)
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=one_chip)

    args = jax.tree.map(place, spec.args)
    assert args[0][0].shape == (93, 1 << LOG_N)
    _compile(spec.fn, *args)


@pytest.mark.parametrize("label,widths,log_n,L", [
    ("sha256-lde8 2^16", (93, 105, 46), 16, 8),
    ("keccak256-era 2^18", (155, 166, 62), 18, 2),
])
def test_round3_pick_of_the_committed_cosets(one_chip, label, widths, log_n, L):
    """ISSUE 27: one coset of the three committed storages read in one
    program, at the cells' widths: the chip's compiler takes the dynamic
    slice along the row axis of the (B, L n) planes and writes exactly the
    coset's bytes (no relayout of the storage, nothing held besides)."""
    from boojum_tpu.prover import prover as P

    n = 1 << log_n
    oracles = tuple(_pair(one_chip, B, L * n) for B in widths)
    c = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = P._coset_eval_pick.lower(oracles, c, n).compile()
    mem = compiled.memory_analysis()
    # the planes' (8, 128) tiling pads each group's columns to eights
    tiled = sum(-(-B // 8) * 8 for B in widths) * n * 8
    assert mem.output_size_in_bytes - tiled < 1 << 16, label
    assert mem.temp_size_in_bytes == 0, label


# the Era cells' chunks at 2^18 rows: 64 columns of a coset evaluation cut
# from a 130-column group under one row; 32 columns of a commit under the
# two rows of LDE 2
@pytest.mark.parametrize("form", ["coset_eval", "lde"])
def test_fused_forward_ntt_at_the_era_chunks(one_chip, form):
    """ISSUE 35: the forward transform of 2^18 rows as one program a chunk:
    the matmul kernel with the rows and two outer radix-2 stages as its
    radix-4 prologue, the chunk's slice and the relayouts to and from the
    kernel's (blocks, 256, 256) inside the same program; it lowers and
    compiles for the chip and holds no more than a chunk beside its
    arguments and result."""
    from boojum_tpu.ntt import limb_ntt as LN

    n = 1 << 18
    if form == "coset_eval":
        start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        program = LN._COSET_EVAL_FORWARD[1]
        args = (_pair(one_chip, 130, n), _pair(one_chip, n), start, 64, 18)
    else:
        program = LN._LDE_FORWARD[1]
        args = (_pair(one_chip, 32, n), _pair(one_chip, 2, n), None, None, 18)
    compiled = program.lower(*args).compile()
    txt = compiled.as_text()
    assert txt.count("custom_call_target=\"tpu_custom_call\"") == 1
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >> 20 == 128  # (64, n) or (32, 2, n) x 2
    assert mem.temp_size_in_bytes <= mem.output_size_in_bytes + (1 << 20)


# the commits' chunks above 2^16 rows: 64 columns cut from the tree cell's
# 130-column witness at 2^18 rows; a whole 32-column chunk at 2^19
@pytest.mark.parametrize("B,b,log_n", [(130, 64, 18), (32, 32, 19)])
def test_fused_inverse_ntt_at_the_era_chunks(one_chip, B, b, log_n):
    """ISSUE 40: the commits' inverse transform as one program a chunk: the
    inverse matmul kernel on the values as they lie, the chunk's slice and
    the relayouts to and from the kernel's (b, 256, 2^k 256 h) inside the
    same program, and at 2^19 rows the trailing stage as a program of its
    own; each lowers and compiles for the chip with one Mosaic call (the
    trailing program none) and holds no temporary beyond a chunk."""
    from boojum_tpu.ntt import limb_ntt as LN
    from boojum_tpu.ntt import mxu_ntt

    n = 1 << log_n
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    call = (None, None) if b == B else (start, b)
    compiled = LN._imono_p_fused.lower(_pair(one_chip, B, n), *call).compile()
    assert compiled.as_text().count("custom_call_target=\"tpu_custom_call\"") == 1
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >> 20 == 128  # (64, 2^18) or (32, 2^19) x 2
    assert mem.temp_size_in_bytes <= mem.output_size_in_bytes + (1 << 20)
    if not mxu_ntt.leading_outer_stages(log_n):
        return
    staged = _pair(one_chip, b, 2, 256, 1024)
    compiled = LN._imono_p_trailing.lower(staged).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >> 20 == 128
    assert mem.temp_size_in_bytes <= mem.output_size_in_bytes + (1 << 20)


def test_blake2s_witness_leaf_program(one_chip):
    """ISSUE 42: the Blake2s leaf hash of the SHA-256 cell's witness oracle
    (93 columns x 2^19 leaves as the LDE planes lie) is plain XLA on word
    vectors: it compiles for the chip with no Mosaic call, writes the
    (2^19, 4) digest planes and holds no copy of the columns (a leaf-major
    transpose or a padded block would be one)."""
    from boojum_tpu.hashes import blake2s as b2s

    B = COPY_COLS + 8 * 4 + 1
    lde = _pair(one_chip, B, LDE, 1 << LOG_N)
    compiled = jax.jit(b2s.leaf_hash_planes).lower(*lde).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    columns = 2 * 4 * B * LEAVES
    assert mem.argument_size_in_bytes == columns
    assert mem.temp_size_in_bytes < columns // 4
