"""The forward NTT above the single-kernel ceiling as ONE program a chunk
(ISSUE 35): the coset row and the outer radix-2 DIF stages are a radix-2^k
prologue of the matmul kernel (`mxu_ntt._fwd_radix_planes`), k = 1 at
twice the ceiling and 2 from four times up; further leading stages stay in
the `outer` program before it.

Interpret mode, with the ceiling lowered to the smallest kernel (2^14, as
tests/test_pallas_kernels.py's hybrid tests do) so that 2^15, 2^16 and
2^17 rows stand for 2^17, 2^18 and 2^19. The reference is the staged u64
transform of `ntt.py`, word for word.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from boojum_tpu.field import gl, limbs
from boojum_tpu.ntt import limb_ntt as LN
from boojum_tpu.ntt import mxu_ntt, ntt
from boojum_tpu.utils import metrics

CEILING = 14


def _rand(shape, seed):
    a = np.random.default_rng(seed).integers(
        0, gl.P, size=shape, dtype=np.uint64
    )
    a[..., 0] = gl.P - 1  # max limbs through every butterfly
    return a


def _planes(a):
    lo, hi = limbs.split_np(a)
    return jnp.asarray(lo), jnp.asarray(hi)


def _join(p):
    return np.asarray(limbs.join(p))


@pytest.fixture
def lowered_ceiling(monkeypatch):
    """The MXU path as the TPU takes it, at a size interpret mode affords:
    ceiling 2^14, the dispatcher steered to the kernels from the test, the
    kernel itself in interpret mode."""
    fused = mxu_ntt._fwd_radix_planes
    monkeypatch.setattr(mxu_ntt, "MAX_LOG_N", CEILING)
    monkeypatch.setattr(LN, "_mxu_ntt_ready", lambda n, ctx: True)
    monkeypatch.setattr(
        mxu_ntt, "_fwd_radix_planes",
        lambda p, s, k, interpret: fused(p, s, k, True),
    )
    programs = [*LN._NTT_FORWARD, *LN._LDE_FORWARD, *LN._COSET_EVAL_FORWARD]
    yield
    for program in programs:  # traced under the lowered ceiling
        program.clear_cache()


@pytest.fixture
def counters():
    reg = metrics.MetricsRegistry()
    previous = metrics.install_registry(reg)
    yield reg.counters
    metrics.install_registry(previous)


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("scaled", [True, False])
def test_prologue_is_the_row_and_k_dif_stages(k, scaled):
    """`radix_prologue` on the 2^k parts of a column against
    `dif_stages_p` over the whole column: the same 2^k blocks, in the
    order the matmul kernel transforms them."""
    log_n, parts = CEILING + k, 1 << k
    n = 1 << log_n
    ctx = mxu_ntt.get_mxu_ctx(CEILING)
    x, s = _rand((n,), 40 + k), _rand((n,), 50 + k)
    staged = gl.mul_np(x, s) if scaled else x
    want = LN.dif_stages_p(_planes(staged), LN.PlaneNTTContext(log_n), 0, k)

    def blocks(a):
        lo, hi = _planes(a.reshape(parts, ctx.R, ctx.C))
        return [(lo[g], hi[g]) for g in range(parts)]

    wl, wh = mxu_ntt._radix_tables(CEILING, k)
    tables = [(wl[m], wh[m]) for m in range(parts - 1)]
    got = mxu_ntt.radix_prologue(
        blocks(x), blocks(s) if scaled else None, tables
    )
    assert len(got) == parts
    got = _join((
        jnp.stack([g[0] for g in got]), jnp.stack([g[1] for g in got])
    ))
    assert np.array_equal(got.reshape(n), _join(want))


def test_the_fourth_root_of_unity_is_a_shift():
    """i = omega_n^(n/4) = 2^48 in Goldilocks: `_mul_i` multiplies by it
    with shifts and one reduction, at the limbs' extremes too."""
    x = np.array(
        [0, 1, gl.P - 1, (1 << 32) - 1, 1 << 32, 1 << 63, gl.P - (1 << 32)],
        dtype=np.uint64,
    )
    assert gl.pow_(gl.omega(18), 1 << 16) == 1 << 48
    want = gl.mul_np(x, np.uint64(1 << 48))
    assert np.array_equal(_join(mxu_ntt._mul_i(_planes(x))), want)


@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("cols", [1, 2, 5])
def test_coset_evaluation_under_one_row(lowered_ceiling, counters, k, cols):
    """`scaled_fft_p`: (cols, n) monomials under one row, one chunk."""
    n = 1 << (CEILING + k)
    a, row = _rand((cols, n), 60 + k), _rand((n,), 70 + cols)
    want = ntt.fft_natural_to_bitreversed_xla(
        jnp.asarray(gl.mul_np(a, row[None]))
    )
    got = LN.scaled_fft_p(_planes(a), _planes(row), cols * n * 8)
    assert np.array_equal(_join(got), np.asarray(want))
    assert counters["ntt.fused_outer_stages"] == min(k, 2) * cols


def test_coset_evaluation_in_column_chunks(lowered_ceiling, counters):
    """A group wider than its chunk: 64 columns and a remainder of 3,
    each chunk one dispatch that cuts its columns from the whole group."""
    cols, n = 67, 1 << (CEILING + 1)
    a, row = _rand((cols, n), 80), _rand((n,), 81)
    assert LN.scaled_fft_chunks(cols, n, 64 * n * 8) == {0: 64, 64: 3}
    want = ntt.fft_natural_to_bitreversed_xla(
        jnp.asarray(gl.mul_np(a, row[None]))
    )
    got = LN.scaled_fft_p(_planes(a), _planes(row), 64 * n * 8)
    assert np.array_equal(_join(got), np.asarray(want))
    assert counters["ntt.fused_outer_stages"] == cols


@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("L", [2, 8])
def test_lde_under_L_rows(lowered_ceiling, counters, k, L):
    """`lde_from_monomial_p` above the ceiling: (cols, n) -> (cols, L, n),
    the kernel's grid over (columns, rows)."""
    cols, n = 3, 1 << (CEILING + k)
    a = _rand((cols, n), 90 + k + L)
    want = ntt._lde_from_monomial_jit(jnp.asarray(a), L)
    got = LN.lde_from_monomial_p(_planes(a), L)
    assert got[0].shape == (cols, L, n)
    assert np.array_equal(_join(got), np.asarray(want))
    assert counters["ntt.fused_outer_stages"] == min(k, 2) * cols * L


@pytest.mark.parametrize("k", [1, 2, 3])
def test_transform_without_a_row(lowered_ceiling, counters, k):
    """`fft_natural_to_bitreversed_p` above the ceiling: the same kernel
    without the row's products, any leading shape."""
    n = 1 << (CEILING + k)
    a = _rand((2, 2, n), 100 + k)
    want = ntt.fft_natural_to_bitreversed_xla(jnp.asarray(a))
    got = LN.fft_natural_to_bitreversed_p(_planes(a))
    assert np.array_equal(_join(got), np.asarray(want))
    assert counters["ntt.fused_outer_stages"] == min(k, 2) * 4


def test_at_the_ceiling_nothing_is_fused(counters, monkeypatch):
    """Up to 2^MAX_LOG_N rows the LDE is `mxu_ntt._lde_planes` as before
    and the counter reads 0: present, so that a cell without the
    mechanism says so."""
    assert [mxu_ntt.fused_outer_stages(m) for m in (14, 16, 17, 18, 19, 22)] \
        == [0, 0, 1, 2, 2, 2]
    monkeypatch.setattr(LN, "_mxu_ntt_ready", lambda n, ctx: True)
    lde = mxu_ntt._lde_planes
    monkeypatch.setattr(
        mxu_ntt, "_lde_planes", lambda c, s, log_n, _: lde(c, s, log_n, True)
    )
    a = _rand((2, 1 << CEILING), 110)
    want = ntt._lde_from_monomial_jit(jnp.asarray(a), 2)
    got = LN.lde_from_monomial_p(_planes(a), 2)
    assert np.array_equal(_join(got), np.asarray(want))
    assert counters["ntt.fused_outer_stages"] == 0


@pytest.mark.parametrize("form", ["row", "rows", "no row"])
def test_every_form_refuses_a_tracer(form):
    """The fused program is dispatched on its own, a chunk at a time: a
    caller's jit may not hold it (PERF.md, Open question 10)."""
    n = 1 << 18
    p = LN.sdsp(2, n)
    scale = {"row": LN.sdsp(n), "rows": LN.sdsp(2, n), "no row": None}[form]

    def traced(q, s):
        return LN._hybrid_fwd_p(q, 18, LN._COSET_EVAL_FORWARD, s)

    with pytest.raises(TypeError, match="a device program of its own"):
        jax.eval_shape(traced, p, scale)


# the three Era cells' round-3 groups (witness, setup, stage 2) and the
# columns of the commits a prove makes (witness, stage 2, quotient)
ERA_SHAPES = {
    "poseidon2-era.closed-tree64k": ((130, 138, 38), (130, 38, 16), 4440),
    "recursive-verifier.closed-aggregate": (
        (130, 138, 38), (130, 38, 16), 4440),
    # 167 setup columns (`shape_bucket(...).B_setup`; ISSUE 35 reckoned 166
    # and 5,560): the chip's own counter reads 5,572 (PERF.md, PR 35)
    "keccak256-era.closed-2k": ((155, 167, 62), (155, 62, 16), 5572),
}


@pytest.mark.parametrize("cell", sorted(ERA_SHAPES))
def test_counter_at_the_era_shapes(cell, counters, monkeypatch):
    """`ntt.fused_outer_stages` over one prove's dispatches at 2^18 rows,
    LDE 2 under an 8-coset quotient: 6 cosets transform the three groups,
    all 8 the shifted z's two columns, and three commits extend their
    columns to L = 2: twice the column transforms. Shapes only: the
    programs are abstractly evaluated, the chunk walks are the library's."""
    from boojum_tpu.ntt.ntt import _col_chunks
    from boojum_tpu.prover import resident as RES

    groups, commits, expect = ERA_SHAPES[cell]
    n, L, Q = 1 << 18, 2, 8

    def shapes_only(program):
        def run(p, scale, start, size, log_n):
            return jax.eval_shape(
                lambda *a: program(*a, size, log_n), p, scale, start
            )
        return run

    monkeypatch.setattr(LN, "_mxu_ntt_ready", lambda n, ctx: True)
    for name in ("_COSET_EVAL_FORWARD", "_LDE_FORWARD"):
        monkeypatch.setattr(
            LN, name, tuple(map(shapes_only, getattr(LN, name)))
        )
    monkeypatch.setattr(
        LN, "_assemble_chunks_p",
        lambda shape, produce, starts: [produce(i) for i in starts],
    )
    for B in groups * (Q - L) + (2,) * Q:
        LN.scaled_fft_p(LN.sdsp(B, n), LN.sdsp(n), RES._SWEEP_EVAL_CHUNK)
    for B in commits:
        per = _col_chunks(B, n * 8 * L) or B  # `lde_from_monomial_p`'s walk
        for i in range(0, B, per):
            chunk = LN.sdsp(min(per, B - i), n)
            LN._lde_one_p(chunk, L, int(gl.MULTIPLICATIVE_GENERATOR))
    assert counters["ntt.fused_outer_stages"] == expect


@pytest.mark.parametrize("log_n,parts", [
    (17, ["fused"]), (18, ["fused"]), (19, ["outer", "fused"]),
])
def test_library_lists_the_fused_program(monkeypatch, log_n, parts):
    """The enumeration above 2^16 rows: one fused program for each size of
    chunk, the outer program before it only above 2^18 rows, and neither a
    `:scale` nor, at 2^17 and 2^18, an `:outer` entry."""
    from boojum_tpu.prover import resident as RES

    monkeypatch.setattr(LN, "_mxu_ntt_ready", lambda n, ctx: True)
    n = 1 << log_n
    per = RES._SWEEP_EVAL_CHUNK // (n * 8)
    B = 2 * per + 2
    names = [s[0] for s in RES.coset_eval_kernel_specs("wit", B, n, 8)]
    stem = "coset_eval_wit_limbres"
    assert names == [f"{stem}:row"] + [
        f"{stem}:fft_b{b}:{part}" for b in (2, per) for part in parts
    ]
    lde = LN.plane_ntt_kernel_specs(8, log_n, 2, mono=False)
    assert [s[0] for s in lde] == [
        f"lde_hybrid_limbres_b8_n{n}_L2:{part}" for part in parts
    ]
    for _name, fn, args in lde:
        out = jax.eval_shape(fn, *args)
        assert out[0].shape == (8, 2, n)
    for _name, fn, _args in lde + RES.coset_eval_kernel_specs("wit", B, n, 8):
        assert ("lde_planes" in fn.__name__) != ("coset_eval" in fn.__name__)
