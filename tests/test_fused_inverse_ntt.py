"""The commits' inverse transform above the single-kernel ceiling as the
matmul kernel (ISSUE 40): `monomial_from_values_p` hands a chunk of values
in NATURAL order to `mxu_ntt._inv_radix_planes`, whose radix-2^k stage is
the last k outer radix-2 stages, k = 1 at twice the ceiling and 2 from four
times up; one further stage trails it in a program of its own.

Interpret mode, with the ceiling lowered to the smallest kernel (2^14, as
tests/test_fused_forward_ntt.py) so that 2^15, 2^16 and 2^17 rows stand for
2^17, 2^18 and 2^19. The reference is `_imono_p_jit`, the XLA stages the
kernel replaced, word for word.
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
    a[..., 1::5] = gl.P - 1
    return a


def _planes(a):
    lo, hi = limbs.split_np(a)
    return jnp.asarray(lo), jnp.asarray(hi)


def _join(p):
    return np.asarray(limbs.join(p))


@pytest.fixture
def lowered_ceiling(monkeypatch):
    """The MXU path as the TPU takes it, at a size interpret mode affords:
    ceiling 2^14, the dispatcher steered to the kernel from the test, the
    kernel itself in interpret mode."""
    kernel, program = mxu_ntt._inv_radix_planes, LN._imono_p_fused
    monkeypatch.setattr(mxu_ntt, "MAX_LOG_N", CEILING)
    monkeypatch.setattr(LN, "_mxu_ntt_ready", lambda n, ctx: True)
    monkeypatch.setattr(
        mxu_ntt, "_inv_radix_planes",
        lambda p, k, trailing, interpret: kernel(p, k, trailing, True),
    )
    yield
    program.clear_cache()  # traced under the lowered ceiling


@pytest.fixture
def counters():
    reg = metrics.MetricsRegistry()
    previous = metrics.install_registry(reg)
    yield reg.counters
    metrics.install_registry(previous)


def _from_digits(d):
    """(8, ..) balanced base-256 digit planes -> canonical u64."""
    d = np.asarray(d).astype(np.int64)
    v = sum(d[k].astype(object) << (8 * k) for k in range(8))
    return (v % gl.P).astype(np.uint64)


@pytest.mark.parametrize("k,trailing", [(1, 0), (2, 0), (2, 1)])
def test_constant_matrices_are_the_plain_inverse_dft(k, trailing):
    """No bit reversal is folded anywhere: the two matrices are
    omega_C^(-jc tc) and n^-1 omega_R^(-jr tr) as they stand, and the
    tables the exponents of the kernel's header."""
    ctx = mxu_ntt.get_mxu_ctx(CEILING)
    R, C, G, H = ctx.R, ctx.C, 1 << k, 1 << trailing
    e, f, tw1, tw2 = mxu_ntt._inv_radix_consts(CEILING, k, trailing)
    n_sub = G * R * C
    w_inv = gl.inv(gl.omega(CEILING + k))
    i = np.arange(C, dtype=np.int64)
    wc = gl.powers_np(gl.pow_(w_inv, G * R), C)  # omega_C^-1
    assert np.array_equal(_from_digits(e), wc[(i[:, None] * i[None, :]) % C])
    wr = gl.powers_np(gl.pow_(w_inv, G * C), R)
    scaled = gl.mul_np(
        wr[(i[:, None] * i[None, :]) % R], np.uint64(gl.inv(n_sub * H))
    )
    assert np.array_equal(_from_digits(f), scaled)
    t1, t2 = _join(tw1), _join(tw2)
    assert t1.shape == (G - 1, C, 128) and t2.shape == (G, C, R * H)
    for m, jc, tr, q, h in [(1, 3, 5, G - 1, H - 1), (G - 1, C - 1, R - 1, 1, 0)]:
        assert t1[m - 1, jc, 77] == gl.pow_(w_inv, R * m * jc)
        assert t2[q, jc, tr * H + h] == gl.pow_(w_inv, tr * (q * C + jc))


@pytest.mark.parametrize("k", [1, 2])
def test_radix_stage_is_two_tables_around_an_inverse_butterfly(k):
    """`radix_inverse_stage` against its definition: part g times w1[g],
    the size-2^k inverse DFT across the parts at every index (omega_4 =
    2^48), result q times w2[q]; `p - 1` in every operand."""
    G = 1 << k
    z = _rand((G, 8, 128), 10 + k)
    w1, w2 = _rand((G - 1, 8, 128), 20 + k), _rand((G, 8, 128), 30 + k)
    w1[:, 0], w2[:, 0] = gl.P - 1, gl.P - 1
    root_inv = gl.inv(gl.pow_(1 << 48, 4 // G))  # omega_G^-1
    a = [z[0]] + [gl.mul_np(z[g], w1[g - 1]) for g in range(1, G)]
    want = []
    for q in range(G):
        acc = np.zeros_like(z[0], dtype=object)
        for g in range(G):
            acc += gl.mul_np(
                a[g], np.uint64(gl.pow_(root_inv, g * q))
            ).astype(object)
        want.append(gl.mul_np((acc % gl.P).astype(np.uint64), w2[q]))

    def pairs(x):
        lo, hi = _planes(x)
        return [(lo[g], hi[g]) for g in range(x.shape[0])]

    got = mxu_ntt.radix_inverse_stage(pairs(z), pairs(w1), pairs(w2))
    assert len(got) == G
    for q in range(G):
        assert np.array_equal(_join(got[q]), want[q]), q


def test_trailing_program_is_the_last_dit_stage():
    """`_imono_p_trailing` on the monomials of a column's even and odd
    values against `dit_stages_p`'s last stage over the two halves."""
    log_n, b = 12, 3
    n = 1 << log_n
    parts = _rand((b, 2, 8, n // 16), 40)
    want = LN.dit_stages_p(
        _planes(parts.reshape(b, n)), LN.PlaneNTTContext(log_n),
        log_n - 1, log_n,
    )
    got = LN._imono_p_trailing(_planes(parts))
    assert got[0].shape == (b, n)
    assert np.array_equal(_join(got), _join(want))


def test_the_transform_is_the_inverse_dft_by_definition(lowered_ceiling):
    """A few monomials of one column against n^-1 sum v[t] omega^(-t m)."""
    log_n = CEILING + 2
    n = 1 << log_n
    v = _rand((1, n), 45)
    got = _join(LN.monomial_from_values_p(_planes(v)))[0]
    w_inv = gl.inv(gl.omega(log_n))
    t = np.arange(n, dtype=np.int64)
    for m in (0, 1, 129, n // 4 + 7, n - 1):
        row = gl.powers_np(gl.pow_(w_inv, m), n)
        total = int(gl.mul_np(v[0], row).astype(object).sum() % gl.P)
        assert int(got[m]) == gl.mul(total, gl.inv(n)), m


@pytest.mark.parametrize("stages", [1, 2, 3])
@pytest.mark.parametrize("cols", [1, 2, 5])
def test_monomials_word_for_word(lowered_ceiling, counters, stages, cols):
    """`monomial_from_values_p` above the ceiling against `_imono_p_jit`:
    k = 1, k = 2, and k = 2 with one trailing stage; one chunk."""
    n = 1 << (CEILING + stages)
    p = _planes(_rand((cols, n), 50 + stages + cols))
    want = LN._imono_p_jit(p)
    got = LN.monomial_from_values_p(p)
    assert got[0].shape == (cols, n)
    assert np.array_equal(_join(got), _join(want))
    assert counters["ntt.fused_inverse_stages"] == min(stages, 2) * cols
    assert counters["ntt.trailing_outer_stages"] == (stages == 3) * cols


@pytest.mark.parametrize("stages", [1, 3])
def test_monomials_in_column_chunks(lowered_ceiling, counters, monkeypatch,
                                    stages):
    """A stack wider than its chunk: 64 columns and a remainder of 3, each
    chunk one dispatch that cuts its columns from the whole stack (and its
    trailing stage's)."""
    cols, n = 67, 1 << (CEILING + stages)
    monkeypatch.setattr(ntt, "_NTT_CHUNK_BUDGET", 64 * n * 8)
    assert LN.imono_chunks(cols, n) == {0: 64, 64: 3}
    p = _planes(_rand((cols, n), 60 + stages))
    want = LN._imono_p_jit(p)
    calls = []
    fused = LN._imono_p_fused
    monkeypatch.setattr(
        LN, "_imono_p_fused",
        lambda q, start, size: (
            calls.append((q[0].shape, size)) or fused(q, start, size)
        ),
    )
    got = LN.monomial_from_values_p(p)
    assert calls == [((cols, n), 64), ((cols, n), 3)]
    assert np.array_equal(_join(got), _join(want))
    assert counters["ntt.fused_inverse_stages"] == min(stages, 2) * cols
    assert counters["ntt.trailing_outer_stages"] == (stages == 3) * cols


def _programs_run(monkeypatch, shapes_only=False):
    """Record which of the inverse's programs a call dispatches (and, with
    `shapes_only`, evaluate them abstractly)."""
    ran = []
    for name in ("_imono_p_jit", "_imono_p_fused", "_imono_p_trailing"):
        fn = getattr(LN, name)

        def spy(*a, _fn=fn, _name=name):
            ran.append((_name, a[0][0].shape))
            return jax.eval_shape(_fn, *a) if shapes_only else _fn(*a)

        monkeypatch.setattr(LN, name, spy)
    return ran


@pytest.mark.parametrize("log_n", [CEILING, CEILING + 4])
def test_at_the_ceiling_and_past_the_reach_the_xla_form(
    lowered_ceiling, counters, monkeypatch, log_n
):
    """Up to 2^MAX_LOG_N rows, and past what the radix stage and one
    trailing stage reach, a chunk is `_imono_p_jit` as before; the counter
    reads 0: present, so that a cell without the mechanism says so."""
    n = 1 << log_n
    assert not LN.inverse_is_own_program(n)
    ran = _programs_run(monkeypatch)
    p = _planes(_rand((2, n), 70))
    got = LN.monomial_from_values_p(p)
    assert ran == [("_imono_p_jit", (2, n))]
    want = ntt._monomial_from_values_jit(limbs.join(p))
    assert np.array_equal(_join(got), np.asarray(want))
    assert counters["ntt.fused_inverse_stages"] == 0
    assert counters["ntt.trailing_outer_stages"] == 0


def test_off_the_tpu_the_xla_form(counters, monkeypatch):
    """Where the MXU transforms are not native (here: the CPU) every size
    is `_imono_p_jit`, chunk for chunk."""
    n = 1 << 17
    assert not LN.inverse_is_own_program(n)
    monkeypatch.setattr(ntt, "_NTT_CHUNK_BUDGET", 2 * n * 8)
    ran = _programs_run(monkeypatch, shapes_only=True)
    monkeypatch.setattr(
        LN, "_assemble_chunks_p",
        lambda shape, produce, starts: [produce(i) for i in starts],
    )
    zeros = jnp.zeros((5, n), jnp.uint32)
    LN.monomial_from_values_p((zeros, zeros))
    assert ran == [("_imono_p_jit", (b, n)) for b in (2, 2, 1)]
    assert counters["ntt.fused_inverse_stages"] == 0


def test_a_tracer_gets_the_xla_form(lowered_ceiling, monkeypatch):
    """`shard_sweep._mono_fn_p` traces `monomial_from_values_p` inside
    `shard_map`: the kernel's program is dispatched on its own, so a
    tracer takes the XLA stages, at every size."""
    ran = _programs_run(monkeypatch)
    n = 1 << (CEILING + 2)
    out = jax.eval_shape(LN.monomial_from_values_p, LN.sdsp(3, n))
    assert out[0].shape == (3, n)
    assert ran == [("_imono_p_jit", (3, n))]


def test_stages_by_size():
    assert [mxu_ntt.fused_outer_stages(m) for m in (14, 16, 17, 18, 19)] \
        == [0, 0, 1, 2, 2]
    assert [mxu_ntt.leading_outer_stages(m) for m in (14, 16, 17, 18, 19, 20)] \
        == [0, 0, 0, 0, 1, 2]


# the columns a prove hands `commit_pipeline_p` (witness, stage 2; the
# quotient's monomials come from `quotient_interp_p`), the rows, and the two
# counters over one prove
ERA_SHAPES = {
    "poseidon2-era.closed-tree64k": ((130, 38), 18, 336, 0),
    "recursive-verifier.closed-aggregate": ((130, 38), 18, 336, 0),
    "keccak256-era.closed-2k": ((155, 62), 18, 434, 0),
    "keccak256-era-512k.closed-12k": ((155, 62), 19, 434, 217),
    "sha256-lde8.closed-8k": ((93, 46), 16, 0, 0),
}


@pytest.mark.parametrize("cell", sorted(ERA_SHAPES))
def test_counters_at_the_cells_shapes(cell, counters, monkeypatch):
    """`ntt.fused_inverse_stages` and `ntt.trailing_outer_stages` over one
    prove's commits. Shapes only: the programs are abstractly evaluated,
    the chunk walk is the library's."""
    commits, log_n, fused, trailing = ERA_SHAPES[cell]
    n = 1 << log_n

    def shapes_only(program, static):
        def run(*a):
            dyn = a[: len(a) - static]
            return jax.eval_shape(
                lambda *d: program(*d, *a[len(dyn):]), *dyn
            )
        return run

    monkeypatch.setattr(LN, "_mxu_ntt_ready", lambda n, ctx: True)
    for name, static in (
        ("_imono_p_fused", 1), ("_imono_p_trailing", 0), ("_imono_p_jit", 0)
    ):
        monkeypatch.setattr(LN, name, shapes_only(getattr(LN, name), static))
    monkeypatch.setattr(
        LN, "_assemble_chunks_p",
        lambda shape, produce, starts: [produce(i) for i in starts],
    )
    for B in commits:
        LN.monomial_from_values_p(LN.sdsp(B, n))
    assert counters["ntt.fused_inverse_stages"] == fused
    assert counters["ntt.trailing_outer_stages"] == trailing


@pytest.mark.parametrize("log_n,parts", [
    (17, ["fused"]), (18, ["fused"]), (19, ["fused", "trailing"]),
])
def test_library_lists_what_a_dispatch_runs(monkeypatch, log_n, parts):
    """The enumeration above 2^16 rows: for each size of chunk the fused
    program on the WHOLE stack (its slice is inside), the trailing program
    on what it returns only above 2^18 rows, and no `_imono_p_jit`."""
    monkeypatch.setattr(LN, "_mxu_ntt_ready", lambda n, ctx: True)
    n = 1 << log_n
    per = (128 << 20) // (n * 8)
    B = 2 * per + 2
    specs = LN.plane_ntt_kernel_specs(B, log_n)
    assert [s[0] for s in specs] == [
        f"imono_kernel_limbres_b{b}_n{n}:{part}"
        for b in (2, per) for part in parts
    ]
    for name, fn, args in specs:
        assert "imono_p" in fn.__name__  # `families.json`: the commit's
        out = jax.eval_shape(fn, *args)
        if name.endswith(":fused"):
            assert args[0][0].shape == (B, n)
            b = args[2]
            staged = out
        if name.endswith(parts[-1]):
            assert out[0].shape == (b, n)
        else:
            assert out[0].shape == (b, 2, 256, 1024)
        if name.endswith(":trailing"):
            assert args[0][0].shape == staged[0].shape
    one = LN.plane_ntt_kernel_specs(per, log_n)
    assert [s[2][1:3] for s in one if s[0].endswith(":fused")] == [(None, None)]
    below = LN.plane_ntt_kernel_specs(B, 16)
    assert [s[1] for s in below] == [LN._imono_p_jit] * len(below)
