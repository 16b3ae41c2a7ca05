"""Limb-domain quotient sweep + FRI fold (ISSUE 4).

The tentpole moved the quotient-stage cores and the FRI fold off emulated
uint64 onto fused u32-limb Pallas kernels (`prover/pallas_sweep.py`, algebra
in `field/limb_ops.py`). These tests pin, on the CPU backend (kernels in
interpret mode):

- u64<->limb parity of every limb op `field/limb_ops.py` adds, over
  randomized inputs INCLUDING boundary values near p and non-canonical
  2^64-1 words (base ops mirror the u64 algorithms bit-for-bit even on
  non-canonical inputs; ext ops are canonical-domain);
- per-kernel parity of the standalone sweep wrappers (gate terms, copy
  permutation, both lookup modes, FRI fold) against the u64 stage cores,
  across tiled and non-tiled domain sizes. The kernels take and return
  (lo, hi) planes; the tests split the inputs and join the outputs.

The whole-prove parity of the plane pipeline is tests/test_limb_resident.py
(slow lane).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from boojum_tpu.field import extension as ext_f
from boojum_tpu.field import gl
from boojum_tpu.field import goldilocks as gf
from boojum_tpu.field import limb_ops as lop
from boojum_tpu.field import limbs

# values that stress every carry/borrow/canonicalization branch: around 0,
# around p, around the 2^32 limb seam, and the non-canonical top band
BOUNDARY = np.array(
    [
        0, 1, 2, 7,
        0xFFFFFFFF, 0x100000000, 0x100000001,
        gl.P - 2, gl.P - 1, gl.P, gl.P + 1,
        0xFFFFFFFF00000000, 2**64 - 2, 2**64 - 1,
    ],
    dtype=np.uint64,
)


def _full_range(rng, size):
    """Random u64 (incl. non-canonical >= p) with the boundary set mixed in."""
    x = rng.integers(0, 2**64, size=size, dtype=np.uint64)
    take = min(len(BOUNDARY), size)
    x[:take] = BOUNDARY[:take]
    return jnp.asarray(rng.permutation(x))


def _canonical(rng, size):
    x = rng.integers(0, gl.P, size=size, dtype=np.uint64)
    canon_boundary = BOUNDARY[BOUNDARY < gl.P]
    take = min(len(canon_boundary), size)
    x[:take] = canon_boundary[:take]
    return jnp.asarray(rng.permutation(x))


def _j(pair):
    return np.asarray(limbs.join(pair))


def _s(x):
    return limbs.split(x)


# ---------------------------------------------------------------------------
# Property parity: base-field limb ops (non-canonical inputs included)
# ---------------------------------------------------------------------------


def test_base_op_parity_full_range():
    """limbs mirrors goldilocks op-for-op, so parity holds BITWISE even on
    non-canonical inputs (both emulations walk the same wrap/borrow
    fixups)."""
    rng = np.random.default_rng(1)
    x = _full_range(rng, 257)
    y = _full_range(rng, 257)
    for name, lfn, ufn in [
        ("add", limbs.add, gf.add),
        ("sub", limbs.sub, gf.sub),
        ("mul", limbs.mul, gf.mul),
    ]:
        np.testing.assert_array_equal(
            _j(lfn(_s(x), _s(y))), np.asarray(ufn(x, y)), err_msg=name
        )
    for name, lfn, ufn in [
        ("neg", limbs.neg, gf.neg),
        ("double", limbs.double, gf.double),
        ("sqr", limbs.sqr, gf.sqr),
    ]:
        np.testing.assert_array_equal(
            _j(lfn(_s(x))), np.asarray(ufn(x)), err_msg=name
        )


def test_mul_small_and_powers_parity():
    rng = np.random.default_rng(2)
    x = _full_range(rng, 129)
    for k in (0, 1, 2, 3, 7, 12, 255):
        np.testing.assert_array_equal(
            _j(lop.mul_small(_s(x), k)),
            np.asarray(gf.mul_small(x, k)),
            err_msg=f"mul_small k={k}",
        )
    xc = _canonical(rng, 65)
    pows = lop.powers(_s(xc), 6)
    acc = jnp.ones_like(xc)
    for j, p in enumerate(pows):
        np.testing.assert_array_equal(_j(p), np.asarray(acc), err_msg=f"p^{j}")
        acc = gf.mul(acc, xc)


def test_horner_parity():
    rng = np.random.default_rng(3)
    x = _canonical(rng, 130)
    coeffs = [_canonical(rng, 130) for _ in range(5)]
    got = _j(lop.horner([_s(c) for c in coeffs], _s(x)))
    ref = jnp.zeros_like(x)
    for c in reversed(coeffs):
        ref = gf.add(gf.mul(ref, x), c)
    np.testing.assert_array_equal(got, np.asarray(ref))


def test_broadcast_helpers():
    rng = np.random.default_rng(4)
    x = _s(_canonical(rng, 33))
    np.testing.assert_array_equal(_j(lop.zeros_like(x)), np.zeros(33))
    np.testing.assert_array_equal(_j(lop.ones_like(x)), np.ones(33))
    v = gl.P - 5
    np.testing.assert_array_equal(_j(lop.full_like(x, v)), np.full(33, v))
    # const_ext bakes reduced numpy scalars
    c = lop.const_ext(gl.P + 3, 2**64 - 1)
    assert int(limbs.join((jnp.uint32(c[0][0]), jnp.uint32(c[0][1])))) == 3
    assert (
        int(limbs.join((jnp.uint32(c[1][0]), jnp.uint32(c[1][1]))))
        == (2**64 - 1) % gl.P
    )


# ---------------------------------------------------------------------------
# Property parity: GF(p^2) limb ops (canonical domain)
# ---------------------------------------------------------------------------


def _rand_ext(rng, size):
    return (_canonical(rng, size), _canonical(rng, size))


def _sx(e):
    return lop.ext_split(e)


def _jx(e):
    c0, c1 = lop.ext_join(e)
    return np.asarray(c0), np.asarray(c1)


def _assert_ext_equal(got, ref, msg=""):
    g0, g1 = _jx(got) if isinstance(got[0], tuple) else (
        np.asarray(got[0]), np.asarray(got[1])
    )
    np.testing.assert_array_equal(g0, np.asarray(ref[0]), err_msg=msg)
    np.testing.assert_array_equal(g1, np.asarray(ref[1]), err_msg=msg)


def test_ext_op_parity():
    rng = np.random.default_rng(5)
    a = _rand_ext(rng, 131)
    b = _rand_ext(rng, 131)
    base = _canonical(rng, 131)
    _assert_ext_equal(limbs.ext_add(_sx(a), _sx(b)), ext_f.add(a, b), "add")
    _assert_ext_equal(limbs.ext_sub(_sx(a), _sx(b)), ext_f.sub(a, b), "sub")
    _assert_ext_equal(limbs.ext_mul(_sx(a), _sx(b)), ext_f.mul(a, b), "mul")
    _assert_ext_equal(lop.ext_neg(_sx(a)), ext_f.neg(a), "neg")
    _assert_ext_equal(lop.ext_sqr(_sx(a)), ext_f.sqr(a), "sqr")
    _assert_ext_equal(
        lop.ext_mul_by_base(_sx(a), _s(base)),
        ext_f.mul_by_base(a, base),
        "mul_by_base",
    )


def test_ext_powers_and_horner_parity():
    rng = np.random.default_rng(6)
    g = _rand_ext(rng, 1)
    pows = lop.ext_powers(_sx(g), 5)
    acc = (jnp.ones_like(g[0]), jnp.zeros_like(g[1]))
    for j, p in enumerate(pows):
        _assert_ext_equal(p, acc, f"g^{j}")
        acc = ext_f.mul(acc, g)
    x = _rand_ext(rng, 67)
    coeffs = [_rand_ext(rng, 67) for _ in range(4)]
    got = lop.ext_horner([_sx(c) for c in coeffs], _sx(x))
    ref = ext_f.zeros(x[0].shape)
    for c in reversed(coeffs):
        ref = ext_f.add(ext_f.mul(ref, x), c)
    _assert_ext_equal(got, ref, "ext_horner")


def test_accumulate_parity():
    from boojum_tpu.prover.stages import accumulate_ext, accumulate_ext_ext

    rng = np.random.default_rng(7)
    term_b = _canonical(rng, 68)
    term_e = _rand_ext(rng, 68)
    ch = _rand_ext(rng, 1)
    acc0 = _rand_ext(rng, 68)
    # base-term accumulate, from None and from a live accumulator
    _assert_ext_equal(
        lop.accumulate(None, _s(term_b), _sx(ch)),
        accumulate_ext(None, term_b, ch),
        "accumulate None",
    )
    _assert_ext_equal(
        lop.accumulate(_sx(acc0), _s(term_b), _sx(ch)),
        accumulate_ext(acc0, term_b, ch),
        "accumulate",
    )
    _assert_ext_equal(
        lop.ext_accumulate(_sx(acc0), _sx(term_e), _sx(ch)),
        accumulate_ext_ext(acc0, term_e, ch),
        "ext_accumulate",
    )


def test_aggregate_columns_parity():
    from boojum_tpu.prover.stages import (
        _ext_powers_traced,
        aggregate_lookup_columns,
    )

    rng = np.random.default_rng(8)
    cols = [_canonical(rng, 69) for _ in range(3)]
    tid = _canonical(rng, 69)
    g = (jnp.uint64(11), jnp.uint64(13))
    beta = (jnp.uint64(17), jnp.uint64(19))
    gpow_u64 = _ext_powers_traced(g, 4)
    ref = aggregate_lookup_columns(cols, tid, gpow_u64, beta)
    got = lop.aggregate_columns(
        [_s(c) for c in cols],
        _s(tid),
        [_sx(p) for p in gpow_u64],
        _sx((beta[0], beta[1])),
    )
    _assert_ext_equal(got, ref, "aggregate_columns")
    # table_id_col=None branch
    ref2 = aggregate_lookup_columns(cols, None, gpow_u64, beta)
    got2 = lop.aggregate_columns(
        [_s(c) for c in cols], None, [_sx(p) for p in gpow_u64], _sx(beta)
    )
    _assert_ext_equal(got2, ref2, "aggregate_columns no-tid")


# ---------------------------------------------------------------------------
# Per-kernel parity: standalone sweep wrappers vs the u64 stage cores
# ---------------------------------------------------------------------------


def _rnd(rng, *s):
    return jnp.asarray(rng.integers(0, gl.P, s, dtype=np.uint64))


# jitted like the prover dispatches them, but compiled without XLA:CPU's
# fusion emitters: with them (jax 0.9.0) the limb cores compile in 17 s
# and then RUN for over half an hour at n=96; without, 15 s and 3 ms
# (CHANGES.md PR 24). Integer arithmetic: the results are the same.
_jit = functools.partial(
    jax.jit, compiler_options={"xla_cpu_use_fusion_emitters": False}
)
# a permutation-sized gate unrolls to some 10^5 limb operations, over which
# XLA:CPU's default pipeline takes five minutes; without LLVM's
# optimisation passes, under two (here, 8 cores). Same integers.
_jit_unoptimised = functools.partial(
    jax.jit,
    compiler_options={
        "xla_cpu_use_fusion_emitters": False,
        "xla_backend_optimization_level": 0,
        "xla_llvm_disable_expensive_passes": True,
    },
)


# 256 exercises the tiled pallas path (R=2 sublane rows); 96 the
# non-tiled plain-XLA fallback of the same cores
@pytest.mark.parametrize("n", [256, 96])
def test_cp_quotient_kernel_parity(n):
    from boojum_tpu.prover import pallas_sweep as ps
    from boojum_tpu.prover.stages import _cp_quotient_core, chunk_columns

    rng = np.random.default_rng(10)
    C = 7
    chunks = tuple(tuple(c) for c in chunk_columns(C, 4))
    z = (_rnd(rng, n), _rnd(rng, n))
    zs = (_rnd(rng, n), _rnd(rng, n))
    partials = [(_rnd(rng, n), _rnd(rng, n)) for _ in range(len(chunks) - 1)]
    copy, sigma = _rnd(rng, C, n), _rnd(rng, C, n)
    xs, l0 = _rnd(rng, n), _rnd(rng, n)
    b = (jnp.uint64(3), jnp.uint64(5))
    g = (jnp.uint64(7), jnp.uint64(11))
    a0, a1 = _rnd(rng, 1 + len(chunks)), _rnd(rng, 1 + len(chunks))
    ks = tuple(int(x) for x in rng.integers(1, gl.P, C, dtype=np.uint64))
    ref = _cp_quotient_core(
        z, zs, partials, copy, sigma, xs, l0, b, g, a0, a1, chunks, ks
    )
    got = _jit(lambda *a: ps.cp_quotient(*a, chunks, ks))(
        _sx(z), _sx(zs), [_sx(p) for p in partials], _s(copy), _s(sigma),
        _s(xs), _s(l0), b, g, a0, a1
    )
    _assert_ext_equal(got, ref, f"cp n={n}")


@pytest.mark.parametrize("general", [False, True])
def test_lookup_quotient_kernel_parity(general):
    from boojum_tpu.prover import pallas_sweep as ps
    from boojum_tpu.prover.stages import (
        _lookup_quotient_core,
        _lookup_quotient_core_general,
    )

    rng = np.random.default_rng(11)
    n, R, w = 256, 3, 4
    a_ldes = [(_rnd(rng, n), _rnd(rng, n)) for _ in range(R)]
    b_lde = (_rnd(rng, n), _rnd(rng, n))
    cols, tid = _rnd(rng, R * w, n), _rnd(rng, n)
    tbl, mult = _rnd(rng, w + 1, n), _rnd(rng, n)
    b = (jnp.uint64(3), jnp.uint64(5))
    g = (jnp.uint64(7), jnp.uint64(11))
    a0, a1 = _rnd(rng, R + 1), _rnd(rng, R + 1)
    if general:
        sel = _rnd(rng, n)
        ref = _lookup_quotient_core_general(
            a_ldes, b_lde, cols, tid, tbl, mult, sel, b, g, a0, a1, R, w
        )
        got = _jit(lambda *a: ps.lookup_quotient_general(*a, R, w))(
            [_sx(a) for a in a_ldes], _sx(b_lde), _s(cols), _s(tid),
            _s(tbl), _s(mult), _s(sel), b, g, a0, a1
        )
    else:
        ref = _lookup_quotient_core(
            a_ldes, b_lde, cols, tid, tbl, mult, b, g, a0, a1, R, w
        )
        got = _jit(lambda *a: ps.lookup_quotient(*a, R, w))(
            [_sx(a) for a in a_ldes], _sx(b_lde), _s(cols), _s(tid),
            _s(tbl), _s(mult), b, g, a0, a1
        )
    _assert_ext_equal(got, ref, f"lookup general={general}")


def _fma_case(rng, n):
    from boojum_tpu.cs.gates import FmaGate
    from boojum_tpu.examples import EXAMPLE_GEOMETRY as geom

    return FmaGate.instance(), geom, _rnd(rng, 8, n), _rnd(rng, 6, n)


def _poseidon2_flat_case(rng, n):
    """Upstream's flattened Poseidon2 gate on the Era geometry: one
    permutation a row of 130 columns, 2,036 field operations, 118 terms."""
    from boojum_tpu.cs.gates import Poseidon2FlattenedGate
    from boojum_tpu.cs.types import CSGeometry

    geom = CSGeometry(
        num_columns_under_copy_permutation=130,
        num_witness_columns=0,
        num_constant_columns=8,
        max_allowed_constraint_degree=7,
    )
    return (
        Poseidon2FlattenedGate.instance(), geom,
        _rnd(rng, 130, n), _rnd(rng, 8, n),
    )


@pytest.mark.parametrize("case,scan_threshold,jit", [
    pytest.param(_fma_case, None, _jit, id="None"),
    pytest.param(_fma_case, 1, _jit, id="1"),
    pytest.param(
        _poseidon2_flat_case, None, _jit_unoptimised, id="poseidon2_flat"
    ),
])
def test_gate_terms_kernel_parity(case, scan_threshold, jit, monkeypatch):
    """The limb kernel traces every gate directly, small or
    permutation-sized (the form the chip chose, PERF.md PR 32); the u64
    sweep it is held to replays a gate past the scan threshold under
    lax.scan: threshold 1 sends even the 3-op FMA program through that
    replay, and the flattened Poseidon2 gate (2,036 operations on 256
    random rows) goes through it at the default threshold."""
    from boojum_tpu.cs.gate_capture import _PACKED_CACHE
    from boojum_tpu.prover import pallas_sweep as ps
    from boojum_tpu.prover.stages import _build_gate_sweep

    if scan_threshold is not None:
        monkeypatch.setenv("BOOJUM_TPU_SCAN_GATE_THRESHOLD", str(scan_threshold))
    saved = dict(_PACKED_CACHE)
    try:
        rng = np.random.default_rng(12)
        n = 256
        gate, geom, copy, const = case(rng, n)
        gates = (gate,)
        paths = ((),)
        terms = gate.num_repetitions(geom) * gate.num_terms
        a0, a1 = _rnd(rng, terms), _rnd(rng, terms)
        ref = _build_gate_sweep(gates, paths, geom)(copy, None, const, a0, a1)
        limb_fn = ps.gate_terms_fn(gates, paths, geom)
        got = jit(lambda c, k, tb: limb_fn(c, None, k, tb))(
            _s(copy), _s(const), ps._pack_table(a0, a1)
        )
        _assert_ext_equal(
            got, ref, f"gate {gate.name} threshold={scan_threshold}"
        )
    finally:
        _PACKED_CACHE.clear()
        _PACKED_CACHE.update(saved)


@pytest.mark.parametrize("m", [512, 64])
def test_fri_fold_kernel_parity(m):
    from boojum_tpu.prover import pallas_sweep as ps
    from boojum_tpu.prover.fri import _ch_table_np, _fold_once_jit
    from boojum_tpu.prover.stages import ext_scalar

    rng = np.random.default_rng(13)
    vals = (_rnd(rng, m), _rnd(rng, m))
    invx = _rnd(rng, m // 2)
    ch = tuple(int(v) for v in rng.integers(0, gl.P, 2, dtype=np.uint64))
    ref = _fold_once_jit(vals, ext_scalar(ch), invx)
    got = _jit(ps.fri_fold_planes)(
        _sx(vals), jnp.asarray(_ch_table_np(ch)), _s(invx)
    )
    _assert_ext_equal(got, ref, f"fold m={m}")
