"""CS synthesis + witness resolution + satisfiability tests (gate-level test
strategy per reference testing_tools.rs harness)."""

import numpy as np

from boojum_tpu.cs.types import CSGeometry
from boojum_tpu.cs.implementations import ConstraintSystem
from boojum_tpu.cs.gates import (
    BooleanConstraintGate,
    ConditionalSwapGate,
    ConstantsAllocatorGate,
    DotProductGate,
    FmaGate,
    PublicInputGate,
    ReductionGate,
    ReductionByPowersGate,
    SelectionGate,
    SimpleNonlinearityGate,
    U32AddGate,
    U32FmaGate,
    U32SubGate,
    ZeroCheckGate,
)
from boojum_tpu.prover.satisfiability import check_if_satisfied
from boojum_tpu.field import gl

GEOM = CSGeometry(
    num_columns_under_copy_permutation=16,
    num_witness_columns=0,
    num_constant_columns=6,
    max_allowed_constraint_degree=4,
)


def fresh_cs(max_len=64):
    return ConstraintSystem(GEOM, max_len)


def test_fma_gate_and_resolver():
    cs = fresh_cs()
    a = cs.alloc_variable_with_value(3)
    b = cs.alloc_variable_with_value(5)
    c = cs.alloc_variable_with_value(7)
    d = FmaGate.fma(cs, a, b, c, 2, 11)
    assert cs.get_value(d) == (2 * 3 * 5 + 11 * 7) % gl.P
    asm = cs.into_assembly()
    assert check_if_satisfied(asm, verbose=True)


def test_deferred_resolution_order():
    cs = fresh_cs()
    a = cs.alloc_variable_without_value()
    b = cs.alloc_variable_without_value()
    # register a resolution depending on unset inputs first
    out = cs.alloc_variable_without_value()
    cs.set_values_with_dependencies([a, b], [out], lambda v: [gl.add(v[0], v[1])])
    cs.resolver.set_value(a, 10)
    assert not cs.resolver.is_resolved(out)
    cs.resolver.set_value(b, 20)
    assert cs.get_value(out) == 30


def test_gate_zoo_satisfiable():
    cs = fresh_cs(256)
    x = cs.alloc_variable_with_value(9)
    y = cs.alloc_variable_with_value(12)
    FmaGate.fma(cs, x, y, x, 1, 1)
    five = ConstantsAllocatorGate.allocate_constant(cs, 5)
    bool_v = cs.alloc_variable_with_value(1)
    BooleanConstraintGate.enforce(cs, bool_v)
    ReductionGate.reduce(cs, [x, y, five, bool_v], [1, 2, 3, 4])
    ReductionByPowersGate.reduce(cs, [x, y, five, bool_v], 1 << 8)
    SelectionGate.select(cs, bool_v, x, y)
    ConditionalSwapGate.swap(cs, bool_v, x, y)
    DotProductGate.dot(cs, [(x, y), (x, x), (y, y), (five, x)])
    ZeroCheckGate.is_zero(cs, x)
    z0 = cs.alloc_variable_with_value(0)
    ZeroCheckGate.is_zero(cs, z0)
    SimpleNonlinearityGate.apply(cs, x, 42)
    a32 = cs.alloc_variable_with_value(0xFFFFFFFF)
    b32 = cs.alloc_variable_with_value(0x12345678)
    zero = cs.zero_var()
    U32AddGate.add(cs, a32, b32, zero)
    U32SubGate.sub(cs, b32, a32, zero)
    U32FmaGate.fma(cs, a32, b32, b32, zero)
    asm = cs.into_assembly()
    assert check_if_satisfied(asm, verbose=True)


def test_unsatisfied_detected():
    cs = fresh_cs()
    a = cs.alloc_variable_with_value(3)
    b = cs.alloc_variable_with_value(5)
    c = cs.alloc_variable_with_value(7)
    d = FmaGate.fma(cs, a, b, c)
    # corrupt the witness after the fact (read first: with the native tape
    # engine the value materializes lazily, and an unflushed write would be
    # overwritten by the flush)
    assert cs.get_value(d) == (3 * 5 + 7)
    cs.resolver.values[d] = 999
    asm = cs.into_assembly()
    assert not check_if_satisfied(asm)


def test_public_input():
    cs = fresh_cs()
    v = cs.alloc_variable_with_value(1234)
    PublicInputGate.place(cs, v)
    asm = cs.into_assembly()
    assert asm.public_inputs == [(0, 0, 1234)] or len(asm.public_inputs) == 1
    assert check_if_satisfied(asm)


def test_row_amortization():
    # 4 fma instances with same constants share one row (16 cols / width 4)
    cs = fresh_cs()
    for _ in range(4):
        a = cs.alloc_variable_with_value(2)
        FmaGate.fma(cs, a, a, a)
    rows_used = cs.next_row
    # one row for fma, plus zero/one constant rows if any
    fma_rows = sum(
        1
        for r in range(rows_used)
        if cs.gates[cs.row_gate[r]].name == "fma"
    )
    assert fma_rows == 1
    asm = cs.into_assembly()
    assert check_if_satisfied(asm)


def test_ext_fma_gate():
    import random

    from boojum_tpu.cs.gates.ext_fma import ExtFmaGate
    from boojum_tpu.field import extension as ext_host

    geom = CSGeometry(16, 0, 6, 4)
    cs = ConstraintSystem(geom, 64)
    rng = random.Random(3)
    a = tuple(cs.alloc_variable_with_value(rng.randrange(gl.P)) for _ in range(2))
    b = tuple(cs.alloc_variable_with_value(rng.randrange(gl.P)) for _ in range(2))
    c = tuple(cs.alloc_variable_with_value(rng.randrange(gl.P)) for _ in range(2))
    d = ExtFmaGate.fma(cs, a, b, c, coeff_ab=(2, 3), coeff_c=(5, 7))
    av = (cs.get_value(a[0]), cs.get_value(a[1]))
    bv = (cs.get_value(b[0]), cs.get_value(b[1]))
    cv = (cs.get_value(c[0]), cs.get_value(c[1]))
    expect = ext_host.add_s(
        ext_host.mul_s(ext_host.mul_s((2, 3), av), bv),
        ext_host.mul_s((5, 7), cv),
    )
    assert (cs.get_value(d[0]), cs.get_value(d[1])) == tuple(expect)
    iv = ExtFmaGate.inversion(cs, a)
    assert ext_host.mul_s(av, (cs.get_value(iv[0]), cs.get_value(iv[1]))) == (1, 0)
    asm = cs.into_assembly()
    assert check_if_satisfied(asm, verbose=True)
    # tamper
    asm.copy_cols_values[6, 0] = (int(asm.copy_cols_values[6, 0]) + 1) % gl.P
    assert not check_if_satisfied(asm)


def test_native_flush_with_far_waiter():
    """A python closure parked on a place beyond the arena capacity must not
    crash the native tape flush (regression: unguarded resolved[p] index)."""
    from boojum_tpu.dag import make_resolver

    r = make_resolver(capacity=16)
    out = 2
    r.add_resolution([100000], [out], lambda v: [v[0] + 1])
    r.set_value(0, 7)  # benign
    # native op -> tape; flush via get_value must not IndexError
    from boojum_tpu.native import OP_CONST, get_lib

    if get_lib() is None:
        return
    r.add_resolution([], [1], lambda _: [5], native=(OP_CONST, (5,)))
    assert r.get_value(1) == 5
    r.set_value(100000, 9)
    assert r.get_value(out) == 10


def test_native_resolver_poison_on_failed_batch():
    """A failed native batch (lookup miss) poisons the resolver: the original
    error surfaces (chained) on every later read instead of a misleading
    'place unresolved' assert."""
    import pytest

    from boojum_tpu.dag import make_resolver
    from boojum_tpu.dag.resolver import NativeTapeResolver
    from boojum_tpu.native import OP_LOOKUP
    from boojum_tpu.examples import xor4_table

    r = make_resolver(capacity=64)
    if not isinstance(r, NativeTapeResolver):
        pytest.skip("native engine unavailable")
    table = xor4_table()
    r.set_value(0, 99)  # not a valid xor4 key (keys are 0..15)
    r.set_value(1, 3)
    r.add_resolution([0, 1], [2], None, native=(OP_LOOKUP, (1,)), table=table)
    with pytest.raises(RuntimeError, match="native"):
        r.get_value(2)
    # subsequent reads surface the poisoning, chained to the root cause
    with pytest.raises(RuntimeError, match="native") as ei:
        r.get_value(2)
    assert ei.value.__cause__ is not None
    with pytest.raises(RuntimeError, match="native"):
        r.wait_till_resolved()
    with pytest.raises(RuntimeError, match="native"):
        r.values_flat(3)


def test_resolution_record_playback():
    """Record/playback of the witness-resolution order (reference
    mt/sorters/sorter_playback.rs): a recorded live run replayed through
    PlaybackResolver reproduces the identical witness with zero dependency
    tracking, and diverging synthesis is detected."""
    from boojum_tpu.cs.types import CSGeometry
    from boojum_tpu.cs.implementations import ConstraintSystem
    from boojum_tpu.cs.gates import FmaGate, ZeroCheckGate
    from boojum_tpu.dag.resolver import PlaybackResolver, WitnessResolver

    geom = CSGeometry(
        num_columns_under_copy_permutation=8,
        num_witness_columns=0,
        num_constant_columns=6,
        max_allowed_constraint_degree=4,
    )

    def synthesize(cs):
        a = cs.alloc_variable_with_value(3)
        b = cs.alloc_variable_with_value(5)
        for _ in range(20):
            a, b = b, FmaGate.fma(cs, a, b, a, 1, 1)
        flag = ZeroCheckGate.is_zero(cs, b)
        return FmaGate.fma(cs, b, b, flag, 1, 1)

    rec_resolver = WitnessResolver()
    rec_resolver.start_recording()
    cs1 = ConstraintSystem(geom, 1 << 10, resolver=rec_resolver)
    out1 = synthesize(cs1)
    asm1 = cs1.into_assembly()  # padding resolutions are part of the record
    record = rec_resolver.resolution_record()
    assert record, "live run must record resolutions"

    cs2 = ConstraintSystem(
        geom, 1 << 10, resolver=PlaybackResolver(record)
    )
    out2 = synthesize(cs2)
    assert cs2.get_value(out2) == cs1.get_value(out1)
    asm2 = cs2.into_assembly()
    import numpy as np

    np.testing.assert_array_equal(asm1.copy_cols_values, asm2.copy_cols_values)

    # diverging synthesis (extra resolutions) must be detected
    cs3 = ConstraintSystem(geom, 1 << 10, resolver=PlaybackResolver(record))
    synthesize(cs3)
    cs3.alloc_variable_with_value(7)
    synthesize(cs3)  # registers resolutions beyond the record
    import pytest

    with pytest.raises(RuntimeError, match="playback divergence"):
        cs3.resolver.wait_till_resolved()


def test_bounded_gate_wrapper():
    """Row-capped placement (reference BoundedGateWrapper / Bounded*
    allocator variants): instances amortize into rows normally, and the
    wrapper rejects placements beyond the row budget."""
    import pytest

    from boojum_tpu.cs.gates import BoundedGateWrapper, FmaGate

    cs = fresh_cs(64)
    bounded = BoundedGateWrapper(FmaGate.instance(), max_rows=2)
    per_row = FmaGate.instance().num_repetitions(GEOM)
    for _ in range(2 * per_row):  # exactly fills the budget
        a = cs.alloc_variable_with_value(2)
        b = cs.alloc_variable_with_value(3)
        c = cs.alloc_variable_with_value(4)
        d = cs.alloc_variable_without_value()
        cs.set_values_with_dependencies(
            [a, b, c], [d], lambda v: [(v[0] * v[1] + v[2]) % gl.P]
        )
        bounded.place(cs, [a, b, c, d], (1, 1))
    # the budget is exactly full: the next placement would open a third
    # row and must be refused BEFORE the CS is mutated
    rows_before = cs.next_row
    a = cs.alloc_variable_with_value(5)
    d = cs.alloc_variable_without_value()
    cs.set_values_with_dependencies(
        [a], [d], lambda v: [(v[0] * v[0] + v[0]) % gl.P]
    )
    with pytest.raises(RuntimeError, match="row budget"):
        bounded.place(cs, [a, a, a, d], (1, 1))
    assert cs.next_row == rows_before  # nothing was placed
    assert check_if_satisfied(cs.into_assembly(), verbose=True)


def test_explicit_constants_allocator_gate():
    """ExplicitConstantsAllocatorGate (reference
    constants_allocator_as_explicit_constraint.rs): allocates 0/1/-1 plus a
    set as baked-literal constraints with ZERO constant columns; proves
    e2e and rejects a tampered constant."""
    from boojum_tpu.cs.gates import (
        ExplicitConstantsAllocatorGate,
        FmaGate,
        PublicInputGate,
    )
    from boojum_tpu.cs.implementations import ConstraintSystem
    from boojum_tpu.examples import EXAMPLE_GEOMETRY
    from boojum_tpu.field import gl
    from boojum_tpu.prover import ProofConfig, generate_setup, prove, verify
    from boojum_tpu.prover.satisfiability import check_if_satisfied

    cs = ConstraintSystem(EXAMPLE_GEOMETRY, 1 << 10)
    table = ExplicitConstantsAllocatorGate.allocate(cs, (5, 1 << 32))
    assert cs.get_value(table[0]) == 0
    assert cs.get_value(table[1]) == 1
    assert cs.get_value(table[gl.P - 1]) == gl.P - 1
    assert cs.get_value(table[5]) == 5
    a = table[5]
    b = table[1 << 32]
    out = a
    for _ in range(300):
        out = FmaGate.fma(cs, out, b, a, 1, 1)
    PublicInputGate.place(cs, out)
    asm = cs.into_assembly()
    assert check_if_satisfied(asm)
    cfg = ProofConfig(fri_lde_factor=4, num_queries=8, fri_final_degree=8)
    setup = generate_setup(asm, cfg)
    proof = prove(asm, setup, cfg)
    assert verify(setup.vk, proof, asm.gates)

    # tamper the allocated constant's witness value -> unsatisfiable
    import numpy as np

    loc = np.argwhere(asm.copy_placement == table[5])
    c, r = loc[0]
    asm.copy_cols_values[c, r] = 6
    assert not check_if_satisfied(asm)
