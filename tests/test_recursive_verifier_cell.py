"""The benchmark configuration `recursive-verifier`, held to its references
on the CPU at a small size (the cell itself, K recorded SHA-256 proofs in
2^18 rows, runs on the chip: benchmark/run.py).

- the builder's path (`benchmark/circuits/recursive_verifier.py::aggregate`)
  with K = 2 over two proofs of the shared 2^10 fixture: satisfiable under
  `check_if_satisfied`, public inputs equal to the builder's own
  Python-integer Poseidon2 digest of the recorded key's cap, which is held
  here to `Poseidon2SpongeHost`; the negative cases: a tampered recorded
  proof makes the builder raise, another key in a slot makes it raise, and
  with the host check stepped over the circuit itself is unsatisfiable;
- a recorded proof and key through the builder's file format and back;
- the verifier's gate set on the limb planes against the u64 sweep on random
  rows, `ConditionalSwapGate` and the selector products included
  (`tests/test_limb_sweep.py` holds the flattened Poseidon2 gate alone);
- what the recorder says of the plan: `stages.gate_sweep_ops_per_row` and
  the gates under the selector tree, and the library `enumerate_kernels`
  lists for this circuit, whose sweep lowers.
"""

import copy
import functools
import importlib.util
import os

import numpy as np
import pytest

from boojum_tpu.cs.types import CSGeometry
from boojum_tpu.field import gl
from boojum_tpu.prover import generate_setup, prove, verify
from boojum_tpu.prover.satisfiability import check_if_satisfied
from boojum_tpu.prover.setup import build_selector_tree
from boojum_tpu.utils import report

from proving import baseline, fma_assembly, small_parts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
GEOMETRY = CSGeometry(
    num_columns_under_copy_permutation=130,
    num_witness_columns=0,
    num_constant_columns=8,
    max_allowed_constraint_degree=7,
)
CAPACITY = 1 << 12
VERIFIER_GATES = [
    "constant", "poseidon2_flat", "fma", "boolean", "reduction4",
    "conditional_swap", "selection", "public_input", "nop",
]


@functools.lru_cache(maxsize=None)
def _builder():
    spec = importlib.util.spec_from_file_location(
        "benchmark_circuit_recursive_verifier",
        os.path.join(BENCH, "circuits", "recursive_verifier.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def inner():
    """(vk, gates, [proof of witness 0, proof of witness 1]) of the shared
    2^10 fma circuit: two proofs another worker made, under one key."""
    asm, setup, config = small_parts()
    proof0, _rep = baseline()
    proof1 = prove(fma_assembly(seed=1), setup, config)
    assert proof0.public_inputs != proof1.public_inputs
    assert verify(setup.vk, proof1, asm.gates)
    return setup.vk, asm.gates, [proof0, proof1]


@pytest.fixture(scope="module")
def outer(inner):
    """The builder's circuit over slots (proof 0, proof 1), recorded."""
    vk, gates, proofs = inner
    with report.flight_recording(label="aggregate") as rec:
        cs = _builder().aggregate(
            GEOMETRY, [(vk, p) for p in proofs], gates, CAPACITY
        )
    return cs, cs.into_assembly(), report.build_report(rec)


def test_two_recorded_proofs_aggregate_satisfiably(outer):
    _cs, asm, _rep = outer
    assert [g.name for g in asm.gates] == VERIFIER_GATES
    assert check_if_satisfied(asm, verbose=True)


def test_public_inputs_are_the_reference_digest_of_the_keys_cap(inner, outer):
    """The builder's Python-integer sponge, from the round constants alone,
    against the program's host sponge; the circuit's 4 public inputs are it."""
    from boojum_tpu.hashes.poseidon2 import Poseidon2SpongeHost

    vk, _gates, _proofs = inner
    _cs, asm, _rep = outer
    want = _builder().reference_cap_digest(vk.setup_merkle_cap)
    flat = [int(v) for digest in vk.setup_merkle_cap for v in digest]
    assert want == [int(v) for v in Poseidon2SpongeHost.hash_leaf(flat)]
    assert [value for _col, _row, value in asm.public_inputs] == want
    # a cap of 5 elements: a last partial chunk, padded with zeros
    odd = [tuple(range(1, 5)), (gl.P - 1,)]
    assert _builder().reference_cap_digest(odd) == [
        int(v) for v in Poseidon2SpongeHost.hash_leaf([1, 2, 3, 4, gl.P - 1])
    ]


def test_the_circuit_does_not_depend_on_which_proof_fills_a_slot(inner, outer):
    """A kept setup serves every seed: slots (1, 0) place the same gates on
    the same variables with the same constants as slots (0, 1)."""
    vk, gates, proofs = inner
    _cs, asm, _rep = outer
    other = _builder().aggregate(
        GEOMETRY, [(vk, p) for p in reversed(proofs)], gates, CAPACITY
    ).into_assembly()
    np.testing.assert_array_equal(other.copy_placement, asm.copy_placement)
    np.testing.assert_array_equal(other.row_gate, asm.row_gate)
    assert other.gate_constants == asm.gate_constants
    assert other.copy_cols_values.tolist() != asm.copy_cols_values.tolist()


def test_a_tampered_recorded_proof_makes_the_builder_raise(inner):
    vk, gates, proofs = inner
    bad = copy.deepcopy(proofs[1])
    c0, c1 = bad.values_at_z[3]
    bad.values_at_z[3] = ((c0 + 1) % gl.P, c1)
    with pytest.raises(AssertionError, match="host verifier rejects"):
        _builder().aggregate(
            GEOMETRY, [(vk, proofs[0]), (vk, bad)], gates, CAPACITY
        )


@pytest.fixture(scope="module")
def other_key():
    """An honest proof under ANOTHER key: the same circuit but for one
    coefficient, so the same shapes and another setup cap."""
    from boojum_tpu.cs.gates import FmaGate, PublicInputGate
    from boojum_tpu.cs.implementations import ConstraintSystem
    from boojum_tpu.examples import EXAMPLE_GEOMETRY as geom

    _asm, _setup, config = small_parts()
    cs = ConstraintSystem(geom, 1 << 10)
    a = cs.alloc_variable_with_value(1)
    b = cs.alloc_variable_with_value(2)
    per_row = FmaGate.instance().num_repetitions(geom)
    for _ in range(((1 << 10) - 8) * per_row):
        a, b = b, FmaGate.fma(cs, a, b, a, 1, 2)
    PublicInputGate.place(cs, b)
    asm = cs.into_assembly()
    setup = generate_setup(asm, config)
    proof = prove(asm, setup, config)
    assert verify(setup.vk, proof, asm.gates)
    return setup.vk, proof


def test_two_keys_in_two_slots(inner, other_key):
    """The builder refuses two keys on the host; stepped past that check,
    each slot verifies alone and the cap equality across slots cannot hold:
    the circuit is unsatisfiable."""
    vk, gates, proofs = inner
    vk2, proof2 = other_key
    assert vk2.setup_merkle_cap != vk.setup_merkle_cap
    slots = [(vk, proofs[0]), (vk2, proof2)]
    b = _builder()
    with pytest.raises(AssertionError, match="another verification key"):
        b.aggregate(GEOMETRY, slots, gates, CAPACITY)
    cs = b.synthesize_slots(GEOMETRY, slots, gates, CAPACITY)[0]
    assert not check_if_satisfied(cs.into_assembly())
    alone = b.synthesize_slots(GEOMETRY, slots[1:], gates, CAPACITY)[0]
    assert check_if_satisfied(alone.into_assembly(), verbose=True)


def test_recorded_proof_and_key_round_trip(inner, tmp_path):
    """Through `boojum_tpu.serialization` and `Proof.to_json` into the
    builder's gzip file and back: the same key, the same proof bytes, the
    same file bytes for the same proof, and it still verifies."""
    from boojum_tpu.serialization import vk_to_json

    vk, gates, proofs = inner
    b = _builder()
    path = tmp_path / "inner.0.json.gz"
    b.write_recorded(str(path), vk, proofs[0], {"seed": 0})
    vk2, proof2, meta = b.read_recorded(str(path))
    assert meta == {"seed": 0}
    assert vk_to_json(vk2) == vk_to_json(vk)
    assert proof2.to_json() == proofs[0].to_json()
    assert verify(vk2, proof2, gates)
    first = path.read_bytes()
    b.write_recorded(str(path), vk2, proof2, {"seed": 0})
    assert path.read_bytes() == first


def test_the_gadget_counts_what_an_inner_proof_costs(outer):
    """`recursion.*` counters and spans of one synthesis, in the flight
    recorder: rows placed an inner proof, permutation rows among them."""
    cs, asm, rep = outer
    counters = rep["metrics"]["counters"]
    assert counters["recursion.inner_proofs"] == 2
    permutation_rows = int(
        (cs.row_gate[: cs.next_row] == cs.gate_index["poseidon2_flat"]).sum()
    )
    # the digest of the cap (2 permutations) is the builder's, after the
    # gadget returned
    assert counters["recursion.permutation_rows"] == permutation_rows - 2
    assert 0 < cs.next_row - counters["recursion.verifier_rows"] < 8
    names = set()

    def walk(spans):
        for s in spans:
            names.add(s["name"])
            walk(s.get("children", ()))

    walk(rep["spans"])
    assert {"recursion.allocate_proof", "recursion.transcript",
            "recursion.quotient_at_z", "recursion.queries"} <= names


def test_plan_of_the_verifiers_gate_set_is_pinned(outer):
    """3,130 field operations a row at 130 columns, 2,036 of them the
    flattened Poseidon2 gate's, and 7 gates masked by a selector product
    (public_input and nop have no terms); the benchmark's hand count of the
    multiplications agrees with the programs the sweep traces."""
    from boojum_tpu.cs.gate_capture import capture_gate_program
    from boojum_tpu.prover.prover import _gate_sweep_stats
    from boojum_tpu.prover.stages import gate_sweep_ops_per_row

    _cs, asm, _rep = outer
    _tree, paths = build_selector_tree(asm.gates)
    assert gate_sweep_ops_per_row(asm.gates, asm.geometry) == 3130
    assert _gate_sweep_stats(asm, paths, planes=True) == (3130, 0, 7)
    assert _gate_sweep_stats(asm, paths, planes=False) == (3130, 1, 7)
    # the shared fixture's own prove carries the counter: fma and the
    # constants allocator share its trace
    _proof, rep = baseline()
    assert rep["metrics"]["counters"]["quotient.selector_tree_gates"] == 2
    from benchmark.costs import recursion_gate

    muls = 0
    for g in asm.gates:
        if not g.num_terms:
            continue
        prog = capture_gate_program(g)
        own = sum(1 for op, *_rest in prog.ops if op == "mul")
        muls += g.num_repetitions(asm.geometry) * (own + 2 * len(prog.terms))
    assert muls == recursion_gate.muls_per_row(130) == 2164


def _rnd(rng, *shape):
    import jax.numpy as jnp

    return jnp.asarray(rng.integers(0, gl.P, shape, dtype=np.uint64))


def test_narrow_gates_and_selectors_on_planes_match_the_u64_sweep(outer):
    """`body_p`'s gate terms for the verifier's six narrow gates under the
    verifier's own selector paths (depths 3 to 6), on 256 random rows: the
    limb-plane kernel against the u64 sweep, word for word.
    `ConditionalSwapGate` is placed by the recursive verifier alone. 20
    columns (4 to 20 repetitions a gate) and not the cell's 130: unrolled
    over 402 terms the interpreted kernel compiles for over seven minutes
    on XLA:CPU."""
    import jax

    from boojum_tpu.field import limbs
    from boojum_tpu.prover import pallas_sweep as ps
    from boojum_tpu.prover.stages import _build_gate_sweep

    _cs, asm, _rep = outer
    _tree, all_paths = build_selector_tree(asm.gates)
    picked = [
        (g, tuple(all_paths[gid])) for gid, g in enumerate(asm.gates)
        if g.num_terms and g.name != "poseidon2_flat"
    ]
    gates = tuple(g for g, _p in picked)
    paths = tuple(p for _g, p in picked)
    assert "conditional_swap" in [g.name for g in gates] and len(gates) == 6
    assert all(len(p) >= 3 for p in paths)
    rng = np.random.default_rng(34)
    n = 256
    geometry = CSGeometry(
        num_columns_under_copy_permutation=20,
        num_witness_columns=0,
        num_constant_columns=8,
        max_allowed_constraint_degree=7,
    )
    copy_cols, const_cols = _rnd(rng, 20, n), _rnd(rng, 8, n)
    terms = sum(g.num_repetitions(geometry) * g.num_terms for g in gates)
    assert terms == 5 + 20 + 20 + 4 + 2 * 4 + 5
    a0, a1 = _rnd(rng, terms), _rnd(rng, terms)
    ref = _build_gate_sweep(gates, paths, geometry)(
        copy_cols, None, const_cols, a0, a1
    )
    limb_fn = ps.gate_terms_fn(gates, paths, geometry)
    # as tests/test_limb_sweep.py compiles a large unrolled limb trace
    got = jax.jit(
        lambda c, k, tb: limb_fn(c, None, k, tb),
        compiler_options={
            "xla_cpu_use_fusion_emitters": False,
            "xla_backend_optimization_level": 0,
            "xla_llvm_disable_expensive_passes": True,
        },
    )(limbs.split(copy_cols), limbs.split(const_cols), ps._pack_table(a0, a1))
    for c in range(2):
        np.testing.assert_array_equal(
            np.asarray(limbs.join(got[c])), np.asarray(ref[c])
        )


def test_enumerate_kernels_lists_this_circuits_library_and_its_sweep_lowers(outer):
    """The library of the outer circuit at the configuration's ProofConfig:
    one sweep kernel shaped by this gate set, which lowers; no lookup
    kernel (the verifier has no table)."""
    import json

    from boojum_tpu.prover import ProofConfig, enumerate_kernels

    _cs, asm, _rep = outer
    with open(os.path.join(BENCH, "configs", "recursive-verifier.json")) as f:
        cfg = ProofConfig(**json.load(f)["proof_config"])
    specs = enumerate_kernels(asm, cfg)
    names = [s.name for s in specs]
    assert len(names) == len(set(names))
    sweeps = [s for s in specs if s.name.startswith("coset_sweep_terms")]
    assert len(sweeps) == 1
    assert not [n for n in names if "lookup" in n]
    sweeps[0].fn.lower(*sweeps[0].args)


def test_build_over_one_recorded_sha256_proof_is_satisfiable():
    """`build()` itself over the committed recorded proof of seed 1 (50
    queries, cap 16, a 2^19-point domain, 8 lookups of width 4: the cell's
    inner settings, which no other test synthesizes), one slot in 2^15 rows:
    the host verifier accepts the recorded proof, the circuit is satisfied
    gate by gate, and the rows are what the configuration says a first
    inner proof costs."""
    import json

    with open(os.path.join(BENCH, "configs", "recursive-verifier.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "closed-aggregate.json")) as f:
        request = json.load(f)["request"]
    params = {**cfg["circuit"]["params"], **request,
              "inner_proofs": 1, "trace_len": 1 << 15}
    b = _builder()
    cs = b.build(params, 1)
    measured = cfg["measured"]
    # the gadget's rows, then the digest's 8 permutations and the public
    # inputs' row
    assert cs.next_row == measured["rows_first_inner_proof"] + 8 + 1
    asm = cs.into_assembly()
    assert asm.trace_len == 1 << 15
    assert [g.name for g in asm.gates] == VERIFIER_GATES
    vk, _proof, meta = b.read_recorded(os.path.join(
        BENCH, params["recorded_dir"], params["recorded"][1]))
    assert meta["seed"] == 1
    assert [v for _c, _r, v in asm.public_inputs] == b.reference_cap_digest(
        vk.setup_merkle_cap)
    assert check_if_satisfied(asm, verbose=True)
