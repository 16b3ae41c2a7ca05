"""The in-circuit Boojum verifier over recorded proofs: what an Era
aggregation worker proves (reference
src/gadgets/recursion/recursive_verifier.rs:380 `RecursiveVerifier::verify`,
its test at :2213, and sha256_bench_recursive_poseidon2.sh; BASELINE.json
configs[3]). `inner_proofs` slots, each the whole verifier of one inner
proof (`boojum_tpu.gadgets.recursion.recursive_verify`: transcript replay,
the inner circuit's own gates at z, copy-permutation and lookup relations,
DEEP, every Merkle path, the FRI folds) in one 130-column circuit: the
flattened Poseidon2 gate beside fma, reduction, selection, conditional-swap
and boolean gates under one selector tree. The witness is made from proofs.

The inner proofs are RECORDED: `benchmark/tools/record_inner_proofs.py`
made them once, on the chip, with the accepted cell
`sha256-lde8.closed-8k`'s own circuit and settings, and wrote proof and
verification key to `benchmark/data/recursive-verifier/inner.<seed>.json.gz`.
Slot i of a run verifies recorded proof `(seed + i) % len(recorded)`: a
recursion worker's input is a proof another worker made.

Public inputs: 4, a Poseidon2 digest of the inner verification key's setup
cap, computed in-circuit over the cap variables the verifier hashed into its
transcript and constrained equal across the slots: an aggregation proof says
WHICH key it verified under.

What `build` holds the circuit to (the configuration's guarantees), none of
it through the code under test:
  (a) host `boojum_tpu.prover.verify` (numpy, none of the prover's kernels)
      accepts every recorded proof under its recorded key, or `build` raises;
  (b) every slot's key has the same setup cap and the gate list the inner
      circuit's builder gives, or `build` raises;
  (c) the 4 public inputs in the witness equal `reference_cap_digest` of the
      recorded cap: the Poseidon2 overwrite sponge on Python integers,
      written below from `hashes/poseidon2_params.py`'s constants alone
      (none of the gadget's or the gate's code).
`prover.satisfiability.check_if_satisfied` on the outer assembly is held in
tier-1 (`tests/test_recursive_verifier_cell.py`: two slots over 2^10-row
proofs, and one slot over a recorded proof of this cell) and was run once at
the cell's size by the PR that brought the cell (PERF.md, PR 34: satisfied);
on Python integers it takes over a minute at 2^18 rows, a quarter of a later
run's set-up, so it is not inside `build`.
"""

from __future__ import annotations

import gzip
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
P = (1 << 64) - (1 << 32) + 1
RATE = 8
WIDTH = 12
HALF_FULL_ROUNDS = 4
PARTIAL_ROUNDS = 22


def _log(msg: str):
    print(f"[recursive_verifier] {msg}", file=sys.stderr, flush=True)


# -- the plain reference of the public inputs ---------------------------------

_M4 = ((5, 7, 1, 3), (4, 6, 1, 1), (1, 3, 5, 7), (1, 1, 4, 6))


def _external_matrix(s):
    """circ(2 M4, M4, M4): M4 on each block of four, plus the sum of the
    three blocks' results."""
    blocks = [
        [sum(_M4[r][c] * s[4 * b + c] for c in range(4)) % P for r in range(4)]
        for b in range(3)
    ]
    sums = [(blocks[0][i] + blocks[1][i] + blocks[2][i]) % P for i in range(4)]
    return [(blocks[b][i] + sums[i]) % P for b in range(3) for i in range(4)]


def reference_permutation(state: list[int]) -> list[int]:
    """Poseidon2 over Goldilocks (eprint 2023/323): t = 12, x^7, 4 + 22 + 4
    rounds, on Python integers."""
    from boojum_tpu.hashes import poseidon2_params as params

    rc = params.ALL_ROUND_CONSTANTS
    diagonal = params.M_I_DIAGONAL
    s = _external_matrix([int(v) % P for v in state])
    for r in range(2 * HALF_FULL_ROUNDS + PARTIAL_ROUNDS):
        if HALF_FULL_ROUNDS <= r < HALF_FULL_ROUNDS + PARTIAL_ROUNDS:
            s[0] = pow(s[0] + rc[WIDTH * r], 7, P)
            total = sum(s) % P
            s = [(diagonal[i] * s[i] + total) % P for i in range(WIDTH)]
        else:
            s = _external_matrix(
                [pow(s[i] + rc[WIDTH * r + i], 7, P) for i in range(WIDTH)]
            )
    return s


def reference_cap_digest(cap) -> list[int]:
    """The overwrite sponge (rate 8, capacity 4, a last partial chunk padded
    with zeros) over the cap's digests laid end to end: 4 elements."""
    flat = [int(v) % P for digest in cap for v in digest]
    state = [0] * WIDTH
    for at in range(0, len(flat), RATE):
        chunk = flat[at : at + RATE]
        state = reference_permutation(
            chunk + [0] * (RATE - len(chunk)) + state[RATE:]
        )
    return state[:4]


# -- the recorded proofs ------------------------------------------------------


def write_recorded(path: str, vk, proof, recorded: dict):
    """One recorded inner proof: verification key and proof through
    `boojum_tpu.serialization` / `Proof.to_json`, gzip with no timestamp so
    that the same proof gives the same bytes."""
    from boojum_tpu.serialization import vk_to_json

    blob = json.dumps({
        "recorded": recorded,
        "vk": json.loads(vk_to_json(vk)),
        "proof": json.loads(proof.to_json()),
    }).encode()
    with open(path, "wb") as f:
        with gzip.GzipFile(fileobj=f, mode="wb", mtime=0, filename="") as z:
            z.write(blob)


def read_recorded(path: str):
    """(vk, proof, recorded) as `write_recorded` wrote them."""
    from boojum_tpu.prover import Proof
    from boojum_tpu.serialization import vk_from_json

    with gzip.open(path, "rb") as f:
        d = json.loads(f.read())
    return (
        vk_from_json(json.dumps(d["vk"])),
        Proof.from_json(json.dumps(d["proof"])),
        d["recorded"],
    )


def load_sibling_builder(name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_circuit_{name}", os.path.join(HERE, f"{name}.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def inner_cell(name: str):
    """(circuit params, proof_config, builder name) of the accepted cell
    whose proofs are verified, from its own configuration and traffic
    files: `<config>.<traffic>`."""
    config_name, traffic = name.split(".", 1)
    with open(os.path.join(BENCH, "configs", f"{config_name}.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{traffic}.json")) as f:
        request = json.load(f)["request"]
    return (
        {**config["circuit"]["params"], **request},
        config["proof_config"],
        config["circuit"]["builder"],
    )


# -- the circuit --------------------------------------------------------------


def synthesize_slots(geometry, slots, gates, capacity: int):
    """One `recursive_verify` a slot ((vk, proof) pairs) into one circuit,
    the slots' setup caps constrained equal to slot 0's, and the Poseidon2
    digest of that cap as the 4 public inputs. Returns (cs, digest
    variables); checks nothing on the host."""
    from boojum_tpu.cs.gates import PublicInputGate
    from boojum_tpu.cs.implementations import ConstraintSystem
    from boojum_tpu.gadgets.field_like_circuit import CircuitOps
    from boojum_tpu.gadgets.poseidon2_rf import circuit_hash_leaf
    from boojum_tpu.gadgets.recursion import recursive_verify

    cs = ConstraintSystem(geometry, capacity)
    bops = CircuitOps(cs)
    cap_vars = None
    for vk, proof in slots:
        _public_inputs, slot_cap = recursive_verify(cs, vk, proof, gates)
        if cap_vars is None:
            cap_vars = slot_cap
            continue
        for mine, first in zip(slot_cap, cap_vars):
            for a, b in zip(mine, first):
                bops.enforce_equal(a, b)
    digest = circuit_hash_leaf(cs, [v for d in cap_vars for v in d])
    for v in digest:
        PublicInputGate.place(cs, v)
    return cs, digest


def aggregate(geometry, slots, gates, capacity: int):
    """The outer circuit over `slots`. Raises unless (a), (b) and (c) of
    the module's docstring hold."""
    from boojum_tpu.prover import verify

    assert slots, "no inner proof to verify"
    names = [g.name for g in gates]
    cap = [tuple(int(v) for v in d) for d in slots[0][0].setup_merkle_cap]
    verified = {}
    for i, (vk, proof) in enumerate(slots):
        assert list(vk.gate_names) == names, (
            f"slot {i}: the recorded key's gates {list(vk.gate_names)} are "
            f"not the inner circuit's {names}"
        )
        assert [tuple(int(v) for v in d) for d in vk.setup_merkle_cap] == cap, (
            f"slot {i} was proved under another verification key than slot 0"
        )
        if id(proof) not in verified:
            verified[id(proof)] = bool(verify(vk, proof, gates))
        assert verified[id(proof)], (
            f"slot {i}: the host verifier rejects the recorded inner proof"
        )
    cs, digest = synthesize_slots(geometry, slots, gates, capacity)
    got = [int(cs.get_value(v)) for v in digest]
    want = reference_cap_digest(cap)
    assert got == want, (
        f"the circuit's public inputs {got} are not the reference Poseidon2 "
        f"digest {want} of the recorded key's setup cap"
    )
    return cs


def build(params: dict, seed: int):
    """`params` is the configuration's `circuit.params` merged with the
    traffic mix's `request`. Returns the synthesized ConstraintSystem."""
    from boojum_tpu.cs.types import CSGeometry

    assert int(params.get("lookup_args", 0)) == 0, "the verifier has no table"
    geometry = CSGeometry(
        num_columns_under_copy_permutation=int(params["copy_columns"]),
        num_witness_columns=int(params.get("witness_columns", 0)),
        num_constant_columns=int(params["constant_columns"]),
        max_allowed_constraint_degree=int(params["constraint_degree"]),
    )
    t0 = time.perf_counter()
    recorded = [
        read_recorded(os.path.join(BENCH, params["recorded_dir"], name))
        for name in params["recorded"]
    ]
    inner_params, inner_config, inner_builder = inner_cell(params["inner"])
    for vk, _proof, _meta in recorded:
        # the recorded key's settings are the inner cell's, to the letter
        assert (
            vk.fri_lde_factor, vk.cap_size, vk.num_queries, vk.pow_bits,
            vk.fri_final_degree, vk.transcript,
        ) == tuple(inner_config[k] for k in (
            "fri_lde_factor", "merkle_tree_cap_size", "num_queries",
            "pow_bits", "fri_final_degree", "transcript",
        )), f"a recorded key's settings are not {params['inner']}'s"
    # the inner circuit's gate list: its own builder at its smallest request
    # (the gate set does not depend on the witness's size)
    gates = load_sibling_builder(inner_builder).build(
        {**inner_params, **params["inner_smallest_request"]}, 0
    ).into_assembly().gates
    k = int(params["inner_proofs"])
    slots = [recorded[(int(seed) + i) % len(recorded)][:2] for i in range(k)]
    t1 = time.perf_counter()
    # the gadget's counters of this synthesis alone, in a registry of its own
    # (a program without them, as this cell's parent commit, logs none)
    from boojum_tpu.utils import metrics

    registry = metrics.MetricsRegistry()
    token = metrics.install_scoped_registry(registry)
    try:
        cs = aggregate(geometry, slots, gates, int(params["trace_len"]))
    finally:
        metrics.reset_scoped_registry(token)
    counters = registry.to_dict()["counters"]
    _log(
        f"{k} inner proofs of {params['inner']}: {cs.next_row} rows of "
        f"{params['trace_len']}; read {t1 - t0:.1f} s, host verify and "
        f"synthesis {time.perf_counter() - t1:.1f} s; " + ", ".join(
            f"{name} {value}" for name, value in sorted(counters.items())
            if name.startswith("recursion.")
        )
    )
    return cs
