"""The benchmark configuration `keccak256-era`, held to its references on
the CPU: upstream's Keccak-256 gadget on the geometry and proof settings of
the golden Era `vk.json` / `proof.json` (130 columns under copy permutation,
8 lookups of width 3, LDE 2 under an 8-chunk quotient, cap 32, 100 queries,
final degree 16; `tests/test_golden.py` records them from the files).

Three readings, each against code that shares nothing with what it checks:

- the gadget at this geometry against `hashes.keccak_host` (pure Python on
  ints): digest parity and satisfiability through the benchmark's own
  builder, at the 2^18 rows the 8-bit tables set as the floor (synthesis
  only, no prove);
- the proof settings through the normal `prove()` and the host verifier
  `verify()` (numpy, none of the prover's kernels);
- the same settings through the plain numpy prover in the reference's
  transcript dialect and `verify_reference_proof`, the verifier that accepts
  the golden Era proof byte for byte, with the full quotient identity.

The 8-bit tables cannot shrink, so a prove of the Keccak circuit itself is
2^18 rows and belongs to the chip. The small size is reached with a circuit
of the same widths built from tables a 2^10-row trace can hold (`xor4`, 256
rows of width 3, and a 4-bit range check): every prover kernel then has the
configuration's column counts, rates, cap and query count, at 2^10 rows.
"""

import copy
import importlib.util
import json
import os

import pytest

from boojum_tpu.compat import compute_fri_schedule
from boojum_tpu.compat.prove_reference import prove_reference_dialect
from boojum_tpu.compat.verifier import verify_reference_proof
from boojum_tpu.cs.types import CSGeometry, LookupParameters
from boojum_tpu.examples import build_xor_lookup_circuit
from boojum_tpu.field import gl
from boojum_tpu.hashes.keccak_host import keccak256 as host_keccak256
from boojum_tpu.prover import (
    ProofConfig,
    generate_setup,
    precompile,
    prove,
    verify,
)
from boojum_tpu.prover.fri import fold_schedule
from boojum_tpu.prover.satisfiability import check_if_satisfied

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SMALL_LOG_N = 10


def _config():
    with open(os.path.join(BENCH, "configs", "keccak256-era.json")) as f:
        return json.load(f)


def _builder():
    spec = importlib.util.spec_from_file_location(
        "benchmark_circuit_keccak256",
        os.path.join(BENCH, "circuits", "keccak256.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _proof_config():
    return ProofConfig(**_config()["proof_config"])


def _small_assembly():
    """2^10 rows on the configuration's geometry: 2100 xor4 lookups, as
    many range checks and an fma chain, one public input."""
    p = _config()["circuit"]["params"]
    cs, _, _ = build_xor_lookup_circuit(
        num_lookups=2100,
        geometry=CSGeometry(
            num_columns_under_copy_permutation=p["copy_columns"],
            num_witness_columns=p["witness_columns"],
            num_constant_columns=p["constant_columns"],
            max_allowed_constraint_degree=p["constraint_degree"],
        ),
        lookup_params=LookupParameters(
            width=p["lookup_width"], num_repetitions=p["lookup_args"]
        ),
        capacity=1 << SMALL_LOG_N,
    )
    asm = cs.into_assembly()
    assert asm.trace_len == 1 << SMALL_LOG_N
    return asm


@pytest.fixture(scope="module")
def small_assembly():
    return _small_assembly()


@pytest.fixture(scope="module")
def proved(small_assembly):
    """(assembly, setup, proof) of the small circuit at the configuration's
    ProofConfig, through the normal generate_setup() and prove()."""
    cfg = _proof_config()
    # the kernel library on a pool first, as the benchmark's set-up does:
    # the 130-column graphs are this file's wall, and whatever cores the
    # other workers leave free compile them side by side
    precompile(small_assembly, cfg, max_workers=os.cpu_count() or 4)
    setup = generate_setup(small_assembly, cfg)
    return small_assembly, setup, prove(small_assembly, setup, cfg)


@pytest.fixture(scope="module")
def reference_artifacts(small_assembly):
    pc = _config()["proof_config"]
    return prove_reference_dialect(
        small_assembly,
        fri_lde_factor=pc["fri_lde_factor"],
        cap_size=pc["merkle_tree_cap_size"],
        security_level=100,
        pow_bits=pc["pow_bits"],
    )


# -- the configuration file against the golden artifacts' records -----------


def test_config_is_the_golden_geometry_and_settings():
    """What tests/test_golden.py reads from vk.json / proof.json: 155
    variable polynomials (130 + 3 x 8 + 1 multiplicity), LDE 2, cap 32,
    100 queries, 16 final monomials, an 8-chunk quotient, PoW 0."""
    c = _config()
    p, pc = c["circuit"]["params"], c["proof_config"]
    assert p["copy_columns"] == 130 and p["witness_columns"] == 0
    assert (p["lookup_width"], p["lookup_args"]) == (3, 8)
    assert p["copy_columns"] + p["lookup_width"] * p["lookup_args"] + 1 == 155
    assert pc["fri_lde_factor"] == 2 and pc["merkle_tree_cap_size"] == 32
    assert pc["num_queries"] == 100 and pc["pow_bits"] == 0
    assert pc["fri_final_degree"] == 16 and pc["transcript"] == "poseidon2"
    assert pc["quotient_degree"] is None and pc["fri_folding_schedule"] is None
    assert len(c["source"]) <= 200
    # nothing but the trace length is reduced, and the published size is
    # named beside the cut
    assert c["reduced"] == ["trace_len"]
    assert "2^20" in c["reduced_from"]["trace_len"]


@pytest.mark.parametrize("log_n,schedule", [
    (20, [3, 3, 3, 3, 3, 1]),  # the golden proof's own
    (18, [3, 3, 3, 3, 2]),     # the cell's trace
    (SMALL_LOG_N, [3, 3]),     # this file's proves
])
def test_query_count_and_fri_schedule_are_the_references(log_n, schedule):
    """The reference's prover.rs:2281 at 100 bits, LDE 2, cap 32, PoW 0
    gives the configuration's 100 queries and final degree 16, and its
    schedule is what the program derives from `fri_folding_schedule: null`."""
    pc = _config()["proof_config"]
    new_pow, queries, ref_schedule, final_degree = compute_fri_schedule(
        security_bits=100, cap_size=pc["merkle_tree_cap_size"],
        pow_bits=pc["pow_bits"], rate_log_two=1, initial_degree_log_two=log_n,
    )
    assert (new_pow, queries, final_degree) == (
        pc["pow_bits"], pc["num_queries"], pc["fri_final_degree"]
    )
    assert ref_schedule == schedule
    assert fold_schedule(
        1 << log_n, pc["fri_final_degree"], pc["fri_folding_schedule"]
    ) == schedule


# -- the gadget at the Era geometry, through the benchmark's builder --------


@pytest.mark.parametrize("num_bytes,seed", [(100, 2147490001), (300, 7)])
def test_gadget_at_era_geometry_matches_host_keccak(num_bytes, seed):
    """One block and three: the builder's circuit has the host digest of the
    seeded message as its 32 public inputs, is satisfied, and sits at the
    2^18-row floor of the 8-bit tables."""
    builder = _builder()
    params = {**_config()["circuit"]["params"], "message_bytes": num_bytes}
    cs = builder.build(params, seed)
    asm = cs.into_assembly()
    assert asm.trace_len == 1 << 18
    assert sorted(g.name for g in asm.gates) == [
        "constant", "fma", "nop", "public_input"
    ]
    digest = host_keccak256(builder.message(num_bytes, seed))
    assert bytes(v for (_c, _r, v) in asm.public_inputs) == digest
    assert check_if_satisfied(asm)


def test_builder_refuses_a_wrong_digest(monkeypatch):
    """The assertion inside build() is live: with the host reference made
    to answer another digest, synthesis fails."""
    import boojum_tpu.hashes.keccak_host as host

    monkeypatch.setattr(host, "keccak256", lambda data: b"\x00" * 32)
    params = {**_config()["circuit"]["params"], "message_bytes": 10}
    with pytest.raises(AssertionError, match="host Keccak-256"):
        _builder().build(params, 1)


# -- the proof settings through prove() and the host verifier ----------------


def test_era_settings_prove_and_verify(proved):
    asm, setup, proof = proved
    pc = _config()["proof_config"]
    assert setup.vk.fri_lde_factor == 2
    assert setup.vk.quotient_degree == 8
    assert len(proof.queries) == pc["num_queries"]
    assert len(proof.witness_cap) == pc["merkle_tree_cap_size"]
    # 2 x 8 quotient leaf values a query, committed at LDE 2
    assert len(proof.queries[0].quotient.leaf_values) == 2 * 8
    assert len(proof.queries[0].witness.leaf_values) == 155
    assert len(proof.final_fri_monomials) == pc["fri_final_degree"]
    assert [len(f.leaf_values) for f in proof.queries[0].fri] == [16, 16]
    assert verify(setup.vk, proof, asm.gates)


def test_altered_opening_is_rejected(proved):
    asm, setup, proof = proved
    bad = copy.deepcopy(proof)
    c0, c1 = bad.values_at_z[0]
    bad.values_at_z[0] = ((c0 + 1) % gl.P, c1)
    assert not verify(setup.vk, bad, asm.gates)


def test_altered_lookup_opening_is_rejected(proved):
    """The lookup argument's own openings: the A_i and B sums at 0."""
    asm, setup, proof = proved
    assert len(proof.values_at_0) == 8 + 1
    bad = copy.deepcopy(proof)
    c0, c1 = bad.values_at_0[0]
    bad.values_at_0[0] = ((c0 + 1) % gl.P, c1)
    assert not verify(setup.vk, bad, asm.gates)


# -- the independent reference: the numpy prover in the reference's dialect --


def test_reference_dialect_accepts_these_settings(reference_artifacts):
    """Committed at LDE 2 under an 8-chunk quotient, as the golden proof is;
    accepted with the full quotient identity at z."""
    art = reference_artifacts
    pc = _config()["proof_config"]
    assert art.vk.quotient_degree == 8
    assert len(art.proof.queries_per_fri_repetition) == pc["num_queries"]
    assert len(art.proof.final_fri_monomials[0]) == pc["fri_final_degree"]
    q = art.proof.queries_per_fri_repetition[0]
    assert [len(f.leaf_elements) for f in q.fri] == [2 * (1 << 3)] * 2
    assert len(art.proof.witness_oracle_cap) == pc["merkle_tree_cap_size"]
    assert verify_reference_proof(
        art.vk, art.proof, art.config, check_quotient_identity=True
    )


def test_reference_dialect_rejects_an_altered_lookup_sum(reference_artifacts):
    art = reference_artifacts
    bad = copy.deepcopy(art.proof)
    c0, c1 = bad.values_at_0[0]
    bad.values_at_0[0] = ((c0 + 1) % gl.P, c1)
    assert not verify_reference_proof(art.vk, bad, art.config)


# -- what the geometry forced in the program: the coset barrier rule ---------


class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


GIB = 1 << 30


@pytest.mark.parametrize("label,group_columns,log_n,in_use,expect", [
    # the anchor cell: 93 + 105 + 46 + 2 columns at 2^16 rows: all queued
    ("sha256-lde8 2^16", 246, 16, 2 * GIB, 0),
    # this configuration: 155 + 166 + 62 + 2 columns at 2^18 rows beside
    # 3 GiB of oracles: eight working sets do not fit half of what is free
    ("keccak256-era 2^18", 385, 18, 3 * GIB, 4),
    # SHA-256 at 2^20 rows (ROADMAP M1): a barrier after every coset
    ("sha256 2^20", 246, 20, 7 * GIB, 1),
])
def test_sweep_barrier_is_chosen_from_device_memory(
    monkeypatch, label, group_columns, log_n, in_use, expect
):
    import jax

    from boojum_tpu.prover import prover as P

    monkeypatch.setattr(
        jax, "local_devices",
        lambda: [_Device({"bytes_limit": 16 * GIB - (256 << 20),
                          "bytes_in_use": in_use})],
    )
    ws = P._sweep_working_set_bytes(group_columns, 1 << log_n)
    assert ws == 2 * 8 * group_columns << log_n
    assert P._sweep_barrier_stride(8, ws) == expect, label
    # a backend that reports no limit (XLA:CPU) never gets a barrier
    monkeypatch.setattr(jax, "local_devices", lambda: [_Device(None)])
    assert P._sweep_barrier_stride(8, ws) == 0


def test_forward_ntt_above_2_16_refuses_to_be_traced_into_one_program():
    """On the v5e XLA's outer radix-2 stages and the MXU kernel of a forward
    transform above 2^16 do not come back when compiled into one program
    (PERF.md, PR 26): `_hybrid_fwd_p` dispatches the transform as a program
    of its own (since PR 35 the stages are the kernel's prologue) and
    raises if a caller's jit would hold it after all."""
    import jax
    import jax.numpy as jnp

    from boojum_tpu.ntt import limb_ntt as LN

    p = (jnp.zeros((2, 1 << 17), jnp.uint32),) * 2
    with pytest.raises(TypeError, match="a device program of its own"):
        jax.jit(lambda q: LN._hybrid_fwd_p(q, 17, LN._LDE_FORWARD))(p)
    # off a TPU the MXU transform is not in use and nothing changes
    assert not LN.forward_is_own_program(1 << 18)


@pytest.mark.parametrize("resident", [False, True])
def test_era_library_lists_the_pick_beside_the_transforms(
    monkeypatch, small_assembly, resident
):
    """ISSUE 27: at LDE 2 under an 8-coset quotient, cosets 0 and 1 of
    round 3 are read from the commitments and cosets 2-7 transformed: the
    library lists the pick over the three committed storages AND the four
    groups' transforms, and each of them lowers."""
    from boojum_tpu.prover.precompile import enumerate_kernels
    from boojum_tpu.prover.shape_key import shape_bucket

    monkeypatch.setenv("BOOJUM_TPU_LIMB_RESIDENT", "1" if resident else "0")
    sfx = "_limbres" if resident else ""
    cfg = _proof_config()
    specs = {s.name: s for s in enumerate_kernels(small_assembly, cfg)}
    evals = sorted(n for n in specs if n.startswith("coset_eval_"))
    assert evals == sorted(
        f"coset_eval_{tag}{sfx}" for tag in ("pick", "wit", "setup", "s2", "zs")
    ), evals
    oracles, _c, n = specs[f"coset_eval_pick{sfx}"].args
    widths = [(o[0] if resident else o).shape for o in oracles]
    N = n * cfg.fri_lde_factor
    sb = shape_bucket(small_assembly, cfg)
    assert sb.B_wit == 155 and sb.S == 62
    assert widths == [(sb.B_wit, N), (sb.B_setup, N), (sb.S, N)], widths
    for name in evals:
        specs[name].fn.lower(*specs[name].args)


def test_era_library_lists_one_fused_transform_a_chunk_at_the_cells_size(
    monkeypatch,
):
    """ISSUE 35: at the Era cells' 2^18 rows a group's coset evaluation is
    the row's pick and ONE fused program for each size of column chunk
    (64 and the remainder), where PR 26 listed a scale, an outer-stage and
    an MXU program for each; a commit's LDE likewise. Their names keep
    `coset_eval` and `lde_planes`, which the benchmark's families and
    `kernel.lde_hbm_share` find them by."""
    from boojum_tpu.ntt import limb_ntt as LN
    from boojum_tpu.prover import resident as RES

    monkeypatch.setattr(LN, "_mxu_ntt_ready", lambda n, ctx: True)
    n = 1 << 18
    for tag, B, sizes in (("wit", 155, (27, 64)), ("zs", 2, (2,))):
        specs = RES.coset_eval_kernel_specs(tag, B, n, 8)
        stem = f"coset_eval_{tag}_limbres"
        assert [s[0] for s in specs] == [f"{stem}:row"] + [
            f"{stem}:fft_b{b}:fused" for b in sizes
        ]
        assert {s[1].__name__ for s in specs[1:]} == {
            "_coset_eval_hybrid_fused_p"
        }
    lde = LN.plane_ntt_kernel_specs(155, 18, 2, mono=False)
    assert [s[0] for s in lde] == [
        f"lde_hybrid_limbres_b{b}_n{n}_L2:fused" for b in (27, 32)
    ]
    assert {s[1].__name__ for s in lde} == {"_lde_planes_hybrid_fused_p"}
    # ISSUE 40: the commits' inverse transform likewise: the inverse matmul
    # kernel's program for each size of chunk (witness 155 = 64 + 64 + 27,
    # stage 2 62), and at 2^19 rows the trailing stage with it
    # (155 = 4 x 32 + 27, 62 = 32 + 30); `imono_p` is the families' name
    for log_n, parts, chunks in (
        (18, ("fused",), {155: (27, 64), 62: (62,)}),
        (19, ("fused", "trailing"), {155: (27, 32), 62: (30, 32)}),
    ):
        for B, sizes in chunks.items():
            mono = LN.plane_ntt_kernel_specs(B, log_n)
            assert [s[0] for s in mono] == [
                f"imono_kernel_limbres_b{b}_n{1 << log_n}:{part}"
                for b in sizes for part in parts
            ]
            assert {s[1].__name__ for s in mono} == {
                f"_imono_p_{part}" for part in parts
            }
