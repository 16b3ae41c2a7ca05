"""Precompile subsystem (ISSUE 1).

The tentpole split the fused prover round graphs (`_commit_fused`, the
stage-2 tail, the unrolled chunk products) into a library of shape-keyed
top-level kernels plus a parallel precompiler. These tests pin that the
kernel enumeration — for the SHA-256 bench geometry, and for the limb,
limb-resident and shard_map variants of the shared 2^10 circuit — lowers
cleanly on CPU (no tracing errors) and feeds the compile ledger one entry
per kernel with monotonic timestamps. The parity of the split pipelines
with the graphs they replaced is tests/test_split_graph_parity.py.
"""

import pytest

from boojum_tpu.cs.implementations import ConstraintSystem
from boojum_tpu.cs.types import CSGeometry, LookupParameters
from boojum_tpu.prover import ProofConfig
from boojum_tpu.utils.profiling import CompileLedger
from proving import fma_assembly, small_config

SHA_GEOM = CSGeometry(
    num_columns_under_copy_permutation=60,
    num_witness_columns=0,
    num_constant_columns=8,
    max_allowed_constraint_degree=7,
)
SHA_LOOKUP = LookupParameters(width=4, num_repetitions=8)
# the bench's proof shape (bench.py), at tier-1-friendly query count
SHA_CONFIG = ProofConfig(
    fri_lde_factor=8,
    merkle_tree_cap_size=16,
    num_queries=4,
    pow_bits=0,
    fri_final_degree=16,
)


def _sha_assembly():
    from boojum_tpu.gadgets import allocate_u8_input, sha256

    cs = ConstraintSystem(SHA_GEOM, 1 << 15, lookup_params=SHA_LOOKUP)
    sha256(cs, allocate_u8_input(cs, b"precompile me"))
    return cs.into_assembly()


def test_sha_geometry_enumeration_lowers_with_ledger():
    from boojum_tpu.prover.precompile import enumerate_kernels, precompile

    asm = _sha_assembly()
    specs = enumerate_kernels(asm, SHA_CONFIG)
    assert len(specs) > 20, "kernel library unexpectedly small"
    names = [s.name for s in specs]
    assert len(set(names)) == len(names), "duplicate kernel names"

    ledger = CompileLedger()
    out = precompile(asm, SHA_CONFIG, ledger=ledger, lower_only=True)
    assert out is ledger
    errors = [e for e in ledger.entries if "error" in e]
    assert not errors, f"kernels failed to lower: {errors}"
    assert len(ledger.entries) == len(specs)
    stamps = [e["ts"] for e in ledger.entries]
    assert stamps == sorted(stamps), "ledger timestamps not monotonic"
    assert all(e["trace_s"] >= 0.0 for e in ledger.entries)
    # lower-only must not claim compile work happened
    assert all(e["compile_s"] == 0.0 for e in ledger.entries)
    summary = ledger.summary()
    assert summary["num_kernels"] == len(specs)


@pytest.mark.parametrize("resident", [False, True])
def test_sha_geometry_enumerates_the_pick_not_the_transforms(
    monkeypatch, resident
):
    """ISSUE 27: at LDE 8 under an 8-coset quotient every coset of round 3
    is a committed one, so the witness, setup and stage-2 transforms are
    never dispatched: the library lists the pick (which lowers) and the
    shifted z's transform alone."""
    from boojum_tpu.prover.precompile import enumerate_kernels

    monkeypatch.setenv("BOOJUM_TPU_LIMB_RESIDENT", "1" if resident else "0")
    sfx = "_limbres" if resident else ""
    specs = {s.name: s for s in enumerate_kernels(_sha_assembly(), SHA_CONFIG)}
    evals = sorted(n for n in specs if n.startswith("coset_eval_"))
    assert evals == [f"coset_eval_pick{sfx}", f"coset_eval_zs{sfx}"], evals
    pick = specs[f"coset_eval_pick{sfx}"]
    oracles, _c, n = pick.args
    N = n * SHA_CONFIG.fri_lde_factor
    shapes = [
        (o[0] if resident else o).shape for o in oracles
    ]
    assert len(shapes) == 3 and all(s[1] == N for s in shapes), shapes
    assert "dynamic_slice" in pick.fn.lower(*pick.args).as_text()


def test_limb_resident_kernels_enumerate_and_lower(monkeypatch):
    """ISSUE 10 satellite: with BOOJUM_TPU_LIMB_RESIDENT=1 the enumeration
    swaps to the RESIDENT plane-kernel set (`*_limbres` ledger names —
    plane NTTs, plane sponges/commits, the resident sweep and FRI chain,
    the stage-2/DEEP plane twins), it LOWERS on CPU, and the converting
    names disappear (only the dispatched variant is enumerated)."""
    from boojum_tpu.prover.precompile import enumerate_kernels, precompile

    monkeypatch.setenv("BOOJUM_TPU_LIMB_RESIDENT", "1")
    asm, cfg = fma_assembly(), small_config()
    specs = enumerate_kernels(asm, cfg)
    names = [s.name for s in specs]
    assert "coset_sweep_terms_limbres" in names
    assert "coset_sweep_terms" not in names
    res_folds = [n for n in names if n.startswith("fri_fold_limbres_")]
    assert res_folds, names
    assert not any(n.startswith("fri_fold_k") for n in names)
    assert "chunk_num_den_limbres" in names
    assert "z_and_partials_limbres" in names
    assert "evals_limbres" in names
    assert "deep_combine_limbres" in names
    assert "node_layers_limbres" in names
    assert any(n.startswith("wit:imono_limbres_") for n in names), names
    assert any(n.startswith("wit:lde_limbres_") for n in names), names
    # every resident spec lowers cleanly on CPU
    ledger = CompileLedger()
    precompile(asm, cfg, ledger=ledger, lower_only=True)
    by_name = {e["name"]: e for e in ledger.entries}
    for name in (
        ["coset_sweep_terms_limbres", "chunk_num_den_limbres",
         "z_and_partials_limbres", "evals_limbres",
         "deep_combine_limbres", "deep_extras_limbres",
         "node_layers_limbres", "quotient_interp_limbres",
         "deep_denoms_limbres", "zshift_limbres"]
        + res_folds
    ):
        assert name in by_name, name
        assert "error" not in by_name[name], by_name[name]

    # the AOT bundle key separates the variants (a resident bundle must
    # never serve a converting process)
    from boojum_tpu.prover.aot import variant_fingerprint

    assert variant_fingerprint()["representation"] == "planes"
    monkeypatch.setenv("BOOJUM_TPU_LIMB_RESIDENT", "0")
    assert variant_fingerprint()["representation"] == "u64"
    names_u64 = [s.name for s in enumerate_kernels(asm, cfg)]
    assert "coset_sweep_terms" in names_u64
    assert "coset_sweep_terms_limbres" not in names_u64


def test_mesh_shard_map_kernels_enumerate_and_lower(monkeypatch):
    """ISSUE 5 satellite: enumerate_kernels(mesh_shape=(2,4)) swaps in the
    shard_map `_sm` kernel variants (per-chip iNTT + fused LDE/pivot/leaf
    graph, per-coset eval with explicit all_to_all, the sm terms sweep,
    the per-chip FRI leaf/fold graphs, the one-graph DEEP codeword), they
    LOWER on the forced-8-device CPU, and the ledger records ONLY the
    dispatched variant — none of the meshless twins ride along."""
    import jax as _jax

    if len(_jax.devices()) < 8:
        import pytest

        pytest.skip("needs 8 virtual devices")
    from boojum_tpu.prover.precompile import enumerate_kernels, precompile

    asm, cfg = fma_assembly(), small_config()
    specs = enumerate_kernels(asm, cfg, mesh_shape=(2, 4))
    names = [s.name for s in specs]
    for want in (
        "wit:mono_sm", "wit:lde_pivot_leaf_sm", "coset_eval_wit_sm",
        "coset_sweep_terms_sm", "deep_codeword_sm",
    ):
        assert want in names, names
    assert any(n.startswith("fri_leaf_k") and n.endswith("_sm")
               for n in names), names
    assert any(n.startswith("fri_fold_k") and n.endswith("_sm")
               for n in names), names
    # only the dispatched variant: the meshless twins must be absent
    assert "coset_sweep_terms" not in names
    assert "coset_eval_wit" not in names
    assert not any(
        n.startswith("fri_commit_k") for n in names
    ), "meshless FRI commit enumerated alongside the sm one"
    assert "deep_combine" not in names
    assert "node_layers" not in names

    ledger = CompileLedger()
    precompile(asm, cfg, ledger=ledger, lower_only=True, mesh_shape=(2, 4))
    by_name = {e["name"]: e for e in ledger.entries}
    for name in names:
        assert name in by_name, name
        assert "error" not in by_name[name], by_name[name]

    # meshless enumeration is untouched: no _sm names
    names0 = [s.name for s in enumerate_kernels(asm, cfg)]
    assert not any(n.endswith("_sm") for n in names0)
