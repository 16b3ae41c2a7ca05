"""Artifact (de)serialization: VK JSON, proof JSON, setup fast format.

Counterpart of the reference's `MemcopySerializable` memcpy-style setup
serialization (`/root/reference/src/cs/implementations/fast_serialization.rs:12`,
impls in `polynomial_storage.rs:85,159`) and the serde JSON proof/VK artifacts
(`proof.json` / `vk.json` at the reference repo root). Setup storages are
dense numpy arrays here, so the "memcpy format" is a single `.npz` holding
every array (including the precomputed Merkle layers — loading re-uploads to
device without recomputing anything)."""

from __future__ import annotations

import json

import numpy as np

from .cs.types import CSGeometry, LookupParameters
from .merkle import MerkleTreeWithCap
from .prover.setup import SetupData, VerificationKey


# -- verification key --------------------------------------------------------


def vk_to_json(vk: VerificationKey) -> str:
    return json.dumps(vk.to_dict())


def vk_from_json(s: str) -> VerificationKey:
    d = json.loads(s)
    geometry = CSGeometry(**d["geometry"])
    lp = d.get("lookup_params")
    lookup_params = LookupParameters(**lp) if lp else None
    return VerificationKey(
        geometry=geometry,
        trace_len=int(d["trace_len"]),
        fri_lde_factor=int(d["fri_lde_factor"]),
        cap_size=int(d["cap_size"]),
        num_queries=int(d["num_queries"]),
        pow_bits=int(d["pow_bits"]),
        fri_final_degree=int(d["fri_final_degree"]),
        gate_names=list(d["gate_names"]),
        selector_paths=[list(p) for p in d["selector_paths"]],
        public_input_locations=[tuple(x) for x in d["public_input_locations"]],
        setup_merkle_cap=[tuple(int(v) for v in c) for c in d["setup_merkle_cap"]],
        num_copy_cols=int(d["num_copy_cols"]),
        num_wit_cols=int(d["num_wit_cols"]),
        lookup_params=lookup_params,
        num_lookup_tables=int(d.get("num_lookup_tables", 0)),
        fri_folding_schedule=d.get("fri_folding_schedule"),
        quotient_degree=(
            int(d["quotient_degree"])
            if d.get("quotient_degree") is not None
            else None
        ),
        transcript=_checked_transcript(d.get("transcript", "poseidon2")),
        tree_hasher=_checked_tree_hasher(d.get("tree_hasher", "poseidon2")),
    )


def _checked_transcript(kind: str) -> str:
    from .transcript import TRANSCRIPTS

    if kind not in TRANSCRIPTS:
        raise ValueError(f"unknown transcript kind in vk: {kind!r}")
    return kind


def _checked_tree_hasher(name: str) -> str:
    from .prover.config import TREE_HASHERS

    if name not in TREE_HASHERS:
        raise ValueError(f"unknown tree hasher in vk: {name!r}")
    return name


# -- setup fast serialization ------------------------------------------------


def save_setup(path: str, setup: SetupData):
    """One .npz with every dense array + the VK as embedded JSON."""
    arrays = {
        "sigma_cols": np.asarray(setup.sigma_cols),
        "constant_cols": np.asarray(setup.constant_cols),
        "setup_monomials": np.asarray(setup.setup_monomials),
        # streamed-mode setups carry no materialized LDE (rebuilt lazily)
        **(
            {"setup_lde": np.asarray(setup.setup_lde)}
            if setup.setup_lde is not None
            else {}
        ),
        "non_residues": np.asarray(setup.non_residues, dtype=np.uint64),
        "vk_json": np.frombuffer(
            vk_to_json(setup.vk).encode(), dtype=np.uint8
        ),
        "tree_num_layers": np.asarray(
            [len(setup.setup_tree.layers)], dtype=np.int64
        ),
        "tree_cap_size": np.asarray(
            [setup.setup_tree.cap_size], dtype=np.int64
        ),
    }
    for i, layer in enumerate(setup.setup_tree.layers):
        arrays[f"tree_layer_{i}"] = np.asarray(layer)
    np.savez(path, **arrays)


def load_setup(path: str) -> SetupData:
    import jax.numpy as jnp

    with np.load(path) as z:
        vk = vk_from_json(bytes(z["vk_json"]).decode())
        num_layers = int(z["tree_num_layers"][0])
        cap_size = int(z["tree_cap_size"][0])
        layers = [jnp.asarray(z[f"tree_layer_{i}"]) for i in range(num_layers)]
        tree = MerkleTreeWithCap.from_layers(layers, cap_size)
        return SetupData(
            vk=vk,
            sigma_cols=z["sigma_cols"],
            constant_cols=z["constant_cols"],
            setup_monomials=jnp.asarray(z["setup_monomials"]),
            setup_lde=(
                jnp.asarray(z["setup_lde"]) if "setup_lde" in z else None
            ),
            setup_tree=tree,
            selector_paths=vk.selector_paths,
            non_residues=[int(v) for v in z["non_residues"]],
        )
