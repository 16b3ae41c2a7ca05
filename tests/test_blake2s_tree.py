"""The Blake2s tree hasher (ISSUE 42): `ProofConfig(tree_hasher="blake2s")`.

Held here, on the CPU backend:

- the device hash (`hashes/blake2s.py` through the programs
  `merkle.tree_hasher("blake2s")` hands out) against `hashlib`, word for
  word, on the u64 form and on the limb planes: leaf digests at widths
  around the 8-element block (8 is a full final block) and the node layers
  down to caps 1 and 16, through the plain reference
  `compat/blake2s_tree.py`;
- the 2^10 acceptance circuit proved through the normal `prove()` under a
  Blake2s tree with the Blake2s and the Poseidon2 transcript: `verify()`
  true, one flipped byte of a query path's sibling or of a cap digest
  false, the witness and setup oracles' caps and every query path of them
  equal to the reference's over the same LDE;
- the key's round trip with and without the field, and the four refusals.

The u64/XLA variant is how tier-1 reaches the path. The limb-resident
variant's prove (equal proof bytes and checkpoint stream) is slow-lane like
every resident prove on the CPU (`proving.interpret_e2e` says why); its
programs are the planes cases below, which are tier-1.
"""

import dataclasses
import functools
import json

import jax.numpy as jnp
import numpy as np
import pytest

from boojum_tpu import merkle
from boojum_tpu.compat import blake2s_tree as ref
from boojum_tpu.prover import generate_setup, prove, verify
from boojum_tpu.prover.config import TreeHasherNotSupported
from boojum_tpu.utils import report
from proving import (
    checkpoint_stream,
    environ,
    fma_assembly,
    interpret_e2e,
    mesh_2x4,
    small_config,
)

WIDTHS = [1, 7, 8, 9, 16, 93]
LEAVES = 64


def _columns(B, seed=0):
    """(B, 2, LEAVES / 2) seeded random u64 words, any 64-bit value: a
    column stack as the prover's LDE storages lie."""
    rng = np.random.default_rng(1000 * B + seed)
    return rng.integers(
        0, 1 << 64, size=(B, 2, LEAVES // 2), dtype=np.uint64
    )


def _split(x):
    return (
        jnp.asarray((x & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
        jnp.asarray((x >> np.uint64(32)).astype(np.uint32)),
    )


def _join(p):
    return np.asarray(p[0]).astype(np.uint64) | (
        np.asarray(p[1]).astype(np.uint64) << np.uint64(32)
    )


def _leaf_digests(form, cols):
    H = merkle.tree_hasher("blake2s")
    if form == "u64":
        return np.asarray(H.leaf_digests_device(jnp.asarray(cols)))
    return _join(H.leaf_digests_planes(_split(cols)))


def _node_layers(form, digests, cap):
    H = merkle.tree_hasher("blake2s")
    if form == "u64":
        return [
            np.asarray(x)
            for x in H.node_layers_device(jnp.asarray(digests), cap)
        ]
    return [_join(x) for x in H.node_layers_planes(_split(digests), cap)]


@pytest.mark.parametrize("form", ["u64", "planes"])
@pytest.mark.parametrize("B", WIDTHS)
def test_leaf_digests_are_hashlibs(B, form):
    cols = _columns(B)
    rows = cols.reshape(B, -1).T
    got = _leaf_digests(form, cols)
    assert got.shape == (LEAVES, 4) and got.dtype == np.uint64
    want = ref.tree_layers(rows, LEAVES)[0]
    assert (got == want).all()
    # and by the byte, without the reference's own layering
    import hashlib

    assert got[5].astype("<u8").tobytes() == hashlib.blake2s(
        rows[5].astype("<u8").tobytes()
    ).digest()
    assert tuple(int(w) for w in got[7]) == ref.leaf_digest(rows[7])


@pytest.mark.parametrize("form", ["u64", "planes"])
@pytest.mark.parametrize("cap", [1, 16])
def test_node_layers_are_hashlibs_down_to_the_cap(cap, form):
    rows = _columns(9).reshape(9, -1).T
    want = ref.tree_layers(rows, cap)
    got = _node_layers(form, want[0], cap)
    assert [x.shape for x in got] == [x.shape for x in want]
    assert got[-1].shape == (cap, 4)
    for g, w in zip(got, want):
        assert (g == w).all()
    assert ref.node_digest(want[0][0], want[0][1]) == tuple(
        int(w) for w in want[1][0]
    )


def test_the_commit_counts_its_compressions():
    from boojum_tpu.hashes.blake2s import compressions
    from boojum_tpu.utils import metrics

    # the SHA-256 cell's three commits: 93, 46 and 16 columns at 2^19
    N, cap = 1 << 19, 16
    assert sum(compressions(b, N, cap) for b in (93, 46, 16)) == 12_058_576
    H = merkle.tree_hasher("blake2s")
    reg = metrics.MetricsRegistry()
    prev = metrics.install_registry(reg)
    try:
        H.commit_layers_device(jnp.asarray(_columns(9)), 4)
        H.commit_layers_planes(_split(_columns(16)), 4)
    finally:
        metrics.install_registry(prev)
    assert reg.counters["merkle.blake2s_compressions"] == (
        LEAVES * 2 + LEAVES - 4
    ) * 2
    assert reg.counters["merkle.commit_layer_builds"] == 2


def test_poseidon2_resolves_to_the_functions_themselves():
    H = merkle.tree_hasher("poseidon2")
    assert H is merkle.POSEIDON2 is merkle.tree_hasher()
    assert H.leaf_digests_device is merkle.leaf_digests_device
    assert H.node_layers_device is merkle.node_layers_device
    assert H.commit_layers_device is merkle.commit_layers_device
    assert H.leaf_digests_planes is merkle.leaf_digests_planes
    assert H.node_layers_planes is merkle.node_layers_planes
    assert H.commit_layers_planes is merkle.commit_layers_planes
    from boojum_tpu.prover import fri

    assert fri.fri_commit_fn(H, True) is fri._fri_commit_fn_p
    assert fri.fri_commit_fn(H, False) is fri._fri_commit_fn
    with pytest.raises(ValueError):
        merkle.tree_hasher("keccak256")


# ---------------------------------------------------------------------------
# The 2^10 acceptance circuit under a Blake2s tree, through prove()/verify()
# ---------------------------------------------------------------------------

TRANSCRIPTS = ["blake2s", "poseidon2"]


def _config(transcript):
    return dataclasses.replace(
        small_config(), tree_hasher="blake2s", transcript=transcript
    )


@functools.lru_cache(maxsize=None)
def _parts(transcript):
    asm, cfg = fma_assembly(), _config(transcript)
    return asm, generate_setup(asm, cfg), cfg


@functools.lru_cache(maxsize=None)
def _proved(transcript, resident=False):
    asm, setup, cfg = _parts(transcript)
    env = {"BOOJUM_TPU_LIMB_RESIDENT": "1"} if resident else {}
    with environ(env):
        with report.flight_recording(label=f"blake2s_{transcript}") as rec:
            proof = prove(asm, setup, cfg)
    return proof, report.build_report(rec)


@pytest.mark.parametrize("transcript", TRANSCRIPTS)
def test_prove_verifies_and_says_its_hasher(transcript):
    asm, setup, _cfg = _parts(transcript)
    proof, rep = _proved(transcript)
    assert setup.vk.tree_hasher == "blake2s"
    assert setup.vk.transcript == transcript
    assert verify(setup.vk, proof, asm.gates)
    assert report.validate_report(rep) == []
    counters = rep["metrics"]["counters"]
    # 2 materialized commits in the pipeline + the quotient's
    assert counters["merkle.commit_layer_builds"] == 3
    assert counters["merkle.blake2s_compressions"] > 0
    prove_span, = [s for s in rep["spans"] if s["name"] == "prove"]
    assert prove_span["attrs"]["prover.tree_hasher"] == "blake2s"
    # the byte transcript permutes nothing; the sponge does, as ever
    perms = counters.get("transcript.permutations", 0)
    assert (perms == 0) == (transcript == "blake2s")
    # the hasher is load-bearing: the same proof under a Poseidon2 key fails
    wrong = dataclasses.replace(setup.vk, tree_hasher="poseidon2")
    assert not verify(wrong, proof, asm.gates)


def test_the_transcript_kinds_draw_different_proofs_over_one_setup_cap():
    (_a, s_b, _c), (_a2, s_p, _c2) = _parts("blake2s"), _parts("poseidon2")
    assert s_b.vk.setup_merkle_cap == s_p.vk.setup_merkle_cap
    assert _proved("blake2s")[0].to_json() != _proved("poseidon2")[0].to_json()
    # the first oracle is committed before any challenge is drawn
    assert _proved("blake2s")[0].witness_cap == _proved("poseidon2")[0].witness_cap


def _flipped(digest, word=0, bit=0):
    d = list(digest)
    d[word] = int(d[word]) ^ (1 << bit)
    return tuple(d)


@pytest.mark.parametrize("where", [
    "witness_path", "setup_path", "fri_path", "witness_cap", "fri_cap",
    "quotient_leaf",
])
@pytest.mark.parametrize("transcript", TRANSCRIPTS)
def test_one_flipped_byte_fails(transcript, where):
    from boojum_tpu.prover import Proof

    asm, setup, _cfg = _parts(transcript)
    bad = Proof.from_json(_proved(transcript)[0].to_json())
    q = bad.queries[1]
    if where == "witness_path":
        q.witness.path[0] = _flipped(q.witness.path[0], word=3, bit=63)
    elif where == "setup_path":
        q.setup.path[-1] = _flipped(q.setup.path[-1])
    elif where == "fri_path":
        q.fri[0].path[1] = _flipped(q.fri[0].path[1], word=2, bit=9)
    elif where == "witness_cap":
        bad.witness_cap[0] = _flipped(bad.witness_cap[0], word=1, bit=40)
    elif where == "fri_cap":
        bad.fri_caps[0][-1] = _flipped(bad.fri_caps[0][-1])
    else:
        q.quotient.leaf_values[0] = int(q.quotient.leaf_values[0]) ^ 1
    assert not verify(setup.vk, bad, asm.gates)


def _witness_rows(asm, cfg):
    """The witness oracle's LDE, a row a leaf, by the library's transforms
    (the u64 path: the prove's own is freed with the prove)."""
    from boojum_tpu.ntt import lde_from_monomial, monomial_from_values

    cols = [np.asarray(asm.copy_cols_values)]
    if asm.num_lookup_cols:
        cols.append(np.asarray(asm.lookup_cols_values))
    if asm.wit_placement.shape[0]:
        cols.append(np.asarray(asm.wit_cols_values))
    if asm.lookups_enabled:
        cols.append(np.asarray(asm.multiplicities)[None, :])
    values = jnp.asarray(np.concatenate(cols, axis=0))
    lde = np.asarray(
        lde_from_monomial(monomial_from_values(values), cfg.fri_lde_factor)
    )
    return lde.reshape(lde.shape[0], -1).T


@pytest.mark.parametrize("transcript", TRANSCRIPTS)
def test_caps_and_paths_are_the_references_over_the_same_lde(transcript):
    asm, setup, cfg = _parts(transcript)
    proof = _proved(transcript)[0]
    cap = cfg.merkle_tree_cap_size
    setup_lde = np.asarray(setup.setup_lde)
    oracles = {
        "witness": (_witness_rows(asm, cfg), proof.witness_cap),
        "setup": (
            setup_lde.reshape(setup_lde.shape[0], -1).T,
            setup.vk.setup_merkle_cap,
        ),
    }
    for name, (rows, proof_cap) in oracles.items():
        layers = ref.tree_layers(rows, cap)
        assert ref.cap_of(layers) == [
            tuple(int(w) for w in d) for d in proof_cap
        ], name
        for q in proof.queries:
            opened = getattr(q, name)
            leaf = np.asarray(opened.leaf_values, dtype=np.uint64)
            idx, = np.nonzero((rows == leaf).all(axis=1))
            assert len(idx) == 1, name
            assert [tuple(int(w) for w in s) for s in opened.path] == (
                ref.path_of(layers, int(idx[0]))
            ), name
            assert ref.verify_path(
                opened.leaf_values, opened.path, proof_cap, int(idx[0])
            )
    # the device kept the layers the reference builds, every one
    tree = setup.setup_tree
    want = ref.tree_layers(oracles["setup"][0], cap)
    assert len(tree.layers) == len(want)
    for got, w in zip(tree.layers, want):
        assert (np.asarray(got) == w).all()


def test_verify_runs_none_of_the_device_hash(monkeypatch):
    from boojum_tpu.hashes import blake2s as b2s

    asm, setup, _cfg = _parts("blake2s")
    proof = _proved("blake2s")[0]

    def never(*_a, **_k):
        raise AssertionError("verify() reached the device hash")

    for name in ("compress", "leaf_words", "node_words"):
        monkeypatch.setattr(b2s, name, never)
    monkeypatch.setattr(merkle, "tree_hasher", never)
    assert verify(setup.vk, proof, asm.gates)


@interpret_e2e
@pytest.mark.parametrize("transcript", TRANSCRIPTS)
def test_resident_variant_proves_the_same_bytes(transcript):
    """Limb planes against u64 words: equal proof bytes and checkpoint
    streams (run by hand for PR 42 with the slow lane's XLA flag: equal)."""
    asm, setup, _cfg = _parts(transcript)
    p_u, r_u = _proved(transcript)
    p_r, r_r = _proved(transcript, resident=True)
    assert p_r.to_json() == p_u.to_json()
    assert checkpoint_stream(r_r) == checkpoint_stream(r_u) != []
    assert verify(setup.vk, p_r, asm.gates)
    counters = r_r["metrics"]["counters"]
    assert counters["merkle.resident_commits"] > 0
    assert counters.get("limb.splits", 0) == counters.get("limb.joins", 0) == 0
    assert (
        counters["merkle.blake2s_compressions"]
        == r_u["metrics"]["counters"]["merkle.blake2s_compressions"]
    )


def test_checkpoint_stream_names_every_cap():
    stream = checkpoint_stream(_proved("blake2s")[1])
    labels = [label for _seq, _round, label, _digest in stream]
    for label in ("setup_cap", "witness_cap", "fri_cap_0"):
        assert label in labels
    # a stream is a function of the transcript kind too
    assert stream != checkpoint_stream(_proved("poseidon2")[1])


# ---------------------------------------------------------------------------
# The key, kept and read back
# ---------------------------------------------------------------------------


def test_key_round_trip_with_and_without_the_field(tmp_path):
    from boojum_tpu.serialization import (
        load_setup,
        save_setup,
        vk_from_json,
        vk_to_json,
    )

    asm, setup, cfg = _parts("blake2s")
    text = vk_to_json(setup.vk)
    assert json.loads(text)["tree_hasher"] == "blake2s"
    assert vk_from_json(text).to_dict() == setup.vk.to_dict()
    # a file from before the field is a Poseidon2 key
    d = json.loads(text)
    del d["tree_hasher"]
    old = vk_from_json(json.dumps(d))
    assert old.tree_hasher == "poseidon2"
    assert old.transcript == "blake2s"
    d["tree_hasher"] = "sha3"
    with pytest.raises(ValueError, match="tree hasher"):
        vk_from_json(json.dumps(d))
    # the setup's own format: the layers come back as they were hashed
    path = str(tmp_path / "setup.npz")
    save_setup(path, setup)
    back = load_setup(path)
    assert back.vk.to_dict() == setup.vk.to_dict()
    assert back.setup_tree.get_cap() == setup.setup_tree.get_cap()
    proof = prove(asm, back, cfg)
    assert proof.to_json() == _proved("blake2s")[0].to_json()


def test_shape_key_tells_the_hashers_apart():
    from boojum_tpu.prover.shape_key import bucket_key

    asm = fma_assembly()
    k_p = bucket_key(asm, small_config())
    k_b = bucket_key(asm, dataclasses.replace(small_config(), tree_hasher="blake2s"))
    assert k_b == k_p + ":Hblake2s"


def test_the_library_lists_blake2s_programs_only_under_a_blake2s_key():
    from boojum_tpu.prover import enumerate_kernels

    asm = fma_assembly()

    def hashers(cfg):
        names = {}
        for s in enumerate_kernels(asm, cfg):
            fn = getattr(s.fn, "__name__", "")
            if "leaf_digests" in fn or "node_layers" in fn or "fri_oracle" in fn \
                    or s.name.startswith("fri_commit"):
                names[s.name] = fn
        return names

    plain = hashers(small_config())
    assert plain and not any("blake2s" in n + f for n, f in plain.items())
    b2s = hashers(_config("blake2s"))
    assert len(b2s) == len(plain)
    assert all("blake2s" in f for f in b2s.values()), b2s
    assert "node_layers_blake2s" in b2s
    assert "wit:leaf_digests_blake2s" in b2s


# ---------------------------------------------------------------------------
# Refused, by name: no deployment stands behind these
# ---------------------------------------------------------------------------


def test_refused_on_a_streamed_commit():
    asm, setup, cfg = _parts("blake2s")
    with environ({"BOOJUM_TPU_STREAM_LDE": "1"}):
        with pytest.raises(TreeHasherNotSupported, match="streamed"):
            prove(asm, setup, cfg)
        with pytest.raises(TreeHasherNotSupported, match="streamed"):
            generate_setup(asm, cfg)
        from boojum_tpu.prover import enumerate_kernels

        with pytest.raises(TreeHasherNotSupported, match="streamed"):
            enumerate_kernels(asm, cfg)


def test_refused_under_a_mesh():
    asm, setup, cfg = _parts("blake2s")
    with pytest.raises(TreeHasherNotSupported, match="mesh"):
        prove(asm, setup, cfg, mesh=mesh_2x4())


def test_refused_in_the_babybear_prover():
    from proving import _fma_assembly

    with environ({"BOOJUM_TPU_FIELD": "babybear"}):
        asm = _fma_assembly(6, 0, "babybear")
        assert asm.field == "babybear"
        with pytest.raises(TreeHasherNotSupported, match="BabyBear"):
            generate_setup(asm, _config("blake2s"))


def test_refused_as_recursive_verifys_inner_key():
    from boojum_tpu.cs.implementations import ConstraintSystem
    from boojum_tpu.gadgets.recursion import recursive_verify
    from test_recursion import RECURSION_GEOM

    asm, setup, _cfg = _parts("poseidon2")
    outer = ConstraintSystem(RECURSION_GEOM, 1 << 10)
    with pytest.raises(TreeHasherNotSupported, match="recursive_verify"):
        recursive_verify(outer, setup.vk, _proved("poseidon2")[0], asm.gates)
