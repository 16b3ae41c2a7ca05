"""Split-graph parity (ISSUE 1).

The precompile tentpole split the fused prover round graphs
(`_commit_fused`, the stage-2 tail, the unrolled chunk products) into
shape-keyed top-level kernels (tests/test_precompile.py pins their
enumeration). These tests pin that the split pipelines are BIT-identical
to the pre-split monolithic graphs they replaced, both as unit parities
(commit pipeline, streamed digests, chunk scan) and as a round-output
check on the shared 2^10 circuit's actual proof.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from boojum_tpu.field import gl
from boojum_tpu.field import extension as ext_f
from boojum_tpu.field import goldilocks as gf
from boojum_tpu.ntt import lde_from_monomial, monomial_from_values
from proving import baseline, small_parts


# ---------------------------------------------------------------------------
# Pre-split monolithic forms, kept verbatim as parity oracles
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(1, 2))
def _presplit_commit(values, L: int, cap: int):
    """The round-3 `_commit_fused` materialized path, one graph."""
    from boojum_tpu.merkle import _tree_layers

    mono = monomial_from_values(values)
    lde = lde_from_monomial(mono, L)
    B = lde.shape[0]
    return mono, lde, _tree_layers(lde.reshape(B, -1).T, cap)


@partial(jax.jit, static_argnums=(6,))
def _presplit_chunk_num_den(copy_vals, sigma_vals, ks, xs, b, g, chunks):
    """The fully unrolled `_all_chunk_num_den` (pre-scan form)."""
    nums0, nums1, dens0, dens1 = [], [], [], []
    for chunk in chunks:
        num_p = den_p = None
        for col in chunk:
            w = copy_vals[col]
            kx = gf.mul(xs, ks[col])
            num = (
                gf.add(gf.add(w, gf.mul(kx, b[0])), g[0]),
                gf.add(gf.mul(kx, b[1]), g[1]),
            )
            s = sigma_vals[col]
            den = (
                gf.add(gf.add(w, gf.mul(s, b[0])), g[0]),
                gf.add(gf.mul(s, b[1]), g[1]),
            )
            num_p = num if num_p is None else ext_f.mul(num_p, num)
            den_p = den if den_p is None else ext_f.mul(den_p, den)
        nums0.append(num_p[0])
        nums1.append(num_p[1])
        dens0.append(den_p[0])
        dens1.append(den_p[1])
    return (
        (jnp.stack(nums0), jnp.stack(nums1)),
        (jnp.stack(dens0), jnp.stack(dens1)),
    )


def _rand(rng, *shape):
    return jnp.asarray(rng.integers(0, gl.P, shape, dtype=np.uint64))


def test_commit_pipeline_parity_vs_presplit():
    from boojum_tpu.prover.prover import _commit_pipeline

    rng = np.random.default_rng(7)
    values = _rand(rng, 10, 1 << 8)
    mono_ref, lde_ref, layers_ref = _presplit_commit(values, 4, 4)
    mono, lde, layers = _commit_pipeline(values, 4, 4, stream=False)
    np.testing.assert_array_equal(np.asarray(mono_ref), np.asarray(mono))
    np.testing.assert_array_equal(np.asarray(lde_ref), np.asarray(lde))
    assert len(layers_ref) == len(layers)
    for a, b in zip(layers_ref, layers):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streamed_digest_blocks_parity():
    """Block-dispatched streamed digests == the traceable one-graph form,
    including the trailing-partial-chunk sponge padding (B % 8 != 0) and
    a ragged final column block (B % COL_BLOCK != 0)."""
    from boojum_tpu.prover.streaming import (
        COL_BLOCK,
        streamed_leaf_digests,
        streamed_leaf_digests_blocks,
    )

    rng = np.random.default_rng(11)
    for B in (8, 13, COL_BLOCK + 5):
        mono = _rand(rng, B, 1 << 8)
        ref = streamed_leaf_digests(mono, 2)
        got = streamed_leaf_digests_blocks(mono, 2)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_stream_commit_pipeline_parity_vs_presplit():
    from boojum_tpu.prover.prover import _commit_pipeline

    rng = np.random.default_rng(13)
    values = _rand(rng, 9, 1 << 8)
    _mono_ref, _lde_ref, layers_ref = _presplit_commit(values, 4, 4)
    mono, lde, layers = _commit_pipeline(values, 4, 4, stream=True)
    assert lde is None  # streamed mode never materializes the storage
    assert len(layers_ref) == len(layers)
    for a, b in zip(layers_ref, layers):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunk_num_den_scan_parity_vs_presplit():
    from boojum_tpu.prover.stages import _all_chunk_num_den, chunk_columns

    rng = np.random.default_rng(3)
    n = 1 << 8
    for C, deg in ((18, 7), (8, 4), (5, 7), (7, 7)):
        cv, sv = _rand(rng, C, n), _rand(rng, C, n)
        ks = _rand(rng, C)
        xs = _rand(rng, n)
        b = (jnp.uint64(3), jnp.uint64(5))
        g = (jnp.uint64(7), jnp.uint64(11))
        chunks = tuple(tuple(c) for c in chunk_columns(C, deg))
        ref = _presplit_chunk_num_den(cv, sv, ks, xs, b, g, chunks)
        got = _all_chunk_num_den(cv, sv, ks, xs, b, g, chunks)
        for i in range(2):
            for j in range(2):
                np.testing.assert_array_equal(
                    np.asarray(ref[i][j]), np.asarray(got[i][j])
                )


def test_prove_round_outputs_match_presplit_2pow10():
    """End-to-end: the split prover's round-1 commitment on a real 2^10
    circuit equals the PRE-SPLIT monolithic commit graph applied to the
    same witness columns — the proof's witness cap is a round output, so
    this pins the whole split pipeline (iNTT -> LDE -> leaf sponge -> node
    stack) against the fused original on proof bytes, not just arrays."""
    # the shared smallest honest config (L=2, few queries, shallow FRI):
    # the parity claim is about commit bytes, not proof strength
    asm, _setup, cfg = small_parts()
    proof = baseline()[0]
    # no lookups / witness columns in this geometry: the committed stack
    # is exactly the copy columns (prover._upload_witness)
    wit = jnp.asarray(np.asarray(asm.copy_cols_values))
    _mono, _lde, layers = _presplit_commit(
        wit, cfg.fri_lde_factor, cfg.merkle_tree_cap_size
    )
    presplit_cap = [
        tuple(int(x) for x in row) for row in np.asarray(layers[-1])
    ]
    assert [tuple(c) for c in proof.witness_cap] == presplit_cap
