"""Round 3 reads the cosets the commits already evaluated (ISSUE 27).

The first min(L, Q) cosets of the rate-Q quotient domain are the cosets of
the committed rate-L LDE, so on those the witness, setup and stage-2 groups
are read from the committed storage (`prover.coset_is_committed`,
`prover._coset_eval_pick`) in place of a scale + forward NTT from the
monomials. These tests pin, on the CPU:

- the identity on the host-built scale tables, for L < Q, L = Q and L > Q;
- element-for-element equality of the read and the transform, on the u64
  path at 2^10 rows and on planes against `resident._coset_eval_q_p`;
- the rule itself (what reads, what transforms);
- end to end: proof bytes and the checkpoint stream of the shared 2^10
  prove equal those of a prove with the rule switched off, and the flight
  recording counts 3 x min(L, Q) reads and 4Q - that transforms.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from boojum_tpu.field import gl
from boojum_tpu.field import limbs
from boojum_tpu.ntt import lde_from_monomial
from boojum_tpu.ntt import limb_ntt as LN
from boojum_tpu.ntt.ntt import bitreverse_indices, lde_scale_rows
from boojum_tpu.prover import prover as P
from boojum_tpu.prover import resident as RES
from boojum_tpu.prover.streaming import MonomialPlanesSource, MonomialSource
from proving import baseline, checkpoint_stream, prove_recorded, small_parts

G = int(gl.MULTIPLICATIVE_GENERATOR)


def _shifts(log_n, rate):
    log_r = rate.bit_length() - 1
    w = gl.omega(log_n + log_r)
    return [gl.mul(G, gl.pow_(w, int(j))) for j in bitreverse_indices(log_r)]


@pytest.mark.parametrize("L,Q", [(2, 8), (8, 8), (4, 2), (1, 8)])
def test_first_cosets_of_the_quotient_domain_are_the_commits(L, Q):
    log_n = 6
    tq = LN._lde_scale_planes(log_n, Q, G)
    tl = LN._lde_scale_planes(log_n, L, G)
    m = min(L, Q)
    for plane_q, plane_l in zip(tq, tl):
        np.testing.assert_array_equal(
            np.asarray(plane_q)[:m], np.asarray(plane_l)[:m]
        )
    # and no further: past L the quotient domain leaves the committed one
    sq, sl = _shifts(log_n, Q), _shifts(log_n, L)
    assert sq[:m] == sl[:m]
    if Q > L:
        assert sq[L] not in sl


def _random_stack(rng, B, n):
    return jnp.asarray(rng.integers(0, gl.P, (B, n), dtype=np.uint64))


@pytest.mark.parametrize("L", [2, 8])
def test_pick_equals_the_transform_u64(L):
    """Every group's width of the shared circuit, every c < L, at 2^10
    rows: the committed storage's coset IS `_coset_eval_q`'s output."""
    from boojum_tpu.prover.shape_key import shape_bucket

    asm, setup, cfg = small_parts()
    sb = shape_bucket(asm, cfg)
    n, log_n, Q = sb.trace_len, sb.log_n, sb.quotient_degree
    assert n == 1 << 10 and Q == 8
    scale_q = lde_scale_rows(log_n, Q)
    rng = np.random.default_rng(27)
    monos = {
        "wit": _random_stack(rng, sb.B_wit, n),
        "setup": jnp.asarray(setup.setup_monomials),
        "s2": _random_stack(rng, sb.S, n),
    }
    assert monos["setup"].shape == (sb.B_setup, n)
    oracles = {
        t: lde_from_monomial(m, L).reshape(m.shape[0], L * n)
        for t, m in monos.items()
    }
    if L == cfg.fri_lde_factor:
        # the setup's own committed storage, as round 3 holds it
        np.testing.assert_array_equal(
            np.asarray(oracles["setup"]),
            np.asarray(setup.setup_lde).reshape(sb.B_setup, L * n),
        )
    for c in range(L):
        ci = jnp.int32(c)
        picked = P._coset_eval_pick(tuple(oracles.values()), ci, n)
        for (tag, mono), got in zip(monos.items(), picked):
            want = P._coset_eval_q(mono, scale_q, ci)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want), err_msg=f"{tag} coset {c}"
            )


# compiled without XLA:CPU's fusion emitters, as tests/test_limb_sweep.py's
# standalone parities are (with them the limb cores run for half an hour)
_jit = functools.partial(
    jax.jit, compiler_options={"xla_cpu_use_fusion_emitters": False}
)


def test_plane_pick_equals_the_plane_transform():
    B, L, Q, log_n = 3, 2, 8, 8
    n = 1 << log_n
    rng = np.random.default_rng(28)
    mono = rng.integers(0, gl.P, (B, n), dtype=np.uint64)
    mono[0, :4] = [0, 1, gl.P - 1, 0xFFFFFFFF]
    lo, hi = limbs.split_np(mono)
    mono_p = (jnp.asarray(lo), jnp.asarray(hi))
    lde_p = _jit(lambda p: LN.lde_from_monomial_p(p, L))(mono_p)
    assert lde_p[0].shape == (B, L, n)
    flat_p = (lde_p[0].reshape(B, L * n), lde_p[1].reshape(B, L * n))
    scale_q = LN._lde_scale_planes(log_n, Q, G)
    transform = _jit(RES._coset_eval_q_p)
    for c in range(L):
        ci = jnp.int32(c)
        ((got_lo, got_hi),) = P._coset_eval_pick((flat_p,), ci, n)
        want_lo, want_hi = transform(mono_p, scale_q, ci)
        np.testing.assert_array_equal(np.asarray(got_lo), np.asarray(want_lo))
        np.testing.assert_array_equal(np.asarray(got_hi), np.asarray(want_hi))
    # the u64 LDE of the same monomials, joined: one storage, two forms
    np.testing.assert_array_equal(
        limbs.join_np(np.asarray(flat_p[0]), np.asarray(flat_p[1])),
        np.asarray(lde_from_monomial(jnp.asarray(mono), L)).reshape(B, L * n),
    )


def test_the_rule_reads_only_materialized_commitments_below_min_L_Q():
    stored = jnp.zeros((3, 16), jnp.uint64)
    planes = (jnp.zeros((3, 16), jnp.uint32), jnp.zeros((3, 16), jnp.uint32))
    for oracle in (stored, planes):
        assert [P.coset_is_committed(c, 2, 8, oracle) for c in range(8)] == (
            [True, True] + [False] * 6
        )
        assert all(P.coset_is_committed(c, 8, 8, oracle) for c in range(8))
        # L > Q: every coset of the (smaller) quotient domain is committed
        assert all(P.coset_is_committed(c, 4, 2, oracle) for c in range(2))
    # a streamed commit kept no storage; the shifted z has no commitment
    mono = jnp.zeros((3, 8), jnp.uint64)
    assert not P.coset_is_committed(0, 2, 8, MonomialSource(mono, 2))
    assert not P.coset_is_committed(
        0, 2, 8, MonomialPlanesSource((mono, mono), 2)
    )
    assert not P.coset_is_committed(0, 2, 8, None)


def _counters(rep):
    return rep["metrics"]["counters"]


def test_shared_prove_reads_its_commitments_and_keeps_its_bytes(monkeypatch):
    asm, setup, cfg = small_parts()
    L, Q = cfg.fri_lde_factor, setup.vk.effective_quotient_degree()
    proof, rep = baseline()
    c = _counters(rep)
    assert c["quotient.coset_evals_reused"] == 3 * min(L, Q) == 6
    assert c["ntt.coset_evals"] == 4 * Q - 3 * min(L, Q) == 26
    # the same prove with every evaluation transformed, as before ISSUE 27
    monkeypatch.setattr(P, "coset_is_committed", lambda *a: False)
    plain, plain_rep = prove_recorded("no_reuse")
    c = _counters(plain_rep)
    assert c["quotient.coset_evals_reused"] == 0
    assert c["ntt.coset_evals"] == 4 * Q
    assert plain.to_json() == proof.to_json()
    assert checkpoint_stream(plain_rep) == checkpoint_stream(rep)
