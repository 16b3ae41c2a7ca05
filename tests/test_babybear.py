"""BabyBear field backend (ISSUE 19): one u32 lane = one field element.

The tentpole swaps the limb-plane machinery for a plane-free kernel set
when BOOJUM_TPU_FIELD=babybear: p = 2^31 - 2^27 + 1 fits a single u32
lane, so every HBM-bound kernel moves HALF the bytes of its Goldilocks
(lo, hi)-plane twin and no split/join conversion exists anywhere. These
tests pin:

- field ops: scalar / numpy / device parity on random AND near-p
  boundary values; inverse/pow identities; the GF(p^4) = GF(p)[w]/(w^4
  - 11) extension tower (w^4 = 11, mul assoc/commute, ext_inv * x = 1,
  Frobenius-based device inverse == scalar inverse);
- NTT/LDE: device transforms match the numpy reference twins and
  round-trip;
- the 2^10 mini-STARK e2e: device prove accepted by its own verifier,
  Fiat-Shamir checkpoint stream DETERMINISTIC across runs and
  bit-identical between the device and NumPy-reference backends, the
  verifier actually rejecting a corrupted proof;
- ZERO limb.splits / limb.joins during a BabyBear prove (there are no
  planes to convert) while the `_bb` kernel counters move;
- the dispatcher: `enumerate_kernels` emits the `_bb` set under the env
  var (and never otherwise), the set lowers on CPU via
  `precompile(lower_only=True)`, limb residency is vetoed, the shape
  bucket key / AOT variant fingerprint carry the field, and the
  Goldilocks key stays byte-identical with the env unset;
- the cost model: `_bb` kernels are costed at elem_bytes=4 — exactly
  half the HBM bytes of the same-geometry Goldilocks kernel (the >= 2x
  byte-reduction claim, pinned per family) — and the report validator
  REJECTS a line claiming field=babybear while counting limb
  conversions.
"""

import functools
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from boojum_tpu.field import babybear as bb
from boojum_tpu.field.spec import BABYBEAR, GOLDILOCKS

# near-p boundary values: wraparound coverage for every binary op
EDGE = [0, 1, 2, bb.P - 1, bb.P - 2, bb.P // 2, (1 << 27), (1 << 27) - 1]


def _rng(seed=0):
    return np.random.default_rng(seed)


def _rand_vals(k=64, seed=0):
    return [int(v) for v in _rng(seed).integers(0, bb.P, k)] + EDGE


# ---------------------------------------------------------------------------
# Base field ops: scalar / numpy / device parity + identities
# ---------------------------------------------------------------------------


def test_spec_constants():
    assert bb.P == 2013265921 == (1 << 31) - (1 << 27) + 1
    assert BABYBEAR.two_adicity == 27
    assert BABYBEAR.half == (bb.P + 1) // 2
    assert pow(BABYBEAR.radix2_subgroup_generator, 1 << 27, bb.P) == 1
    assert pow(BABYBEAR.radix2_subgroup_generator, 1 << 26, bb.P) != 1
    # one u32 lane per element vs the Goldilocks 64-bit element
    assert BABYBEAR.elem_bytes == 4 and GOLDILOCKS.elem_bytes == 8
    # report.py re-declares the backend names (standalone-load rule,
    # like its id-format regexes) — keep them in lockstep with SPECS
    from boojum_tpu.field.spec import SPECS
    from boojum_tpu.utils.report import FIELD_NAMES

    assert set(FIELD_NAMES) == set(SPECS)


def test_scalar_numpy_device_parity():
    import jax.numpy as jnp

    vals = _rand_vals(seed=1)
    a = np.array(vals, dtype=np.uint32)
    b = np.array(list(reversed(vals)), dtype=np.uint32)
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    for name, s_fn, np_fn, dev_fn in [
        ("add", bb.add_s, bb.add_np, bb.add),
        ("sub", bb.sub_s, bb.sub_np, bb.sub),
        ("mul", bb.mul_s, bb.mul_np, bb.mul),
    ]:
        want = np.array(
            [s_fn(int(x), int(y)) for x, y in zip(a, b)], dtype=np.uint32
        )
        assert np.array_equal(np_fn(a, b), want), name
        assert np.array_equal(np.asarray(dev_fn(aj, bj)), want), name
    want_neg = np.array([bb.neg_s(int(x)) for x in a], dtype=np.uint32)
    assert np.array_equal(np.asarray(bb.neg(aj)), want_neg)
    want_sqr = np.array([bb.mul_s(int(x), int(x)) for x in a], np.uint32)
    assert np.array_equal(np.asarray(bb.sqr(aj)), want_sqr)


def test_inverse_and_pow_identities():
    import jax.numpy as jnp

    vals = [v for v in _rand_vals(seed=2) if v != 0]
    for v in vals:
        assert bb.mul_s(v, bb.inv_s(v)) == 1
        assert bb.pow_s(v, bb.P - 1) == 1
    arr = jnp.asarray(np.array(vals, dtype=np.uint32))
    inv = np.asarray(bb.batch_inverse_xla(arr))
    for v, iv in zip(vals, inv):
        assert bb.mul_s(int(v), int(iv)) == 1
    # device pow_const against scalar pow on an awkward exponent
    e = (bb.P - 3) // 5
    want = np.array([bb.pow_s(int(v), e) for v in vals], dtype=np.uint32)
    assert np.array_equal(np.asarray(bb.pow_const(arr, e)), want)


# ---------------------------------------------------------------------------
# GF(p^4) extension tower
# ---------------------------------------------------------------------------


def _rand_ext(seed):
    r = _rng(seed)
    return tuple(int(v) for v in r.integers(0, bb.P, 4))


def test_ext_tower_nonresidue():
    w = (0, 1, 0, 0)
    w2 = bb.ext_mul_s(w, w)
    w4 = bb.ext_mul_s(w2, w2)
    assert w4 == (bb.EXT_NONRESIDUE, 0, 0, 0) == (11, 0, 0, 0)


def test_ext_mul_commutes_and_associates():
    a, b, c = _rand_ext(3), _rand_ext(4), _rand_ext(5)
    assert bb.ext_mul_s(a, b) == bb.ext_mul_s(b, a)
    assert bb.ext_mul_s(bb.ext_mul_s(a, b), c) == bb.ext_mul_s(
        a, bb.ext_mul_s(b, c)
    )
    # distributes over add
    assert bb.ext_mul_s(a, bb.ext_add_s(b, c)) == bb.ext_add_s(
        bb.ext_mul_s(a, b), bb.ext_mul_s(a, c)
    )


def test_ext_inverse_scalar_and_device():
    import jax.numpy as jnp

    cases = [_rand_ext(s) for s in range(6, 14)]
    # boundary coords too: elements with near-p coordinates
    cases += [(bb.P - 1, 0, bb.P - 2, 1), (1, bb.P - 1, 0, bb.P - 1)]
    for x in cases:
        assert bb.ext_mul_s(x, bb.ext_inv_s(x)) == bb.ONE_S
    # device Frobenius/norm inverse == scalar inverse, vectorized
    arrs = tuple(
        jnp.asarray(np.array([c[k] for c in cases], np.uint32))
        for k in range(4)
    )
    inv = bb.ext_inv(arrs)
    for i, x in enumerate(cases):
        got = tuple(int(np.asarray(inv[k])[i]) for k in range(4))
        assert got == bb.ext_inv_s(x), x
    # numpy twin
    inv_np = bb.ext_inv_np(tuple(np.array([c[k] for c in cases],
                                          np.uint32) for k in range(4)))
    for i, x in enumerate(cases):
        got = tuple(int(inv_np[k][i]) for k in range(4))
        assert got == bb.ext_inv_s(x), x


def test_ext_frobenius_fixes_base():
    base = bb.ext_from_base_s(123456789)
    for k in range(1, 4):
        assert bb.ext_frobenius_s(base, k) == base
    x = _rand_ext(15)
    # frobenius^4 = identity
    y = x
    for _ in range(4):
        y = bb.ext_frobenius_s(y, 1)
    assert y == x


# ---------------------------------------------------------------------------
# NTT / LDE twins
# ---------------------------------------------------------------------------


def test_ntt_roundtrip_and_numpy_parity():
    from boojum_tpu.ntt import bb_ntt

    log_n, B = 8, 3
    n = 1 << log_n
    x = _rng(7).integers(0, bb.P, (B, n)).astype(np.uint32)
    mono_np = bb_ntt.ntt_np(x, inverse=True)
    back = bb_ntt.ntt_np(mono_np, inverse=False)
    assert np.array_equal(back, x)
    import jax.numpy as jnp

    mono_dev = np.asarray(
        bb_ntt.monomial_from_values_bb(jnp.asarray(x), log_n)
    )
    assert np.array_equal(mono_dev, mono_np)
    vals_dev = np.asarray(
        bb_ntt.values_from_monomial_bb(jnp.asarray(mono_np), log_n)
    )
    assert np.array_equal(vals_dev, x)


def test_lde_device_numpy_parity_and_pointwise():
    from boojum_tpu.ntt import bb_ntt
    import jax.numpy as jnp

    log_n, L = 6, 4
    n = 1 << log_n
    shift = BABYBEAR.multiplicative_generator
    mono = _rng(8).integers(0, bb.P, (2, n)).astype(np.uint32)
    lde_np = bb_ntt.lde_np(mono, L, shift)
    lde_dev = np.asarray(
        bb_ntt.lde_from_monomial_bb(jnp.asarray(mono), log_n, L, shift)
    )
    assert np.array_equal(lde_dev, lde_np)
    # natural-order contract: out[j] = f(shift * w_N^j)
    wN = bb.omega(log_n + 2)
    coeffs = [int(c) for c in mono[0]]
    for j in [0, 1, 5, n * L - 1]:
        xj = bb.mul_s(shift, bb.pow_s(wN, j))
        want = 0
        for i in reversed(range(n)):
            want = bb.add_s(bb.mul_s(want, xj), coeffs[i])
        assert int(lde_np[0, j]) == want, j


# ---------------------------------------------------------------------------
# 2^10 e2e: prove -> verify, checkpoint determinism, backend parity
# ---------------------------------------------------------------------------


def _checkpointed_prove(backend_factory):
    from boojum_tpu.prover.bb_prover import BBProofConfig, prove_babybear
    from boojum_tpu.utils.report import (
        CheckpointLog,
        install_checkpoint_log,
    )

    log = CheckpointLog()
    prev = install_checkpoint_log(log)
    try:
        proof = prove_babybear(
            pub=5, cfg=BBProofConfig(log_n=10),
            backend=backend_factory(),
        )
    finally:
        install_checkpoint_log(prev)
    return proof, log.entries


@functools.lru_cache(maxsize=1)
def _reference_runs():
    from boojum_tpu.compat.prove_reference_bb import NumpyBackendBB

    return (
        _checkpointed_prove(NumpyBackendBB),
        _checkpointed_prove(NumpyBackendBB),
    )


@functools.lru_cache(maxsize=1)
def _device_run():
    """ONE device-backend 2^10 prove shared by the e2e tests, recorded
    under a metrics registry (the zero-conversion guard reads it)."""
    from boojum_tpu.prover.bb_prover import DeviceBackendBB
    from boojum_tpu.utils import metrics

    reg = metrics.start_metrics()
    try:
        proof, entries = _checkpointed_prove(DeviceBackendBB)
    finally:
        metrics.stop_metrics()
    return proof, entries, reg.to_dict()


def test_e2e_device_prove_verifies():
    from boojum_tpu.prover.bb_verifier import check_babybear

    proof, _, _ = _device_run()
    ok, reason = check_babybear(proof)
    assert ok, reason


def test_e2e_reference_prove_verifies_and_is_deterministic():
    from boojum_tpu.prover.bb_verifier import check_babybear

    (p1, e1), (p2, e2) = _reference_runs()
    ok, reason = check_babybear(p1)
    assert ok, reason
    # Fiat-Shamir checkpoint stream: deterministic across runs
    assert e1 == e2
    assert [e["label"] for e in e1][:4] == [
        "bb_params", "witness_cap", "alpha", "quotient_cap",
    ]
    assert e1[-1]["label"] == "query_indices"


def test_e2e_device_matches_reference_checkpoints():
    """Backend parity by construction: the device and numpy backends
    must produce the same checkpoint stream (same transcript, same
    challenges, same committed caps) — any device-kernel divergence from
    the reference leg lands here."""
    _, dev_entries, _ = _device_run()
    (_, ref_entries), _ = _reference_runs()
    assert dev_entries == ref_entries


def test_e2e_verifier_rejects_corruption():
    import dataclasses

    from boojum_tpu.prover.bb_verifier import check_babybear

    (proof, _), _ = _reference_runs()
    bad = dataclasses.replace(
        proof,
        evals={**proof.evals, "wz": bb.ext_add_s(proof.evals["wz"],
                                                 bb.ONE_S)},
    )
    ok, _ = check_babybear(bad)
    assert not ok
    bad2 = dataclasses.replace(proof, pub=(proof.pub + 1) % bb.P)
    ok2, _ = check_babybear(bad2)
    assert not ok2


def test_zero_limb_conversions_during_bb_prove():
    """THE plane-free guard: a BabyBear prove records ZERO limb
    conversions of any kind — interior OR edge — because there are no
    (lo, hi) planes anywhere on the path; meanwhile the `_bb` kernel
    counters all moved (the guard is not vacuous)."""
    _, _, md = _device_run()
    c = md["counters"]
    for k in ("limb.splits", "limb.joins", "limb.host_splits",
              "limb.host_joins"):
        assert c.get(k, 0) == 0, (k, c)
    assert c["quotient.bb_coset_sweeps"] >= 1
    assert c["deep.bb_accumulates"] >= 1
    assert c["fri.bb_folds"] >= 6
    assert c["merkle.bb_commits"] >= 8


# ---------------------------------------------------------------------------
# Dispatcher: variant selection, lowering, cache keys
# ---------------------------------------------------------------------------


def _fma_cfg_asm():
    from proving import fma_assembly, small_config

    return fma_assembly(), small_config()


def test_dispatcher_selects_bb_set_and_vetoes_limbs(monkeypatch):
    from boojum_tpu.prover.precompile import enumerate_kernels
    from boojum_tpu.prover.aot import variant_fingerprint
    from boojum_tpu.utils.pallas_util import resolve_variant
    from boojum_tpu.prover.shape_key import shape_bucket

    asm, cfg = _fma_cfg_asm()
    monkeypatch.delenv("BOOJUM_TPU_FIELD", raising=False)
    key_gl = shape_bucket(asm, cfg).key
    assert ":F" not in key_gl  # byte-identical pre-seam Goldilocks key
    assert variant_fingerprint()["field"] == "goldilocks"
    assert not any("_bb" in s.name for s in enumerate_kernels(asm, cfg))

    monkeypatch.setenv("BOOJUM_TPU_FIELD", "babybear")
    # even with limb residency forced on, babybear vetoes it
    monkeypatch.setenv("BOOJUM_TPU_LIMB_RESIDENT", "1")
    assert resolve_variant().planes is False
    asm._shape_bucket_cache = {}
    assert shape_bucket(asm, cfg).key == key_gl + ":Fbabybear"
    assert variant_fingerprint()["field"] == "babybear"
    specs = enumerate_kernels(asm, cfg)
    names = [s.name for s in specs]
    assert names and all("_bb" in n for n in names)
    assert any(n.startswith("coset_sweep_terms_bb") for n in names)
    assert any(n.startswith("fri_fold_bb_k1") for n in names)
    asm._shape_bucket_cache = {}


def test_bb_enumeration_lowers_on_cpu(monkeypatch):
    from boojum_tpu.prover.precompile import enumerate_kernels, precompile

    monkeypatch.setenv("BOOJUM_TPU_FIELD", "babybear")
    asm, cfg = _fma_cfg_asm()
    asm._shape_bucket_cache = {}
    specs = enumerate_kernels(asm, cfg)
    assert all("_bb" in s.name for s in specs)
    precompile(asm, cfg, specs=specs, lower_only=True, max_workers=2)
    asm._shape_bucket_cache = {}


# ---------------------------------------------------------------------------
# Cost model: half the HBM bytes, stamped field, lying lines rejected
# ---------------------------------------------------------------------------


def test_bb_kernels_cost_half_the_hbm_bytes():
    """The perf claim, pinned analytically per family: every byte term
    of the plane-free kernels scales by elem_bytes=4 against the
    8-byte Goldilocks element — exactly 2x fewer HBM bytes for the
    same geometry (flops deliberately reuse the u64 calibration as a
    conservative upper bound, so only bytes are pinned)."""
    from boojum_tpu.utils import costmodel as cm

    for fam_gl, fam_bb in [
        (cm.ntt_cost(16, 1 << 10), cm.ntt_cost(16, 1 << 10, 4.0)),
        (cm.lde_cost(16, 1 << 10, 4), cm.lde_cost(16, 1 << 10, 4, 4.0)),
        (cm.sweep_cost(1 << 12, 8.0), cm.sweep_cost(1 << 12, 8.0, 4.0)),
        (cm.deep_cost(5, 1 << 12), cm.deep_cost(5, 1 << 12, 4.0)),
        (cm.fold_cost(1 << 12), cm.fold_cost(1 << 12, 1, 4.0)),
        (cm.binv_cost(1 << 12), cm.binv_cost(1 << 12, 4.0)),
    ]:
        assert fam_bb["hbm_bytes"] * 2 == fam_gl["hbm_bytes"]
        assert fam_bb["hbm_bytes"] > 0


def test_bb_cost_sheet_covers_enumeration(monkeypatch):
    from boojum_tpu.prover.precompile import enumerate_kernels
    from boojum_tpu.utils import costmodel as cm

    monkeypatch.setenv("BOOJUM_TPU_FIELD", "babybear")
    asm, cfg = _fma_cfg_asm()
    asm._shape_bucket_cache = {}
    specs = enumerate_kernels(asm, cfg)
    sheet = cm.cost_sheet(specs)
    assert set(sheet) == {s.name for s in specs}
    for name, ent in sheet.items():
        assert ent["hbm_bytes"] > 0, name
        assert ent["family"] not in ("fallback", "error"), name
        assert ent["field"] == "babybear", name
        assert ent["elem_bytes"] == 4, name
    asm._shape_bucket_cache = {}


def test_check_gate_rejects_babybear_lie():
    """`prove_report.py --check` FAILS a line whose cost record claims
    field=babybear while the same line counted limb conversions — the
    one thing a BabyBear prove can never do — and rejects unknown field
    names outright."""
    from boojum_tpu.utils.report import validate_report

    line = {
        "kind": "x", "schema": 0, "wall_s": 0.0,
        "cost": {"field": "babybear"},
        "metrics": {"counters": {"limb.splits": 3, "limb.joins": 0}},
    }
    probs = validate_report(line)
    assert any("claims field=babybear" in p for p in probs), probs
    line["metrics"]["counters"] = {"limb.splits": 0, "limb.joins": 0}
    assert not any(
        "claims field=babybear" in p for p in validate_report(line)
    )
    line["cost"]["field"] = "mersenne31"
    assert any("cost record field" in p for p in validate_report(line))
