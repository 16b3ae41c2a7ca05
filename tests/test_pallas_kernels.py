"""Parity tests for the u32-limb field forms and the Pallas TPU kernels.

The limb ops are pure jnp and run anywhere; the kernels run in interpret
mode here (the CPU suite) and as real Mosaic kernels on TPU — dispatchers in
hashes/poseidon2.py and ntt/ntt.py route to them only on the TPU backend, so
everything below pins bit-parity between the two implementations.
"""

import numpy as np
import jax.numpy as jnp
import pytest

# interpret-mode kernel runs compile slowly on XLA:CPU (~30-90s each); the
# full set is the slow lane (-m slow) and runs on real TPU hardware via the
# bench + scripts, while tier-1 keeps one per kernel family.
slow_only = pytest.mark.slow

from boojum_tpu.field import gl, limbs
from boojum_tpu.field import goldilocks as gf
from boojum_tpu.field import extension as ext
from boojum_tpu.hashes import poseidon2_params as params

# M_I's diagonal as exponents, as the kernel derives them
DIAG_LOG2 = [d.bit_length() - 1 for d in params.M_I_DIAGONAL]


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, gl.P, size=shape, dtype=np.uint64)


EDGE = np.array(
    [0, 1, 2, gl.P - 1, gl.P - 2, 0xFFFFFFFF, 0x100000000, gl.P >> 1],
    dtype=np.uint64,
)


class TestLimbOps:
    def setup_method(self, _):
        a64 = np.concatenate([_rand(1 << 10, 10), EDGE, EDGE])
        b64 = np.concatenate([_rand(1 << 10, 11), EDGE, EDGE[::-1].copy()])
        self.a64, self.b64 = jnp.asarray(a64), jnp.asarray(b64)
        self.a = limbs.split(self.a64)
        self.b = limbs.split(self.b64)

    def _eq(self, got_pair, want64):
        assert np.array_equal(
            np.asarray(limbs.join(got_pair)), np.asarray(want64)
        )

    def test_add_sub_mul(self):
        self._eq(limbs.add(self.a, self.b), gf.add(self.a64, self.b64))
        self._eq(limbs.sub(self.a, self.b), gf.sub(self.a64, self.b64))
        self._eq(limbs.mul(self.a, self.b), gf.mul(self.a64, self.b64))

    def test_unary(self):
        self._eq(limbs.sqr(self.a), gf.sqr(self.a64))
        self._eq(limbs.neg(self.a), gf.neg(self.a64))
        self._eq(limbs.double(self.a), gf.double(self.a64))

    def test_mul_const(self):
        c = gl.RADIX_2_SUBGROUP_GENERATOR
        self._eq(
            limbs.mul_const(self.a, limbs.const_pair(c)),
            gf.mul(self.a64, jnp.uint64(c)),
        )

    # the twelve exponents of M_I's diagonal (k = 0 among them) and both
    # ends of the range the op takes
    @pytest.mark.parametrize("k", DIAG_LOG2 + [1, 31])
    def test_mul_pow2(self, k):
        want = gf.mul(self.a64, jnp.uint64(1 << k))
        self._eq(limbs.mul_pow2(self.a, k), want)
        self._eq(
            limbs.mul_pow2(self.a, k, plus=self.b), gf.add(want, self.b64)
        )

    def test_mul_pow2_per_row(self):
        """One exponent per leading row, one reduction on the restack, the
        broadcast addend folded in: the internal matrix's own call."""
        rows = np.stack([np.roll(np.asarray(self.a64), i) for i in range(12)])
        pows = np.array(params.M_I_DIAGONAL, dtype=np.uint64)[:, None]
        want = gf.add(gf.mul(jnp.asarray(rows), jnp.asarray(pows)), self.b64)
        got = limbs.mul_pow2(
            limbs.split(jnp.asarray(rows)), tuple(DIAG_LOG2), plus=self.b
        )
        self._eq(got, want)

    def test_ext_mul(self):
        got = limbs.ext_mul((self.a, self.b), (self.b, self.a))
        want = ext.mul((self.a64, self.b64), (self.b64, self.a64))
        for g, w in zip(got, want):
            self._eq(g, w)

    def test_split_join_roundtrip(self):
        self._eq(self.a, self.a64)


def _edge_states():
    """Width-12 states made of EDGE values: every rotation, so that each
    value meets each row of the internal matrix."""
    return np.stack([np.roll(np.resize(EDGE, 12), i) for i in range(12)])


def _host_permutation(states):
    """Python integers only: none of the repo's device code."""
    from boojum_tpu.hashes.poseidon2 import poseidon2_permutation_host

    return np.array(
        [poseidon2_permutation_host([int(x) for x in row]) for row in states],
        dtype=np.uint64,
    )


def _count_eqns(jaxpr):
    """Equations of a jaxpr, those of nested jaxprs (loop bodies, inner
    jits) in place of the equation that holds them."""
    n = 0
    for eqn in jaxpr.eqns:
        inner = [
            getattr(v, "jaxpr", v)
            for p in eqn.params.values()
            for v in (p if isinstance(p, (tuple, list)) else (p,))
            if hasattr(getattr(v, "jaxpr", v), "eqns")
        ]
        n += sum(_count_eqns(j) for j in inner) if inner else 1
    return n


class TestPoseidon2Kernel:
    def test_permutation_interpret(self):
        from boojum_tpu.hashes import poseidon2 as p2
        from boojum_tpu.hashes import pallas_poseidon2 as pp2

        state = np.concatenate([_rand((244, 12), 20), _edge_states()])
        got = np.asarray(pp2.permutation(jnp.asarray(state), interpret=True))
        want = p2.poseidon2_permutation_xla(jnp.asarray(state))
        assert np.array_equal(got, np.asarray(want))
        # and against python integers, so that a fault shared by the limb
        # and the u64 device code cannot pass
        assert np.array_equal(got[-24:], _host_permutation(state[-24:]))

    def test_permutation_planes_xla_known_answer(self):
        """The limb XLA twin (the same round body as the kernel) against
        the host permutation on edge-value and random states."""
        from boojum_tpu.hashes import poseidon2 as p2

        state = np.concatenate([_edge_states(), _rand((20, 12), 24)])
        got = p2.poseidon2_permutation_planes_xla(
            limbs.split(jnp.asarray(state))
        )
        assert np.array_equal(
            np.asarray(limbs.join(got)), _host_permutation(state)
        )

    def test_round_body_traces_no_more_equations(self):
        """The tracing bill: this body is inlined in a dozen kernels and
        traced again by each in every process. Limits are the counts on
        the tree before PR 25 (full multiply by the diagonal): 4803 for the
        permutation, 582 for the internal matrix of one partial round."""
        import jax
        from boojum_tpu.hashes import pallas_poseidon2 as pp2

        rc = jnp.asarray(pp2.rc_table())
        lo = jnp.zeros((12, 8, 128), jnp.uint32)
        perm = jax.make_jaxpr(pp2._permutation_planes_stacked)(rc, lo, lo)
        assert _count_eqns(perm.jaxpr) <= 4803
        mds = jax.make_jaxpr(pp2._internal_mds_planes)(lo, lo)
        assert _count_eqns(mds.jaxpr) <= 582

    @slow_only
    def test_sponge_interpret(self):
        from boojum_tpu.hashes import poseidon2 as p2
        from boojum_tpu.hashes import pallas_poseidon2 as pp2

        for width in (8, 9, 21):
            vals = jnp.asarray(_rand((256, width), 21))
            got = pp2.sponge_hash(vals, interpret=True)
            want = p2.leaf_hash_xla(vals)
            assert np.array_equal(np.asarray(got), np.asarray(want)), width

    @slow_only
    def test_node_hash_shape_via_sponge(self):
        from boojum_tpu.hashes import poseidon2 as p2
        from boojum_tpu.hashes import pallas_poseidon2 as pp2

        left = jnp.asarray(_rand((256, 4), 22))
        right = jnp.asarray(_rand((256, 4), 23))
        got = pp2.sponge_hash(
            jnp.concatenate([left, right], axis=-1), interpret=True
        )
        want = p2.node_hash_xla(left, right)
        assert np.array_equal(np.asarray(got), np.asarray(want))


# The grid step's rows (ISSUE 29): one rule, `pallas_poseidon2.step_rows`.
# Shapes are the three cells' own calls: 20 / 12 chunks the witness leaves
# (Era, anchor), 8 / 6 stage 2, 2 the quotient, 1 a node layer, at 2^19
# leaves (R 4096; the first node layer has 2^18 nodes, R 2048) and at the
# small cell's 2^17 (R 1024, 512).
@pytest.mark.parametrize(
    "chunks, R, want",
    [
        (20, 4096, 8), (12, 4096, 8), (8, 4096, 8),
        (6, 4096, 8), (2, 4096, 8), (1, 2048, 8),
        (20, 1024, 8), (12, 1024, 8), (8, 1024, 8),
        (6, 1024, 8), (2, 1024, 8), (1, 512, 8),
        (1, 8, 8),  # the smallest batch the dispatchers send (1024 states)
        (1, 2, 2), (3, 4, 4),  # R < 8: the whole axis
        (1, 12, 12), (2, 100, 100),  # no multiple of 8 divides R
        # the widest leaf the dispatcher sends (1024 values): 8 rows where
        # they are legal; a whole axis past the VMEM budget is refused
        (128, 4096, 8), (128, 12, ValueError), (20, 100, ValueError),
    ],
)
def test_poseidon2_step_rows(chunks, R, want):
    from boojum_tpu.hashes import pallas_poseidon2 as pp2

    if want is ValueError:
        with pytest.raises(ValueError):
            pp2.step_rows(chunks, R)
        return
    tile = pp2.step_rows(chunks, R)
    assert tile == want
    # legal for Mosaic: divides R, and a multiple of 8 or R itself
    assert R % tile == 0 and (tile % 8 == 0 or tile == R)


@pytest.fixture(scope="module")
def tile_cases():
    """Inputs of 8192 states (R 64: every tile below divides it) with the
    digests `test_sponge_interpret` / `test_permutation_interpret` hold
    the kernels to: the u64 XLA twins, and the host's python integers on
    the edge states."""
    from boojum_tpu.hashes import poseidon2 as p2

    state = np.concatenate([_rand((8192 - 12, 12), 25), _edge_states()])
    vals = _rand((8192, 21), 26)  # three chunks, the last one padded
    return {
        "permute": (state, np.asarray(
            p2.poseidon2_permutation_xla(jnp.asarray(state)))),
        "sponge": (vals, np.asarray(p2.leaf_hash_xla(jnp.asarray(vals)))),
    }


@pytest.mark.parametrize("tile", [8, 16, 64])
@pytest.mark.parametrize("kernel", ["permute", "sponge"])
def test_poseidon2_kernels_equal_at_every_tile(
    tile_cases, monkeypatch, kernel, tile
):
    """`_permute_planes` and `_sponge_planes` (interpret mode) give the
    same digests whatever the grid step: the rule is free to choose."""
    from boojum_tpu.hashes import pallas_poseidon2 as pp2

    monkeypatch.setattr(pp2, "step_rows", lambda chunks, R: tile)
    data, want = tile_cases[kernel]
    fn = pp2.permutation if kernel == "permute" else pp2.sponge_hash
    got = np.asarray(fn(jnp.asarray(data), interpret=True))
    assert np.array_equal(got, want)
    if kernel == "permute":
        assert np.array_equal(got[-12:], _host_permutation(data[-12:]))


class TestMXUNTTKernel:
    """Bit-parity of the MXU matmul-NTT (ntt/mxu_ntt.py) vs the staged-XLA
    path. Interpret mode executes the same exact-integer int8/i32 ops on
    CPU, so equality here pins the kernel's arithmetic, including the
    balanced-digit int8 dots and the biased 15-diagonal mod-p fold."""

    LOG_N = 14  # smallest MXU-dispatched size

    def test_balanced_digits_boundaries(self):
        """The host digit bake and the in-kernel extraction agree and
        reconstruct x mod p for every branch of the x -> x-p switch:
        x <= M (plain), x > M (two's-complement subtract), and the carry
        chain's saturating bytes."""
        from boojum_tpu.ntt.mxu_ntt import _M_BAL, _digits8_np

        cases = np.array(
            [0, 1, 127, 128, 255, 256, _M_BAL - 1, _M_BAL, _M_BAL + 1,
             (1 << 32) - 1, 1 << 32, (1 << 63) - 1, 1 << 63,
             gl.P - 1, gl.P - 2, gl.P - (1 << 32)],
            dtype=np.uint64,
        )
        digs = np.asarray(_digits8_np(cases)).astype(np.int64)
        for i, x in enumerate(cases):
            v = sum(int(digs[k, i]) * (1 << (8 * k)) for k in range(8))
            assert (v - int(x)) % gl.P == 0, hex(int(x))
            assert all(-128 <= int(digs[k, i]) <= 127 for k in range(8))

    def test_kernel_digit_planes_boundaries(self):
        """Pin the KERNEL-side digit extraction (_digit_planes: u32-pair
        gt comparison, lo!=0 carry, byte carry chain) at the exact _M_BAL
        tie-break — hi == 0x7F7F7F7F with lo on/around the boundary — and
        at the lo==0 carry special case; the host bake (_digits8_np) is the
        independently-implemented reference."""
        from boojum_tpu.field import limbs
        from boojum_tpu.ntt.mxu_ntt import _M_BAL, _digit_planes, _digits8_np

        cases = np.array(
            [_M_BAL - 1, _M_BAL, _M_BAL + 1,
             # hi exactly at the tie-break word, lo sweeping the switch
             (0x7F7F7F7F << 32) | 0x00000000,
             (0x7F7F7F7F << 32) | 0x7F7F7F7E,
             (0x7F7F7F7F << 32) | 0x7F7F7F7F,
             (0x7F7F7F7F << 32) | 0x7F7F7F80,
             (0x7F7F7F7F << 32) | 0xFFFFFFFF,
             # x > M with lo == 0: the (gt & lo != 0) carry branch
             1 << 63, (0x80000000 << 32),
             (0xFFFFFFFF << 32), gl.P - 1, gl.P - (1 << 32)],
            dtype=np.uint64,
        )
        want = np.asarray(_digits8_np(cases)).astype(np.int64)
        lo, hi = limbs.split_np(cases)
        got_planes = _digit_planes((jnp.asarray(lo), jnp.asarray(hi)))
        got = np.stack([np.asarray(p) for p in got_planes]).astype(np.int64)
        assert (got == want).all(), np.nonzero((got != want).any(axis=0))

    def _data(self, log_n, cols=2, seed=30):
        a = _rand((cols, 1 << log_n), seed)
        # adversarial rows: all p-1 (max limbs everywhere) and small values
        a[0, :] = gl.P - 1
        return jnp.asarray(a)

    def test_fwd_inv_interpret(self):
        from boojum_tpu.ntt import ntt
        from boojum_tpu.ntt import mxu_ntt

        a = self._data(self.LOG_N)
        want = ntt.fft_natural_to_bitreversed_xla(a)
        got = mxu_ntt.fft_natural_to_bitreversed(a, interpret=True)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        wanti = ntt.ifft_bitreversed_to_natural_xla(want)
        goti = mxu_ntt.ifft_bitreversed_to_natural(want, interpret=True)
        assert np.array_equal(np.asarray(goti), np.asarray(wanti))

    @slow_only
    def test_fwd_inv_interpret_all_sizes(self):
        from boojum_tpu.ntt import ntt
        from boojum_tpu.ntt import mxu_ntt

        for log_n in (15, 16):
            a = self._data(log_n, cols=1, seed=31 + log_n)
            want = ntt.fft_natural_to_bitreversed_xla(a)
            got = mxu_ntt.fft_natural_to_bitreversed(a, interpret=True)
            assert np.array_equal(np.asarray(got), np.asarray(want)), log_n
            wanti = ntt.ifft_bitreversed_to_natural_xla(want)
            goti = mxu_ntt.ifft_bitreversed_to_natural(want, interpret=True)
            assert np.array_equal(np.asarray(goti), np.asarray(wanti)), log_n

    @slow_only
    def test_hybrid_interpret(self):
        """2^17: one XLA outer stage + two per-block 2^16 kernels."""
        from boojum_tpu.ntt import ntt
        from boojum_tpu.ntt import mxu_ntt

        a = self._data(17, cols=1, seed=33)
        want = ntt.fft_natural_to_bitreversed_xla(a)
        got = mxu_ntt.fft_natural_to_bitreversed(a, interpret=True)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        wanti = ntt.ifft_bitreversed_to_natural_xla(want)
        goti = mxu_ntt.ifft_bitreversed_to_natural(want, interpret=True)
        assert np.array_equal(np.asarray(goti), np.asarray(wanti))

    def test_monomials_above_the_kernel_ceiling_run_apart(self, monkeypatch):
        """`monomial_from_values` above the single-kernel ceiling on the
        MXU path: the bit reversal, the per-block inverse kernels and the
        outer stages as separate programs (as ONE program, 10 columns of
        2^18 did not come back on the v5e: PERF.md, PR 32), equal to the
        staged-XLA inverse; traced into a caller's jit it stays the one
        program. The ceiling is lowered to the smallest kernel so that
        2^15 is a hybrid size interpret mode can afford."""
        import jax

        from boojum_tpu.ntt import mxu_ntt, ntt

        a = self._data(self.LOG_N + 1, cols=3, seed=35)
        want = ntt._monomial_from_values_jit(a)  # XLA: off a TPU
        monkeypatch.setattr(mxu_ntt, "MAX_LOG_N", self.LOG_N)
        monkeypatch.setattr(ntt, "_mxu_ntt_ready", lambda n, ctx: True)
        apart = mxu_ntt.ifft_hybrid_apart
        calls = []

        def interpreted(x, log_n):
            calls.append((tuple(x.shape), log_n))
            return apart(x, log_n, True)

        monkeypatch.setattr(mxu_ntt, "ifft_hybrid_apart", interpreted)
        got = ntt.monomial_from_values(a)
        assert calls == [((3, 1 << (self.LOG_N + 1)), self.LOG_N + 1)]
        assert np.array_equal(np.asarray(got), np.asarray(want))
        names = [name for name, _fn, _args in ntt.ntt_kernel_specs(
            3, self.LOG_N + 1)]
        assert [n.rsplit("_b", 1)[0] for n in names] == [
            "imono_brev", "imono_blocks", "imono_outer"]
        jax.make_jaxpr(ntt.monomial_from_values)(a)
        assert len(calls) == 1

    def test_lde_interpret(self):
        from boojum_tpu.ntt import ntt
        from boojum_tpu.ntt import mxu_ntt

        co = self._data(self.LOG_N, cols=1, seed=34)
        want = ntt._lde_from_monomial_jit(co, 4)
        scale = ntt._lde_scale_cached(
            self.LOG_N, 4, gl.MULTIPLICATIVE_GENERATOR % gl.P
        )
        got = mxu_ntt.lde_from_monomial(co, scale, interpret=True)
        assert np.array_equal(np.asarray(got), np.asarray(want))
