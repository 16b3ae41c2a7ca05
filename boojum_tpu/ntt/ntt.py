"""Radix-2 NTT / coset LDE over Goldilocks, batched across trace columns.

TPU-native counterpart of the reference FFT layer
(`/root/reference/src/fft/mod.rs:398` fft_natural_to_bitreversed, `:464`
ifft_natural_to_natural, `:308` distribute_powers) and the LDE transform family
(`src/cs/implementations/utils.rs:270`). Instead of 16-lane SIMD butterflies,
every stage is one whole-array reshape+butterfly expressed in jnp; XLA fuses
the modular-arithmetic ops and tiles them on the VPU. Columns batch along
leading axes, so one call transforms the entire witness at once.

Domain conventions (chosen so FRI pairing and Merkle layout are contiguous):
- forward: natural input -> bit-reversed output (Gentleman-Sande / DIF)
- inverse: bit-reversed input -> natural output (Cooley-Tukey / DIT)
- LDE storage: shape (..., lde_factor, n); coset axis is indexed by the
  BIT-REVERSED coset index, each coset internally bit-reversed. Flattening the
  last two axes yields the full 2^(a+b) domain {g·w_N^i} in bit-reversed order
  of i (since brev_N(k·lde + j) = brev(j)·n + brev(k)): FRI fold pairs
  (x, -x) are then adjacent.
"""

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..field import gl
from ..field import extension as ext
from ..field import goldilocks as gf


def bitreverse_indices(log_n: int) -> np.ndarray:
    """Permutation perm[i] = bitreverse(i, log_n) as int32 numpy array."""
    n = 1 << log_n
    idx = np.arange(n, dtype=np.uint32)
    rev = np.zeros_like(idx)
    for b in range(log_n):
        rev |= ((idx >> b) & 1) << (log_n - 1 - b)
    return rev.astype(np.int32)


def powers_device(base: int, count: int) -> jax.Array:
    """[1, b, b^2, ..., b^(count-1)] as a host-built table.

    Host numpy + one upload (or a graph constant when called inside a
    trace): the previous log-doubling DEVICE loop dispatched ~2*log2(count)
    eager executables with shape-unique cache keys — a compile EACH, every
    fresh process, for every twiddle/power table."""
    assert count & (count - 1) == 0, "count must be a power of two"
    # ensure_compile_time_eval: first touch may happen inside a jit trace,
    # where a bare jnp.asarray would yield a (leakable) constant tracer
    with jax.ensure_compile_time_eval():
        return jnp.asarray(_powers_np(base % gl.P, count))


@lru_cache(maxsize=64)
def _powers_np(base: int, count: int) -> np.ndarray:
    return gl.powers_np(base, count)


class NTTContext:
    """Cached twiddle tables for size-2^log_n transforms."""

    def __init__(self, log_n: int):
        assert 0 < log_n <= gl.TWO_ADICITY
        self.log_n = log_n
        self.n = 1 << log_n
        self.omega = gl.omega(log_n)
        self.omega_inv = gl.inv(self.omega)
        half = max(self.n // 2, 1)
        # contexts are cached across jit traces (lru_cache below): build the
        # tables under ensure_compile_time_eval even if first touched inside
        # a trace, or the cached arrays would be leaked tracers
        with jax.ensure_compile_time_eval():
            self.n_inv = jnp.uint64(gl.inv(self.n))
            self.tw = powers_device(self.omega, half) if self.n > 1 else None
            self.itw = (
                powers_device(self.omega_inv, half) if self.n > 1 else None
            )
            self.brev = jnp.asarray(bitreverse_indices(log_n))


@lru_cache(maxsize=None)
def get_ntt_context(log_n: int) -> NTTContext:
    return NTTContext(log_n)


def _mxu_ntt_ready(n: int, ctx) -> bool:
    """True when the MXU matmul-NTT kernel should take this transform.

    Default-ON on TPU (the kernel moves the multiply work onto the systolic
    array and beats the staged-XLA emulated-u64 path; parity is exact);
    `pallas_util.force_xla()` asks for the XLA reference."""
    from ..utils.pallas_util import pallas_enabled

    if not pallas_enabled():
        return False
    from . import mxu_ntt

    if not mxu_ntt.size_fits(n):
        return False
    # custom contexts (non-standard roots) keep the generic path
    return ctx is None or ctx is get_ntt_context(n.bit_length() - 1)


def fft_natural_to_bitreversed(
    a: jax.Array, ctx: NTTContext | None = None
) -> jax.Array:
    """DIF NTT along the last axis; output in bit-reversed order.

    Dispatches to the MXU matmul kernel on TPU (bit-identical results);
    the staged-XLA form below is the generic path."""
    if _mxu_ntt_ready(a.shape[-1], ctx):
        from . import mxu_ntt

        return mxu_ntt.fft_natural_to_bitreversed(a)
    return fft_natural_to_bitreversed_xla(a, ctx)


def ifft_bitreversed_to_natural(
    a: jax.Array, ctx: NTTContext | None = None
) -> jax.Array:
    """DIT inverse NTT (incl. 1/n) along the last axis; see the XLA form."""
    if _mxu_ntt_ready(a.shape[-1], ctx):
        from . import mxu_ntt

        return mxu_ntt.ifft_bitreversed_to_natural(a)
    return ifft_bitreversed_to_natural_xla(a, ctx)


def dif_stages(a: jax.Array, ctx: NTTContext, start: int, end: int) -> jax.Array:
    """Radix-2 DIF butterfly stages [start, end) of a size-ctx.n transform.

    Stage s combines elements ctx.n >> (s+1) apart; running stages [0, k)
    leaves 2^k independent plain sub-transforms of size n/2^k — which is
    what lets the hybrid MXU path (mxu_ntt.py) hand contiguous blocks to
    the matmul kernel bit-exactly."""
    n = ctx.n
    lead = a.shape[:-1]
    for s in range(start, end):
        block = n >> s
        half = block >> 1
        tw = ctx.tw[:: n // block][:half] if half > 1 else ctx.tw[:1]
        x = a.reshape(lead + (n // block, 2, half))
        u = x[..., 0, :]
        v = x[..., 1, :]
        top = gf.add(u, v)
        bot = gf.mul(gf.sub(u, v), tw)
        a = jnp.stack([top, bot], axis=-2).reshape(lead + (n,))
    return a


def dit_stages(a: jax.Array, ctx: NTTContext, start: int, end: int) -> jax.Array:
    """Radix-2 DIT butterfly stages [start, end) (no 1/n scaling)."""
    n = ctx.n
    lead = a.shape[:-1]
    for s in range(start, end):
        block = 2 << s
        half = block >> 1
        tw = ctx.itw[:: n // block][:half] if half > 1 else ctx.itw[:1]
        x = a.reshape(lead + (n // block, 2, half))
        u = x[..., 0, :]
        wv = gf.mul(x[..., 1, :], tw)
        top = gf.add(u, wv)
        bot = gf.sub(u, wv)
        a = jnp.stack([top, bot], axis=-2).reshape(lead + (n,))
    return a


@partial(jax.jit, static_argnums=(1,))
def fft_natural_to_bitreversed_xla(a: jax.Array, ctx: NTTContext | None = None) -> jax.Array:
    """DIF NTT along the last axis; output in bit-reversed order."""
    n = a.shape[-1]
    log_n = n.bit_length() - 1
    assert 1 << log_n == n
    if ctx is None:
        ctx = get_ntt_context(log_n)
    return dif_stages(a, ctx, 0, log_n)


@partial(jax.jit, static_argnums=(1,))
def ifft_bitreversed_to_natural_xla(a: jax.Array, ctx: NTTContext | None = None) -> jax.Array:
    """DIT inverse NTT along the last axis; input bit-reversed, output natural.

    Includes the 1/n scaling.
    """
    n = a.shape[-1]
    log_n = n.bit_length() - 1
    assert 1 << log_n == n
    if ctx is None:
        ctx = get_ntt_context(log_n)
    return gf.mul(dit_stages(a, ctx, 0, log_n), ctx.n_inv)


def ifft_natural_to_natural(a: jax.Array, ctx: NTTContext | None = None) -> jax.Array:
    """Interpolate monomial coefficients from values over H in natural order."""
    n = a.shape[-1]
    log_n = n.bit_length() - 1
    if ctx is None:
        ctx = get_ntt_context(log_n)
    return ifft_bitreversed_to_natural(a[..., ctx.brev], ctx)


@partial(jax.jit, static_argnums=(1,))
def distribute_powers(a: jax.Array, base: int) -> jax.Array:
    """a[..., i] *= base^i (the coset shift before a forward transform)."""
    n = a.shape[-1]
    return gf.mul(a, powers_device(base, n))


@partial(jax.jit, static_argnums=(1, 2))
def _lde_from_monomial_jit(
    coeffs: jax.Array,
    lde_factor: int,
    coset: int = gl.MULTIPLICATIVE_GENERATOR,
) -> jax.Array:
    """Low-degree-extend monomial coeffs (..., n) -> (..., lde_factor, n).

    Coset axis is indexed by bit-reversed coset index; each coset is the
    bit-reversed evaluations over {coset·w_N^j·<w_n>}. Flattening the last two
    axes gives the full LDE domain in bit-reversed enumeration.
    """
    n = coeffs.shape[-1]
    log_n = n.bit_length() - 1
    log_lde = lde_factor.bit_length() - 1
    assert 1 << log_lde == lde_factor
    ctx = get_ntt_context(log_n)
    scale = _lde_scale_cached(log_n, lde_factor, int(coset) % gl.P)
    scaled = gf.mul(coeffs[..., None, :], scale)  # (..., lde, n)
    return fft_natural_to_bitreversed(scaled, ctx)


def lde_scale_rows(
    log_n: int, lde_factor: int, coset: int = gl.MULTIPLICATIVE_GENERATOR
) -> jax.Array:
    """Public accessor for the cached (lde, n) coset-scale matrix (rows in
    bit-reversed coset order) — row c scales monomials onto LDE coset c."""
    return _lde_scale_cached(log_n, lde_factor, int(coset) % gl.P)


def warm_domain_caches(log_n: int, lde_factor: int) -> None:
    """Populate the challenge-independent transform caches for one
    (trace, rate) geometry: the size-n and full-domain twiddle contexts
    plus the coset-scale matrix. The overlapped prover calls this at
    round 0 (prover._prefetch_challenge_independent) so rounds 1-5 never
    pay a table build at a transcript barrier; safe to call any time —
    everything here is lru-cached and enqueue-only."""
    get_ntt_context(log_n)
    log_lde = lde_factor.bit_length() - 1
    if log_lde:
        get_ntt_context(log_n + log_lde)
    lde_scale_rows(log_n, lde_factor)


@lru_cache(maxsize=None)
def _lde_scale_cached(log_n: int, lde_factor: int, coset: int) -> jax.Array:
    """(lde, n) scale matrix shift_j^i (rows in bit-reversed coset order)."""
    n = 1 << log_n
    log_lde = lde_factor.bit_length() - 1
    w_full = gl.omega(log_n + log_lde)
    brev_lde = bitreverse_indices(log_lde)
    shifts = [
        gl.mul(coset % gl.P, gl.pow_(w_full, int(j))) for j in brev_lde
    ]
    with jax.ensure_compile_time_eval():
        return jnp.asarray(np.stack([_powers_np(s, n) for s in shifts]))


def lde_from_monomial(
    coeffs: jax.Array,
    lde_factor: int,
    coset: int = gl.MULTIPLICATIVE_GENERATOR,
) -> jax.Array:
    """Low-degree-extend monomial coeffs (..., n) -> (..., lde_factor, n).

    Coset axis is indexed by bit-reversed coset index; each coset is the
    bit-reversed evaluations over {coset*w_N*<w_n>}. Flattening the last two
    axes gives the full LDE domain in bit-reversed enumeration. Large column
    batches are processed in chunks to bound the transform's transient
    memory (see monomial_from_values). On TPU the coset-scale multiply and
    all butterfly stages run as ONE fused Pallas kernel per column/coset.
    """
    n = coeffs.shape[-1]
    if _mxu_ntt_ready(n, None):
        from . import mxu_ntt

        log_n = n.bit_length() - 1
        scale = _lde_scale_cached(log_n, lde_factor, int(coset) % gl.P)
        if coeffs.ndim < 2:
            return mxu_ntt.lde_from_monomial(coeffs, scale)
        B = coeffs.shape[0]
        per = _col_chunks(B, coeffs.size // B * 8 * lde_factor)
        if per is None:
            return mxu_ntt.lde_from_monomial(coeffs, scale)
        return _assemble_chunks(
            coeffs.shape[:-1] + (lde_factor, n),
            lambda i: mxu_ntt.lde_from_monomial(coeffs[i : i + per], scale),
            range(0, B, per),
        )
    if coeffs.ndim < 2:
        return _lde_from_monomial_jit(coeffs, lde_factor, coset)
    B = coeffs.shape[0]
    per = _col_chunks(B, coeffs.size // B * 8 * lde_factor)
    if per is None:
        return _lde_from_monomial_jit(coeffs, lde_factor, coset)
    return _assemble_chunks(
        coeffs.shape[:-1] + (lde_factor, n),
        lambda i: _lde_from_monomial_jit(coeffs[i : i + per], lde_factor, coset),
        range(0, B, per),
    )


@jax.jit
def _monomial_from_values_jit(values: jax.Array) -> jax.Array:
    return ifft_natural_to_natural(values)


# The unrolled radix-2 stages keep O(log n) live stage buffers; chunk big
# column batches so the transient peak stays bounded (the 2^20-row traces
# OOM'd 16 GB HBM inside one monolithic (B, L, n) transform otherwise).
_NTT_CHUNK_BUDGET = 128 << 20  # bytes of INPUT columns per chunk


def _col_chunks(total_cols: int, bytes_per_col: int):
    per = max(1, _NTT_CHUNK_BUDGET // max(bytes_per_col, 1))
    if per >= total_cols:
        return None
    return per


def _assemble_chunks(shape, produce, starts):
    """Write per-chunk results into a donated output buffer in place (a
    concatenate would transiently double the multi-GB footprint)."""
    out = jnp.zeros(shape, jnp.uint64)
    for i in starts:
        out = _write_block(out, produce(i), i)
    return out


@partial(jax.jit, donate_argnums=(0,), static_argnums=(2,))
def _write_block(buf, chunk, i: int):
    return jax.lax.dynamic_update_slice_in_dim(buf, chunk, i, axis=0)


def chunk_shapes(total_cols: int, bytes_per_col: int) -> list[int]:
    """Distinct column-chunk heights the chunked transform wrappers below
    actually dispatch for a (total_cols, …) batch — the shape key set a
    precompiler must cover (prover/precompile.py)."""
    per = _col_chunks(total_cols, bytes_per_col)
    if per is None:
        return [total_cols]
    return sorted({min(per, total_cols - i) for i in range(0, total_cols, per)})


def ntt_kernel_specs(B: int, log_n: int, lde_factor: int | None = None,
                     coset: int = gl.MULTIPLICATIVE_GENERATOR,
                     mono: bool = True) -> list:
    """(name, jitted_fn, args) triples for the exact top-level executables
    `monomial_from_values` (when `mono`) and `lde_from_monomial` (when
    `lde_factor` is given) dispatch for a (B, 2^log_n) column stack —
    mirroring the MXU-vs-XLA routing, the hybrid-size split and the
    column chunking, so `fn.lower(*args).compile()` populates the very
    cache keys the prover later hits. Args are ShapeDtypeStructs (plus
    static scalars); nothing here allocates device memory."""
    n = 1 << log_n

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint64)

    specs = []
    if mono and _inverse_is_apart(n):
        from . import mxu_ntt

        for b in chunk_shapes(B, n * 8):
            specs += [
                (f"imono_brev_b{b}_n{n}", _bitreversed_jit, (sds(b, n),)),
                (f"imono_blocks_b{b}_n{n}", mxu_ntt._ifft_hybrid_blocks,
                 (sds(b, n), log_n, False)),
                (f"imono_outer_b{b}_n{n}", mxu_ntt._ifft_hybrid_outer,
                 (sds(b, n), log_n)),
            ]
    elif mono:
        specs += [
            (f"imono_b{b}_n{n}", _monomial_from_values_jit, (sds(b, n),))
            for b in chunk_shapes(B, n * 8)
        ]
    if lde_factor is None:
        return specs
    L = int(lde_factor)
    mxu = _mxu_ntt_ready(n, None)
    for b in chunk_shapes(B, n * 8 * L):
        if not mxu:
            specs.append((
                f"lde_b{b}_n{n}_L{L}",
                _lde_from_monomial_jit,
                (sds(b, n), L, int(coset) % gl.P),
            ))
            continue
        from . import mxu_ntt
        from ..field import limbs

        if log_n > mxu_ntt.MAX_LOG_N:
            # hybrid sizes: eager coset scale + one _fft_hybrid dispatch
            specs.append((
                f"lde_hybrid_b{b}_n{n}_L{L}",
                mxu_ntt._fft_hybrid,
                (sds(b, L, n), log_n, False),
            ))
            continue
        ctx = mxu_ntt.get_mxu_ctx(log_n)
        planes = jax.eval_shape(
            lambda a: limbs.split(a.reshape(-1, ctx.R, ctx.C)), sds(b, n)
        )
        s_planes = jax.eval_shape(
            lambda s: limbs.split(s.reshape(L, ctx.R, ctx.C)), sds(L, n)
        )
        specs.append((
            f"lde_mxu_b{b}_n{n}_L{L}",
            mxu_ntt._lde_planes,
            (planes, s_planes, log_n, False),
        ))
    return specs


@jax.jit
def _bitreversed_jit(values: jax.Array) -> jax.Array:
    n = values.shape[-1]
    return values[..., get_ntt_context(n.bit_length() - 1).brev]


def _inverse_is_apart(n: int) -> bool:
    """True where `monomial_from_values` runs the inverse transform of
    size n as separate device programs (`mxu_ntt.ifft_hybrid_apart`)."""
    if not _mxu_ntt_ready(n, None):
        return False
    from . import mxu_ntt

    return n.bit_length() - 1 > mxu_ntt.MAX_LOG_N


def _monomial_chunk(values: jax.Array) -> jax.Array:
    n = values.shape[-1]
    if isinstance(values, jax.core.Tracer) or not _inverse_is_apart(n):
        return _monomial_from_values_jit(values)
    from . import mxu_ntt

    return mxu_ntt.ifft_hybrid_apart(
        _bitreversed_jit(values), n.bit_length() - 1
    )


def monomial_from_values(values: jax.Array) -> jax.Array:
    """Values over H (natural order) -> monomial coefficients (column
    batches chunked to bound transient memory)."""
    if values.ndim < 2:
        return _monomial_chunk(values)
    B = values.shape[0]
    per = _col_chunks(B, values.size // B * 8)
    if per is None:
        return _monomial_chunk(values)
    return _assemble_chunks(
        values.shape,
        lambda i: _monomial_chunk(values[i : i + per]),
        range(0, B, per),
    )


@jax.jit
def _eval_with_pows(coeffs: jax.Array, p0: jax.Array, p1: jax.Array):
    c0 = gf.mul(coeffs, p0)
    c1 = gf.mul(coeffs, p1)
    # sum over last axis, mod p: reduce via pairwise modular adds
    return (_modsum(c0), _modsum(c1))


def eval_monomial_at_ext_point(coeffs: jax.Array, z, z_pows=None):
    """Evaluate base-field monomial polys (..., n) at an extension point z.

    z is a host scalar (c0, c1); returns ext pair of shape (...,). Uses a
    power table + reduction instead of a sequential Horner chain (the
    device-friendly analogue of the reference's barycentric evaluation,
    `/root/reference/src/cs/implementations/utils.rs:1025`). The reduction
    core is jitted; the z-dependent power table stays an array argument so
    new challenges never retrace.
    """
    n = coeffs.shape[-1]
    if z_pows is None:
        z_pows = ext_powers_device(z, n)
    return _eval_with_pows(coeffs, z_pows[0], z_pows[1])


@partial(jax.jit, static_argnums=(1,))
def _ext_powers_jit(z01, count: int):
    """Log-doubling power table built in ONE compiled graph (the eager
    version dispatched log2(count) growing-array ops per call, each with
    its own launch overhead and compile)."""
    p0 = jnp.ones((1,), jnp.uint64)
    p1 = jnp.zeros((1,), jnp.uint64)
    step = (z01[0], z01[1])  # z^cur, maintained by squaring
    cur = 1
    while cur < count:
        n0, n1 = ext.mul((p0, p1), step)
        p0 = jnp.concatenate([p0, n0])
        p1 = jnp.concatenate([p1, n1])
        step = ext.mul(step, step)
        cur *= 2
    return (p0, p1)


def ext_powers_device(z, count: int):
    """Powers [1, z, ..., z^(count-1)] of an ext scalar, as pair of arrays."""
    assert count & (count - 1) == 0
    z01 = jnp.asarray(np.array([int(z[0]), int(z[1])], dtype=np.uint64))
    return _ext_powers_jit(z01, count)


def _modsum(a: jax.Array) -> jax.Array:
    """Modular sum along the last axis via log-depth pairwise folding."""
    n = a.shape[-1]
    while n > 1:
        if n % 2 == 1:
            a = jnp.concatenate(
                [a, jnp.zeros(a.shape[:-1] + (1,), a.dtype)], axis=-1
            )
            n += 1
        a = gf.add(a[..., : n // 2], a[..., n // 2 :])
        n //= 2
    return a[..., 0]
