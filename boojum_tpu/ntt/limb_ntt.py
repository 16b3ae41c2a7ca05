"""Limb-plane NTT / coset LDE: the resident-mode transform layer (ISSUE 10).

`ntt.py` computes in XLA-emulated uint64 and `mxu_ntt.py` converts u64->limb
planes at every public entry — which is exactly the boundary tax the
limb-resident prove deletes. This module is the transform layer whose
CANONICAL representation is a `(lo, hi)` uint32 plane pair shaped like the
u64 array it replaces:

- twiddle/scale tables are built on HOST (numpy `_powers_np` + `split_np`),
  so no device-side u64<->limb conversion exists anywhere in the layer;
- the staged radix-2 butterflies are `field/limbs.py` ops (exact mod p,
  canonical in/out), so every value is bit-identical to the u64 path;
- where the MXU matmul kernel is native (TPU, 2^14..2^22), the plane entries
  feed `mxu_ntt._fft_planes/_ifft_planes/_lde_planes` DIRECTLY — the
  split/join wrappers of `mxu_ntt`'s u64 entries never run.

Layout convention: same shapes as the u64 arrays, as a pair of uint32
arrays. Big column batches chunk exactly like `ntt.monomial_from_values` /
`lde_from_monomial` (shared `_col_chunks`), writing into two donated u32
buffers.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..field import gl
from ..field import limbs
from ..utils import metrics as _metrics
from .ntt import (
    _col_chunks,
    _mxu_ntt_ready,
    _powers_np,
    bitreverse_indices,
)


@lru_cache(maxsize=None)
class PlaneNTTContext:
    """Host-built twiddle planes for size-2^log_n transforms."""

    def __init__(self, log_n: int):
        self.log_n = log_n
        self.n = 1 << log_n
        self.omega = gl.omega(log_n)
        half = max(self.n // 2, 1)
        with jax.ensure_compile_time_eval():
            tw_lo, tw_hi = limbs.split_np(_powers_np(self.omega, half))
            itw_lo, itw_hi = limbs.split_np(
                _powers_np(gl.inv(self.omega), half)
            )
            self.tw = (jnp.asarray(tw_lo), jnp.asarray(tw_hi))
            self.itw = (jnp.asarray(itw_lo), jnp.asarray(itw_hi))
            self.brev = jnp.asarray(bitreverse_indices(log_n))
        self.n_inv = limbs.const_pair(gl.inv(self.n))


def _tw_slice(tw, n, block, half):
    if half > 1:
        return tw[0][:: n // block][:half], tw[1][:: n // block][:half]
    return tw[0][:1], tw[1][:1]


def dif_stages_p(p, ctx: PlaneNTTContext, start: int, end: int):
    """Radix-2 DIF stages [start, end) on planes (ntt.dif_stages twin)."""
    n = ctx.n
    lo, hi = p
    lead = lo.shape[:-1]
    for s in range(start, end):
        block = n >> s
        half = block >> 1
        tw = _tw_slice(ctx.tw, n, block, half)
        xl = lo.reshape(lead + (n // block, 2, half))
        xh = hi.reshape(lead + (n // block, 2, half))
        u = (xl[..., 0, :], xh[..., 0, :])
        v = (xl[..., 1, :], xh[..., 1, :])
        top = limbs.add(u, v)
        bot = limbs.mul(limbs.sub(u, v), tw)
        lo = jnp.stack([top[0], bot[0]], axis=-2).reshape(lead + (n,))
        hi = jnp.stack([top[1], bot[1]], axis=-2).reshape(lead + (n,))
    return lo, hi


def dit_stages_p(p, ctx: PlaneNTTContext, start: int, end: int):
    """Radix-2 DIT stages [start, end) on planes (no 1/n scaling)."""
    n = ctx.n
    lo, hi = p
    lead = lo.shape[:-1]
    for s in range(start, end):
        block = 2 << s
        half = block >> 1
        tw = _tw_slice(ctx.itw, n, block, half)
        xl = lo.reshape(lead + (n // block, 2, half))
        xh = hi.reshape(lead + (n // block, 2, half))
        u = (xl[..., 0, :], xh[..., 0, :])
        wv = limbs.mul((xl[..., 1, :], xh[..., 1, :]), tw)
        top = limbs.add(u, wv)
        bot = limbs.sub(u, wv)
        lo = jnp.stack([top[0], bot[0]], axis=-2).reshape(lead + (n,))
        hi = jnp.stack([top[1], bot[1]], axis=-2).reshape(lead + (n,))
    return lo, hi


# ---------------------------------------------------------------------------
# Staged-XLA plane transforms (jitted entries)
# ---------------------------------------------------------------------------


@jax.jit
def _fft_p_jit(p):
    n = p[0].shape[-1]
    log_n = n.bit_length() - 1
    ctx = PlaneNTTContext(log_n)
    return dif_stages_p(p, ctx, 0, log_n)


@jax.jit
def _ifft_p_jit(p):
    n = p[0].shape[-1]
    log_n = n.bit_length() - 1
    ctx = PlaneNTTContext(log_n)
    return limbs.mul_const(dit_stages_p(p, ctx, 0, log_n), ctx.n_inv)


@jax.jit
def _imono_p_jit(p):
    """Values over H (natural) -> monomials, on planes."""
    n = p[0].shape[-1]
    ctx = PlaneNTTContext(n.bit_length() - 1)
    p = (p[0][..., ctx.brev], p[1][..., ctx.brev])
    return limbs.mul_const(dit_stages_p(p, ctx, 0, ctx.log_n), ctx.n_inv)


@lru_cache(maxsize=None)
def _lde_scale_planes(log_n: int, lde_factor: int, coset: int):
    """Host-built (lde, n) coset-scale planes (ntt._lde_scale_cached twin)."""
    n = 1 << log_n
    log_lde = lde_factor.bit_length() - 1
    w_full = gl.omega(log_n + log_lde)
    brev_lde = bitreverse_indices(log_lde)
    shifts = [
        gl.mul(coset % gl.P, gl.pow_(w_full, int(j))) for j in brev_lde
    ]
    with jax.ensure_compile_time_eval():
        lo, hi = limbs.split_np(np.stack([_powers_np(s, n) for s in shifts]))
        return jnp.asarray(lo), jnp.asarray(hi)


@partial(jax.jit, static_argnums=(1, 2))
def _lde_p_jit(p, lde_factor: int, coset: int):
    n = p[0].shape[-1]
    log_n = n.bit_length() - 1
    scale = _lde_scale_planes(log_n, lde_factor, coset)
    scaled = limbs.mul((p[0][..., None, :], p[1][..., None, :]), scale)
    return _fft_body(scaled)


def _fft_body(p):
    n = p[0].shape[-1]
    log_n = n.bit_length() - 1
    return dif_stages_p(p, PlaneNTTContext(log_n), 0, log_n)


# ---------------------------------------------------------------------------
# MXU dispatch + hybrid sizes
# ---------------------------------------------------------------------------


def _mxu_fft_p(p, inverse: bool):
    """The MXU transforms on planes: one kernel up to 2^MAX_LOG_N rows;
    above, the inverse is `_hybrid_inv_p` and the forward a program of
    its own (`_hybrid_fwd_p`, here without a row)."""
    from . import mxu_ntt

    n = p[0].shape[-1]
    log_n = n.bit_length() - 1
    if log_n > mxu_ntt.MAX_LOG_N:
        if inverse:
            return _hybrid_inv_p(p, log_n)
        return _hybrid_fwd_p(p, log_n, _NTT_FORWARD)
    ctx = mxu_ntt.get_mxu_ctx(log_n)
    lead = p[0].shape[:-1]
    flat = (p[0].reshape(-1, ctx.R, ctx.C), p[1].reshape(-1, ctx.R, ctx.C))
    fn = mxu_ntt._ifft_planes if inverse else mxu_ntt._fft_planes
    out = fn(flat, log_n, False)
    return out[0].reshape(lead + (n,)), out[1].reshape(lead + (n,))


def forward_is_own_program(n: int) -> bool:
    """True where the forward transform of size n is a device program of
    its own (`_hybrid_fwd_p`) and must not be traced into a caller's jit."""
    from . import mxu_ntt

    n = int(n)
    return _mxu_ntt_ready(n, None) and n.bit_length() - 1 > mxu_ntt.MAX_LOG_N


def _forward_programs(prefix: str):
    """(outer, fused): the jitted programs of `_hybrid_fwd_p`, named
    `<prefix>_outer_p` and `<prefix>_fused_p` so that a device trace (and
    `benchmark/families.json`) gives each caller's time to its own layer.
    Both take `(p, scale, start, size, log_n)`: rows [start, start + size)
    of the planes `p` (`start` a device scalar; None: all of `p`) under
    `scale`: an (n,) row -> (.., n), (L, n) rows -> (.., L, n), None: no
    row. Slices and reshapes happen inside: nothing runs beside them."""

    def take(p, scale, start, size):
        if start is not None:
            p = tuple(
                jax.lax.dynamic_slice_in_dim(a, start, size, 0) for a in p
            )
        rows = () if scale is None else scale[0].shape[:-1]
        return p, p[0].shape[:-1] + rows + p[0].shape[-1:]

    def outer(p, scale, start, size, log_n: int):
        """Above 2^(MAX_LOG_N + 2) rows: the rows, which belong before the
        first stage, and the leading stages the kernel does not absorb."""
        from .mxu_ntt import leading_outer_stages

        p, _ = take(p, scale, start, size)
        if scale is not None and scale[0].ndim == 2:
            p = tuple(a[..., None, :] for a in p)
        p = p if scale is None else limbs.mul(p, scale)
        stages = leading_outer_stages(log_n)
        return dif_stages_p(p, PlaneNTTContext(log_n), 0, stages)

    def fused(p, scale, start, size, log_n: int):
        """The matmul kernel on (blocks, R, C): the rows and the last
        `fused_outer_stages(log_n)` outer stages are its prologue."""
        from . import mxu_ntt

        ctx = mxu_ntt.get_mxu_ctx(mxu_ntt.MAX_LOG_N)
        p, shape = take(p, scale, start, size)

        def blocks(v):
            return v and tuple(a.reshape(-1, ctx.R, ctx.C) for a in v)

        k = mxu_ntt.fused_outer_stages(log_n)
        out = mxu_ntt._fwd_radix_planes(blocks(p), blocks(scale), k, False)
        return out[0].reshape(shape), out[1].reshape(shape)

    outer.__name__ = outer.__qualname__ = f"{prefix}_outer_p"
    fused.__name__ = fused.__qualname__ = f"{prefix}_fused_p"
    return tuple(jax.jit(f, static_argnums=(3, 4)) for f in (outer, fused))


_NTT_FORWARD = _forward_programs("_ntt_hybrid")
# the commits' LDE keeps `lde_planes` in its programs' names at every size
# (`mxu_ntt._lde_planes` at or below 2^MAX_LOG_N): one name to find it by
_LDE_FORWARD = _forward_programs("_lde_planes_hybrid")
_COSET_EVAL_FORWARD = _forward_programs("_coset_eval_hybrid")


@partial(jax.jit, static_argnums=(1,))
def _hybrid_inv_p(p, log_n: int):
    """2^17..2^22, inverse: per-block MXU kernels, then plane XLA outer
    radix-2 stages (mxu_ntt._ifft_hybrid twin)."""
    return hybrid_inv_outer_p(hybrid_inv_kernels_p(p, log_n), log_n)


def hybrid_inv_kernels_p(p, log_n: int):
    """The first half of `_hybrid_inv_p`: the inverse matmul kernel over
    every 2^MAX_LOG_N block."""
    from . import mxu_ntt

    outer = log_n - mxu_ntt.MAX_LOG_N
    lead = p[0].shape[:-1]
    blocks = (
        p[0].reshape(lead + (1 << outer, 1 << mxu_ntt.MAX_LOG_N)),
        p[1].reshape(lead + (1 << outer, 1 << mxu_ntt.MAX_LOG_N)),
    )
    out = _mxu_fft_p(blocks, True)
    return (
        out[0].reshape(lead + (1 << log_n,)),
        out[1].reshape(lead + (1 << log_n,)),
    )


def hybrid_inv_outer_p(p, log_n: int):
    """The second half: the outer radix-2 DIT stages and the 1/2^outer."""
    from . import mxu_ntt

    outer = log_n - mxu_ntt.MAX_LOG_N
    out = dit_stages_p(p, PlaneNTTContext(log_n), mxu_ntt.MAX_LOG_N, log_n)
    return limbs.mul_const(out, limbs.const_pair(gl.inv(1 << outer)))


# Outer DIT stages that ONE program has held beside the per-block inverse
# kernels and returned on the v5e: 5, at 2^21 (the quotient interpolation
# of every 2^18-row cell, 36 ms). With 6, at 2^22 (2^19 rows under an
# 8-coset quotient), `_quotient_interp_p` did not return in 600 s (my chip
# run, PR 39; PERF.md Open question 10: the third program of XLA stages +
# a matmul kernel to stall at a size nobody had run). Past it a caller
# dispatches the two halves as programs of their own. An OBSERVED boundary,
# set from that one stall and not from its cause: revisit it with Open
# question 10, which asks why such a program stalls.
INVERSE_FUSED_OUTER_STAGES = 5


def inverse_is_two_programs(n: int) -> bool:
    """True where the inverse transform of size n must be dispatched as
    its two halves (`hybrid_inv_kernels_p`, `hybrid_inv_outer_p`), each in
    a program of its own."""
    from . import mxu_ntt

    n = int(n)
    outer = n.bit_length() - 1 - mxu_ntt.MAX_LOG_N
    return _mxu_ntt_ready(n, None) and outer > INVERSE_FUSED_OUTER_STAGES


def fft_natural_to_bitreversed_p(p):
    """DIF NTT on planes along the last axis (bit-reversed output)."""
    if _mxu_ntt_ready(int(p[0].shape[-1]), None):
        return _mxu_fft_p(p, False)
    return _fft_p_jit(p)


def ifft_bitreversed_to_natural_p(p):
    """DIT inverse NTT on planes (incl. 1/n)."""
    if _mxu_ntt_ready(int(p[0].shape[-1]), None):
        return _mxu_fft_p(p, True)
    return _ifft_p_jit(p)


@partial(jax.jit, static_argnums=(1,))
def distribute_powers_p(p, base: int):
    """p[..., i] *= base^i on planes (host-built scale table)."""
    n = p[0].shape[-1]
    with jax.ensure_compile_time_eval():
        lo, hi = limbs.split_np(_powers_np(int(base) % gl.P, n))
        scale = (jnp.asarray(lo), jnp.asarray(hi))
    return limbs.mul(p, scale)


# ---------------------------------------------------------------------------
# Chunked public entries (monomial_from_values / lde_from_monomial twins)
# ---------------------------------------------------------------------------


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(4,))
def _write_block_p(buf_lo, buf_hi, chunk_lo, chunk_hi, i: int):
    return (
        jax.lax.dynamic_update_slice_in_dim(buf_lo, chunk_lo, i, axis=0),
        jax.lax.dynamic_update_slice_in_dim(buf_hi, chunk_hi, i, axis=0),
    )


def _assemble_chunks_p(shape, produce, starts):
    out_lo = jnp.zeros(shape, jnp.uint32)
    out_hi = jnp.zeros(shape, jnp.uint32)
    for i in starts:
        clo, chi = produce(i)
        out_lo, out_hi = _write_block_p(out_lo, out_hi, clo, chi, i)
    return out_lo, out_hi


def _hybrid_fwd_p(p, log_n: int, programs, scale=None, start=None, size=None):
    """2^17..2^22, forward, under the rows `scale` if any: ONE device
    program, the matmul kernel with the rows and the last two outer
    radix-2 stages as its prologue (above 2^18 rows the `outer` program of
    the leading stages runs before it). Dispatched on its own, never
    traced into a caller's jit: compiled into one program, XLA stages and
    the kernel did not come back on the v5e once the batch was more than a
    few columns ((32, 2, 2^18) planes: over 170 s, my chip run, PR 26)."""
    if isinstance(p[0], jax.core.Tracer):
        raise TypeError(
            "the forward NTT above 2^16 is a device program of its own: call "
            "it outside jit (limb_ntt.forward_is_own_program says when)"
        )
    from . import mxu_ntt

    outer, fused = programs
    if mxu_ntt.leading_outer_stages(log_n):
        p = outer(p, scale, start, size, log_n)
        scale = start = size = None
    out = fused(p, scale, start, size, log_n)
    _count_fused_stages(log_n, out[0].size >> log_n)
    return out


def _count_fused_stages(log_n: int, transforms: int):
    """`ntt.fused_outer_stages`: the radix-2 stages the kernel of one
    forward dispatch absorbed, times its column transforms (0 a transform
    up to 2^MAX_LOG_N rows, where there is no outer stage); and its twin
    `ntt.leading_outer_stages`: the XLA stages of the `outer` program
    before it (0 up to 2^(MAX_LOG_N + 2) rows)."""
    from .mxu_ntt import fused_outer_stages, leading_outer_stages

    _metrics.count(
        "ntt.fused_outer_stages", fused_outer_stages(log_n) * transforms
    )
    _metrics.count(
        "ntt.leading_outer_stages", leading_outer_stages(log_n) * transforms
    )


def scaled_fft_chunks(B: int, n: int, chunk_bytes: int) -> dict:
    """{first column: columns} of the chunks `scaled_fft_p` walks."""
    per = max(1, int(chunk_bytes) // (n * 8))
    return {i: min(per, B - i) for i in range(0, B, per)}


def scaled_fft_p(p, row_p, chunk_bytes: int):
    """(B, n) monomial planes times one (n,) scale row, then the forward
    NTT: a coset evaluation. For sizes whose forward transform is its own
    program (`forward_is_own_program`): column chunks of at most
    `chunk_bytes`, each ONE dispatch (its slice, the row, the transform)."""
    B, n = p[0].shape
    chunks = scaled_fft_chunks(B, n, chunk_bytes)

    def produce(i):
        return _hybrid_fwd_p(
            p, n.bit_length() - 1, _COSET_EVAL_FORWARD,
            row_p, jnp.int32(i), chunks[i],
        )

    if len(chunks) == 1:
        return produce(0)
    return _assemble_chunks_p(p[0].shape, produce, chunks)


def _lde_one_p(p, lde_factor: int, coset: int):
    n = int(p[0].shape[-1])
    if _mxu_ntt_ready(n, None):
        from . import mxu_ntt

        log_n = n.bit_length() - 1
        if log_n > mxu_ntt.MAX_LOG_N:
            scale = _lde_scale_planes(log_n, lde_factor, coset)
            return _hybrid_fwd_p(p, log_n, _LDE_FORWARD, scale)
        _count_fused_stages(log_n, p[0].size // n * lde_factor)
        ctx = mxu_ntt.get_mxu_ctx(log_n)
        lead = p[0].shape[:-1]
        flat = (
            p[0].reshape(-1, ctx.R, ctx.C),
            p[1].reshape(-1, ctx.R, ctx.C),
        )
        scale = _lde_scale_planes(log_n, lde_factor, coset)
        s_planes = (
            scale[0].reshape(lde_factor, ctx.R, ctx.C),
            scale[1].reshape(lde_factor, ctx.R, ctx.C),
        )
        out = mxu_ntt._lde_planes(flat, s_planes, log_n, False)
        return (
            out[0].reshape(lead + (lde_factor, n)),
            out[1].reshape(lead + (lde_factor, n)),
        )
    return _lde_p_jit(p, lde_factor, coset)


def lde_chunk_sizes(b: int, n: int, lde_factor: int) -> list[int]:
    """The column counts of the chunks `lde_from_monomial_p` walks over a
    (b, n) stack: one forward dispatch each."""
    per = _col_chunks(b, n * 8 * lde_factor) or b
    return [min(per, b - i) for i in range(0, b, per)]


def lde_from_monomial_p(
    p, lde_factor: int, coset: int = int(gl.MULTIPLICATIVE_GENERATOR)
):
    """Monomial planes (..., n) -> (..., lde_factor, n) LDE planes."""
    coset = int(coset) % gl.P
    lo, hi = p
    n = lo.shape[-1]
    if lo.ndim < 2:
        return _lde_one_p(p, lde_factor, coset)
    B = lo.shape[0]
    per = _col_chunks(B, lo.size // B * 8 * lde_factor)
    if per is None:
        return _lde_one_p(p, lde_factor, coset)
    return _assemble_chunks_p(
        lo.shape[:-1] + (lde_factor, n),
        lambda i: _lde_one_p(
            (lo[i : i + per], hi[i : i + per]), lde_factor, coset
        ),
        range(0, B, per),
    )


def inverse_is_own_program(n: int) -> bool:
    """True where the inverse transform of n values in natural order is the
    matmul kernel's (`_imono_kernel_p`: above 2^MAX_LOG_N rows, as far as
    the kernel's radix stage and one trailing stage reach)."""
    from . import mxu_ntt

    n = int(n)
    log_n = n.bit_length() - 1
    return (
        _mxu_ntt_ready(n, None)
        and log_n > mxu_ntt.MAX_LOG_N
        and mxu_ntt.leading_outer_stages(log_n) <= mxu_ntt.MAX_TRAILING_OUTER
    )


@partial(jax.jit, static_argnums=(2,))
def _imono_p_fused(p, start, size):
    """Columns [start, start + size) of the (B, n) values `p` (`start` a
    device scalar; None: all of `p`) through the inverse matmul kernel,
    whose radix stage is the last `fused_outer_stages(log_n)` outer stages
    -> their monomials, (size, n); above 2^(MAX_LOG_N + 2) rows the
    kernel's own (size, 2^trailing, R, 2^k C), for `_imono_p_trailing`."""
    from . import mxu_ntt

    if start is not None:
        p = tuple(jax.lax.dynamic_slice_in_dim(a, start, size, 0) for a in p)
    ctx = mxu_ntt.get_mxu_ctx(mxu_ntt.MAX_LOG_N)
    b, n = p[0].shape
    log_n = n.bit_length() - 1
    trailing = mxu_ntt.leading_outer_stages(log_n)
    out = mxu_ntt._inv_radix_planes(
        tuple(a.reshape(b, ctx.C, -1) for a in p),
        mxu_ntt.fused_outer_stages(log_n), trailing, False,
    )
    return out if trailing else tuple(a.reshape(b, n) for a in out)


@jax.jit
def _imono_p_trailing(parts):
    """The last outer DIT stage, over the monomials of a column's even and
    odd values as `_imono_p_fused` leaves them, (b, 2, R, 2^k C): both in
    natural order, so the stage is elementwise. A program of its own:
    XLA stages and a matmul kernel in one program have stalled on the
    v5e (PERF.md, Open question 10)."""
    b, _two, rows, cols = parts[0].shape
    n = 2 * rows * cols
    itw = PlaneNTTContext(n.bit_length() - 1).itw
    even = tuple(a[:, 0] for a in parts)
    odd = limbs.mul(
        tuple(a[:, 1] for a in parts),
        tuple(w.reshape(rows, cols) for w in itw),
    )
    top, bot = limbs.add(even, odd), limbs.sub(even, odd)
    return tuple(
        jnp.stack([t, u], axis=1).reshape(b, n) for t, u in zip(top, bot)
    )


def _imono_kernel_p(p, start=None, size=None):
    """One chunk of `monomial_from_values_p` where the inverse is the
    kernel's: ONE dispatch, and the trailing stage's above 2^18 rows."""
    from . import mxu_ntt

    log_n = int(p[0].shape[-1]).bit_length() - 1
    out = _imono_p_fused(p, start, size)
    if mxu_ntt.leading_outer_stages(log_n):
        out = _imono_p_trailing(out)
    _count_inverse_stages(log_n, out[0].shape[0])
    return out


def _count_inverse_stages(log_n: int, transforms: int):
    """`ntt.fused_inverse_stages`: the outer radix-2 stages that the
    kernel's radix stage took in one dispatch of the commits' inverse
    transform, times the column transforms that went through the kernel
    (none where the XLA form ran: up to 2^MAX_LOG_N rows and off the TPU);
    its twin `ntt.trailing_outer_stages`: those of `_imono_p_trailing`."""
    from .mxu_ntt import fused_outer_stages, leading_outer_stages

    _metrics.count(
        "ntt.fused_inverse_stages", fused_outer_stages(log_n) * transforms
    )
    _metrics.count(
        "ntt.trailing_outer_stages", leading_outer_stages(log_n) * transforms
    )


def imono_chunks(B: int, n: int) -> dict:
    """{first column: columns} of the chunks `monomial_from_values_p`
    walks over a (B, n) stack."""
    per = _col_chunks(B, n * 8) or B
    return {i: min(per, B - i) for i in range(0, B, per)}


def monomial_from_values_p(p):
    """Values over H -> monomial coefficients, on planes (chunked). Above
    2^MAX_LOG_N rows, where the MXU transforms are native, a chunk is the
    inverse matmul kernel on the values as they lie (`_imono_kernel_p`);
    elsewhere, and under a tracer, the XLA stages of `_imono_p_jit`."""
    lo, hi = p
    if lo.ndim < 2:
        return _imono_p_jit(p)
    B, n = lo.shape[0], lo.shape[-1]
    chunks = imono_chunks(B, lo.size // B)
    traced = isinstance(lo, jax.core.Tracer)
    kernel = lo.ndim == 2 and not traced and inverse_is_own_program(n)
    if not (kernel or traced):  # present and 0: nothing went to the kernel
        _count_inverse_stages(n.bit_length() - 1, 0)

    def produce(i):
        size = chunks[i]
        if kernel:
            cut = (None, None) if size == B else (jnp.int32(i), size)
            return _imono_kernel_p(p, *cut)
        return _imono_p_jit(
            p if size == B else (lo[i : i + size], hi[i : i + size])
        )

    if len(chunks) == 1:
        return produce(0)
    return _assemble_chunks_p(lo.shape, produce, chunks)


# ---------------------------------------------------------------------------
# Precompile enumeration (ntt.ntt_kernel_specs twin, resident names)
# ---------------------------------------------------------------------------


def sdsp(*shape):
    """A (lo, hi) pair of u32 ShapeDtypeStructs: one plane element."""
    s = jax.ShapeDtypeStruct(shape, jnp.uint32)
    return (s, s)


def hybrid_fwd_kernel_specs(name: str, call: tuple, log_n: int,
                            programs) -> list:
    """What `_hybrid_fwd_p` dispatches for `call` = (p, scale, start,
    size), as it takes them (shapes for arrays): the fused program, and
    above 2^(MAX_LOG_N + 2) rows the outer program before it."""
    from . import mxu_ntt

    outer, fused = programs
    if not mxu_ntt.leading_outer_stages(log_n):
        return [(f"{name}:fused", fused, (*call, log_n))]
    staged = jax.eval_shape(lambda *a: outer(*a, call[3], log_n), *call[:3])
    return [
        (f"{name}:outer", outer, (*call, log_n)),
        (f"{name}:fused", fused, (staged, None, None, None, log_n)),
    ]


def imono_kernel_specs(B: int, log_n: int) -> list:
    """What `monomial_from_values_p` dispatches for a (B, 2^log_n) stack
    where the inverse is the kernel's: for each size of chunk the fused
    program (the chunk's first column is a device scalar, so chunks of one
    size share it) and, above 2^(MAX_LOG_N + 2) rows, the trailing stage
    on what it returns."""
    from . import mxu_ntt

    n = 1 << log_n
    ctx = mxu_ntt.get_mxu_ctx(mxu_ntt.MAX_LOG_N)
    k = mxu_ntt.fused_outer_stages(log_n)
    trailing = mxu_ntt.leading_outer_stages(log_n)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    sizes = set(imono_chunks(B, n).values())
    specs = []
    for b in sorted(sizes):
        call = (sdsp(B, n), None, None) if b == B else (sdsp(B, n), i32, b)
        name = f"imono_kernel_limbres_b{b}_n{n}"
        specs.append((f"{name}:fused", _imono_p_fused, call))
        if trailing:
            staged = sdsp(b, 1 << trailing, ctx.R, ctx.C << k)
            specs.append((f"{name}:trailing", _imono_p_trailing, (staged,)))
    return specs


def plane_ntt_kernel_specs(B: int, log_n: int, lde_factor: int | None = None,
                           coset: int = int(gl.MULTIPLICATIVE_GENERATOR),
                           mono: bool = True) -> list:
    """(name, jitted_fn, args) triples for the plane transforms a resident
    prove dispatches for a (B, 2^log_n) column stack — mirroring the
    MXU-vs-XLA routing and the chunk walk of the u64 ntt_kernel_specs."""
    from .ntt import chunk_shapes

    n = 1 << log_n
    specs = []
    if mono and inverse_is_own_program(n):
        specs += imono_kernel_specs(B, log_n)
    elif mono:
        specs += [
            (f"imono_limbres_b{b}_n{n}", _imono_p_jit, (sdsp(b, n),))
            for b in chunk_shapes(B, n * 8)
        ]
    if lde_factor is None:
        return specs
    L = int(lde_factor)
    coset = int(coset) % gl.P
    mxu = _mxu_ntt_ready(n, None)
    for b in chunk_shapes(B, n * 8 * L):
        if not mxu:
            specs.append((
                f"lde_limbres_b{b}_n{n}_L{L}", _lde_p_jit,
                (sdsp(b, n), L, coset),
            ))
            continue
        from . import mxu_ntt

        if log_n > mxu_ntt.MAX_LOG_N:
            specs += hybrid_fwd_kernel_specs(
                f"lde_hybrid_limbres_b{b}_n{n}_L{L}",
                (sdsp(b, n), sdsp(L, n), None, None), log_n, _LDE_FORWARD,
            )
            continue
        ctx = mxu_ntt.get_mxu_ctx(log_n)
        specs.append((
            f"lde_mxu_limbres_b{b}_n{n}_L{L}", mxu_ntt._lde_planes,
            (sdsp(b, ctx.R, ctx.C), sdsp(L, ctx.R, ctx.C), log_n, False),
        ))
    return specs
