"""NTT on the MXU: four-step decomposition as exact int8 digit matmuls.

TPU-native counterpart of the reference's vectorized NTT tier
(`/root/reference/src/fft/mod.rs:852,1088` + the AVX-512/NEON MixedGL
butterflies in `src/field/goldilocks/avx512_impl.rs`): where those beat the
generic scalar path with hand-packed SIMD, this beats XLA's emulated-u64
butterflies by moving the multiply work onto the systolic array.

A size-n transform (n = R*C, R,C <= 256) is two matrix products against
CONSTANT DFT matrices plus one elementwise twiddle:

  forward  (natural -> bit-reversed):  out = ((D_R @ X) * T) @ D_C^T
  inverse  (bit-reversed -> natural):  out = F @ ((X * 1) @ E_inv * T_inv)

with
  X      = the column viewed as an (R, C) matrix, x[i] at X[i // C][i % C]
  D_R    = omega_R^(brev(a) * r)            (R x R)
  T      = omega_n^(c * brev(a))            (R x C)
  D_C    = omega_C^(brev(d) * c)            (C x C)
  E_inv  = omega_C^(-brev(c) * c')          (C x C)
  T_inv  = omega_n^(-c' * brev(r))          (R x C)
  F      = n^-1 * omega_R^(-r' * brev(r))   (R x R)

Both conventions come out so the row-major flattening of the result IS the
bit-reversed (resp. natural) order — no transposes anywhere.

Exact integer matmul on the MXU: every Goldilocks operand is written in
BALANCED base-256 — eight signed digits d_k in [-128, 127] — and the 64
per-(digit,digit) products run as int8 x int8 -> int32 dots, the MXU's
native (and fastest: 2x bf16 on v5e) integer mode, with exact int32
accumulation at any contraction length used here. Representability: the
8-digit balanced range is [-0x8080808080808080, 0x7F7F7F7F7F7F7F7F] (=: [m,
M], every byte -128 resp. +127), and p + m < M, so for every canonical x
either x itself (x <= M) or x - p (two's complement) has an exact form —
the in-kernel conversion is one conditional `+= 2^32-1` (== -p mod 2^64)
plus a byte-wise carry chain. The 64 product planes are accumulated into 15
signed diagonal planes on the VPU, biased non-negative, then folded mod p
with 2^64 = eps = 2^32 - 1, 2^96 = -1, 2^128 = -2^32 (mod p), and the
constant bias contribution is subtracted at the end.

Sizes 2^14..2^16 run as single fused kernels; 2^17..2^22 run the leading
(resp. trailing) radix-2 stages in XLA and drop bit-exactly into per-block
2^16 kernels (DIF stage s only combines elements 2^16 apart for s < log_n-16);
on limb planes the forward and the commits' inverse take two into the kernel.

Outputs are bit-identical to the staged-XLA path (`ntt.py`): same twiddle
constants, exact integer arithmetic, canonical representatives.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..field import gl
from ..field import limbs
from ..utils.pallas_util import imap32

MIN_LOG_N = 14  # below this C < 128 lanes and the XLA path is already cheap
MAX_LOG_N = 16  # single-kernel ceiling; larger sizes go hybrid
MAX_HYBRID_LOG_N = 22

_u32 = jnp.uint32
_MASK8 = np.uint32(0xFF)
_P_LO = np.uint32(1)
_P_HI = np.uint32(0xFFFFFFFF)
_FULL = np.uint32(0xFFFFFFFF)

# Largest value representable in 8 balanced base-256 digits: 127 per byte.
# For canonical x > _M_BAL the kernel switches to the x - p representative
# (p + (minimum representable) < _M_BAL, so one switch always suffices).
_M_BAL = 0x7F7F7F7F7F7F7F7F
_M_WORD = np.uint32(0x7F7F7F7F)
# Diagonal bias making the signed diagonal planes non-negative before the
# unsigned fold: |Q_k| <= 8 pairs * 256 terms * 128*128 = 2^25.
_BIAS = np.int32(1 << 25)
_BIAS_TOTAL = sum((1 << 25) << (8 * k) for k in range(15)) % gl.P
_BIAS_PAIR = (
    np.uint32(_BIAS_TOTAL & 0xFFFFFFFF),
    np.uint32(_BIAS_TOTAL >> 32),
)

from ..utils.pallas_util import tpu_compiler_params

_COMPILER_PARAMS = tpu_compiler_params(100 * 1024 * 1024)


def _brev(log_n: int) -> np.ndarray:
    from .ntt import bitreverse_indices

    return bitreverse_indices(log_n).astype(np.int64)


def _pow_table(base: int, count: int) -> np.ndarray:
    return gl.powers_np(base, count)


def _digits8_np(x: np.ndarray):
    """u64 canonical -> (8, ..) int8 planes of balanced base-256 digits."""
    x = np.asarray(x, dtype=np.uint64)
    # x - p mod 2^64 == x + (2^32 - 1); numpy wraps mod 2^64
    u = np.where(x > np.uint64(_M_BAL), x + np.uint64(0xFFFFFFFF), x)
    digs = []
    carry = np.zeros(x.shape, dtype=np.int64)
    for k in range(8):
        b = ((u >> np.uint64(8 * k)) & np.uint64(0xFF)).astype(np.int64)
        t = b + carry
        ge = t >= 128
        digs.append((t - 256 * ge).astype(np.int8))
        carry = ge.astype(np.int64)
    return jnp.asarray(np.stack(digs))


def _pair_np(x: np.ndarray):
    lo, hi = limbs.split_np(x)
    return jnp.asarray(lo), jnp.asarray(hi)


class MXUNTTContext:
    """Baked constant matrices for one (log_R, log_C) split."""

    def __init__(self, log_n: int):
        assert MIN_LOG_N <= log_n <= MAX_LOG_N
        self.log_n = log_n
        self.n = 1 << log_n
        self.log_R = (log_n + 1) // 2
        self.log_C = log_n // 2
        R, C = 1 << self.log_R, 1 << self.log_C
        self.R, self.C = R, C

        wR = gl.omega(self.log_R)
        wC = gl.omega(self.log_C)
        wn = gl.omega(log_n)
        brR = _brev(self.log_R)
        brC = _brev(self.log_C)
        r_idx = np.arange(R, dtype=np.int64)
        c_idx = np.arange(C, dtype=np.int64)

        powsR = _pow_table(wR, R)
        powsC = _pow_table(wC, C)
        powsn = _pow_table(wn, self.n)
        powsRi = _pow_table(gl.inv(wR), R)
        powsCi = _pow_table(gl.inv(wC), C)
        powsni = _pow_table(gl.inv(wn), self.n)

        D_R = powsR[(brR[:, None] * r_idx[None, :]) % R]  # (R, R)
        D_C = powsC[(brC[:, None] * c_idx[None, :]) % C]  # (C, C)
        T = powsn[(brR[:, None] * c_idx[None, :]) % self.n]  # (R, C)
        E_inv = powsCi[(brC[:, None] * c_idx[None, :]) % C]  # (C, C): [c][c']
        T_inv = powsni[(brR[:, None] * c_idx[None, :]) % self.n]  # (R, C)
        n_inv = gl.inv(self.n)
        powsRi_scaled = np.array(
            [gl.mul(int(v), n_inv) for v in powsRi], dtype=np.uint64
        )
        F = powsRi_scaled[(r_idx[:, None] * brR[None, :]) % R]  # (R, R)

        with jax.ensure_compile_time_eval():
            self.dr = _digits8_np(D_R)  # (8, R, R)
            self.dct = _digits8_np(D_C.T.copy())  # (8, C, C)
            self.tw = _pair_np(T)
            self.einv = _digits8_np(E_inv)
            self.tw_inv = _pair_np(T_inv)
            self.f = _digits8_np(F)


@lru_cache(maxsize=None)
def get_mxu_ctx(log_n: int) -> MXUNTTContext:
    return MXUNTTContext(log_n)


# ---------------------------------------------------------------------------
# In-kernel exact GL matmul: int8 digit dots + int32 diagonals + mod-p fold
# ---------------------------------------------------------------------------


def _digit_planes(x):
    """(lo, hi) u32 pair (canonical) -> list of 8 int8 balanced-digit planes."""
    lo, hi = x
    gt = ((hi > _M_WORD) | ((hi == _M_WORD) & (lo > _M_WORD))).astype(_u32)
    # x + (2^32 - 1) where x > M  (== x - p mod 2^64, two's complement)
    lo2 = lo - gt
    hi2 = hi + (gt & (lo != 0).astype(_u32))
    planes = []
    carry = jnp.zeros_like(lo, dtype=jnp.int32)
    for w in (lo2, hi2):
        for j in range(4):
            b = (w >> np.uint32(8 * j)) & _MASK8 if j else w & _MASK8
            t = b.astype(jnp.int32) + carry
            ge = (t >= 128).astype(jnp.int32)
            planes.append((t - 256 * ge).astype(jnp.int8))
            carry = ge
    return planes


def _b2u(x):
    return x.astype(_u32)


def _addmod_any(a, b):
    """(a + b) mod p on u32 pairs, correct for ANY u64 representatives
    (unlike limbs.add, which assumes canonical inputs). Result < 2^64 and
    congruent mod p; not necessarily canonical."""
    lo = a[0] + b[0]
    c0 = _b2u(lo < b[0])
    hi_t = a[1] + b[1]
    c1 = _b2u(hi_t < b[1])
    hi = hi_t + c0
    c2 = _b2u(hi < c0)
    carry = c1 | c2  # the two sub-carries cannot both fire for u64 operands
    # += carry * eps (2^64 ≡ eps); the +eps can itself wrap once more
    lo2 = lo - carry
    d1 = carry & _b2u(lo != 0)
    c3 = d1 & _b2u(hi == _FULL)
    hi2 = hi + d1
    lo3 = lo2 - c3
    d2 = c3 & _b2u(lo2 != 0)
    hi3 = hi2 + d2  # cannot wrap a third time: value is < 2^33 by then
    return lo3, hi3


def _eps_times(v):
    """eps * v as a u64 pair, exact for any u32 v: v*2^32 - v."""
    return np.uint32(0) - v, v - _b2u(v != 0)


def _p_minus_small(v):
    """p - v for u32 v (v*2^96 ≡ -v mod p)."""
    lo = _P_LO - v
    borrow = _b2u(v > 1)
    return lo, _P_HI - borrow


def _p_minus_hi(v):
    """p - v*2^32 for u32 v (v*2^128 ≡ -v*2^32 mod p)."""
    return jnp.full_like(v, _P_LO), _P_HI - v


def _fold15_signed(Q):
    """15 SIGNED int32 diagonal planes (|Q_k| <= 2^25) -> canonical GL pair.

    Bias each plane non-negative, run the unsigned fold, subtract the baked
    bias total mod p."""
    Qb = [(q + _BIAS).astype(_u32) for q in Q]
    acc = _fold15(Qb)
    bias = (
        jnp.full_like(acc[0], _BIAS_PAIR[0]),
        jnp.full_like(acc[1], _BIAS_PAIR[1]),
    )
    return limbs.sub(acc, bias)


def _fold15(Q):
    """15 int32 diagonal planes (Q_k < 2^31) -> canonical GL (lo, hi) pair.

    W = sum_k Q_k * 2^(8k) accumulated exactly into five u32 words with wrap
    counters, then folded with 2^64 ≡ eps, 2^96 ≡ -1, 2^128 ≡ -2^32 (mod p).
    """
    w = [None] * 5
    cnt = [None] * 5

    def _add_word(j, val):
        if w[j] is None:
            w[j] = val
            return
        nw = w[j] + val
        c = _b2u(nw < val)
        cnt[j] = c if cnt[j] is None else cnt[j] + c
        w[j] = nw

    for k in range(15):
        q = Q[k].astype(_u32)
        j, m = divmod(k, 4)
        sh = 8 * m
        _add_word(j, (q << np.uint32(sh)) if sh else q)
        if sh:
            _add_word(j + 1, q >> np.uint32(32 - sh))
    zero = jnp.zeros_like(Q[0].astype(_u32))
    for j in range(5):
        if w[j] is None:
            w[j] = zero
    # resolve wrap counters upward (w4 stays tiny: W < 2^140, so no overflow)
    for j in range(4):
        if cnt[j] is not None:
            _add_word(j + 1, cnt[j])

    acc = (w[0], w[1])
    acc = _addmod_any(acc, _eps_times(w[2]))
    acc = _addmod_any(acc, _p_minus_small(w[3]))
    acc = _addmod_any(acc, _p_minus_hi(w[4]))
    return limbs._canonicalize(*acc)


def _gl_matmul(x, dref, side: str):
    """Exact GL matmul of data pair `x` against baked int8 digit planes.

    side='left':  result = D @ X   (contract over X's rows)
    side='right': result = X @ D   (contract over X's cols)
    """
    planes = _digit_planes(x)
    Q = [None] * 15
    for u in range(8):
        du = dref[u]
        for v in range(8):
            if side == "left":
                p = jnp.dot(du, planes[v], preferred_element_type=jnp.int32)
            else:
                p = jnp.dot(planes[v], du, preferred_element_type=jnp.int32)
            k = u + v
            Q[k] = p if Q[k] is None else Q[k] + p
    return _fold15_signed(Q)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------
# G columns process per grid step (see _TARGET_N): the dots become
# (R, R) @ (R, G*C) / (G*R, C) @ (C, C), so the MXU sees an N dimension of
# G*C instead of C. The relayouts between the row-stacked and lane-stacked
# views are leading-axis transposes (sublane shuffles).


def _pair_t(x, perm):
    return (jnp.transpose(x[0], perm), jnp.transpose(x[1], perm))


def _tile_lanes(t, G, R, C):
    """(R, C) twiddle plane -> (R, G*C): repeat per column along lanes."""
    return jnp.broadcast_to(t[:, None, :], (R, G, C)).reshape(R, G * C)


def _tile_rows(t, G, R, C):
    """(R, C) twiddle plane -> (G*R, C): repeat per column along rows."""
    return jnp.broadcast_to(t[None], (G, R, C)).reshape(G * R, C)


def _fwd_body(ctx, x, dr, dct, tlo, thi, G):
    R, C = ctx.R, ctx.C
    if G > 1:
        # (G, R, C) -> (R, G*C): lane-stack the G column matrices
        x = _pair_t(x, (1, 0, 2))
        x = (x[0].reshape(R, G * C), x[1].reshape(R, G * C))
        tlo, thi = _tile_lanes(tlo, G, R, C), _tile_lanes(thi, G, R, C)
    y = _gl_matmul(x, dr, "left")
    y = limbs.mul(y, (tlo, thi))
    if G > 1:
        # (R, G*C) -> (G*R, C): row-stack for the right-multiply
        y = (y[0].reshape(R, G, C), y[1].reshape(R, G, C))
        y = _pair_t(y, (1, 0, 2))
        y = (y[0].reshape(G * R, C), y[1].reshape(G * R, C))
    return _gl_matmul(y, dct, "right")


def _fwd_kernel(ctx, G, dr, dct, tlo, thi, xl, xh, ol, oh):
    x = (xl[:], xh[:]) if G > 1 else (xl[0], xh[0])
    z = _fwd_body(ctx, x, dr, dct, tlo[:], thi[:], G)
    if G > 1:
        R, C = ctx.R, ctx.C
        ol[:] = z[0].reshape(G, R, C)
        oh[:] = z[1].reshape(G, R, C)
    else:
        ol[0] = z[0]
        oh[0] = z[1]


def _fwd_scaled_kernel(ctx, dr, dct, tlo, thi, sl, sh, xl, xh, ol, oh):
    x = limbs.mul((xl[0], xh[0]), (sl[0], sh[0]))
    z = _fwd_body(ctx, x, dr, dct, tlo[:], thi[:], 1)
    ol[0, 0] = z[0]
    oh[0, 0] = z[1]


def _inv_kernel(ctx, G, einv, f, tlo, thi, xl, xh, ol, oh):
    R, C = ctx.R, ctx.C
    if G > 1:
        x = (xl[:].reshape(G * R, C), xh[:].reshape(G * R, C))
        tlo_t, thi_t = _tile_rows(tlo[:], G, R, C), _tile_rows(thi[:], G, R, C)
    else:
        x = (xl[0], xh[0])
        tlo_t, thi_t = tlo[:], thi[:]
    y = _gl_matmul(x, einv, "right")
    y = limbs.mul(y, (tlo_t, thi_t))
    if G > 1:
        # (G*R, C) -> (R, G*C) for the left-multiply
        y = (y[0].reshape(G, R, C), y[1].reshape(G, R, C))
        y = _pair_t(y, (1, 0, 2))
        y = (y[0].reshape(R, G * C), y[1].reshape(R, G * C))
    z = _gl_matmul(y, f, "left")
    if G > 1:
        z = (z[0].reshape(R, G, C), z[1].reshape(R, G, C))
        z = _pair_t(z, (1, 0, 2))
        ol[:] = z[0]
        oh[:] = z[1]
    else:
        ol[0] = z[0]
        oh[0] = z[1]


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _const_spec(shape):
    nd = len(shape)
    return pl.BlockSpec(
        shape,
        imap32(lambda *_: (0,) * nd),
        memory_space=pltpu.VMEM,
    )


def _data_spec(R, C, G=1):
    return pl.BlockSpec(
        (G, R, C), imap32(lambda b: (b, 0, 0)), memory_space=pltpu.VMEM
    )


# Columns per grid step: the dot's N dimension becomes G*C. The MXU wants
# N >= ~1024 to stream (isolated dot throughput ~3x at G=4 vs G=1 for
# C=256); end-to-end NTT gain is smaller — the pipeline is DMA/layout
# bound — but G=4 is never slower, so it is the default.
_TARGET_N = 1024


def _pad_cols(planes, G):
    """Zero-pad the column batch to a multiple of G (returns B_orig)."""
    lo, hi = planes
    B = lo.shape[0]
    pad = (-B) % G
    if pad:
        z = jnp.zeros((pad,) + lo.shape[1:], lo.dtype)
        lo = jnp.concatenate([lo, z])
        hi = jnp.concatenate([hi, z])
    return (lo, hi), B


@partial(jax.jit, static_argnums=(1, 2))
def _fft_planes(planes, log_n: int, interpret: bool):
    ctx = get_mxu_ctx(log_n)
    R, C = ctx.R, ctx.C
    G = max(1, _TARGET_N // C)
    (lo, hi), B = _pad_cols(planes, G)
    spec = _data_spec(R, C, G)
    Bp = lo.shape[0]
    out_shape = jax.ShapeDtypeStruct((Bp, R, C), jnp.uint32)
    out = pl.pallas_call(
        partial(_fwd_kernel, ctx, G),
        grid=(Bp // G,),
        out_shape=[out_shape, out_shape],
        in_specs=[
            _const_spec((8, R, R)),
            _const_spec((8, C, C)),
            _const_spec((R, C)),
            _const_spec((R, C)),
            spec,
            spec,
        ],
        out_specs=[spec, spec],
        interpret=interpret,
        compiler_params=None if interpret else _COMPILER_PARAMS,
    )(ctx.dr, ctx.dct, *ctx.tw, lo, hi)
    return out[0][:B], out[1][:B]


@partial(jax.jit, static_argnums=(1, 2))
def _ifft_planes(planes, log_n: int, interpret: bool):
    ctx = get_mxu_ctx(log_n)
    R, C = ctx.R, ctx.C
    G = max(1, _TARGET_N // C)
    (lo, hi), B = _pad_cols(planes, G)
    spec = _data_spec(R, C, G)
    Bp = lo.shape[0]
    out_shape = jax.ShapeDtypeStruct((Bp, R, C), jnp.uint32)
    out = pl.pallas_call(
        partial(_inv_kernel, ctx, G),
        grid=(Bp // G,),
        out_shape=[out_shape, out_shape],
        in_specs=[
            _const_spec((8, C, C)),
            _const_spec((8, R, R)),
            _const_spec((R, C)),
            _const_spec((R, C)),
            spec,
            spec,
        ],
        out_specs=[spec, spec],
        interpret=interpret,
        compiler_params=None if interpret else _COMPILER_PARAMS,
    )(ctx.einv, ctx.f, *ctx.tw_inv, lo, hi)
    return out[0][:B], out[1][:B]


@partial(jax.jit, static_argnums=(2, 3))
def _lde_planes(coeff_planes, scale_planes, log_n: int, interpret: bool):
    """coeffs (B, R, C) x scale (L, R, C) -> (B, L, R, C), scale+NTT fused."""
    ctx = get_mxu_ctx(log_n)
    clo, chi = coeff_planes
    slo, shi = scale_planes
    B = clo.shape[0]
    L = slo.shape[0]
    R, C = ctx.R, ctx.C
    cspec = pl.BlockSpec(
        (1, R, C), imap32(lambda b, l: (b, 0, 0)), memory_space=pltpu.VMEM
    )
    sspec = pl.BlockSpec(
        (1, R, C), imap32(lambda b, l: (l, 0, 0)), memory_space=pltpu.VMEM
    )
    ospec = pl.BlockSpec(
        (1, 1, R, C),
        imap32(lambda b, l: (b, l, 0, 0)),
        memory_space=pltpu.VMEM,
    )
    out_shape = jax.ShapeDtypeStruct((B, L, R, C), jnp.uint32)
    return pl.pallas_call(
        partial(_fwd_scaled_kernel, ctx),
        grid=(B, L),
        out_shape=[out_shape, out_shape],
        in_specs=[
            _const_spec((8, R, R)),
            _const_spec((8, C, C)),
            _const_spec((R, C)),
            _const_spec((R, C)),
            sspec,
            sspec,
            cspec,
            cspec,
        ],
        out_specs=[ospec, ospec],
        interpret=interpret,
        compiler_params=None if interpret else _COMPILER_PARAMS,
    )(ctx.dr, ctx.dct, *ctx.tw, slo, shi, clo, chi)


# ---------------------------------------------------------------------------
# Public entry points (uint64 in / uint64 out)
# ---------------------------------------------------------------------------


def size_fits(n: int) -> bool:
    return (1 << MIN_LOG_N) <= n <= (1 << MAX_HYBRID_LOG_N)


def _to_planes(a: jax.Array, R: int, C: int):
    lead = a.shape[:-1]
    flat = a.reshape(-1, R, C)
    return limbs.split(flat), lead


def _from_planes(planes, lead, n):
    return limbs.join(planes).reshape(lead + (n,))


def fft_natural_to_bitreversed(a: jax.Array, interpret: bool = False):
    n = a.shape[-1]
    log_n = n.bit_length() - 1
    if log_n > MAX_LOG_N:
        return _fft_hybrid(a, log_n, interpret)
    ctx = get_mxu_ctx(log_n)
    planes, lead = _to_planes(a, ctx.R, ctx.C)
    out = _fft_planes(planes, log_n, interpret)
    return _from_planes(out, lead, n)


def ifft_bitreversed_to_natural(a: jax.Array, interpret: bool = False):
    n = a.shape[-1]
    log_n = n.bit_length() - 1
    if log_n > MAX_LOG_N:
        return _ifft_hybrid(a, log_n, interpret)
    ctx = get_mxu_ctx(log_n)
    planes, lead = _to_planes(a, ctx.R, ctx.C)
    out = _ifft_planes(planes, log_n, interpret)
    return _from_planes(out, lead, n)


def lde_from_monomial(coeffs: jax.Array, scale: jax.Array, interpret: bool = False):
    """coeffs (..., n), scale (lde, n) -> (..., lde, n); fused scale+NTT."""
    n = coeffs.shape[-1]
    log_n = n.bit_length() - 1
    lde = scale.shape[0]
    if log_n > MAX_LOG_N:
        from ..field import goldilocks as gf

        scaled = gf.mul(coeffs[..., None, :], scale)
        return _fft_hybrid(scaled, log_n, interpret)
    ctx = get_mxu_ctx(log_n)
    planes, lead = _to_planes(coeffs, ctx.R, ctx.C)
    s_planes = limbs.split(scale.reshape(lde, ctx.R, ctx.C))
    out = _lde_planes(planes, s_planes, log_n, interpret)
    return limbs.join(out).reshape(lead + (lde, n))


# ---------------------------------------------------------------------------
# Hybrid sizes (2^17..2^22): XLA outer radix-2 stages + per-block kernels
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(1, 2))
def _fft_hybrid(a: jax.Array, log_n: int, interpret: bool):
    from .ntt import dif_stages, get_ntt_context

    n = 1 << log_n
    outer = log_n - MAX_LOG_N
    ctx = get_ntt_context(log_n)
    a = dif_stages(a, ctx, 0, outer)
    lead = a.shape[:-1]
    blocks = a.reshape(lead + (1 << outer, 1 << MAX_LOG_N))
    out = fft_natural_to_bitreversed(blocks, interpret)
    return out.reshape(lead + (n,))


def _ifft_hybrid_blocks_body(a: jax.Array, log_n: int, interpret: bool):
    """The 2^MAX_LOG_N-blocks' own inverse kernels (each includes its
    1/2^MAX_LOG_N) on bit-reversed input of size 2^log_n."""
    outer = log_n - MAX_LOG_N
    lead = a.shape[:-1]
    blocks = a.reshape(lead + (1 << outer, 1 << MAX_LOG_N))
    out = ifft_bitreversed_to_natural(blocks, interpret)
    return out.reshape(lead + (1 << log_n,))


def _ifft_hybrid_outer_body(out: jax.Array, log_n: int):
    """The outer radix-2 DIT stages and the leftover 1/2^outer."""
    from ..field import goldilocks as gf
    from .ntt import dit_stages, get_ntt_context

    out = dit_stages(out, get_ntt_context(log_n), MAX_LOG_N, log_n)
    return gf.mul(out, jnp.uint64(gl.inv(1 << (log_n - MAX_LOG_N))))


@partial(jax.jit, static_argnums=(1, 2))
def _ifft_hybrid(a: jax.Array, log_n: int, interpret: bool):
    return _ifft_hybrid_outer_body(
        _ifft_hybrid_blocks_body(a, log_n, interpret), log_n
    )


_ifft_hybrid_blocks = jax.jit(_ifft_hybrid_blocks_body, static_argnums=(1, 2))
_ifft_hybrid_outer = jax.jit(_ifft_hybrid_outer_body, static_argnums=(1,))


def ifft_hybrid_apart(a: jax.Array, log_n: int, interpret: bool = False):
    """`_ifft_hybrid` as two device programs, for a caller outside jit.
    Compiled with the bit reversal into the ONE program of
    `ntt._monomial_from_values_jit`, the inverse of (10, 2^18) u64 columns
    did not come back in 150 s on the v5e, where (64, 2^18) takes 28 ms;
    apart, the reversal, these two and 10 columns take 0.48 s (my chip
    run, PR 32). The forward's story, on planes, is
    `limb_ntt._hybrid_fwd_p`'s (PR 26)."""
    return _ifft_hybrid_outer(_ifft_hybrid_blocks(a, log_n, interpret), log_n)


# ---------------------------------------------------------------------------
# 2^17 and 2^18 rows forward as ONE kernel: the coset row and the outer
# radix-2 DIF stages are a radix-2^k prologue of the matmul kernel (PR 35)
# ---------------------------------------------------------------------------
# A column of 2^(MAX_LOG_N + k) rows is 2^k contiguous parts of 2^MAX_LOG_N,
# and k DIF stages only combine the elements at one index j of every part.
# For k = 2, parts A, B, C, D, the row s, w = omega_n and i = w^(n/4) = 2^48:
#
#   a, b, c, d = A[j] s[j], B[j] s[j + n/4], C[j] s[j + n/2], D[j] s[j + 3n/4]
#   y0[j] =  (a + c) + (b + d)
#   y1[j] = ((a + c) - (b + d)) * w^(2j)
#   y2[j] = ((a - c) + i (b - d)) * w^j
#   y3[j] = ((a - c) - i (b - d)) * w^(3j)
#
# and y0..y3 are the blocks the matmul kernel transforms, in its order. It
# is added BELOW the kernels above so that theirs keep their line numbers
# (a Mosaic program's cache key carries the call stack it was traced under).

MAX_FUSED_OUTER = 2  # outer stages the prologue absorbs: radix 4
# Rows of a block a prologue step holds. The kernel alone on 64 columns of
# 2^18 rows under a row: 8.93 ms at 8 rows (one sublane tile), 7.75 at 16,
# 7.86 at 32, 8.68 with the whole block in one step; the blocks' matmul
# kernel without a prologue 6.55 (my chip run, PR 35).
_PROLOGUE_ROWS = 16


def fused_outer_stages(log_n: int) -> int:
    """Radix-2 stages of a size-2^log_n forward transform that run inside
    the matmul kernel (`_fwd_radix_planes`): 0 up to the single-kernel
    ceiling, 1 at the next size, 2 from there up."""
    return max(0, min(log_n - MAX_LOG_N, MAX_FUSED_OUTER))


def leading_outer_stages(log_n: int) -> int:
    """The outer stages before those: what an XLA program still runs
    ahead of the kernel, above 2^(MAX_LOG_N + MAX_FUSED_OUTER) rows."""
    return max(0, log_n - MAX_LOG_N) - fused_outer_stages(log_n)


@lru_cache(maxsize=None)
def _radix_tables(log_block: int, k: int):
    """(lo, hi), each (2^k - 1, R, C): w^(m j) for m = 1 .. 2^k - 1 and
    j < 2^log_block, w = omega of the 2^(log_block + k) rows, laid out as
    the kernel's blocks (element j at [j // C][j % C])."""
    ctx = get_mxu_ctx(log_block)
    w = gl.omega(log_block + k)
    # the radix-4 butterfly multiplies by i = w^(n/4) with shifts alone
    assert k == 1 or gl.pow_(w, 1 << log_block) == 1 << 48
    tabs = np.stack([
        _pow_table(gl.pow_(w, m), 1 << log_block).reshape(ctx.R, ctx.C)
        for m in range(1, 1 << k)
    ])
    with jax.ensure_compile_time_eval():
        return _pair_np(tabs)


def _mul_i(x):
    """x * 2^48, a primitive fourth root of unity (2^96 = -1 mod p): a
    16-bit shift and a word shift, then the 128-bit reduction."""
    q0, q1, q2 = limbs._shl96(x, 16)
    return limbs.reduce128(jnp.zeros_like(q0), q0, q1, q2)


def radix_prologue(x, s, w):
    """The 2^k parts `x` of a column times the parts `s` of its row (None:
    no row), then k radix-2 DIF stages across the parts, k = 1 or 2. `w`:
    the 2^k - 1 tables of `_radix_tables`. Pairs in, pairs out, exact."""
    if s is not None:
        x = [limbs.mul(xi, si) for xi, si in zip(x, s)]
    if len(x) == 2:
        a, b = x
        return [limbs.add(a, b), limbs.mul(limbs.sub(a, b), w[0])]
    a, b, c, d = x
    ac, bd = limbs.add(a, c), limbs.add(b, d)
    ca, db = limbs.sub(a, c), _mul_i(limbs.sub(b, d))
    return [
        limbs.add(ac, bd),
        limbs.mul(limbs.sub(ac, bd), w[1]),
        limbs.mul(limbs.add(ca, db), w[0]),
        limbs.mul(limbs.sub(ca, db), w[2]),
    ]


def _fwd_radix_kernel(ctx, G, scaled, dr, dct, tlo, thi, wl, wh, *refs):
    """One column (and one row of the scale) a grid step: the prologue a
    few rows at a time, staged in the output block, then `_fwd_body` on
    the G blocks it left there."""
    sl, sh = refs[:2] if scaled else (None, None)
    xl, xh, ol, oh = refs[-4:]
    R, C = ctx.R, ctx.C

    def step(r, _):
        # i32 arithmetic: under x64 a bare product is i64, which Mosaic refuses
        first = pl.multiple_of(jnp.int32(_PROLOGUE_ROWS) * r, _PROLOGUE_ROWS)
        rows = pl.ds(first, _PROLOGUE_ROWS)
        x = [(xl[g, rows, :], xh[g, rows, :]) for g in range(G)]
        s = None
        if scaled:
            s = [(sl[g, rows, :], sh[g, rows, :]) for g in range(G)]
        w = [(wl[m, rows, :], wh[m, rows, :]) for m in range(G - 1)]
        for g, y in enumerate(radix_prologue(x, s, w)):
            ol[g, rows, :] = y[0]
            oh[g, rows, :] = y[1]

    steps = jnp.int32(R // _PROLOGUE_ROWS)
    jax.lax.fori_loop(jnp.int32(0), steps, step, None)
    z = _fwd_body(ctx, (ol[:], oh[:]), dr, dct, tlo[:], thi[:], G)
    ol[:] = z[0].reshape(G, R, C)
    oh[:] = z[1].reshape(G, R, C)


@partial(jax.jit, static_argnums=(2, 3))
def _fwd_radix_planes(planes, scale_planes, k: int, interpret: bool):
    """Forward transforms of 2^(MAX_LOG_N + k) rows, k = 1 or 2, scale
    and outer stages fused. `planes`: (B * 2^k, R, C), the 2^k parts of B
    columns; `scale_planes`: (L * 2^k, R, C), the parts of L rows, or None
    for a transform without a row (L = 1) -> (B * L * 2^k, R, C): column
    b under row l, bit-reversed, at blocks [(b * L + l) * 2^k, + 2^k)."""
    G = 1 << k
    ctx = get_mxu_ctx(MAX_LOG_N)
    R, C = ctx.R, ctx.C
    lo, hi = planes
    B = lo.shape[0] // G
    scaled = scale_planes is not None
    L = scale_planes[0].shape[0] // G if scaled else 1

    def spec(index):
        return pl.BlockSpec((G, R, C), imap32(index), memory_space=pltpu.VMEM)

    tables = _radix_tables(MAX_LOG_N, k)
    scale_specs = [spec(lambda b, l: (l, 0, 0))] * 2 if scaled else []
    out_shape = jax.ShapeDtypeStruct((B * L * G, R, C), jnp.uint32)
    return pl.pallas_call(
        partial(_fwd_radix_kernel, ctx, G, scaled),
        grid=(B, L),
        out_shape=[out_shape, out_shape],
        in_specs=[
            _const_spec((8, R, R)),
            _const_spec((8, C, C)),
            _const_spec((R, C)),
            _const_spec((R, C)),
            _const_spec((G - 1, R, C)),
            _const_spec((G - 1, R, C)),
            *scale_specs,
            spec(lambda b, l: (b, 0, 0)),
            spec(lambda b, l: (b, 0, 0)),
        ],
        out_specs=[spec(lambda b, l: (b * L + l, 0, 0))] * 2,
        interpret=interpret,
        compiler_params=None if interpret else _COMPILER_PARAMS,
    )(ctx.dr, ctx.dct, *ctx.tw, *tables, *(scale_planes or ()), lo, hi)


# ---------------------------------------------------------------------------
# 2^17 .. 2^19 rows inverse, NATURAL order in and out, as ONE kernel: the
# outer radix-2 DIT stages are a radix-2^k stage of the matmul kernel (PR 40)
# ---------------------------------------------------------------------------
# A column of h * 2^k * R * C values v[t] (h = 2^trailing) is h decimated
# sub-columns u_e[t'] = v[h t' + e] of n' = 2^k R C values; with G = 2^k
#
#   t' = tc (G R) + g R + tr      tc < C, g < G, tr < R
#   j' = jr (G C) + q C + jc      jr < R, q < G, jc < C
#   w^(-t' j') = wC^(-tc jc) . w^(-R g jc) . wG^(-g q) . w^(-tr (q C + jc))
#                . wR^(-tr jr)                      (w = omega of n' values)
#
# so the sub-column's inverse is: a product with the PLAIN C x C inverse DFT
# matrix over tc, the table w^(-R g jc), a radix-G butterfly across g, the
# table w^(-tr (q C + jc)), and a product with the plain R x R matrix over
# tr (n^-1 folded in). v.reshape(C, G R h) is [tc][g R h + tr h + e]: the G
# parts are LANE blocks of the column as it lies in memory, the first
# product is a left-multiply at N = G R h, and the values reach the second
# as [tr][q C + jc] through one 2-D transpose a part (rows tr h + e: the h
# sub-columns are strided row reads). Its result (R, G C) is the natural
# order of the monomials. No bit reversal anywhere: the digit reversal of
# a natural-order transform is that one transpose. The h sub-columns'
# results meet in `limb_ntt`'s trailing stage, a program of its own.

# `leading_outer_stages(log_n)` counts, for this inverse, the outer DIT
# stages that the radix stage does not take and an XLA program runs AFTER
# the kernel: sub-columns a grid step holds, 2 at most (VMEM).
MAX_TRAILING_OUTER = 1
# Rows of a part a step of the radix stage holds: (64, 2^18) in 7.08 ms at
# 8, 7.04 at 16, 7.05 at 32 (my chip run, PR 40): no difference.
_RADIX_STAGE_ROWS = 16
_MATMUL_LANES = 1024  # lanes a product takes at a time (`_TARGET_N`)


@lru_cache(maxsize=None)
def _inv_radix_consts(log_block: int, k: int, trailing: int):
    """(e, f, tw1, tw2) of the header: the two plain inverse DFT matrices
    as int8 digit planes (f times the inverse of ALL the rows,
    2^(log_block + k + trailing)); tw1 (2^k - 1, C, 128): w^(-R m jc) for
    m = 1 .. 2^k - 1, constant along the lanes; tw2 (2^k, C, R h):
    w^(-tr (q C + jc)) at [q][jc][tr h + e]."""
    ctx = get_mxu_ctx(log_block)
    R, C = ctx.R, ctx.C
    G, H = 1 << k, 1 << trailing
    n_sub = G * R * C
    w = gl.omega(log_block + k)
    # the radix-4 butterfly multiplies by i = w^(n'/4) with shifts alone
    assert k < 2 or gl.pow_(w, n_sub // 4) == 1 << 48
    pows = _pow_table(gl.inv(w), n_sub)
    jc = np.arange(C, dtype=np.int64)
    jr = np.arange(R, dtype=np.int64)
    E = pows[(G * R * jc[:, None] * jc[None, :]) % n_sub]
    F = gl.mul_np(
        pows[(G * C * jr[:, None] * jr[None, :]) % n_sub],
        np.uint64(gl.inv(n_sub << trailing)),
    )
    m = np.arange(1, G, dtype=np.int64)
    tw1 = pows[(R * m[:, None] * jc[None, :]) % n_sub]
    tw1 = np.broadcast_to(tw1[:, :, None], (G - 1, C, 128))
    q = np.arange(G, dtype=np.int64)
    tw2 = pows[
        (jr[None, None, :] * (q[:, None, None] * C + jc[None, :, None]))
        % n_sub
    ]
    with jax.ensure_compile_time_eval():
        return (
            _digits8_np(E), _digits8_np(F), _pair_np(np.ascontiguousarray(tw1)),
            _pair_np(np.repeat(tw2, H, axis=-1)),
        )


def radix_inverse_stage(z, w1, w2):
    """The 2^k parts `z` of a column between the inverse's two products:
    part g times `w1[g - 1]`, the inverse radix-2^k butterfly across the
    parts, result q times `w2[q]`; k = 1 or 2. Pairs in and out, exact."""
    z = [z[0]] + [limbs.mul(zi, wi) for zi, wi in zip(z[1:], w1)]
    if len(z) == 2:
        a, b = z
        y = [limbs.add(a, b), limbs.sub(a, b)]
    else:
        a, b, c, d = z
        ac, bd = limbs.add(a, c), limbs.add(b, d)
        ca, db = limbs.sub(a, c), _mul_i(limbs.sub(b, d))
        # i^-1 = -i: the forward's butterfly with its odd results swapped
        y = [
            limbs.add(ac, bd), limbs.sub(ca, db),
            limbs.sub(ac, bd), limbs.add(ca, db),
        ]
    return [limbs.mul(yi, wi) for yi, wi in zip(y, w2)]


def _inv_radix_kernel(ctx, G, H, e, f, t1l, t1h, t2l, t2h, xl, xh, ol, oh,
                      sl, sh, ul, uh):
    """One column a grid step. `s` (C, G R H) holds it between the
    products, `u` its parts' transposes, (G R H, C) in lane tiles."""
    R, C = ctx.R, ctx.C
    W = R * H  # lanes of a part
    L = G * W
    for first in range(0, L, _MATMUL_LANES):
        lanes = slice(first, min(first + _MATMUL_LANES, L))
        y = _gl_matmul((xl[0, :, lanes], xh[0, :, lanes]), e, "left")
        sl[:, lanes] = y[0]
        sh[:, lanes] = y[1]

    def part(g):
        return slice(g * W, (g + 1) * W)

    def step(r, _):
        # i32 arithmetic: under x64 a bare product is i64, which Mosaic refuses
        rows = pl.ds(
            pl.multiple_of(jnp.int32(_RADIX_STAGE_ROWS) * r, _RADIX_STAGE_ROWS),
            _RADIX_STAGE_ROWS,
        )

        def lanes_of(t):  # (rows, 128), constant along the lanes -> (rows, W)
            return jnp.concatenate([t] * (W // 128), axis=1)

        z = [(sl[rows, part(g)], sh[rows, part(g)]) for g in range(G)]
        w1 = [
            (lanes_of(t1l[m, rows, :]), lanes_of(t1h[m, rows, :]))
            for m in range(G - 1)
        ]
        w2 = [(t2l[q, rows, :], t2h[q, rows, :]) for q in range(G)]
        for q, y in enumerate(radix_inverse_stage(z, w1, w2)):
            sl[rows, part(q)] = y[0]
            sh[rows, part(q)] = y[1]

    steps = jnp.int32(C // _RADIX_STAGE_ROWS)
    jax.lax.fori_loop(jnp.int32(0), steps, step, None)
    # a strided row read wants a base 128 lanes wide: `u` is (C / 128, G R H,
    # 128), the transposes' lane tiles side by side
    tiles = range(C // 128)
    for q in range(G):
        for s, u in ((sl, ul), (sh, uh)):
            t = s[:, part(q)].T
            for c in tiles:
                u[c, part(q), :] = t[:, c * 128:(c + 1) * 128]
    for h in range(H):
        def rows_of(u):  # sub-column h of every part: (R, G C)
            return jnp.concatenate(
                [
                    u[c, pl.ds(q * W + h, R, stride=H), :]
                    for q in range(G) for c in tiles
                ],
                axis=1,
            )

        z = _gl_matmul((rows_of(ul), rows_of(uh)), f, "left")
        ol[0, h] = z[0]
        oh[0, h] = z[1]


@partial(jax.jit, static_argnums=(1, 2, 3))
def _inv_radix_planes(planes, k: int, trailing: int, interpret: bool):
    """Inverse transforms of B columns of 2^(MAX_LOG_N + k + trailing)
    values in natural order, `planes` (B, C, 2^(k + trailing) R) as the
    columns lie in memory -> (B, 2^trailing, R, 2^k C): the monomials of
    the decimated sub-columns, each in natural order, the 1/n of the whole
    column in them."""
    G, H = 1 << k, 1 << trailing
    ctx = get_mxu_ctx(MAX_LOG_N)
    R, C = ctx.R, ctx.C
    lo, hi = planes
    B, L = lo.shape[0], G * H * R
    e, f, tw1, tw2 = _inv_radix_consts(MAX_LOG_N, k, trailing)
    in_spec = pl.BlockSpec(
        (1, C, L), imap32(lambda b: (b, 0, 0)), memory_space=pltpu.VMEM
    )
    out_spec = pl.BlockSpec(
        (1, H, R, G * C), imap32(lambda b: (b, 0, 0, 0)),
        memory_space=pltpu.VMEM,
    )
    out_shape = jax.ShapeDtypeStruct((B, H, R, G * C), jnp.uint32)
    return pl.pallas_call(
        partial(_inv_radix_kernel, ctx, G, H),
        grid=(B,),
        out_shape=[out_shape, out_shape],
        in_specs=[
            _const_spec((8, C, C)),
            _const_spec((8, R, R)),
            _const_spec((G - 1, C, 128)),
            _const_spec((G - 1, C, 128)),
            _const_spec((G, C, H * R)),
            _const_spec((G, C, H * R)),
            in_spec,
            in_spec,
        ],
        out_specs=[out_spec, out_spec],
        scratch_shapes=[pltpu.VMEM((C, L), jnp.uint32)] * 2
        + [pltpu.VMEM((C // 128, L, 128), jnp.uint32)] * 2,
        interpret=interpret,
        compiler_params=None if interpret else _COMPILER_PARAMS,
    )(e, f, *tw1, *tw2, lo, hi)
