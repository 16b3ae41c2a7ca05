"""Kernel microbenchmarks, one JSON line per metric.

Counterpart of the reference's criterion benches + profiling binary
(`/root/reference/benches/benchmarks.rs:20`,
`/root/reference/profiling-target/src/main.rs:17`): field mul, NTT across
sizes, Poseidon2 permutation, batch inversion — so per-round kernel work is
tracked by the record instead of ad-hoc session numbers.

All metrics chain reps ON DEVICE inside one dispatch (jax.lax.fori_loop), so
a reading is the chip's time and not the host's per-launch overhead.

Usage: python bench_micro.py  (JSON lines on stdout; backend = ambient JAX)
       python bench_micro.py poseidon2  (the Poseidon2 section alone)
       python bench_micro.py binv       (the batch-inversion section alone)
       python bench_micro.py ntt        (the transforms above 2^16 rows alone:
                                         the forward, then the commits' inverse)
       python bench_micro.py ntt inverse [log_n ..] (the commits' inverse alone)
       python bench_micro.py transcript (the host permutation under the transcript)
"""

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from boojum_tpu.field import gl
from boojum_tpu.field import goldilocks as gf


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, gl.P, size=shape, dtype=np.uint64))


def timed_chain(body, x, reps):
    @jax.jit
    def run(v):
        return jax.lax.fori_loop(0, reps, lambda _, u: body(u), v)

    jax.block_until_ready(run(x))  # compile
    t0 = time.perf_counter()
    jax.block_until_ready(run(x))
    return (time.perf_counter() - t0) / reps


def host_identity() -> dict:
    """The machine/software identity block stamped on every JSON line
    (ISSUE 12 satellite): host CPU fingerprint, device kind, backend,
    jax/jaxlib versions — the SAME fields prover/aot.py validates bundle
    portability on, so `prove_report.py --trend` can group micro lines
    by machine and software version instead of mixing a laptop's numbers
    into a pod's series. platform_info() memoizes per process."""
    try:
        from boojum_tpu.prover.aot import platform_info

        return platform_info()
    except Exception:
        return {}


def emit(metric, value, unit, **extra):
    line = {"metric": metric, "value": value, "unit": unit, **extra}
    ident = host_identity()
    if ident:
        line["host"] = ident
    print(json.dumps(line))


def main():
    backend = jax.default_backend()

    # field mul throughput (a <- a*a + c keeps the chain live)
    n = 1 << 22
    a = _rand((n,), 1)
    c = _rand((n,), 2)
    dt = timed_chain(lambda v: gf.add(gf.mul(v, v), c), a, 8)
    emit("field_mul_elems_per_s", int(n / dt), "elems/s", backend=backend)

    # NTT fwd+inv pairs across sizes (64 columns at bench scale)
    from boojum_tpu.ntt import (
        fft_natural_to_bitreversed,
        ifft_bitreversed_to_natural,
    )

    for log_n in (12, 14, 16, 18, 20):
        cols = max(1, (1 << 22) >> log_n)
        x = _rand((cols, 1 << log_n), 3 + log_n)
        reps = 4 if log_n >= 18 else 8
        dt = timed_chain(
            lambda v: ifft_bitreversed_to_natural(
                fft_natural_to_bitreversed(v)
            ),
            x,
            reps,
        )
        emit(
            f"ntt_2^{log_n}_pair_elems_per_s",
            int(2 * cols * (1 << log_n) / dt),
            "elems/s",
            cols=cols,
            backend=backend,
        )

    poseidon2_section(backend)

    batch_inverse_section(backend)
    sweep_section(backend)
    resident_section(backend)
    field_section(backend)
    mesh_section(backend)


# the cells' inversions (PERF.md section 5): Keccak's 32 public inputs,
# its 22 chunk and 9 lookup denominators, DEEP's two, the anchor's lookup
BINV_SHAPES = (
    (32, 1 << 19), (22, 1 << 18), (9, 1 << 18), (2, 1 << 19), (9, 1 << 16),
)


def batch_inverse_section(backend):
    """Batch inversion: the u64 routine on 2^20 elements, and on the TPU
    the limb-plane routine the prover dispatches (`lop.batch_inverse_jit`)
    at the cells' shapes, with multiplications a second by the plan's own
    count (`lop.batch_inverse_muls`: what `field.batch_inverse_muls`
    adds for the call)."""
    from boojum_tpu.field import limb_ops as lop

    b = _rand((1 << 20,), 50)
    b = jnp.where(b == 0, jnp.uint64(1), b)
    dt = timed_chain(gf.batch_inverse_xla, b, 4)
    emit(
        "batch_inverse_elems_per_s", int((1 << 20) / dt), "elems/s",
        backend=backend,
    )
    if backend != "tpu":
        return  # XLA:CPU runs the u32 limb cores for minutes a shape
    rng = np.random.default_rng(51)
    for shape in BINV_SHAPES:
        # canonical and nonzero: lo >= 1, hi < 2^32 - 1
        lo = jnp.asarray(rng.integers(1, 1 << 32, shape, dtype=np.uint32))
        hi = jnp.asarray(
            rng.integers(0, (1 << 32) - 1, shape, dtype=np.uint32)
        )
        dt = timed_call(lop.batch_inverse_jit, ((lo, hi),), reps=5)
        muls = lop.batch_inverse_muls(shape)
        emit(
            "batch_inverse_planes_muls_per_s", int(muls / dt), "muls/s",
            shape=list(shape), ms=round(dt * 1e3, 3), plan_muls=muls,
            muls_per_elem=round(muls / (shape[0] * shape[1]), 3),
            backend=backend,
        )


# the Era cells' chunks (PERF.md section 5): 64 columns of a coset
# evaluation under one row, 32 columns of a commit's LDE under L = 2 rows
NTT_SHAPES = (("coset_eval", 64, None), ("lde", 32, 2))


def ntt_section(backend, log_n=18):
    """The forward transform above 2^16 rows on limb planes: the one fused
    program a chunk (`limb_ntt._hybrid_fwd_p`: the rows and the outer
    radix-2 stages are the matmul kernel's prologue) beside the three
    programs it replaced (PR 34's scale, XLA outer stages and per-block
    matmul kernel, rebuilt here from the pieces the library keeps), on the
    same planes and held equal to the bit. Multiplications a second by
    count: a column transform under a row costs the fused kernel's
    prologue 7 n / 4 (4 by the row, 3 by the twiddle tables), the scale
    program n and the two outer stages n / 2 each."""
    if backend != "tpu":
        return  # the matmul kernel is native on the TPU alone
    from boojum_tpu.field import limbs
    from boojum_tpu.ntt import limb_ntt as LN
    from boojum_tpu.ntt import mxu_ntt

    n = 1 << log_n
    stages = log_n - mxu_ntt.MAX_LOG_N
    ctx = mxu_ntt.get_mxu_ctx(mxu_ntt.MAX_LOG_N)

    @partial(jax.jit, static_argnums=(3,))
    def scale_p(p, rows, start, size):
        if start is not None:
            p = tuple(
                jax.lax.dynamic_slice_in_dim(a, start, size, 0) for a in p
            )
        if rows[0].ndim == 2:
            p = (p[0][..., None, :], p[1][..., None, :])
        return limbs.mul(p, rows)

    @jax.jit
    def outer_p(p):
        p = LN.dif_stages_p(p, LN.PlaneNTTContext(log_n), 0, stages)
        return tuple(a.reshape(-1, ctx.R, ctx.C) for a in p)

    @partial(jax.jit, static_argnums=(1,))
    def mxu_p(flat, shape):
        out = mxu_ntt._fft_planes(flat, mxu_ntt.MAX_LOG_N, False)
        return out[0].reshape(shape), out[1].reshape(shape)

    rng = np.random.default_rng(60)

    def planes(*shape):
        return tuple(
            jnp.asarray(rng.integers(0, hi, shape, dtype=np.uint32))
            for hi in (1 << 32, (1 << 32) - 1)  # canonical: hi < 2^32 - 1
        )

    for name, cols, L in NTT_SHAPES:
        p, rows = planes(cols, n), planes(*((L, n) if L else (n,)))
        transforms = cols * (L or 1)
        programs = LN._LDE_FORWARD if L else LN._COSET_EVAL_FORWARD
        # a coset evaluation's chunk is cut from its group inside the program
        call = (p, rows) + ((None, None) if L else (jnp.int32(0), cols))
        fused = partial(LN._hybrid_fwd_p, p, log_n, programs, *call[1:])
        dt_fused = timed_call(fused, (), reps=5)
        dt_scale = timed_call(scale_p, call, reps=5)
        scaled = scale_p(*call)
        dt_outer = timed_call(outer_p, (scaled,), reps=5)
        flat = outer_p(scaled)
        dt_mxu = timed_call(mxu_p, (flat, scaled[0].shape), reps=5)
        want, got = mxu_p(flat, scaled[0].shape), fused()
        equal = all(bool(jnp.array_equal(w, g)) for w, g in zip(want, got))
        line = dict(
            shape=[cols] + ([L] if L else []) + [n], backend=backend,
            equal_to_staged=equal,
        )
        for part, dt, muls in (
            ("fused", dt_fused, 7 * n // 4 * transforms),
            ("scale", dt_scale, n * transforms),
            ("outer", dt_outer, stages * (n // 2) * transforms),
            ("mxu", dt_mxu, 0),
        ):
            emit(
                f"ntt_forward_{name}_{part}_ms", round(dt * 1e3, 3), "ms",
                us_per_transform=round(dt * 1e6 / transforms, 2),
                muls_per_s=int(muls / dt), **line,
            )
        emit(
            f"ntt_forward_{name}_staged_over_fused",
            round((dt_scale + dt_outer + dt_mxu) / dt_fused, 3), "x", **line,
        )


# the commits' chunks (PERF.md section 5): a whole chunk and the tree
# cell's remainder at 2^18 rows, a whole chunk and Keccak's remainder at
# 2^19; and 64 columns at 2^16 rows, where the commits keep the XLA stages
NTT_INVERSE_SHAPES = ((64, 18), (2, 18), (32, 19), (27, 19), (64, 16))
NTT_STEP_DEADLINE_S = 300  # a stalled program costs minutes, not the call


def ntt_inverse_section(backend, log_ns=()):
    """The commits' inverse transform on limb planes, a chunk at a time.
    Above 2^16 rows: the matmul kernel on the values as they lie
    (`limb_ntt._imono_kernel_p`: fused, and the trailing stage at 2^19)
    beside the XLA stages it replaced (`_imono_p_jit`) and beside the form
    the u64 path has (a bit-reversal gather, the per-block inverse kernels
    and the outer DIT stages, three programs rebuilt here from the pieces
    the library keeps). At 2^16 rows, where the commits dispatch the XLA
    stages: those beside the gather and the one inverse kernel of that
    size, so that a later PR knows what the XLA form costs there. All held
    equal to `_imono_p_jit` to the bit. Every step runs under a deadline
    that dumps the stacks and exits. `log_ns`: those sizes alone."""
    if backend != "tpu":
        return  # the matmul kernel is native on the TPU alone
    import faulthandler

    from boojum_tpu.ntt import limb_ntt as LN
    from boojum_tpu.ntt import mxu_ntt

    def step(fn, args, reps=5):
        faulthandler.dump_traceback_later(NTT_STEP_DEADLINE_S, exit=True)
        dt = timed_call(fn, args, reps=reps)
        faulthandler.cancel_dump_traceback_later()
        return dt

    @jax.jit
    def brev_p(p):
        brev = LN.PlaneNTTContext(p[0].shape[-1].bit_length() - 1).brev
        return p[0][..., brev], p[1][..., brev]

    blocks_p = jax.jit(LN.hybrid_inv_kernels_p, static_argnums=(1,))
    outer_p = jax.jit(LN.hybrid_inv_outer_p, static_argnums=(1,))
    rng = np.random.default_rng(61)

    def planes(*shape):
        return tuple(
            jnp.asarray(rng.integers(0, hi, shape, dtype=np.uint32))
            for hi in (1 << 32, (1 << 32) - 1)  # canonical: hi < 2^32 - 1
        )

    def equal(a, b):
        return all(bool(jnp.array_equal(x, y)) for x, y in zip(a, b))

    for cols, log_n in NTT_INVERSE_SHAPES:
        if log_ns and log_n not in log_ns:
            continue
        n = 1 << log_n
        p = planes(cols, n)
        line = dict(shape=[cols, n], backend=backend)
        parts = {"xla_stages": step(LN._imono_p_jit, (p,), reps=2)}
        want = LN._imono_p_jit(p)
        if LN.inverse_is_own_program(n):
            parts["kernel"] = step(LN._imono_kernel_p, (p,))
            fused = LN._imono_p_fused(p, None, None)
            parts["kernel_fused"] = step(LN._imono_p_fused, (p, None, None))
            if mxu_ntt.leading_outer_stages(log_n):
                parts["kernel_trailing"] = step(LN._imono_p_trailing, (fused,))
            del fused
            line["equal_to_xla_stages"] = equal(LN._imono_kernel_p(p), want)
        reversed_p = brev_p(p)
        parts["gather"] = step(brev_p, (p,))
        got = blocks_p(reversed_p, log_n)
        parts["gather_blocks"] = step(blocks_p, (reversed_p, log_n))
        if log_n > mxu_ntt.MAX_LOG_N:
            parts["gather_outer"] = step(outer_p, (got, log_n))
            got = outer_p(got, log_n)
        line["gather_form_equal"] = equal(got, want)
        del reversed_p, got, want
        if not line.get("equal_to_xla_stages", True):
            raise SystemExit(f"the kernel's monomials differ at {line}")
        for part, dt in parts.items():
            emit(
                f"ntt_inverse_{part}_ms", round(dt * 1e3, 3), "ms",
                us_per_transform=round(dt * 1e6 / cols, 2), **line,
            )
        if "kernel" in parts:
            emit(
                "ntt_inverse_xla_stages_over_kernel",
                round(parts["xla_stages"] / parts["kernel"], 3), "x", **line,
            )


P2_TILES = (8, 16, 32, 64, 128, 256)
P2_CHUNKS = (1, 2, 6, 8, 12, 20)  # node, quotient, stage-2, witness leaves


def poseidon2_jobs(leaves):
    """The Poseidon2 kernels by grid step: (kind, chunks, tile rows,
    leaves, lowered) for `_sponge_planes` at every (chunks, tile) and
    `_permute_planes` (chunks 0) at every tile."""
    from boojum_tpu.hashes import pallas_poseidon2 as pp2

    jobs = []
    for n in leaves:
        R = n // 128
        for tile in P2_TILES:
            if R % tile:
                continue
            for chunks in P2_CHUNKS:
                v = jax.ShapeDtypeStruct((8 * chunks, R, 128), jnp.uint32)
                low = pp2._sponge_planes.lower(v, v, chunks, tile, False)
                jobs.append(("sponge", chunks, tile, n, low))
            s = jax.ShapeDtypeStruct((12, R, 128), jnp.uint32)
            low = pp2._permute_planes.lower(s, s, tile, False)
            jobs.append(("permute", 0, tile, n, low))
    return jobs


def compile_pool(jobs, workers=12):
    """Compile the lowered programs on a pool; [(compiled or the error's
    first line, seconds)] in the jobs' order."""
    from concurrent.futures import ThreadPoolExecutor

    def one(job):
        t0 = time.perf_counter()
        try:
            out = job[-1].compile()
        except Exception as e:  # a tile the VMEM cap refuses
            out = (str(e).strip().splitlines() or [type(e).__name__])[0][:160]
        return out, time.perf_counter() - t0

    with ThreadPoolExecutor(max_workers=workers) as pool:
        return list(pool.map(one, jobs))


def poseidon2_section(backend, leaves=(1 << 19, 1 << 17)):
    """Poseidon2: the dispatcher's permutation, and on the TPU perms/s of
    the leaf sponge and the bare permutation kernel by (chunks, tile rows),
    with each kernel's compile seconds (ISSUE 29's sweep: the rate is set
    by the grid step's rows, `pallas_poseidon2.step_rows` picks them). The
    kernels compile on a pool first; a tile the VMEM cap refuses is a line
    with `error`."""
    from boojum_tpu.hashes.poseidon2 import poseidon2_permutation

    st = _rand((1 << 18, 12), 40)
    dt = timed_chain(poseidon2_permutation, st, 4)
    emit(
        "poseidon2_perms_per_s", int((1 << 18) / dt), "perms/s",
        backend=backend,
    )
    if backend != "tpu":
        return  # interpret-mode kernels compile for minutes on XLA:CPU
    jobs = poseidon2_jobs(leaves)
    built = compile_pool(jobs)
    rng = np.random.default_rng(41)

    def planes(n):
        # canonical limbs: hi < 2^32 - 1 keeps every value below p
        shape = (8 * max(P2_CHUNKS), n // 128, 128)
        lo = rng.integers(0, 1 << 32, shape, dtype=np.uint32)
        hi = rng.integers(0, (1 << 32) - 1, shape, dtype=np.uint32)
        return jnp.asarray(lo), jnp.asarray(hi)

    inputs = {n: planes(n) for n in leaves}
    first = {}
    for (kind, chunks, tile, n, _), (exe, secs) in zip(jobs, built):
        line = dict(
            kind=kind, chunks=chunks, tile_rows=tile, leaves=n,
            compile_s=round(secs, 2), backend=backend,
        )
        rate = 0
        if isinstance(exe, str):
            line["error"] = exe
        else:
            rows = 8 * chunks or 12
            args = tuple(p[:rows] for p in inputs[n])
            out = exe(*args)
            ref = first.setdefault((kind, chunks, n), out)
            line["equal_to_first_tile"] = all(
                bool(jnp.array_equal(a, b)) for a, b in zip(out, ref)
            )
            dt = timed_call(exe, args)
            line["ms"] = round(dt * 1e3, 3)
            rate = int(n * max(chunks, 1) / dt)
        emit("poseidon2_kernel_perms_per_s", rate, "perms/s", **line)


def timed_call(fn, args, reps=3):
    """Median-free simple timer for non-chainable kernels (outputs have a
    different shape than inputs, so the on-device fori_loop chain of
    timed_chain does not apply; per-call launch overhead is identical for
    both compared paths, so the ratio stays honest)."""
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def sweep_section(backend):
    """ISSUE 4 satellite: per-kernel u64-vs-limb microbench of the quotient
    sweep family (gate terms, cp quotient, lookup quotient, FRI fold) —
    one JSON line per kernel carrying both paths. On non-TPU backends the
    limb kernels run in Pallas interpret mode (tiny sizes, correctness
    smoke more than a perf number); on TPU they are the real fused
    kernels at bench scale."""
    from boojum_tpu.cs.gates import FmaGate
    from boojum_tpu.cs.types import CSGeometry
    from boojum_tpu.field import limbs
    from boojum_tpu.prover import pallas_sweep as ps
    from boojum_tpu.prover.fri import _ch_table_np, _fold_once_jit
    from boojum_tpu.prover.stages import (
        _build_gate_sweep,
        _cp_quotient_core,
        _lookup_quotient_core,
        chunk_columns,
    )

    on_tpu = backend == "tpu"
    n = 1 << (18 if on_tpu else 10)
    reps = 4 if on_tpu else 2
    rng = np.random.default_rng(9)

    def rnd(*s):
        return jnp.asarray(rng.integers(0, gl.P, s, dtype=np.uint64))

    def to_p(x):
        """u64 arrays (nested as the u64 cores take them) -> plane pairs."""
        return jax.tree.map(limbs.split, x)

    def compare(name, u64_fn, limb_fn, args, limb_args, elems):
        dt_u64 = timed_call(jax.jit(u64_fn), args, reps)
        dt_limb = timed_call(jax.jit(limb_fn), limb_args, reps)
        emit(
            f"sweep_{name}_limb_elems_per_s",
            int(elems / dt_limb),
            "elems/s",
            u64_elems_per_s=int(elems / dt_u64),
            limb_over_u64=round(dt_u64 / dt_limb, 3),
            backend=backend,
            interpret=not on_tpu,
        )

    # gate terms (FMA sweep, 2 instances/row)
    geom = CSGeometry(8, 0, 6, 4)
    gates, paths = (FmaGate.instance(),), ((),)
    n_terms = FmaGate.instance().num_repetitions(geom)
    copy, const = rnd(8, n), rnd(6, n)
    a0, a1 = rnd(n_terms), rnd(n_terms)
    u64_gate = _build_gate_sweep(gates, paths, geom)
    limb_gate = ps.gate_terms_fn(gates, paths, geom)
    compare(
        "gate_terms",
        lambda c, k, x, y: u64_gate(c, None, k, x, y),
        lambda c, k, tb: limb_gate(c, None, k, tb),
        (copy, const, a0, a1),
        (to_p(copy), to_p(const), ps._pack_table(a0, a1)), 8 * n,
    )

    # copy-permutation quotient
    C = 8
    chunks = tuple(tuple(c) for c in chunk_columns(C, 4))
    ks = tuple(int(x) for x in rng.integers(1, gl.P, C, dtype=np.uint64))
    z, zs = (rnd(n), rnd(n)), (rnd(n), rnd(n))
    partials = [(rnd(n), rnd(n)) for _ in range(len(chunks) - 1)]
    cp_args = (
        z, zs, partials, rnd(C, n), rnd(C, n), rnd(n), rnd(n),
        (jnp.uint64(3), jnp.uint64(5)), (jnp.uint64(7), jnp.uint64(11)),
        rnd(1 + len(chunks)), rnd(1 + len(chunks)),
    )
    compare(
        "cp_quotient",
        lambda *a: _cp_quotient_core(*a, chunks, ks),
        lambda *a: ps.cp_quotient(*a, chunks, ks),
        cp_args, to_p(cp_args[:7]) + cp_args[7:], C * n,
    )

    # lookup quotient (specialized, SHA-bench width)
    R, w = 4, 4
    lk_args = (
        [(rnd(n), rnd(n)) for _ in range(R)], (rnd(n), rnd(n)),
        rnd(R * w, n), rnd(n), rnd(w + 1, n), rnd(n),
        (jnp.uint64(3), jnp.uint64(5)), (jnp.uint64(7), jnp.uint64(11)),
        rnd(R + 1), rnd(R + 1),
    )
    compare(
        "lookup_quotient",
        lambda *a: _lookup_quotient_core(*a, R, w),
        lambda *a: ps.lookup_quotient(*a, R, w),
        lk_args, to_p(lk_args[:6]) + lk_args[6:], R * w * n,
    )

    # FRI fold
    m = 2 * n
    fold_args = ((rnd(m), rnd(m)), (jnp.uint64(3), jnp.uint64(5)), rnd(m // 2))
    compare(
        "fri_fold",
        lambda v, ch, ix: _fold_once_jit(v, ch, ix),
        lambda v, tb, ix: ps.fri_fold_planes(v, tb, ix),
        fold_args,
        (to_p(fold_args[0]), jnp.asarray(_ch_table_np((3, 5))),
         to_p(fold_args[2])),
        m,
    )


def resident_section(backend):
    """ISSUE 10 satellite: per-kernel boundary-CONVERTING vs limb-RESIDENT
    microbench — iNTT, LDE, leaf sponge, gate-terms sweep, FRI fold chain.
    The converting leg is the u64 kernel (u64 in / u64 out: emulated-u64
    math, for the commit kernels behind their boundary split/join); the
    resident leg consumes and produces (lo, hi) u32 planes end-to-end. Same JSON-line format as the PR 4 `sweep`
    section. On non-TPU backends the Pallas legs run in interpret mode
    (correctness smoke more than a perf number)."""
    from boojum_tpu.field import limbs
    from boojum_tpu.hashes.poseidon2 import leaf_hash, leaf_hash_planes
    from boojum_tpu.ntt import limb_ntt as LN
    from boojum_tpu.ntt import lde_from_monomial, monomial_from_values
    from boojum_tpu.prover import pallas_sweep as ps
    from boojum_tpu.prover import resident as RES
    from boojum_tpu.prover.fri import (
        _ch_table_np,
        _fri_fold_fn,
        _fri_fold_fn_p,
        fold_challenge_tables,
        fold_challenge_tables_p,
    )

    on_tpu = backend == "tpu"
    log_n = 18 if on_tpu else 10
    n = 1 << log_n
    reps = 4 if on_tpu else 2
    rng = np.random.default_rng(21)

    def rnd(*s):
        return jnp.asarray(rng.integers(0, gl.P, s, dtype=np.uint64))

    def compare(name, conv_fn, res_fn, conv_args, res_args, elems):
        dt_c = timed_call(conv_fn, conv_args, reps)
        dt_r = timed_call(res_fn, res_args, reps)
        emit(
            f"resident_{name}_elems_per_s",
            int(elems / dt_r),
            "elems/s",
            converting_elems_per_s=int(elems / dt_c),
            resident_over_converting=round(dt_c / dt_r, 3),
            backend=backend,
            interpret=not on_tpu,
        )

    # iNTT + LDE (the commit pipeline's transforms)
    B = 16
    x = rnd(B, n)
    xp = limbs.split(x)
    compare(
        "imono", monomial_from_values, LN.monomial_from_values_p,
        (x,), (xp,), B * n,
    )
    L = 4
    compare(
        "lde",
        lambda m: lde_from_monomial(m, L),
        lambda m: LN.lde_from_monomial_p(m, L),
        (x,), (xp,), B * n * L,
    )

    # leaf sponge over (N, width) rows
    leaves = rnd(1 << (14 if on_tpu else 11), 16)
    leaves_p = limbs.split(leaves)
    compare(
        "leaf_sponge", leaf_hash, leaf_hash_planes,
        (leaves,), (leaves_p,), int(leaves.shape[0]) * 16,
    )

    # gate-terms sweep (the u64 XLA sweep vs the fused limb kernel)
    from boojum_tpu.cs.gates import FmaGate
    from boojum_tpu.cs.types import CSGeometry
    from boojum_tpu.prover.stages import _build_gate_sweep

    geom = CSGeometry(8, 0, 6, 4)
    gates, paths = (FmaGate.instance(),), ((),)
    n_terms = FmaGate.instance().num_repetitions(geom)
    copy, const = rnd(8, n), rnd(6, n)
    a0 = [int(v) for v in np.asarray(rnd(n_terms))]
    a1 = [int(v) for v in np.asarray(rnd(n_terms))]
    gate = ps.gate_terms_fn(gates, paths, geom)
    gate_u64 = _build_gate_sweep(gates, paths, geom)
    table = jnp.asarray(RES.sc_table_np(a0, a1))
    compare(
        "gate_terms",
        lambda c, k: gate_u64(
            c, None, k, jnp.asarray(np.array(a0, np.uint64)),
            jnp.asarray(np.array(a1, np.uint64)),
        ),
        lambda c, k: gate(c, None, k, table),
        (copy, const), (limbs.split(copy), limbs.split(const)), 8 * n,
    )

    # FRI fold chain (k=3): the u64 chain vs the chain that stays planes
    # across all three folds
    m = 2 * n
    log_m = m.bit_length() - 1
    c0, c1 = rnd(m), rnd(m)
    ch = (3, 5)
    tabs_u = tuple(fold_challenge_tables(log_m, 3))
    tabs_p = tuple(fold_challenge_tables_p(log_m, 3))
    ch01 = jnp.asarray(np.array(ch, dtype=np.uint64))
    tb = jnp.asarray(_ch_table_np(ch))
    c0p, c1p = limbs.split(c0), limbs.split(c1)
    compare(
        "fri_fold_k3",
        lambda a, b: _fri_fold_fn(3, None)(a, b, ch01, tabs_u),
        lambda a, b: _fri_fold_fn_p(3, None)(a, b, tb, tabs_p),
        (c0, c1), (c0p, c1p), m,
    )


def field_section(backend):
    """ISSUE 19 satellite: per-kernel Goldilocks-limb vs BabyBear
    plane-free microbench — iNTT, LDE, leaf sponge, gate-terms sweep,
    FRI fold chain. The Goldilocks leg is the limb-RESIDENT twin (the
    best Goldilocks path: (lo, hi) u32 planes, 8 bytes/elem); the
    BabyBear leg is the plane-free `_bb` kernel (ONE u32 lane,
    4 bytes/elem). Each line carries both backends' throughput plus the
    bytes-per-element of each, so `prove_report.py --trend` tracks the
    two field backends as separate series and the HBM-halving claim
    stays a measured number, not an assertion."""
    from boojum_tpu.field import babybear as bb
    from boojum_tpu.field import limbs
    from boojum_tpu.field.spec import BABYBEAR
    from boojum_tpu.hashes.poseidon2 import leaf_hash_planes
    from boojum_tpu.ntt import bb_ntt
    from boojum_tpu.ntt import limb_ntt as LN
    from boojum_tpu.prover import bb_kernels as K
    from boojum_tpu.prover import pallas_sweep as ps
    from boojum_tpu.prover import resident as RES
    from boojum_tpu.prover.fri import (
        _ch_table_np,
        _fri_fold_fn_p,
        fold_challenge_tables_p,
    )

    on_tpu = backend == "tpu"
    log_n = 18 if on_tpu else 10
    Lf = 4 if on_tpu else 2
    n = 1 << log_n
    N = n * Lf
    reps = 4 if on_tpu else 2
    rng = np.random.default_rng(33)

    def rnd_gl(*s):
        return jnp.asarray(rng.integers(0, gl.P, s, dtype=np.uint64))

    def rnd_bb(*s):
        return jnp.asarray(rng.integers(0, bb.P, s, dtype=np.uint32))

    def compare(name, gl_fn, gl_args, bb_fn, bb_args, gl_elems, bb_elems):
        dt_gl = timed_call(gl_fn, gl_args, reps)
        dt_bb = timed_call(bb_fn, bb_args, reps)
        gl_tp, bb_tp = gl_elems / dt_gl, bb_elems / dt_bb
        emit(
            f"field_{name}_bb_elems_per_s",
            int(bb_tp),
            "elems/s",
            gl_limb_elems_per_s=int(gl_tp),
            bb_over_gl=round(bb_tp / gl_tp, 3),
            bytes_per_elem_bb=4,
            bytes_per_elem_gl=8,
            backend=backend,
            interpret=not on_tpu,
        )

    # iNTT (values -> monomial) + LDE: limb planes vs one u32 lane
    B = 16
    xp = limbs.split(rnd_gl(B, n))
    xb = rnd_bb(B, n)
    compare(
        "imono",
        LN.monomial_from_values_p, (xp,),
        lambda v: bb_ntt.monomial_from_values_bb(v, log_n), (xb,),
        B * n, B * n,
    )
    shift = BABYBEAR.multiplicative_generator
    compare(
        "lde",
        lambda m: LN.lde_from_monomial_p(m, Lf), (xp,),
        lambda m: bb_ntt.lde_from_monomial_bb(m, log_n, Lf, shift), (xb,),
        B * n * Lf, B * n * Lf,
    )

    # leaf sponge: width-12 Goldilocks permutation over (lo, hi) planes
    # vs width-16 BabyBear permutation over bare lanes
    T = 1 << (14 if on_tpu else 11)
    leaves_p = limbs.split(rnd_gl(T, 16))
    cols_b = rnd_bb(16, T)
    compare(
        "leaf_sponge",
        leaf_hash_planes, (leaves_p,),
        K.leaf_digests_bb, (cols_b,),
        T * 16, T * 16,
    )

    # fused quotient sweep: the plane-resident gate-terms kernel vs the
    # BabyBear coset sweep (random division tables — kernel throughput
    # does not depend on table values)
    from boojum_tpu.cs.gates import FmaGate
    from boojum_tpu.cs.types import CSGeometry

    geom = CSGeometry(8, 0, 6, 4)
    gate = ps.gate_terms_fn((FmaGate.instance(),), ((),), geom)
    n_terms = FmaGate.instance().num_repetitions(geom)
    copy_p = limbs.split(rnd_gl(8, n))
    const_p = limbs.split(rnd_gl(6, n))
    a0 = [int(v) for v in np.asarray(rnd_gl(n_terms))]
    a1 = [int(v) for v in np.asarray(rnd_gl(n_terms))]
    table = jnp.asarray(RES.sc_table_np(a0, a1))
    compare(
        "gate_terms",
        lambda c, k: gate(c, None, k, table), (copy_p, const_p),
        lambda w, al, cp, lt, zh, bi: K.coset_sweep_terms_bb(
            w, al, cp, lt, zh, bi, Lf
        ),
        (rnd_bb(N), rnd_bb(4), rnd_bb(2), rnd_bb(N), rnd_bb(N), rnd_bb(N)),
        8 * n, N,
    )

    # FRI fold chain: one k=3 plane-resident fold (GF(p^2): 2 u64/elem)
    # vs the three chained factor-2 `_bb` folds a BabyBear prove
    # actually dispatches (GF(p^4): 4 u32/elem)
    m = N
    log_m = m.bit_length() - 1
    c0p, c1p = limbs.split(rnd_gl(m)), limbs.split(rnd_gl(m))
    tb = jnp.asarray(_ch_table_np((3, 5)))
    tabs_p = tuple(fold_challenge_tables_p(log_m, 3))
    gl_fold = _fri_fold_fn_p(3, None)

    cw = rnd_bb(4, m)
    betas = [rnd_bb(4) for _ in range(3)]
    invtabs = [rnd_bb(m >> (r + 1)) for r in range(3)]

    def bb_fold_chain(c, b0, b1, b2, t0, t1, t2):
        c = K.fri_fold_bb(c, b0, t0)
        c = K.fri_fold_bb(c, b1, t1)
        return K.fri_fold_bb(c, b2, t2)

    compare(
        "fri_fold_chain",
        lambda a, b: gl_fold(a, b, tb, tabs_p), (c0p, c1p),
        bb_fold_chain, (cw, *betas, *invtabs),
        2 * m, 4 * m,
    )


def mesh_section(backend):
    """ISSUE 5 satellite: per-kernel GSPMD-vs-shard_map microbench on the
    largest ('col','row') mesh the local devices allow — the coset
    evaluation (scale+NTT+pivot), the leaf sponge over pivoted rows, the
    FRI fold chain, and the bare all_to_all layout pivot. GSPMD timings
    dispatch the MESHLESS jitted graph on column/row-sharded operands
    (XLA inserts the collectives); shard_map timings run the explicit
    per-chip graphs from parallel/shard_sweep.py. Skipped (no JSON lines)
    on single-device processes."""
    import boojum_tpu.parallel.shard_sweep as SS
    from boojum_tpu.parallel.sharding import prover_mesh
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    D = 1 << (len(devs).bit_length() - 1)  # largest power of two
    if D < 2:
        return
    ncol = 1 << ((D.bit_length() - 1) // 2)
    mesh = Mesh(
        np.array(devs[:D]).reshape(ncol, D // ncol),
        axis_names=("col", "row"),
    )
    on_tpu = backend == "tpu"
    log_n, L, B = (18, 8, 32) if on_tpu else (10, 2, 16)
    n = 1 << log_n
    N = n * L
    rng = np.random.default_rng(11)

    def rnd(*s):
        return jnp.asarray(rng.integers(0, gl.P, s, dtype=np.uint64))

    def emit_pair(name, dt_gspmd, dt_sm, elems):
        emit(
            f"mesh_{name}_sm_elems_per_s",
            int(elems / dt_sm),
            "elems/s",
            gspmd_elems_per_s=int(elems / dt_gspmd),
            sm_over_gspmd=round(dt_gspmd / dt_sm, 3),
            mesh_shape=[int(mesh.shape["col"]), int(mesh.shape["row"])],
            backend=backend,
        )

    col_sh = NamedSharding(mesh, P(("col", "row")))

    # coset evaluation: per-chip scale+NTT then the explicit pivot vs the
    # meshless graph GSPMD-partitioned from a column-sharded operand
    from boojum_tpu.prover.prover import _coset_eval_q

    mono = rnd(B, n)
    scale_q = rnd(L, n)
    ci = jnp.int32(0)
    mono_g = jax.device_put(mono, col_sh)
    # GSPMD legs trace under the ACTIVE mesh, exactly like a real gspmd
    # prove — pallas_enabled()'s active-mesh veto then keeps the plain XLA
    # bodies GSPMD can partition (a meshless trace on TPU would hand a
    # pallas_call over sharded operands to the SPMD partitioner: not the
    # graph the mesh path ever dispatches, and not partitionable)
    with prover_mesh(mesh):
        dt_g = timed_call(
            lambda m_, s_, c_: _coset_eval_q(m_, s_, c_),
            (mono_g, scale_q, ci),
        )
    mono_p = SS.pad_cols_sharded(mono, mesh)
    dt_s = timed_call(
        SS._coset_eval_fn(mesh, B), (mono_p, scale_q, ci)
    )
    emit_pair("coset_eval", dt_g, dt_s, B * n)

    # the materialized commit tail (LDE + col->row pivot + leaf sponge),
    # SAME work both sides: the meshless graph GSPMD-partitioned from the
    # column-sharded monomials (XLA inserts the pivot as a resharding of
    # the transpose) vs the fused per-chip shard_map graph
    from boojum_tpu.hashes.poseidon2 import leaf_hash_xla
    from boojum_tpu.ntt import lde_from_monomial

    def _lde_leaf(m):
        lde = lde_from_monomial(m, L)
        return lde, leaf_hash_xla(lde.reshape(m.shape[0], -1).T)

    with prover_mesh(mesh):
        dt_g = timed_call(jax.jit(_lde_leaf), (mono_g,))
    lde_fn = SS._lde_pivot_leaf_fn(mesh, L, B)
    dt_s = timed_call(lde_fn, (mono_p,))
    emit_pair("leaf_sponge", dt_g, dt_s, N * B)

    # FRI fold chain (k=3)
    from boojum_tpu.prover.fri import _fri_fold_fn

    m = N
    c0, c1 = rnd(m), rnd(m)
    ch01 = rnd(2)
    tabs = tuple(rnd(m >> (j + 1)) for j in range(3))
    c0g = jax.device_put(c0, col_sh)
    c1g = jax.device_put(c1, col_sh)
    with prover_mesh(mesh):
        dt_g = timed_call(
            _fri_fold_fn(3, None), (c0g, c1g, ch01, tabs)
        )
    if SS.fold_shards_ok(m, 3, mesh):
        # both sides fold the same pre-sharded c0g/c1g; only the fold
        # tables still need their device_put (the sm chain consumes them
        # sharded, the meshless graph above took them from host)
        tabs_s = tuple(jax.device_put(t, col_sh) for t in tabs)
        dt_s = timed_call(
            _fri_fold_fn(3, mesh), (c0g, c1g, ch01, tabs_s)
        )
        emit_pair("fri_fold_k3", dt_g, dt_s, m)

    # the bare col->row layout pivot: explicit all_to_all vs the implicit
    # resharding GSPMD inserts for the same layout change
    from jax.experimental.shard_map import shard_map

    flat = rnd(B, N)
    col2_sh = NamedSharding(mesh, P(("col", "row"), None))
    flat_g = jax.device_put(flat, col2_sh)
    dt_g = timed_call(
        jax.jit(
            lambda x: x,
            out_shardings=NamedSharding(mesh, P(None, ("col", "row"))),
        ),
        (flat_g,),
    )
    piv = jax.jit(
        shard_map(
            lambda x: jax.lax.all_to_all(
                x, ("col", "row"), split_axis=1, concat_axis=0, tiled=True
            ),
            mesh=mesh,
            in_specs=(P(("col", "row"), None),),
            out_specs=P(None, ("col", "row")),
            check_rep=False,
        )
    )
    dt_s = timed_call(piv, (flat_g,))
    emit_pair("pivot_all_to_all", dt_g, dt_s, B * N)


def transcript_section():
    """The transcript's permutation on this host, no device (PR 38): the
    one Python permutation, and the prover's engine (native where the
    library loaded) a permutation of its own and inside the 68-block absorb
    of the evaluations at z. Best of 7."""
    from boojum_tpu import transcript as T
    from boojum_tpu.hashes.poseidon2 import poseidon2_permutation_host

    def best_ms(step, per):
        best = float("inf")
        for _ in range(7):
            t0 = time.perf_counter()
            step()
            best = min(best, time.perf_counter() - t0)
        return best * 1e3 / per

    def python_chain():
        s = list(range(1, 13))
        for _ in range(200):
            s = poseidon2_permutation_host(s)

    emit("transcript_perm_python", best_ms(python_chain, 200), "ms")
    t = T.make_prover_transcript("poseidon2")
    engine = type(t).__name__

    def squeezes():
        for _ in range(200):
            t._permute()

    def absorb():
        t.witness_field_elements(range(8 * 68 - 1))
        t.get_challenge()

    emit("transcript_perm_prover", best_ms(squeezes, 200), "ms", engine=engine)
    emit("transcript_perm_prover_absorb68", best_ms(absorb, 68), "ms",
         engine=engine)


if __name__ == "__main__":
    if sys.argv[1:] == ["transcript"]:
        transcript_section()
    elif sys.argv[1:] == ["poseidon2"]:  # that section alone, one chip call
        poseidon2_section(jax.default_backend())
    elif sys.argv[1:] == ["binv"]:
        batch_inverse_section(jax.default_backend())
    elif sys.argv[1:] == ["ntt"]:
        ntt_section(jax.default_backend())
        ntt_inverse_section(jax.default_backend())
    elif sys.argv[1:3] == ["ntt", "inverse"]:
        ntt_inverse_section(jax.default_backend(), [int(a) for a in sys.argv[3:]])
    else:
        main()
