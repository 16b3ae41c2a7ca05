"""Kernel cost model + roofline attribution (ISSUE 12).

The flight recorder answers "how LONG did each stage take"; this module
answers "how long SHOULD it have taken": an analytic cost sheet — field
muls/adds folded into XLA-flop equivalents, HBM bytes moved, ICI bytes
crossed — for every executable `prover/precompile.enumerate_kernels`
emits, parameterized on `ShapeBucket` geometry and the active variant
(representation / mesh / streamed). Joined with the
measured span walls and the `ici.*` / `transfer.*` gauges, it stamps a
validated `cost` record on every ProveReport line: achieved GFLOP/s and
GB/s per stage, the roofline regime (compute- vs memory-bound, from
arithmetic intensity against the device's machine balance) and the
efficiency fraction against peak — the instrument that says WHICH kernel
is leaving performance on the table, per line, per round (ICICLE's
per-kernel achieved-vs-peak posture, PAPERS.md).

Two layers share one set of per-family op-count primitives:

- `cost_sheet(specs)`: per-kernel, per-DISPATCH analytic cost derived
  from each KernelSpec's name + ShapeDtypeStruct args. This is the axis
  cross-checked against XLA's own `compiled.cost_analysis()` /
  `memory_analysis()` actuals, which prover/precompile.py and
  prover/aot.py capture at compile time into CompileLedger entries and
  the AOT bundle manifest (so zero-compile cold processes still carry
  actuals).
- `stage_costs(sb, ...)`: per-STAGE analytic totals over the whole
  prove (a kernel like `coset_eval_wit` dispatches Q times; the stage
  layer owns that multiplicity so the roofline record never needs
  per-dispatch bookkeeping).

Flop convention: XLA's HloCostAnalysis counts ONE flop per elementwise
arithmetic op per element — integer ops included — so "flops" here means
machine elementwise ops, not floating-point math. A Goldilocks field mul
on the emulated-u64 path lowers to ~W_MUL such ops (cross products +
reduce128 chain); the weights below are calibrated against the measured
`cost_analysis()` of the real 2^10 kernel library on XLA:CPU and the
agreement band is documented in BASELINE.md ("Cost model & trend
protocol") and pinned by tests/test_costmodel.py.

Everything here is import-light (stdlib only at module import; jax only
inside device probes) and fails soft: a cost-model bug must never fail a
prove — `attach_cost_record` logs and returns None on any internal
error.
"""

from __future__ import annotations

import json
import math
import os
import threading

# ---------------------------------------------------------------------------
# Field-op weights (XLA elementwise-op equivalents per element, calibrated
# against compiled.cost_analysis() of the 2^10 CPU kernel library — see
# tests/test_costmodel.py::test_analytic_model_within_tolerance_of_xla)
# ---------------------------------------------------------------------------

# one Goldilocks mul (mul + Goldilocks reduction as XLA lowers it on the
# u64 path: widening products, shifts, carry/select chain)
W_MUL = 22.0
# one Goldilocks add: add + overflow correction (compare/select)
W_ADD = 4.0
# one GF(p^2) extension mul: 3 base muls (Karatsuba) + combines
W_EXT_MUL = 3 * W_MUL + 4 * W_ADD
# Poseidon2 t=12 permutation, as measured: 8 full rounds (12 x^7
# sboxes + external MDS) + 22 partial rounds (1 sbox + internal
# diagonal) ≈ 5100 elementwise ops and ~2.2 kB of round-state traffic
P2_FLOPS_PER_PERM = 5100.0
P2_BYTES_PER_PERM = 2200.0
P2_RATE = 8  # sponge absorb rate (field elements per permutation)
# batch inversion as the XLA kernels actually do it (Fermat
# exponentiation chain per element, not the 3-mul Montgomery trick):
# ~64 squarings + ~32 muls of reduction-bearing math per element
BINV_FLOPS_PER_ELEM = 4900.0
BINV_BYTES_PER_ELEM = 1600.0
# one FRI 2-to-1 fold, per SURVIVING element: extension mul-accumulate
# plus the deinterleave gathers and challenge-table indexing
FOLD_FLOPS_PER_ELEM = 700.0
FOLD_BYTES_PER_ELEM = 220.0
# DEEP accumulation, per (column, point): ext mul-add against the
# inverted denominators
DEEP_FLOPS_PER_ELEM = 100.0
DEEP_BYTES_PER_ELEM = 32.0

# BabyBear (ISSUE 19): one u32 LANE per element, so every bytes term is
# elem_bytes/8 of its Goldilocks twin — that factor-2 is the whole point
# of the field backend and is pinned by tests/test_babybear.py. Flop
# weights deliberately REUSE the u64-path calibration (a BabyBear mul is
# one widening mul + mod, far under W_MUL): the `_bb` sheet's flops are
# a conservative upper bound until a device calibration pass lands; its
# bytes are exact per-lane.
BB_ELEM_BYTES = 4.0
# Poseidon2 t=16 BabyBear permutation: 8 full rounds (16 x^7 sboxes +
# M4-block external MDS) + 13 partial rounds over a 64-byte u32 state
P2BB_FLOPS_PER_PERM = 2600.0
P2BB_BYTES_PER_PERM = 1500.0


def _flops(muls: float, adds: float) -> float:
    return muls * W_MUL + adds * W_ADD


# ---------------------------------------------------------------------------
# Device peaks (nominal, documented — BASELINE.md). "flops" is the XLA
# elementwise-op convention above, so peaks are integer-ALU element ops/s,
# not marketed bf16 TFLOPS.
# ---------------------------------------------------------------------------

# device_kind substring ->
#   (peak integer GOP/s, HBM GB/s, ICI GB/s per link, DCN GB/s per host)
# DCN is the cross-host fabric (data-center network) a multi-process mesh's
# collectives cross; ~200 Gb/s NICs per TPU host -> 25 GB/s nominal.
DEVICE_PEAKS = (
    ("v5 lite", (394.0 * 16, 819.0, 186.0, 25.0)),  # v5e: 8 MXU-adj. VPUs
    ("v5e", (394.0 * 16, 819.0, 186.0, 25.0)),
    ("v4", (275.0 * 16, 1228.0, 300.0, 25.0)),
    ("v3", (123.0 * 16, 900.0, 140.0, 25.0)),
    # XLA:CPU single-core nominal: a few int64 lanes at a few GHz
    ("cpu", (20.0, 25.0, 0.0, 0.0)),
)


class UnknownDeviceError(LookupError):
    """The device kind is not in DEVICE_PEAKS and no override names its
    peaks: a roofline share against an invented peak would be a guess."""


def cost_enabled() -> bool:
    """BOOJUM_TPU_COST: stamp the `cost` roofline record on report lines
    and export `cost.*` gauges (default on; =0 disables the plane)."""
    from .transfer import env_flag

    return env_flag("BOOJUM_TPU_COST", True)


def device_peaks() -> dict:
    """The active device's nominal peaks: {kind, peak_gflops,
    peak_hbm_gbps, peak_ici_gbps, peak_dcn_gbps, source}.
    BOOJUM_TPU_COST_PEAKS="gflops,hbm_gbps[,ici_gbps[,dcn_gbps]]"
    overrides the table (source:"env"); a device kind that is in neither
    raises UnknownDeviceError (attach_cost_record then stamps no roofline
    record — a share of a guessed peak is worse than none)."""
    import jax

    dev = jax.devices()[0]
    kind = str(getattr(dev, "device_kind", dev.platform))
    env = os.environ.get("BOOJUM_TPU_COST_PEAKS", "").strip()
    if env:
        # a malformed override falls back to the table (logged), never
        # silently disabling the whole cost plane via attach's guard
        try:
            parts = [float(x) for x in env.split(",")]
            gflops, hbm = parts[0], parts[1]
            ici = parts[2] if len(parts) > 2 else 0.0
            dcn = parts[3] if len(parts) > 3 else 0.0
            return {
                "kind": kind, "peak_gflops": gflops,
                "peak_hbm_gbps": hbm, "peak_ici_gbps": ici,
                "peak_dcn_gbps": dcn, "source": "env",
            }
        except (ValueError, IndexError):
            try:
                from .profiling import log as _plog

                _plog(
                    f"cost model: BOOJUM_TPU_COST_PEAKS={env!r} is not "
                    f'"gflops,hbm_gbps[,ici_gbps[,dcn_gbps]]" — using '
                    f"the device table"
                )
            except Exception:
                pass
    lk = kind.lower()
    for sub, peaks in DEVICE_PEAKS:
        if sub in lk:
            return {
                "kind": kind, "peak_gflops": peaks[0],
                "peak_hbm_gbps": peaks[1], "peak_ici_gbps": peaks[2],
                "peak_dcn_gbps": peaks[3], "source": "table",
            }
    raise UnknownDeviceError(
        f"no peaks for device kind {kind!r}: add it to DEVICE_PEAKS or "
        f"set BOOJUM_TPU_COST_PEAKS"
    )


# ---------------------------------------------------------------------------
# Per-family op-count primitives (shared by the kernel sheet and the
# stage totals — the two layers can never disagree on a family's math)
# ---------------------------------------------------------------------------


def ntt_cost(B: float, n: float, elem_bytes: float = 8.0) -> dict:
    """One batched size-n (i)NTT over B columns: n/2·log2(n) butterflies
    per column (1 mul + 2 adds each) plus a scale pass; each of the
    log2(n) stages re-reads and re-writes the full array. `elem_bytes`
    is the field element's device footprint (8 for Goldilocks limbs, 4
    for the BabyBear u32 lane)."""
    log_n = max(1.0, math.log2(max(n, 2)))
    muls = B * (n / 2) * log_n + B * n
    adds = B * n * log_n
    bytes_ = 2.0 * B * n * elem_bytes * log_n
    return {"flops": _flops(muls, adds), "hbm_bytes": bytes_}


def lde_cost(B: float, n: float, L: float,
             elem_bytes: float = 8.0) -> dict:
    """LDE from monomials at rate L: per coset a scale pass (n muls/col)
    plus a forward size-n NTT."""
    per = ntt_cost(B, n, elem_bytes)
    return {
        "flops": L * (per["flops"] + _flops(B * n, 0)),
        "hbm_bytes": L * per["hbm_bytes"] + B * n * elem_bytes * (L + 1),
    }


def sponge_cost(rows: float, width: float) -> dict:
    """Poseidon2 leaf sponges over `rows` rows of `width` field elements
    (rate-8 absorb)."""
    perms = rows * max(1.0, math.ceil(width / P2_RATE))
    return {
        "flops": perms * P2_FLOPS_PER_PERM,
        "hbm_bytes": perms * P2_BYTES_PER_PERM,
    }


def node_cost(N: float) -> dict:
    """Merkle node stack over N leaf digests: ~N 2-to-1 compressions
    (one permutation each) across all layers."""
    return {
        "flops": N * P2_FLOPS_PER_PERM,
        "hbm_bytes": N * P2_BYTES_PER_PERM,
    }


def binv_cost(m: float, elem_bytes: float = 8.0) -> dict:
    """Batch inversion of m elements (per-element Fermat chain, as the
    XLA kernels lower it)."""
    return {
        "flops": m * BINV_FLOPS_PER_ELEM,
        "hbm_bytes": m * BINV_BYTES_PER_ELEM * (elem_bytes / 8.0),
    }


def sweep_cost(domain: float, terms: float,
               elem_bytes: float = 8.0) -> dict:
    """The fused quotient sweep: `terms` alpha-weighted constraint terms
    evaluated over a `domain`-point coset domain, each an extension
    mul-accumulate on base-field operands."""
    muls = domain * terms * 3
    adds = domain * terms * 3
    return {
        "flops": _flops(muls, adds),
        "hbm_bytes": domain * terms * elem_bytes * 0.5,
    }


def deep_cost(cols: float, N: float, elem_bytes: float = 8.0) -> dict:
    """DEEP quotient accumulation: per column an extension
    mul-accumulate against the inverted denominators over N points."""
    return {
        "flops": cols * N * DEEP_FLOPS_PER_ELEM,
        "hbm_bytes": cols * N * DEEP_BYTES_PER_ELEM * (elem_bytes / 8.0),
    }


def fold_cost(m: float, k: int = 1, elem_bytes: float = 8.0) -> dict:
    """One FRI 2^k-to-1 fold chain from domain size m: each of the k
    halvings is an extension mul-accumulate (plus deinterleave gathers)
    over the surviving half."""
    flops = 0.0
    bytes_ = 0.0
    cur = m
    for _ in range(max(1, k)):
        flops += (cur / 2) * FOLD_FLOPS_PER_ELEM
        bytes_ += (cur / 2) * FOLD_BYTES_PER_ELEM * (elem_bytes / 8.0)
        cur /= 2
    return {"flops": flops, "hbm_bytes": bytes_}


def _zero() -> dict:
    return {"flops": 0.0, "hbm_bytes": 0.0}


def _acc(total: dict, part: dict, mult: float = 1.0):
    total["flops"] += mult * part.get("flops", 0.0)
    total["hbm_bytes"] += mult * part.get("hbm_bytes", 0.0)
    total["ici_bytes"] = total.get("ici_bytes", 0.0) + mult * part.get(
        "ici_bytes", 0.0
    )
    if part.get("dcn_bytes"):
        total["dcn_bytes"] = total.get("dcn_bytes", 0.0) + mult * part[
            "dcn_bytes"
        ]
    return total


# ---------------------------------------------------------------------------
# Per-kernel analytic sheet (the cross-check axis vs XLA actuals)
# ---------------------------------------------------------------------------


def _arg_bytes(a) -> int:
    if isinstance(a, (tuple, list)):
        return sum(_arg_bytes(x) for x in a)
    shape = getattr(a, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * getattr(getattr(a, "dtype", None), "itemsize", 8)


def _arg_shapes(args) -> list[tuple]:
    """Flat list of array shapes among the args (plane pairs flatten to
    their two u32 planes; static ints are skipped)."""
    return [
        tuple(int(d) for d in a.shape) for a in _flatten_args(args)
    ]


def _plane_pair(a) -> bool:
    """A (lo, hi) u32 plane pair — exactly two same-shape 4-byte-dtype
    arrays, the limb-resident argument unit (precompile._sdsp)."""
    if not (isinstance(a, (tuple, list)) and len(a) == 2):
        return False
    lo, hi = a
    sl, sh = getattr(lo, "shape", None), getattr(hi, "shape", None)
    if sl is None or sh is None or tuple(sl) != tuple(sh):
        return False
    return all(
        getattr(getattr(x, "dtype", None), "itemsize", 0) == 4
        for x in (lo, hi)
    )


def _main_elems(args) -> float:
    """Field elements of the LARGEST logical array argument (a (lo, hi)
    u32 plane pair is ONE logical argument — one field element per u32
    pair, i.e. bytes/8 either way)."""
    best = 0
    stack = list(args)
    while stack:
        a = stack.pop(0)
        if _plane_pair(a):
            best = max(best, _arg_bytes(a))
        elif isinstance(a, (tuple, list)):
            stack = list(a) + stack
        elif getattr(a, "shape", None) is not None:
            best = max(best, _arg_bytes(a))
    return best / 8.0


def _flatten_args(args):
    out = []
    stack = list(args)
    while stack:
        a = stack.pop(0)
        if isinstance(a, (tuple, list)):
            stack = list(a) + stack
            continue
        if getattr(a, "shape", None) is not None:
            out.append(a)
    return out


def kernel_cost(name: str, args, mesh_devices: int = 1) -> dict:
    """Analytic {flops, hbm_bytes, ici_bytes, family} for ONE dispatch of
    the named kernel with these (ShapeDtypeStruct) args. Families key on
    the enumeration's ledger names (prover/precompile.py); kernels
    outside every family get a generic elementwise estimate tagged
    family="fallback" — the tolerance cross-check only binds modeled
    families."""
    base = name.split(":", 1)[1] if ":" in name else name
    if "_bb" in base:
        # the BabyBear plane-free kernel set (prover/bb_kernels.py):
        # single u32 lanes, so elements = bytes/4 and every bytes term
        # scales by BB_ELEM_BYTES/8 against its Goldilocks twin
        return _kernel_cost_bb(base, name, args)
    in_bytes = sum(_arg_bytes(a) for a in args)
    E = _main_elems(args)  # field elements of the dominant operand
    shapes = _arg_shapes(args)
    D = max(1, int(mesh_devices))
    c: dict = {"flops": 0.0, "hbm_bytes": 0.0, "ici_bytes": 0.0}

    def fam(family: str, part: dict, ici: float = 0.0):
        c["flops"] = part.get("flops", 0.0)
        c["hbm_bytes"] = part.get("hbm_bytes", 0.0) or float(in_bytes * 2)
        c["ici_bytes"] = ici
        c["family"] = family
        return c

    # dominant-operand (B, n) for column-batched kernels
    Bn = shapes[0] if shapes else (1, 1)
    B = float(Bn[0]) if len(Bn) >= 2 else 1.0
    n = float(Bn[-1]) if Bn else 1.0

    if base.startswith(("imono", "mono")):
        return fam("ntt", ntt_cost(B, n))
    if base.startswith("fwd") or base.startswith("ntt"):
        return fam("ntt", ntt_cost(B, n))
    if "lde_pivot" in base:
        # per-chip LDE + the col->row all_to_all pivot (rate-L payload)
        L = _lde_rate_from(name, shapes)
        part = dict(lde_cost(B, n, L), ici_bytes=0.0)
        if "leaf" in base:
            part = _acc(part, sponge_cost(n * L, B))
        ici = B * n * 8 * L * (D - 1) / D if D > 1 else 0.0
        return fam("lde", part, ici=ici)
    if base.startswith("lde") or "lde_block" in base:
        L = _lde_rate_from(name, shapes)
        return fam("lde", lde_cost(B, n, L))
    if base.startswith("leaf_digests"):
        # args are (B, L, n): rows = L*n, width B
        rows = float(Bn[1] * Bn[2]) if len(Bn) >= 3 else n
        return fam("sponge", sponge_cost(rows, B))
    if base.startswith("absorb"):
        # (N, 12) state x (N, b) block: absorb b cols into N-row sponges
        blk = shapes[1] if len(shapes) > 1 else Bn
        rows = float(blk[0])
        width = float(blk[1]) if len(blk) > 1 else 1.0
        part = sponge_cost(rows, width)
        if "absorb_lde_block" in base:
            part = _acc(part, lde_cost(width, rows, 1.0))
        return fam("sponge", part)
    if base.startswith("node_layers") or base.startswith("node_step"):
        return fam("sponge", node_cost(n if len(Bn) < 2 else float(Bn[0])))
    if base.startswith("node_gather"):
        return fam(
            "ici", {"flops": 0.0, "hbm_bytes": float(in_bytes * 2)},
            ici=float(in_bytes) * (D - 1),
        )
    if base.startswith("coset_eval_pick"):
        # round 3's read of the committed cosets: one coset of each
        # (B, L*n) storage copied out, no arithmetic
        # (the dominant operand's last axis, `n` here, is L*n rows)
        picked = _arg_bytes(args[0]) * float(args[2]) / n
        return fam("transfer", {"flops": 0.0, "hbm_bytes": 2.0 * picked})
    if base.startswith("coset_eval"):
        return fam("ntt", _acc(ntt_cost(B, n), {"flops": _flops(B * n, 0),
                                                "hbm_bytes": 0.0}))
    if base.startswith("coset_sweep_terms"):
        # xs arg is Q*n points; the alpha table length bounds the terms
        # (u64 path: the 1-D capA power arrays; resident path: the
        # (4, S_cols) host-built scalar table)
        domain = max((s[0] for s in shapes if len(s) == 1), default=n)
        cands = [
            s[0] for s in shapes
            if len(s) == 1 and s[0] not in (2,) and s[0] != domain
        ] or [s[1] for s in shapes if len(s) == 2 and s[0] == 4]
        terms = min(cands) if cands else 32
        return fam("sweep", sweep_cost(float(domain), float(terms)))
    if base.startswith("quotient_interp"):
        # coset interpolation: inverse-vandermonde solve over the Q
        # per-coset columns — inversion-chain-heavy, measured per elem
        tot = in_bytes / 8.0
        return fam("interp", {"flops": tot * 350.0,
                              "hbm_bytes": tot * 320.0})
    if base.startswith(("chunk_num_den", "lookup_denominators")):
        return fam("stage2", {
            "flops": E * 410.0, "hbm_bytes": in_bytes * 4.5,
        })
    if base.startswith("z_and_partials"):
        # the grand-product ratios invert their partials — binv-priced
        return fam("stage2", binv_cost(E))
    if base.startswith(("stage2_stack", "zshift")):
        return fam("stage2", {
            "flops": E * W_EXT_MUL, "hbm_bytes": in_bytes * 3.0,
        })
    if "binv" in base or base.startswith("ext_binv"):
        return fam("binv", binv_cost(E))
    if base.startswith(("alpha_powers", "deep_powers")):
        return fam("small", {"flops": E * W_EXT_MUL,
                             "hbm_bytes": in_bytes * 2.0})
    if base.startswith("deep_denoms"):
        # a broadcast subtract per point — cheap, no inversions here
        return fam("deep", {"flops": E * 8.0, "hbm_bytes": E * 40.0})
    if base.startswith("evals"):
        return fam("deep", {"flops": E * W_EXT_MUL,
                            "hbm_bytes": in_bytes * 3.0})
    if base.startswith("deep_codeword"):
        cols = sum(float(s[0]) for s in shapes if len(s) == 2)
        N = max((float(s[-1]) for s in shapes if len(s) == 2), default=n)
        part = deep_cost(cols, N)
        # the boundary col->row source re-layout of the (lo,hi) planes:
        # same convention as lde_pivot and the round5 stage total —
        # global payload, (D-1)/D of it crossing chips
        ici = N * 8 * 2 * (D - 1) / D if D > 1 else 0.0
        return fam("deep", part, ici=ici)
    if base.startswith("deep_block"):
        return fam("deep", deep_cost(B, n))
    if base.startswith("deep_combine"):
        return fam("deep", {"flops": E * 210.0, "hbm_bytes": E * 90.0})
    if base.startswith("deep_extras"):
        return fam("deep", {"flops": E * 600.0, "hbm_bytes": E * 64.0})
    # deep_regen:<ntt-spec> kernels strip to their inner ntt/lde names
    # above ("lde_b.._L.." etc.) and are owned by those branches
    if base.startswith(("fri_fold", "fri_leaf", "fri_commit")):
        k = _fold_k_from(name)
        part = fold_cost(E, k)
        if "leaf" in base or "commit" in base:
            # the pre-fold oracle commit: 2^k-leaf sponges over both
            # extension components
            part = _acc(
                part, sponge_cost(E / float(1 << k), float(2 << k))
            )
        return fam("fri", part)
    if base.startswith("fri_final"):
        return fam("ntt", ntt_cost(1.0, E))
    if base.startswith("witness_upload_concat"):
        return fam("transfer", {"flops": 0.0, "hbm_bytes": in_bytes * 2.0})
    # generic elementwise estimate
    return fam("fallback", {"flops": E * 8.0, "hbm_bytes": in_bytes * 2.0})


def _kernel_cost_bb(base: str, name: str, args) -> dict:
    """Analytic cost of one `_bb` kernel dispatch. Same families as the
    Goldilocks routing so the roofline aggregates them
    together; every entry additionally carries field="babybear" and
    elem_bytes=4 so a report consumer can attribute the byte halving."""
    eb = BB_ELEM_BYTES
    in_bytes = sum(_arg_bytes(a) for a in args)
    shapes = _arg_shapes(args)
    Bn = shapes[0] if shapes else (1, 1)
    B = float(Bn[0]) if len(Bn) >= 2 else 1.0
    n = float(Bn[-1]) if Bn else 1.0
    E = max(
        (float(_shape_elems(s)) for s in shapes), default=1.0
    )  # elements of the dominant operand — u32 lanes, one per element

    def fam(family: str, part: dict) -> dict:
        return {
            "flops": part.get("flops", 0.0),
            "hbm_bytes": part.get("hbm_bytes", 0.0)
            or float(in_bytes * 2),
            "ici_bytes": 0.0,
            "family": family,
            "field": "babybear",
            "elem_bytes": eb,
        }

    if base.startswith(("imono", "mono", "fwd", "ntt")):
        return fam("ntt", ntt_cost(B, n, elem_bytes=eb))
    if base.startswith("lde"):
        return fam("lde", lde_cost(B, n, _lde_rate_from(name, shapes),
                                   elem_bytes=eb))
    if base.startswith("leaf_digests"):
        # (B, N) columns -> N leaves of width B
        rows = n if len(Bn) >= 2 else float(Bn[0])
        perms = rows * max(1.0, math.ceil(B / P2_RATE))
        return fam("sponge", {
            "flops": perms * P2BB_FLOPS_PER_PERM,
            "hbm_bytes": perms * P2BB_BYTES_PER_PERM,
        })
    if base.startswith("node_layers"):
        leaves = float(Bn[0])
        return fam("sponge", {
            "flops": leaves * P2BB_FLOPS_PER_PERM,
            "hbm_bytes": leaves * P2BB_BYTES_PER_PERM,
        })
    if base.startswith("coset_sweep_terms"):
        domain = max((s[0] for s in shapes if len(s) == 1), default=n)
        # transition + boundary over 4 ext coordinates
        return fam("sweep", sweep_cost(float(domain), 8.0, elem_bytes=eb))
    if base.startswith("deep_accumulate"):
        N = max((float(s[-1]) for s in shapes if len(s) == 2), default=n)
        cols = 1.0 + sum(
            float(s[0]) for s in shapes if len(s) == 2 and s[-1] == N
        )
        return fam("deep", deep_cost(cols, N, elem_bytes=eb))
    if base.startswith("fri_fold"):
        return fam("fri", fold_cost(E / 4.0, _fold_k_from(name),
                                    elem_bytes=eb))
    if "binv" in base:
        return fam("binv", binv_cost(E, elem_bytes=eb))
    return fam("fallback", {"flops": E * 8.0,
                            "hbm_bytes": in_bytes * 2.0})


def _shape_elems(s) -> int:
    n = 1
    for d in s:
        n *= int(d)
    return n


def _lde_rate_from(name: str, shapes) -> float:
    """Recover the commit rate L from an lde-family kernel's name
    (lde_L<k>_..., *_lde8_*) or default 2 (the spec args carry only the
    monomial side)."""
    import re

    m = re.search(r"(?:lde|_L)(\d+)", name)
    if m:
        v = int(m.group(1))
        if 1 <= v <= 64:
            return float(v)
    return 2.0


def _fold_k_from(name: str) -> int:
    import re

    m = re.search(r"_k(\d+)", name)
    return int(m.group(1)) if m else 1


def xla_cost_of(compiled) -> dict | None:
    """The XLA-reported actuals of one compiled executable:
    {flops, bytes_accessed, arg_bytes, out_bytes, temp_bytes} — the
    cross-check axis captured at compile time (prover/precompile.py,
    prover/aot.py) into CompileLedger entries and AOT manifests. None
    when the backend exposes neither analysis (never an error: actuals
    are an observability bonus, not a compile requirement)."""
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            f = ca.get("flops")
            if isinstance(f, (int, float)) and f == f and f >= 0:
                out["flops"] = round(float(f), 1)
            b = ca.get("bytes accessed")
            if isinstance(b, (int, float)) and b == b and b >= 0:
                out["bytes_accessed"] = round(float(b), 1)
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        for key, attr in (
            ("arg_bytes", "argument_size_in_bytes"),
            ("out_bytes", "output_size_in_bytes"),
            ("temp_bytes", "temp_size_in_bytes"),
        ):
            v = getattr(ma, attr, None)
            if isinstance(v, (int, float)) and v >= 0:
                out[key] = int(v)
    except Exception:
        pass
    return out or None


def cost_sheet(specs, mesh_devices: int = 1) -> dict:
    """{kernel_name: analytic cost} over a KernelSpec list (one entry per
    executable, per-dispatch units)."""
    out = {}
    for spec in specs:
        try:
            out[spec.name] = kernel_cost(
                spec.name, spec.args, mesh_devices=mesh_devices
            )
        except Exception:  # noqa: BLE001 — one odd spec must not void
            out[spec.name] = {  # the whole sheet
                "flops": 0.0, "hbm_bytes": 0.0, "ici_bytes": 0.0,
                "family": "error",
            }
    return out


# ---------------------------------------------------------------------------
# Per-stage analytic totals (the roofline record's numerator)
# ---------------------------------------------------------------------------

# ONE definition of the prover's stage set (report.PROVE_STAGES): the
# roofline record and the trend gate must never disagree on what a
# "stage" is — cache-state spans (aot_load/aot_warm/overlap_prefetch)
# are deliberately excluded from both
from .report import PROVE_STAGES as STAGE_NAMES  # noqa: E402


def stage_costs(
    sb, config, mesh_devices: int = 1, dcn_fraction: float = 0.0
) -> dict:
    """Analytic per-stage {flops, hbm_bytes, ici_bytes[, dcn_bytes]} for
    one full prove of a circuit in this ShapeBucket — multiplicities
    (Q coset evals, per-oracle commits, the fold schedule) owned HERE,
    so the per-kernel sheet stays per-dispatch. On a multi-host mesh
    `dcn_fraction` (parallel/multihost.dcn_fraction) splits every
    modeled crossing-byte term into intra-host ici_bytes and cross-host
    dcn_bytes — the same topology split the measured dcn.* gauges
    carry."""
    from ..prover.fri import fold_schedule

    n = float(sb.trace_len)
    L = float(sb.lde_factor)
    N = float(sb.domain_len)
    Q = float(sb.quotient_degree)
    D = max(1, int(mesh_devices))
    terms = float(_total_alpha_terms(sb))

    def commit(B: float, mono: bool = True) -> dict:
        total = {"flops": 0.0, "hbm_bytes": 0.0, "ici_bytes": 0.0}
        if mono:
            _acc(total, ntt_cost(B, n))
        _acc(total, lde_cost(B, n, L))
        _acc(total, sponge_cost(N, B))
        _acc(total, node_cost(N))
        if D > 1:
            # col->row Merkle pivot (rate-L planes) + cap all_gather
            total["ici_bytes"] += B * n * 8 * L * (D - 1) / D
            total["ici_bytes"] += float(sb.cap_size) * 4 * 8 * (D - 1)
        return total

    stages: dict = {}
    # round 1: witness upload + commit
    r1 = commit(float(sb.B_wit))
    r1["hbm_bytes"] += sb.B_wit * n * 8  # H2D witness upload
    stages["round1_witness_commit"] = r1
    # round 2: grand product / lookup polys + stage-2 commit
    r2 = {"flops": 0.0, "hbm_bytes": 0.0, "ici_bytes": 0.0}
    _acc(r2, {"flops": sb.Ct * n * 2 * W_EXT_MUL,
              "hbm_bytes": sb.Ct * n * 8 * 4})
    _acc(r2, binv_cost(sb.num_chunks * n))
    if sb.lookups:
        _acc(r2, {"flops": sb.lookup_subargs * sb.lookup_width * n
                  * W_EXT_MUL,
                  "hbm_bytes": sb.lookup_subargs * n * 8 * 2})
        _acc(r2, binv_cost((sb.lookup_subargs + 1) * n))
    _acc(r2, commit(float(sb.S)))
    stages["round2_stage2_commit"] = r2
    # round 3: the coset evals + the fused sweep + interp + quotient
    # commit (LDE only; monomials come from the interp). Meshless, the
    # three committed groups are read from their commitments on the first
    # min(L, Q) cosets (prover.coset_is_committed; a copy) and transformed
    # on the rest; the shifted z transforms on all Q. The shard_map mesh
    # transforms everything (a streamed commit too: not modeled here).
    r3 = {"flops": 0.0, "hbm_bytes": 0.0, "ici_bytes": 0.0}
    committed = float(sb.B_wit + sb.B_setup + sb.S)
    reused = min(L, Q) if D == 1 else 0.0
    _acc(r3, ntt_cost(committed, n), mult=Q - reused)
    _acc(r3, {"flops": 0.0, "hbm_bytes": 2 * committed * n * 8}, mult=reused)
    _acc(r3, ntt_cost(2.0, n), mult=Q)
    _acc(r3, sweep_cost(Q * n, terms))
    # quotient interpolation (inverse-vandermonde, per-elem calibrated)
    _acc(r3, {"flops": 2 * Q * n * 350.0, "hbm_bytes": 2 * Q * n * 320.0})
    _acc(r3, commit(float(sb.B_q), mono=False))
    stages["round3_quotient"] = r3
    # round 4: evaluations at z/zw (horner over monomials)
    stages["round4_evaluations"] = {
        "flops": float(sb.B_all + sb.S) * n * (W_MUL + W_ADD) * 2,
        "hbm_bytes": float(sb.B_all + sb.S) * n * 8,
        "ici_bytes": 0.0,
    }
    # round 5: DEEP accumulation over every committed column + FRI
    r5 = {"flops": 0.0, "hbm_bytes": 0.0, "ici_bytes": 0.0}
    _acc(r5, deep_cost(float(sb.B_all), N))
    _acc(r5, binv_cost(2 * N))
    sched = fold_schedule(
        int(n), config.fri_final_degree,
        getattr(config, "fri_folding_schedule", None),
    )
    cur = N
    for k in sched:
        _acc(r5, fold_cost(cur, int(k)))
        cur /= float(1 << int(k))
        _acc(r5, sponge_cost(cur / 16.0, 16.0))  # per-oracle leaf commit
        _acc(r5, node_cost(cur / 16.0))
    _acc(r5, ntt_cost(1.0, cur))  # final interpolation
    if D > 1:
        r5["ici_bytes"] += N * 8 * 2 * (D - 1) / D
    stages["round5_deep_fri"] = r5
    # queries: gathers + host assembly — bytes, no meaningful flops
    stages["queries"] = {
        "flops": float(sb.num_queries) * 1e3,
        "hbm_bytes": float(sb.num_queries)
        * (sb.B_all + 40.0) * 8 * math.log2(max(N, 2)),
        "ici_bytes": 0.0,
    }
    f = min(max(float(dcn_fraction), 0.0), 1.0)
    for st in stages.values():
        st.setdefault("ici_bytes", 0.0)
        if f > 0.0 and st["ici_bytes"] > 0.0:
            st["dcn_bytes"] = st["ici_bytes"] * f
            st["ici_bytes"] *= 1.0 - f
    # Field scaling (ISSUE 20): the stage formulas above model 8-byte
    # Goldilocks lanes; the BabyBear backend moves the SAME element
    # counts at 4 bytes each, so every traffic term is exactly eb/8 of
    # the Goldilocks sheet. Flops are left alone — the mod-p multiply
    # width is a per-kernel concern the kernel sheet already prices.
    try:
        from ..field.spec import is_babybear

        if is_babybear():
            scale = BB_ELEM_BYTES / 8.0
            for st in stages.values():
                for key in ("hbm_bytes", "ici_bytes", "dcn_bytes"):
                    if key in st:
                        st[key] *= scale
    except Exception:  # noqa: BLE001 — cost model must never fail a prove
        pass
    return stages


def _total_alpha_terms(sb) -> int:
    """total_alpha_terms exactly as enumerate_kernels derives it — via
    the gate set is unavailable here, so approximate from the bucket's
    chunk/lookup geometry plus a per-copy-column gate-term floor."""
    return (
        2 * sb.num_copy_cols + 1 + sb.num_chunks
        + ((sb.lookup_subargs + 1) if sb.lookups else 0)
    )


# ---------------------------------------------------------------------------
# The `cost` record: model x walls x gauges -> roofline
# ---------------------------------------------------------------------------

COST_SCHEMA = 1


def _stage_walls(span_tree: list) -> dict:
    """{stage_name: wall_s} from the prove root's direct children —
    the SAME extraction the trend series uses (report.stage_walls),
    filtered to the prover's stage names."""
    from .report import stage_walls

    return stage_walls(span_tree, names=STAGE_NAMES)


def roofline(entry: dict, wall_s: float, peaks: dict) -> dict:
    """Fold one {flops, hbm_bytes, ici_bytes} entry + its measured wall
    into achieved rates, regime and efficiency-vs-peak. Zero/invalid
    walls get NO achieved/efficiency fields (the validator rejects a
    record that claims efficiency over a zero denominator)."""
    def _sig(v):
        # 4 significant figures, never rounded to zero for positive v
        return float(f"{v:.4g}")

    out = dict(entry)
    # Gate on the ROUNDED wall: the record carries round(wall, 6), so a
    # sub-microsecond wall must not carry achieved fields the validator
    # would reject as efficiency-over-zero.
    wall_s = round(float(wall_s), 6) if wall_s is not None else None
    out["wall_s"] = wall_s
    flops = float(entry.get("flops", 0.0))
    hbm = float(entry.get("hbm_bytes", 0.0))
    intensity = flops / hbm if hbm > 0 else None
    if intensity is not None:
        out["intensity_flop_per_byte"] = _sig(intensity)
    pf = float(peaks.get("peak_gflops") or 0.0)
    pb = float(peaks.get("peak_hbm_gbps") or 0.0)
    balance = (pf / pb) if pb > 0 else None
    if intensity is not None and balance is not None:
        out["regime"] = "compute" if intensity >= balance else "memory"
    if not (isinstance(wall_s, (int, float)) and wall_s > 0):
        return out
    ag = flops / wall_s / 1e9
    ab = hbm / wall_s / 1e9
    out["achieved_gflops"] = _sig(ag)
    out["achieved_gbps"] = _sig(ab)
    ici = float(entry.get("ici_bytes", 0.0))
    if ici > 0:
        out["achieved_ici_gbps"] = _sig(ici / wall_s / 1e9)
    dcn = float(entry.get("dcn_bytes", 0.0))
    if dcn > 0:
        out["achieved_dcn_gbps"] = _sig(dcn / wall_s / 1e9)
    eff = None
    if out.get("regime") == "compute" and pf > 0:
        eff = ag / pf
    elif out.get("regime") == "memory" and pb > 0:
        eff = ab / pb
    if eff is not None:
        out["efficiency"] = _sig(eff)
    return out


def build_cost_record(
    sb,
    config,
    span_tree: list,
    metrics: dict | None = None,
    ledger_costs: dict | None = None,
    sheet: dict | None = None,
    mesh_devices: int = 1,
    peaks: dict | None = None,
    dcn_fraction: float = 0.0,
) -> dict:
    """Assemble the report line's `cost` record (pure: everything it
    reads is already a dict/dataclass, so tests drive it with synthetic
    trees)."""
    peaks = peaks or device_peaks()
    walls = _stage_walls(span_tree)
    stages = stage_costs(
        sb, config, mesh_devices=mesh_devices, dcn_fraction=dcn_fraction
    )
    rec_stages = {}
    total = {"flops": 0.0, "hbm_bytes": 0.0, "ici_bytes": 0.0}
    total_wall = 0.0
    for name, entry in stages.items():
        wall = walls.get(name)
        rec_stages[name] = roofline(
            {k: round(v, 1) for k, v in entry.items()}, wall, peaks
        )
        if isinstance(wall, (int, float)):
            total_wall += wall
        _acc(total, entry)
    try:
        from ..field.spec import active_field

        field_name = active_field()
    except Exception:
        field_name = "goldilocks"
    record: dict = {
        "schema": COST_SCHEMA,
        "field": field_name,
        "device": peaks,
        "stages": rec_stages,
        "total": roofline(
            {k: round(v, 1) for k, v in total.items()},
            total_wall if total_wall > 0 else None, peaks,
        ),
    }
    gauges = (metrics or {}).get("gauges") or {}
    counters = (metrics or {}).get("counters") or {}
    measured_ici = float(
        gauges.get("ici.all_to_all_bytes", 0.0) or 0.0
    ) + float(gauges.get("ici.all_gather_bytes", 0.0) or 0.0)
    if measured_ici > 0:
        record["total"]["ici_bytes_measured"] = round(measured_ici, 1)
    measured_dcn = sum(
        float(gauges.get(g, 0.0) or 0.0)
        for g in (
            "dcn.all_to_all_bytes",
            "dcn.all_gather_bytes",
            "dcn.host_gather_bytes",
        )
    )
    if measured_dcn > 0:
        record["total"]["dcn_bytes_measured"] = round(measured_dcn, 1)
    h2d = counters.get("transfer.h2d_bytes")
    d2h = counters.get("transfer.d2h_bytes")
    if isinstance(h2d, (int, float)) or isinstance(d2h, (int, float)):
        record["total"]["transfer_bytes_measured"] = round(
            float(h2d or 0) + float(d2h or 0), 1
        )
    if sheet:
        record["kernels"] = sorted(sheet)
    if ledger_costs:
        # the evidence claim: kernels whose XLA actuals this record is
        # built on — the report validator rejects names the compile
        # ledger never recorded
        record["attributed_kernels"] = sorted(
            name for name in ledger_costs if name in (sheet or {})
        )
    return record


# ---------------------------------------------------------------------------
# The prover seam + process-level last-record snapshot (/metrics, bench)
# ---------------------------------------------------------------------------

_LAST_LOCK = threading.Lock()
_LAST_RECORD: dict | None = None


def _cached_sheet(assembly, config, mesh_shape=None, specs=None) -> dict:
    """The per-kernel analytic sheet of the DISPATCHED variant, cached
    ON THE ASSEMBLY per (bucket, variant) — same idiom as
    shape_key.shape_bucket; the enumeration walks the selector tree and
    must not re-run per prove. `specs` lets a caller that already
    enumerated (precompile's sweep) skip the second derivation.
    (Derived data, not collector state: two computations of the same
    key are identical, so there is nothing to bleed across packed
    requests.)"""
    from ..prover.aot import variant_fingerprint
    from ..prover.shape_key import bucket_key

    key = (
        bucket_key(assembly, config),
        json.dumps(variant_fingerprint(mesh_shape), sort_keys=True),
    )
    cache = getattr(assembly, "_cost_sheet_cache", None)
    if cache is None:
        cache = {}
        try:
            assembly._cost_sheet_cache = cache
        except Exception:
            cache = None
    if cache is not None and key in cache:
        return cache[key]
    if specs is None:
        from ..prover.precompile import enumerate_kernels

        specs = enumerate_kernels(assembly, config, mesh_shape=mesh_shape)
    D = _mesh_devices(mesh_shape)
    sheet = cost_sheet(specs, mesh_devices=D)
    if cache is not None:
        cache[key] = sheet
    return sheet


def prime_sheet(assembly, config, specs, mesh_shape=None) -> None:
    """Pre-populate the assembly's sheet cache from an ALREADY
    enumerated spec list — precompile calls this after its sweep so the
    first recorded prove never re-walks the enumeration inside its
    `prove` span. Fails soft like the rest of the plane."""
    try:
        if cost_enabled():
            _cached_sheet(assembly, config, mesh_shape=mesh_shape,
                          specs=specs)
    except Exception:
        pass


def _mesh_devices(mesh_shape) -> int:
    if mesh_shape is None:
        return 1
    if isinstance(mesh_shape, (tuple, list)):
        d = 1
        for x in mesh_shape:
            d *= int(x)
        return d
    try:
        d = 1
        for x in dict(mesh_shape.shape).values():
            d *= int(x)
        return d
    except Exception:
        return 1


# the registry families build_cost_record reports as MEASURED traffic;
# cumulative on a long-lived registry (bench multi-rep runs), so the
# prover snapshots them at prove start and the record carries the delta
_MEASURED_GAUGES = (
    "ici.all_to_all_bytes", "ici.all_gather_bytes",
    "dcn.all_to_all_bytes", "dcn.all_gather_bytes",
    "dcn.host_gather_bytes",
)
_MEASURED_COUNTERS = ("transfer.h2d_bytes", "transfer.d2h_bytes")


def measured_baseline() -> dict:
    """Prove-start snapshot of the measured-traffic families on the
    active registry. `attach_cost_record` subtracts it so a process
    that proves N times on one registry stamps per-PROVE ici/transfer
    bytes, not the running total. Fails soft ({} = no subtraction)."""
    try:
        from . import metrics as _metrics

        reg = _metrics.current_registry()
        if reg is None:
            return {}
        snap = reg.to_dict()
        g = snap.get("gauges") or {}
        c = snap.get("counters") or {}
        return {
            "gauges": {
                k: float(g.get(k) or 0.0) for k in _MEASURED_GAUGES
            },
            "counters": {
                k: float(c.get(k) or 0.0) for k in _MEASURED_COUNTERS
            },
        }
    except Exception:  # noqa: BLE001 — a snapshot bug must never
        return {}      # fail a prove


def _subtract_baseline(snap: dict, baseline: dict) -> dict:
    """Copy `snap` with the baseline's measured families subtracted
    (clamped at 0 — a registry swapped mid-prove starts fresh)."""
    out = dict(snap)
    for fam in ("gauges", "counters"):
        base = baseline.get(fam) or {}
        if not base:
            continue
        cur = dict(snap.get(fam) or {})
        for k, v in base.items():
            if k in cur and isinstance(cur[k], (int, float)):
                cur[k] = max(0.0, float(cur[k]) - v)
        out[fam] = cur
    return out


def attach_cost_record(
    assembly, config, mesh=None, baseline=None
) -> dict | None:
    """prover seam: at the end of a successful prove, join the analytic
    model with this prove's span walls / gauges / ledger actuals, stamp
    the `cost` record on the active FlightRecorder (rides the report
    line) and export `cost.*` gauges on the active metrics registry
    (rides /metrics). Fails soft — a cost-model bug must never fail a
    prove."""
    try:
        if not cost_enabled():
            return None
        from . import metrics as _metrics
        from . import report as _report
        from . import spans as _spans
        from .profiling import current_compile_ledger

        rec = _report.current_flight_recorder()
        if rec is None:
            # bench without BOOJUM_TPU_REPORT installs a bare
            # SpanRecorder: still compute the record (it lands on the
            # bench JSON line via last_cost_record), just with no
            # report line to stamp
            spans_rec = _spans.current_recorder()
            if spans_rec is None:
                return None
        else:
            spans_rec = rec.spans
        from ..prover.shape_key import shape_bucket

        sb = shape_bucket(assembly, config)
        mesh_shape = None
        dcn_frac = 0.0
        if mesh is not None:
            from ..prover.aot import _mesh_shape_list
            from .pallas_util import resolve_variant

            if resolve_variant(mesh).mesh == "shard_map":
                mesh_shape = _mesh_shape_list(mesh)
                try:
                    from ..parallel.multihost import dcn_fraction

                    dcn_frac = dcn_fraction(mesh)
                except Exception:
                    dcn_frac = 0.0
        sheet = _cached_sheet(assembly, config, mesh_shape=mesh_shape)
        ledger = current_compile_ledger()
        ledger_costs = (
            ledger.kernel_costs(shape_key=sb.key)
            if ledger is not None else {}
        )
        reg = _metrics.current_registry()
        metrics_snap = reg.to_dict() if reg is not None else {}
        if baseline:
            metrics_snap = _subtract_baseline(metrics_snap, baseline)
        record = build_cost_record(
            sb, config,
            spans_rec.tree(),
            metrics_snap,
            ledger_costs=ledger_costs,
            sheet=sheet,
            mesh_devices=_mesh_devices(mesh_shape),
            dcn_fraction=dcn_frac,
        )
        if rec is not None:
            rec.cost = record
        for name, st in record["stages"].items():
            for key in ("achieved_gflops", "achieved_gbps", "efficiency"):
                v = st.get(key)
                if isinstance(v, (int, float)):
                    _metrics.gauge_set_cost(f"{name}.{key}", v)
        tot = record.get("total") or {}
        for key in ("achieved_gflops", "achieved_gbps", "efficiency"):
            v = tot.get(key)
            if isinstance(v, (int, float)):
                _metrics.gauge_set_cost(f"total.{key}", v)
        with _LAST_LOCK:
            global _LAST_RECORD
            _LAST_RECORD = record
        return record
    except Exception as e:  # noqa: BLE001
        try:
            from .profiling import log as _plog

            _plog(f"cost model: attach failed ({e!r}) — line gets no "
                  f"cost record")
        except Exception:
            pass
        return None


def last_cost_record() -> dict | None:
    """The most recent attached cost record (process-wide) — bench.py
    stamps it on its JSON line; the telemetry provider flattens it."""
    with _LAST_LOCK:
        return _LAST_RECORD


def telemetry_provider() -> dict:
    """Sampler provider: flat {stage.metric: value} gauges of the last
    attached cost record (rides /metrics as
    boojum_tpu_telemetry_cost_* and the report `telemetry` record)."""
    rec = last_cost_record()
    if not rec:
        return {}
    out: dict = {}
    for name, st in (rec.get("stages") or {}).items():
        for key in ("achieved_gflops", "achieved_gbps", "efficiency"):
            v = st.get(key)
            if isinstance(v, (int, float)):
                out[f"{name}.{key}"] = v
    return out
