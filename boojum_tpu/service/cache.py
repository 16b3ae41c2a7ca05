"""Device-resident cache manager: pin amortizable state across requests.

ICICLE's deployment model (PAPERS.md) keeps setup/twiddle/table state
device-resident across proof requests instead of rebuilding per proof;
this manager is that layer for the proving service. Two classes of
state, treated differently because they free differently:

- **Per-setup residency** (the big, evictable items): the sigma column
  stack, grand-product x powers, non-residues and lookup tables that
  `prover._dev_cached` parks on the setup/assembly objects (~8·Ct·n
  bytes for sigma alone — ~0.5 GB at 2^20). The manager holds the only
  long-lived references, measures ACTUAL resident bytes from the
  `_dev_cache` dicts after each request, and evicts least-recently-used
  entries (clearing those dicts, so the buffers free and the next
  request re-uploads on miss) when the byte cap is exceeded.
- **Per-geometry tables** (small, global, shared): twiddle/domain
  contexts (`ntt.warm_domain_caches`), brev-domain constants and FRI
  fold/1-over-x tables live in module `lru_cache`s keyed by
  (log_n, rate) — already shared by every same-shape request and not
  individually evictable. The manager WARMS them at admission (so the
  first request of a bucket pays the build outside a transcript
  barrier) and reports their estimated footprint, but the byte cap
  applies only to the evictable class.

Hits/misses/evictions are charged through
`utils.metrics.count_service_cache` (`service.cache.*`), pinned bytes to
the `service.cache.pinned_bytes` gauge — the `prove_report.py --check`
gate validates the schema.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field

from ..utils import metrics as _metrics
from ..utils.profiling import log as _log


def _dev_cache_bytes(obj) -> int:
    """Actual resident bytes of one host object's `_dev_cache` (the
    prover's device-upload cache seam)."""
    cache = getattr(obj, "_dev_cache", None)
    if not cache:
        return 0
    total = 0
    for v in cache.values():
        for leaf in v if isinstance(v, (tuple, list)) else (v,):
            try:
                total += int(leaf.size) * leaf.dtype.itemsize
            except Exception:
                pass
    return total


@dataclass
class PinnedEntry:
    """One pinned (assembly, setup) residency, keyed by the request's
    shape-bucket key plus the setup's identity (two different circuits
    can share a shape bucket but never a setup)."""

    bucket_key: str
    assembly: object
    setup: object
    bytes: int = 0
    hits: int = 0
    pinned_ts: float = field(default_factory=time.perf_counter)

    def measure(self) -> int:
        self.bytes = _dev_cache_bytes(self.setup) + _dev_cache_bytes(
            self.assembly
        )
        return self.bytes

    def release(self):
        """Drop the device residency: clearing the `_dev_cache` dicts
        releases the manager's references so the buffers free; the next
        prove of this setup transparently re-uploads (a cache MISS, not
        an error)."""
        for obj in (self.setup, self.assembly):
            cache = getattr(obj, "_dev_cache", None)
            if cache:
                cache.clear()


class DeviceCacheManager:
    """Byte-capped LRU over pinned per-setup device residency, plus
    geometry-table warming. Thread-safe; all accounting no-op-cheap when
    no metrics registry is installed."""

    def __init__(self, capacity_bytes: int = 2 << 30):
        self.capacity_bytes = int(capacity_bytes)
        self._lock = threading.Lock()
        # key -> PinnedEntry, most-recently-used LAST
        self._entries: OrderedDict[tuple, PinnedEntry] = OrderedDict()
        self._warmed_geometries: set[tuple] = set()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.evicted_bytes = 0

    # ---- geometry tables -------------------------------------------------
    def warm_geometry(self, bucket) -> bool:
        """Populate the per-geometry transform caches of one shape bucket
        (twiddles for rates L and Q, domain constants, FRI fold tables) —
        idempotent and enqueue-only, exactly the set the prover's round-0
        prefetch touches. Returns True when this call did the warming."""
        from ..utils.pallas_util import resolve_variant

        variant = resolve_variant()
        key = (
            bucket.log_n, bucket.lde_factor, bucket.quotient_degree,
            bucket.fri_final_degree, bucket.fri_schedule, bucket.lookups,
            # field backend (ISSUE 20): a geometry warmed under goldilocks
            # holds u64 twiddles — the same bucket under babybear needs
            # its own u32 table set, so the field is part of the key
            variant.field,
        )
        with self._lock:
            if key in self._warmed_geometries:
                return False
            self._warmed_geometries.add(key)
        if variant.field == "babybear":
            # the babybear full prover (prover/prover_bb.py) consumes the
            # plane-free u32 table set — bb_ntt twiddles/scale tables at
            # trace size and both full-domain rates, the coset domain
            # constants, and the FRI fold-challenge tables; warm exactly
            # that set, nothing limb- or u64-shaped
            from ..field import babybear as _bb
            from ..ntt import bb_ntt as BN
            from ..prover import bb_kernels as BK
            from ..prover import stages_bb as SBB

            shift = int(_bb.SPEC.multiplicative_generator)
            log_L = bucket.lde_factor.bit_length() - 1
            log_Q = bucket.quotient_degree.bit_length() - 1
            for lg in (
                bucket.log_n, bucket.log_n + log_L, bucket.log_n + log_Q,
            ):
                BN._twiddles(lg, False)
                BN._twiddles(lg, True)
            BN._lde_scale_table(bucket.log_n, bucket.lde_factor, shift)
            BN._lde_scale_table(bucket.log_n, bucket.quotient_degree, shift)
            BK.domain_xs_bb(bucket.log_n, bucket.lde_factor, shift)
            BK.domain_xs_bb(bucket.log_n, bucket.quotient_degree, shift)
            BK.zh_inv_bb(bucket.log_n, bucket.quotient_degree, shift)
            SBB.l0_lde_bb(bucket.log_n, bucket.quotient_degree, shift)
            log_full = bucket.log_n + log_L
            num_rounds = (
                bucket.trace_len // bucket.fri_final_degree
            ).bit_length() - 1
            if num_rounds >= 1:
                BK.fri_fold_tables_bb(log_full, shift, num_rounds)
            return True
        if variant.planes:
            # the resident prove consumes the PLANE table set (ISSUE 10)
            # — warm exactly what it will touch, nothing u64
            from ..prover import resident as RES
            from ..ntt import limb_ntt as LN
            from ..field import gl as _gl

            # plane twiddle contexts for trace size and both full-domain
            # rates (the warm_domain_caches twin)
            LN.PlaneNTTContext(bucket.log_n)
            LN.PlaneNTTContext(
                bucket.log_n + (bucket.lde_factor.bit_length() - 1)
            )
            LN.PlaneNTTContext(
                bucket.log_n + (bucket.quotient_degree.bit_length() - 1)
            )
            RES.domain_xs_brev_p(bucket.log_n, bucket.lde_factor)
            RES.domain_xs_brev_p(bucket.log_n, bucket.quotient_degree)
            RES.l0_brev_p(bucket.log_n, bucket.quotient_degree)
            RES.vanishing_inv_brev_p(bucket.log_n, bucket.quotient_degree)
            RES.omega_powers_p(bucket.log_n)
            LN._lde_scale_planes(
                bucket.log_n, bucket.lde_factor,
                int(_gl.MULTIPLICATIVE_GENERATOR),
            )
            LN._lde_scale_planes(
                bucket.log_n, bucket.quotient_degree,
                int(_gl.MULTIPLICATIVE_GENERATOR),
            )
            if bucket.lookups:
                RES.inv_xs_brev_p(bucket.log_n, bucket.lde_factor)
            from ..prover.fri import fold_challenge_tables_p, fold_schedule

            log_full = bucket.log_n + (bucket.lde_factor.bit_length() - 1)
            num_folds = sum(
                fold_schedule(
                    bucket.trace_len, bucket.fri_final_degree,
                    list(bucket.fri_schedule) or None,
                )
            )
            fold_challenge_tables_p(log_full, num_folds)
            return True
        from ..ntt.ntt import warm_domain_caches
        from ..prover.fri import fold_challenge_tables, fold_schedule
        from ..prover.prover import _inv_xs_brev

        warm_domain_caches(bucket.log_n, bucket.lde_factor)
        warm_domain_caches(bucket.log_n, bucket.quotient_degree)
        if bucket.lookups:
            _inv_xs_brev(bucket.log_n, bucket.lde_factor)
        log_full = bucket.log_n + (bucket.lde_factor.bit_length() - 1)
        num_folds = sum(
            fold_schedule(
                bucket.trace_len, bucket.fri_final_degree,
                list(bucket.fri_schedule) or None,
            )
        )
        fold_challenge_tables(log_full, num_folds)
        return True

    # ---- per-setup residency --------------------------------------------
    def pin(self, bucket_key: str, assembly, setup) -> bool:
        """Mark one (assembly, setup) pair resident for the request being
        served. Returns True on a HIT (this setup was already pinned —
        its device buffers survive from an earlier request); False on a
        MISS (newly pinned; the prove will upload into the residency).
        Accounting goes to service.cache.hits/misses."""
        key = (bucket_key, id(setup))
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                entry.hits += 1
                self._entries.move_to_end(key)
                self.hits += 1
                hit = True
            else:
                self._entries[key] = PinnedEntry(bucket_key, assembly, setup)
                self.misses += 1
                hit = False
        _metrics.count_service_cache("hit" if hit else "miss")
        return hit

    def after_request(self):
        """Re-measure resident bytes (uploads happen DURING the prove,
        so sizes are only known afterwards) and evict LRU entries above
        the byte cap. Called by the worker loop after each request."""
        evicted: list[PinnedEntry] = []
        with self._lock:
            total = 0
            for entry in self._entries.values():
                total += entry.measure()
            while total > self.capacity_bytes and len(self._entries) > 1:
                _key, entry = self._entries.popitem(last=False)
                total -= entry.bytes
                if entry.bytes > 0:
                    # a zero-byte entry holds no residency (e.g. its
                    # request failed before uploading) — dropping it is
                    # not an EVICTION, and counting one with a zero byte
                    # gauge would fail the report validator's
                    # evictions-imply-evicted-bytes consistency check
                    self.evictions += 1
                    self.evicted_bytes += entry.bytes
                evicted.append(entry)
            pinned = total
        for entry in evicted:
            # released OUTSIDE the lock: freeing device buffers can call
            # into the backend
            if entry.bytes > 0:
                _metrics.count_service_cache("evict", entry.bytes)
                _log(
                    f"service cache: evicted {entry.bucket_key} "
                    f"({entry.bytes / 2**20:.1f} MiB, {entry.hits} hits)"
                )
            entry.release()
        _metrics.gauge_service("cache.pinned_bytes", pinned)
        return pinned

    # ---- introspection ---------------------------------------------------
    def pinned_bytes(self) -> int:
        with self._lock:
            return sum(e.bytes for e in self._entries.values())

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "pinned_bytes": sum(
                    e.bytes for e in self._entries.values()
                ),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "evicted_bytes": self.evicted_bytes,
                "warmed_geometries": len(self._warmed_geometries),
            }
