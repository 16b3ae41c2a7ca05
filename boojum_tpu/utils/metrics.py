"""Prover metrics registry — the flight recorder's counter/gauge axis.

Counters (host↔device transfer bytes, NTT/Merkle/FRI invocation counts)
and gauges (device-memory high water, live-buffer census) accumulated
alongside the span tree. The module-level helpers (`count`, `gauge_max`,
`stage_boundary`) are no-op-cheap when no registry is installed — one
contextvar read, one global read and a None check — so the prover keeps
them threaded through its hot path permanently. Like the span recorder
(utils/spans.py), the active registry resolves contextvar-first: a
scoped registry (one packed service request) overrides the
process-global default within its execution context only.

Memory sources, best-effort by design:
- `device.memory_stats()` (bytes_in_use / peak_bytes_in_use) where the
  backend exposes it (TPU does; XLA:CPU usually returns None) — guarded,
  absent keys are simply omitted from the report.
- `jax.live_arrays()` census (count + total bytes) — works on every
  backend; it lands in per-stage `boundaries` entries so HBM growth is
  attributable to a stage.
- `mem.in_use_mib.<stage>` (ISSUE 37): the allocator's `bytes_in_use` read
  at the open of every `host.sync` and `host.upload` span
  (utils/transfer.py), folded as a MAXIMUM into one counter a stage.
  Allocation happens at dispatch, so at a sync's open everything the host
  has queued is counted. A stage can reach more between two samples than
  at either (round 3 queues a coset's evaluations and its sweep between
  two uploads), so where the allocator's own `peak_bytes_in_use` rose
  while a stage was open, the stage's close folds that peak in too: the
  stage whose counter is highest is the one that set the process's peak.
  Under a registry only.
"""

from __future__ import annotations

import contextvars
import threading
import time

IN_USE_PREFIX = "mem.in_use_mib."


class MetricsRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self.counters: dict[str, int] = {}
        self.gauges: dict[str, float] = {}
        self.boundaries: list[dict] = []
        # the stage span open now (set by `boundary`, cleared by
        # `stage_closed`): what `sample_in_use` files its reading under,
        # and the allocator's peak as the stage found it
        self.stage: str | None = None
        self.stage_peak: int | None = None

    def count(self, name: str, n: int = 1):
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + int(n)

    def count_max(self, name: str, v: int):
        """A counter that keeps the largest value it was handed."""
        with self._lock:
            if v > self.counters.get(name, 0):
                self.counters[name] = int(v)

    def gauge_set(self, name: str, v: float):
        with self._lock:
            self.gauges[name] = v

    def gauge_max(self, name: str, v: float):
        with self._lock:
            if v > self.gauges.get(name, float("-inf")):
                self.gauges[name] = v

    def gauge_add(self, name: str, v: float):
        with self._lock:
            self.gauges[name] = self.gauges.get(name, 0.0) + float(v)

    def boundary(self, label: str):
        """Record a stage-boundary snapshot: live-buffer census plus (when
        the backend exposes it) device memory stats; also folds the peak
        readings into gauges so the report's summary carries high-water
        marks without walking the boundary list."""
        self.stage = label
        self.stage_peak = None
        entry: dict = {
            "label": label,
            "t_s": round(time.perf_counter() - self._t0, 4),
        }
        census = live_buffer_census()
        if census is not None:
            entry["live_arrays"], entry["live_bytes"] = census
            self.gauge_max("mem.live_bytes_high_water", census[1])
        dm = device_memory_stats()
        if dm:
            entry["device_memory"] = dm
            peak = dm.get("peak_bytes_in_use")
            if peak is not None:
                self.stage_peak = peak
                self.gauge_max("mem.device_peak_bytes_in_use", peak)
            in_use = dm.get("bytes_in_use")
            if in_use is not None:
                self.gauge_max("mem.device_bytes_in_use_high_water", in_use)
        with self._lock:
            self.boundaries.append(entry)

    def fold(self, other: "MetricsRegistry"):
        """Accumulate another registry's snapshot into this one:
        counters ADD (events keep counting across requests), gauges
        LAST-WRITE (a gauge is a current-value reading, Prometheus
        semantics). The proving service folds each request's scoped
        registry into its service-lifetime one so /metrics shows the
        prove counter families after the per-request recorder is
        torn down."""
        snap = other.to_dict()
        with self._lock:
            for k, v in (snap.get("counters") or {}).items():
                if k.startswith(IN_USE_PREFIX):
                    self.counters[k] = max(self.counters.get(k, 0), int(v))
                else:
                    self.counters[k] = self.counters.get(k, 0) + int(v)
            self.gauges.update(snap.get("gauges") or {})

    def to_dict(self) -> dict:
        with self._lock:
            return {
                "counters": dict(sorted(self.counters.items())),
                "gauges": {
                    k: round(float(v), 4)
                    for k, v in sorted(self.gauges.items())
                },
                "boundaries": list(self.boundaries),
            }


# process-global DEFAULT context (bench/CLI posture); scoped registries
# (install_scoped_registry) override it per execution context so packed
# concurrent requests accumulate into disjoint registries
_REGISTRY: MetricsRegistry | None = None
_REGISTRY_CTX: contextvars.ContextVar[MetricsRegistry | None] = (
    contextvars.ContextVar("boojum_tpu.metrics_registry", default=None)
)


def current_registry() -> MetricsRegistry | None:
    """The ACTIVE registry: context-scoped when one is bound, else the
    process-global default."""
    reg = _REGISTRY_CTX.get()
    return reg if reg is not None else _REGISTRY


def install_registry(reg: MetricsRegistry | None) -> MetricsRegistry | None:
    """Swap the process-wide DEFAULT registry; returns the previous one."""
    global _REGISTRY
    prev = _REGISTRY
    _REGISTRY = reg
    return prev


def install_scoped_registry(reg: MetricsRegistry | None):
    """Bind `reg` to the CURRENT execution context only; returns a token
    for reset_scoped_registry."""
    return _REGISTRY_CTX.set(reg)


def reset_scoped_registry(token):
    _REGISTRY_CTX.reset(token)


def start_metrics() -> MetricsRegistry:
    reg = MetricsRegistry()
    install_registry(reg)
    return reg


def stop_metrics() -> MetricsRegistry | None:
    return install_registry(None)


# -- no-op-cheap module-level recording hooks --------------------------------


def count(name: str, n: int = 1):
    reg = current_registry()
    if reg is not None:
        reg.count(name, n)


def gauge_max(name: str, v: float):
    reg = current_registry()
    if reg is not None:
        reg.gauge_max(name, v)


def gauge_add(name: str, v: float):
    reg = current_registry()
    if reg is not None:
        reg.gauge_add(name, v)


def count_upload(x):
    """Tally a fresh host->device upload of a device array `x` (the
    prover's explicit upload seams — prover._dev_cached, the sequenced
    stage-2 table uploads); passes `x` through. A (lo, hi) limb plane
    pair (the resident prove's upload unit) counts both planes."""
    if current_registry() is not None:
        count_bytes_h2d(upload_nbytes(x))
    return x


def upload_nbytes(x) -> int:
    """Bytes of an uploaded device array or (lo, hi) plane pair, from its
    shape and dtype (0 for what has neither)."""
    try:
        if isinstance(x, tuple):
            return sum(int(a.size) * a.dtype.itemsize for a in x)
        return int(x.size) * x.dtype.itemsize
    except Exception:
        return 0


def count_bytes_h2d(nbytes: int, ops: int = 1):
    """Host->device upload accounting (counted at the prover's explicit
    upload seams; transfers inside compiled graphs are invisible here):
    `ops` uploads of `nbytes` together. Numbers only: the site says what
    it hands over (utils/transfer.upload), no array passes through."""
    reg = current_registry()
    if reg is not None:
        reg.count("transfer.h2d_bytes", nbytes)
        reg.count("transfer.h2d_ops", ops)


def count_bytes_d2h(nbytes: int):
    reg = current_registry()
    if reg is not None:
        reg.count("transfer.d2h_bytes", nbytes)
        reg.count("transfer.d2h_ops")


def count_ici_all_to_all(crossing_bytes: float, dcn_bytes: float = 0.0):
    """Tally one explicit all-to-all layout pivot on the shard_map mesh
    (parallel/shard_sweep.py). `crossing_bytes` is the intra-host (ICI)
    portion of the global payload that actually crosses the interconnect;
    `dcn_bytes` is the cross-process (DCN) portion on a multi-host mesh —
    the caller owns the (D-1)/D topology math and the DCN split
    (parallel/multihost.dcn_fraction), this seam owns the gauge names:
    `ici.all_to_alls` / `ici.all_to_all_bytes` (and `ici.pivot_s` for the
    dispatch window, charged by shard_sweep's pivot timer), plus
    `dcn.all_to_alls` / `dcn.all_to_all_bytes` whenever the collective
    crossed a process boundary."""
    reg = current_registry()
    if reg is not None:
        reg.count("ici.all_to_alls")
        reg.gauge_add("ici.all_to_all_bytes", crossing_bytes)
        if dcn_bytes > 0:
            reg.count("dcn.all_to_alls")
            reg.gauge_add("dcn.all_to_all_bytes", dcn_bytes)


def count_ici_all_gather(crossing_bytes: float, dcn_bytes: float = 0.0):
    """Tally one explicit all-gather to replicated (caps, small node
    layers): `ici.all_gathers` / `ici.all_gather_bytes`, with the
    cross-process portion split out as `dcn.all_gathers` /
    `dcn.all_gather_bytes` (same contract as count_ici_all_to_all)."""
    reg = current_registry()
    if reg is not None:
        reg.count("ici.all_gathers")
        reg.gauge_add("ici.all_gather_bytes", crossing_bytes)
        if dcn_bytes > 0:
            reg.count("dcn.all_gathers")
            reg.gauge_add("dcn.all_gather_bytes", dcn_bytes)


def count_dcn_host_gather(dcn_bytes: float):
    """Tally one host-side gather of a non-fully-addressable global array
    (multihost_utils.process_allgather in transfer.to_host / the
    addressable-safe demesh): `dcn.host_gathers` / `dcn.host_gather_bytes`
    bill the bytes this process pulled from OTHER hosts over DCN."""
    reg = current_registry()
    if reg is not None:
        reg.count("dcn.host_gathers")
        reg.gauge_add("dcn.host_gather_bytes", dcn_bytes)


def count_service_cache(event: str, nbytes: int = 0):
    """Tally one device-resident cache-manager event (service/cache.py).
    `event` is "hit" | "miss" | "evict"; the seam owns the `service.*`
    gauge names so the cache manager, the report validator and the SLO
    summary can never disagree on them:
      service.cache.hits / .misses / .evictions   (counters)
      service.cache.evicted_bytes                 (gauge, evictions only)
    """
    reg = current_registry()
    if reg is None:
        return
    if event == "hit":
        reg.count("service.cache.hits")
    elif event == "miss":
        reg.count("service.cache.misses")
    elif event == "evict":
        reg.count("service.cache.evictions")
        reg.gauge_add("service.cache.evicted_bytes", float(nbytes))


def count_aot(event: str):
    """Tally one AOT artifact-store event (prover/aot.py). The seam owns
    the `aot.*` counter names so the artifact loader, the report
    validator and the SLO summary can never disagree on them:
      aot.hits / aot.misses            (warm pass, per kernel)
      aot.builds / aot.bundles_loaded  (per bundle)
      aot.bundle_misses / aot.stale_bundles / aot.corrupt_bundles
      aot.corrupt_entries
    """
    reg = current_registry()
    if reg is not None:
        reg.count(f"aot.{event}")


def gauge_aot_add(name: str, v: float):
    """Accumulate an `aot.<name>` gauge (deserialize_s, load_s,
    bundle_bytes — the artifact store's wall/size axis; the report
    validator requires deserialize_s whenever aot hits/misses were
    counted)."""
    reg = current_registry()
    if reg is not None:
        reg.gauge_add(f"aot.{name}", float(v))


def gauge_set_cost(name: str, v: float):
    """Set a `cost.<name>` gauge (the roofline record's per-stage
    achieved GFLOP/s, GB/s and efficiency fractions — utils/costmodel.py
    exports them here so /metrics and the report line's gauges carry the
    same numbers the `cost` record does)."""
    reg = current_registry()
    if reg is not None:
        reg.gauge_set(f"cost.{name}", float(v))


def gauge_service(name: str, v: float):
    """Set a `service.<name>` gauge (queue depth, pinned bytes, occupancy
    — the proving service's per-request SLO axis)."""
    reg = current_registry()
    if reg is not None:
        reg.gauge_set(f"service.{name}", float(v))


def stage_boundary(label: str):
    reg = current_registry()
    if reg is not None:
        reg.boundary(label)


def _mib_up(nbytes: int) -> int:
    return -(-int(nbytes) // (1 << 20))


def stage_closed():
    """The open stage ends. Where the allocator's peak rose while it was
    open, the stage reached that much, whatever its samples saw: folded
    into its `mem.in_use_mib.<stage>`."""
    reg = current_registry()
    if reg is None or reg.stage is None:
        return
    stage, reg.stage = reg.stage, None
    if reg.stage_peak is None:
        return
    peak = (device_memory_stats() or {}).get("peak_bytes_in_use")
    if peak is not None and peak > reg.stage_peak:
        reg.count_max(IN_USE_PREFIX + stage, _mib_up(peak))


def sample_in_use():
    """Fold the allocator's `bytes_in_use` now (MiB, rounded up) into the
    open stage's `mem.in_use_mib.<stage>` as a maximum. Without a
    registry, outside a stage, or where the backend reports no memory
    (XLA:CPU): nothing."""
    reg = current_registry()
    if reg is None or reg.stage is None:
        return
    room = device_memory_room()
    if room is not None:
        reg.count_max(IN_USE_PREFIX + reg.stage, _mib_up(room[1]))


# -- memory probes -----------------------------------------------------------


def live_buffer_census() -> tuple[int, int] | None:
    """(num_live_arrays, total_bytes) over jax.live_arrays(), or None when
    jax is unavailable."""
    try:
        import jax

        live = jax.live_arrays()
        return len(live), int(
            sum(a.size * a.dtype.itemsize for a in live)
        )
    except Exception:
        return None


def device_memory_room() -> tuple[int, int] | None:
    """(bytes_limit, bytes_in_use) of the first local device, as its
    allocator reports them now (queued work included); None where the
    backend reports no limit (XLA:CPU). What the prover's own memory
    choices read: the streamed-commit threshold and the round-3 barrier."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        return None
    limit = int(stats.get("bytes_limit", 0) or 0)
    if limit <= 0:
        return None
    return limit, int(stats.get("bytes_in_use", 0) or 0)


def device_memory_stats() -> dict | None:
    """Aggregated device.memory_stats() over local devices: sums
    bytes_in_use, maxes peak_bytes_in_use. None/{} when the backend does
    not expose stats (XLA:CPU)."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return None
    in_use = 0
    peak = 0
    seen = False
    kinds = set()
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        seen = True
        kinds.add(getattr(d, "device_kind", str(d.platform)))
        in_use += int(stats.get("bytes_in_use", 0))
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    if not seen:
        return None
    out = {"bytes_in_use": in_use, "device_kinds": sorted(kinds)}
    if peak:
        out["peak_bytes_in_use"] = peak
    return out
