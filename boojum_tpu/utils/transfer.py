"""Async host<->device transfers — the overlapped prove pipeline's seam.

A prover that blocks the host on every device->host pull and uploads the
whole witness in one synchronous `jnp.asarray` drains the device queue at
every transcript interaction. This module gives the prover three
overlap primitives, all bit-transparent (only WHEN bytes move changes,
never what is absorbed into the transcript):

- `HostFetch` / `start_fetch`: a BATCH of device->host pulls started with
  `copy_to_host_async` the moment the producing dispatches are enqueued;
  the host keeps dispatching (challenge-independent prep, transcript
  bookkeeping) and blocks ONCE for the whole batch at `wait()`. The
  in-flight window is charged to the current span as `overlap_s`, the
  blocked remainder as `sync_s`.
- `chunked_upload`: host->device upload of a column stack in bounded row
  chunks through `jax.device_put` (each enqueues asynchronously), joined
  by one on-device concatenate — the upload overlaps whatever host work
  follows (the setup-cap transcript round, in the prover).
- `to_host`: THE blocking single-array pull (multi-process global arrays
  gather first). `parallel.sharding.host_np` delegates here, so every
  blocking pull in the pipeline lands in the same metrics counters.

Every blocking wait counts into `host.blocking_syncs` (one per `to_host`,
one per `HostFetch` batch regardless of batch size); tests/test_overlap.py
pins the count of a 2^10 prove.

What the host was doing (ISSUE 37): every blocking wait is a span
`host.sync` with one child `d2h.<site>` (`sync`, opened inside `to_host`,
`HostFetch.wait` and at the prover's barriers), every upload site a span
`host.upload` with one child `h2d.<site>` (`upload`, which also feeds
`transfer.h2d_ops` / `transfer.h2d_bytes`). Both wrap calls that stay as
they are, keep numbers and never an array, and cost one check when
nothing records.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import time

import numpy as np

from . import metrics as _metrics
from . import spans as _spans

# bytes per host->device chunk of `chunked_upload` (a few chunks per
# bench-scale witness: enough to overlap, not enough to fragment)
H2D_CHUNK_BYTES = 32 << 20


def env_flag(name: str, default: bool) -> bool:
    """Shared boolean env-knob parser: 1/true/on/yes, 0/false/off/no,
    unset/empty -> `default`; anything else raises (a typo'd knob must
    never silently pick a mode)."""
    v = os.environ.get(name, "").strip().lower()
    if v in ("1", "true", "on", "yes"):
        return True
    if v in ("0", "false", "off", "no"):
        return False
    if v == "":
        return default
    raise ValueError(
        f"{name}={v!r}: use 1/true/on/yes or 0/false/off/no"
    )


def env_flag_opt(name: str) -> bool | None:
    """Tri-state form of `env_flag`: True/False for an explicit setting,
    None when the variable is unset/empty (callers supply a context-
    dependent default, e.g. pallas_util.resolve_variant's backend default).
    Same spelling set, same raise-on-junk contract."""
    if not os.environ.get(name, "").strip():
        return None
    return env_flag(name, False)


def _is_device_array(x) -> bool:
    import jax

    return isinstance(x, jax.Array)


def _needs_allgather(x) -> bool:
    import jax

    try:
        return (
            jax.process_count() > 1 and not x.is_fully_addressable
        )
    except Exception:
        return False


def _owning_processes(x) -> list[int]:
    """Sorted process indices owning any shard of a global array."""
    try:
        return sorted(
            {int(getattr(d, "process_index", 0)) for d in x.sharding.device_set}
        )
    except Exception:
        return []


def _addressable_nbytes(x) -> int:
    """Bytes of `x` already resident on THIS host's devices (shard
    metadata only — nothing is transferred)."""
    try:
        return int(
            sum(
                s.data.size * s.data.dtype.itemsize
                for s in x.addressable_shards
            )
        )
    except Exception:
        return 0


# the site a blocking pull is made for, where the pull itself sits in code
# that does not know it (a tree's constructor pulls its cap): the caller
# names it with `pull_site`, `to_host` files its wait under `d2h.<site>`
_PULL_SITE: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "boojum_tpu.pull_site", default=None
)


@contextlib.contextmanager
def pull_site(label: str):
    token = _PULL_SITE.set(label)
    try:
        yield
    finally:
        _PULL_SITE.reset(token)


@contextlib.contextmanager
def sync(label: str | None = None):
    """One blocking wait of the host for the device: span `host.sync` with
    the child `d2h.<label>` (the label given, else the `pull_site` open,
    else `unlabelled`). The span's time is the time the host waited."""
    if not _spans.recording():
        yield
        return
    with _spans.span("host.sync"):
        _metrics.sample_in_use()
        with _spans.span(
            "d2h." + (label or _PULL_SITE.get() or "unlabelled")
        ):
            yield


@contextlib.contextmanager
def upload(what: str, nbytes: int, ops: int = 1):
    """One upload site: span `host.upload` with the child `h2d.<what>`
    around the call(s) that hand host data to the device, counted as `ops`
    uploads of `nbytes` together (`transfer.h2d_ops`, `transfer.h2d_bytes`).
    Yields the `h2d` span's record, or None when no recorder is installed."""
    _metrics.count_bytes_h2d(nbytes, ops)
    if not _spans.recording():
        yield None
        return
    with _spans.span("host.upload"):
        _metrics.sample_in_use()
        with _spans.span(
            "h2d." + what, bytes=int(nbytes), ops=int(ops)
        ) as sp:
            yield sp


def uploaded(what: str, build):
    """`build()` as one upload site whose size is only known from what it
    returns (a device array or a (lo, hi) plane pair; prover._dev_cached)."""
    with upload(what, 0, 0) as sp:
        x = build()
        nbytes = _metrics.upload_nbytes(x)
        _metrics.count_bytes_h2d(nbytes)
        if sp is not None:
            sp["attrs"].update(bytes=nbytes, ops=1)
    return x


def to_host(x, label: str | None = None):
    """Blocking device->host pull; np.asarray that also works for
    MULTI-PROCESS global arrays (a sharded jax.Array spanning
    non-addressable devices cannot be fetched directly — gather it to
    every host first, billing the cross-host bytes to the `dcn.*`
    gauges). Plain numpy/host values pass straight through.

    When the cross-host gather itself fails, raise a clear error naming
    the owning processes and the addressable-shards escape hatch instead
    of falling through to np.asarray's opaque span-of-non-addressable-
    devices failure.

    This is the pipeline's unit of host blocking: one call = one
    `host.blocking_syncs` tick + d2h byte accounting (no-ops without a
    metrics registry)."""
    was_device = _is_device_array(x)
    if was_device and _needs_allgather(x):
        local_nbytes = _addressable_nbytes(x)
        try:
            from jax.experimental import multihost_utils

            with sync(label):
                out = np.asarray(
                    multihost_utils.process_allgather(x, tiled=True)
                )
        except Exception as e:
            import jax

            owners = _owning_processes(x)
            raise RuntimeError(
                f"to_host: array {getattr(x, 'shape', '?')} spans "
                "non-addressable devices (owned by processes "
                f"{owners or '?'}; this is process {jax.process_index()} "
                f"of {jax.process_count()}) and the cross-host gather "
                f"(multihost_utils.process_allgather) failed: {e!r}. "
                "Only this host's addressable shards can be fetched "
                "without a collective — use "
                "[np.asarray(s.data) for s in x.addressable_shards] for "
                "the per-host partial view."
            ) from e
        # every gathered byte NOT already resident on this host's shards
        # arrived over the cross-process (DCN) fabric
        _metrics.count_dcn_host_gather(max(out.nbytes - local_nbytes, 0))
        _metrics.count_bytes_d2h(out.nbytes)
        _metrics.count("host.blocking_syncs")
        return out
    if not was_device:
        return np.asarray(x)
    with sync(label):
        out = np.asarray(x)
    _metrics.count_bytes_d2h(out.nbytes)
    _metrics.count("host.blocking_syncs")
    return out


def prefetch_async(x):
    """Start an async device->host copy of `x` (no wait, no accounting):
    by the time a later blocking pull touches it, the bytes are already
    in flight — or landed. Safe no-op for host values and backends
    without async copies."""
    try:
        if _is_device_array(x) and not _needs_allgather(x):
            x.copy_to_host_async()
    except Exception:
        pass


class HostFetch:
    """A batch of device->host pulls in flight.

    Construction starts every transfer (`copy_to_host_async`) without
    blocking; `wait()` resolves them all with ONE blocking sync, counts
    the batch's d2h bytes, and charges the current span: the window the
    batch was in flight while the host kept working is `overlap_s`, the
    blocked tail inside wait() is `sync_s`."""

    def __init__(self, arrays, label: str | None = None):
        self.arrays = list(arrays)
        self.label = label
        self._out: list | None = None
        self._t_start = time.perf_counter()
        for a in self.arrays:
            prefetch_async(a)

    def wait(self) -> list:
        if self._out is not None:
            return self._out
        t_wait = time.perf_counter()
        out = []
        nbytes = 0
        any_device = False
        waits = any(_is_device_array(a) for a in self.arrays)
        with sync(self.label) if waits else contextlib.nullcontext():
            for a in self.arrays:
                if _is_device_array(a):
                    if _needs_allgather(a):
                        # counts its own sync
                        out.append(to_host(a, self.label))
                        continue
                    any_device = True
                    h = np.asarray(a)
                    nbytes += h.nbytes
                    out.append(h)
                else:
                    out.append(np.asarray(a))
        if any_device:
            _metrics.count_bytes_d2h(nbytes)
            _metrics.count("host.blocking_syncs")
            _metrics.count("transfer.d2h_batches")
        now = time.perf_counter()
        overlap_s = t_wait - self._t_start
        sync_s = now - t_wait
        _metrics.gauge_add("transfer.overlap_s", overlap_s)
        _metrics.gauge_add("transfer.sync_s", sync_s)
        rec = _spans.current_recorder()
        if rec is not None:
            rec.add_sync(sync_s)
            rec.add_overlap(overlap_s)
        self._out = out
        return out


def start_fetch(arrays, label: str | None = None) -> HostFetch:
    """Begin a device->host batch; `.wait() -> list[np.ndarray]`."""
    return HostFetch(arrays, label=label)


def fetch_np(*arrays, label: str | None = None) -> list:
    """Pull several device arrays as one batch (one blocking sync)."""
    return start_fetch(arrays, label=label).wait()


def upload_chunk_shapes(row_counts, n: int) -> list[int]:
    """The per-chunk row counts `chunked_upload` dispatches for a stack of
    (rows_i, n) host arrays — shared with prover/precompile.py so the
    on-device concatenate's shape key is enumerated ahead of dispatch."""
    per = max(1, H2D_CHUNK_BYTES // max(n * 8, 1))
    shapes = []
    for rows in row_counts:
        for i in range(0, int(rows), per):
            shapes.append(min(per, int(rows) - i))
    return shapes


def _concat_rows(*parts):
    import jax.numpy as jnp

    return jnp.concatenate(parts, axis=0)


_CONCAT_JIT = None


def _concat_jit():
    global _CONCAT_JIT
    if _CONCAT_JIT is None:
        import jax

        _CONCAT_JIT = jax.jit(_concat_rows)
    return _CONCAT_JIT


def chunked_upload(host_arrays, planes: bool = False):
    """Upload a list of (rows_i, n) host arrays as one (sum_rows, n)
    device stack.

    Each bounded row chunk goes up through its own `jax.device_put`
    (async enqueue — the host returns to transcript work while the DMA
    runs) and ONE jitted on-device concatenate joins them; bit-identical
    to uploading the host-side concatenation.

    With `planes` (the limb-resident prove, ISSUE 10) each chunk splits
    ONCE on host (`limbs.split_np` — the H2D edge of the residency
    contract) and uploads as two u32 planes; returns the (lo, hi) device
    pair. Same chunk walk, same total bytes."""
    import jax

    host_arrays = [np.asarray(a) for a in host_arrays]
    if planes:
        from ..field import limbs

        split_arrays = [limbs.split_np(a) for a in host_arrays]
        n = host_arrays[0].shape[-1]
        per = max(1, H2D_CHUNK_BYTES // max(n * 8, 1))
        parts_lo, parts_hi = [], []
        for lo, hi in split_arrays:
            for i in range(0, lo.shape[0], per):
                parts_lo.append(jax.device_put(lo[i : i + per]))
                parts_hi.append(jax.device_put(hi[i : i + per]))
        _metrics.count("transfer.h2d_chunks", 2 * len(parts_lo))
        if len(parts_lo) == 1:
            return parts_lo[0], parts_hi[0]
        return _concat_jit()(*parts_lo), _concat_jit()(*parts_hi)
    n = host_arrays[0].shape[-1]
    per = max(1, H2D_CHUNK_BYTES // max(n * 8, 1))
    parts = []
    for arr in host_arrays:
        for i in range(0, arr.shape[0], per):
            parts.append(jax.device_put(arr[i : i + per]))
    _metrics.count("transfer.h2d_chunks", len(parts))
    if len(parts) == 1:
        return parts[0]
    return _concat_jit()(*parts)
