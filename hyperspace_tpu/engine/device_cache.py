"""Host→device transfer cache.

A query executes many device ops over the same cached host columns; re-uploading
them per query would dominate on a real TPU (host→HBM transfers over PCIe). Device
arrays are cached per host-array identity (weakref-keyed, so entries die with their
host arrays — which are themselves owned by the scan cache).

Encoded staging (`engine/encoded_device.py`) uploads NARROW code lanes through
the same memo: callers pass `site`/`flat_bytes`/`charged_bytes` so the entry is
charged its TRUE encoded footprint (codes + dictionary + validity — the PR-8
ScanCache accounting, applied to device memory), warm hits tick
`cache.device_upload.encoded_hits`, and the miss records the flat-vs-staged
byte split in the encoded-staging ledger."""

from __future__ import annotations

import os
import threading
import weakref

import jax.numpy as jnp
import numpy as np

from ..telemetry import accounting as _accounting
from ..telemetry import device_observatory as _devobs
from ..telemetry import metrics as _metrics
from ..telemetry import stage_ledger as _stage_ledger

# Bound once: device_array is the hottest instrumented path (every device op
# over cached host columns) — per-call cost is one locked int add.
_HITS = _metrics.counter("cache.device_upload.hits")
_MISSES = _metrics.counter("cache.device_upload.misses")
# Warm hits served from CODE-SPACE entries: how much of the steady state rides
# encoded staging (the device mirror of `cache.scan.encoded_hits`).
_ENCODED_HITS = _metrics.counter("cache.device_upload.encoded_hits")
# Footprint watermarks (exporter frames / prometheus): live bytes pinned by
# the upload memo, and the high-water mark across the process lifetime.
_CACHE_BYTES = _metrics.gauge("cache.device_upload.bytes")
_CACHE_BYTES_PEAK = _metrics.gauge("cache.device_upload.bytes_peak")

# id(host) -> (weakref, device_array, charged_bytes, encoded); insertion
# order = LRU. `charged_bytes` is what the budget accounting carries for the
# entry — the device array's own bytes for flat stages, the TRUE encoded
# footprint for code-space stages.
_cache: dict = {}
# Device copies are pinned until their host arrays die (the scan cache bounds
# hosts at 4 GiB); this byte budget additionally bounds DEVICE memory so the
# memo can never approach HBM capacity on its own.
_BUDGET = int(os.environ.get("HYPERSPACE_UPLOAD_CACHE_BUDGET", 4 << 30))
_bytes = 0
# Concurrent queries (thread-local active sessions) interleave on this memo;
# RLock because weakref eviction callbacks can fire inside guarded sections.
_lock = threading.RLock()


def _note_bytes() -> None:
    """Publish the live footprint + high-water mark (called with `_lock`
    held, after any `_bytes` mutation)."""
    _CACHE_BYTES.set(_bytes)
    _CACHE_BYTES_PEAK.set_max(_bytes)


def _evict_over_budget(protect_key) -> None:
    global _bytes
    while _bytes > _BUDGET:
        victim = next((k for k in _cache if k != protect_key), None)
        if victim is None:
            return
        dropped = _cache.pop(victim, None)
        if dropped is not None:
            _bytes -= int(dropped[2])
            _note_bytes()


def device_array(
    host: np.ndarray, *, site=None, flat_bytes=None, charged_bytes=None, packed=False
):
    """jnp view of a host numpy array, cached by identity.

    `flat_bytes`/`charged_bytes`/`site` mark an ENCODED stage (narrow code
    lane): the entry is charged `charged_bytes` against the byte budget, the
    upload miss records `flat_bytes` vs the actual narrow bytes in the
    encoded-staging ledger, and warm hits tick the encoded-hit counter.
    `packed=True` additionally marks the stage as a BIT-PACKED lane
    (`engine/packed_codes.py`): the upload's true word bytes land in the
    `packed` tier of the encoded-staging ledger."""
    global _bytes
    if not isinstance(host, np.ndarray):
        return jnp.asarray(host)
    encoded = flat_bytes is not None
    key = id(host)
    with _lock:
        hit = _cache.get(key)
        if hit is not None and hit[0]() is host:
            _cache[key] = _cache.pop(key)  # LRU refresh
            _HITS.inc()
            if hit[3]:
                _ENCODED_HITS.inc()
            return hit[1]

    _MISSES.inc()
    # Upload-miss = a real host→device transfer this query caused. Timing it
    # requires forcing the (async) transfer to completion, so seconds only
    # arrive under HYPERSPACE_DEVICE_TIMING — bytes and count always. The
    # whole miss region is the ``h2d`` stage for attribution: upload bytes
    # bill to a dedicated lane even when the miss fires inside another
    # stage's bracket (innermost label wins).
    with _stage_ledger.stage_scope("h2d"):
        if _devobs.timing_mode():
            import time as _time

            t0 = _time.monotonic()
            dev = jnp.asarray(host)
            dev.block_until_ready()
            upload_s = _time.monotonic() - t0
        else:
            dev = jnp.asarray(host)
            upload_s = None
        _accounting.add("device_upload_bytes", int(dev.nbytes))
        _devobs.record_h2d(int(dev.nbytes), upload_s)
        if encoded:
            _devobs.record_encoded_stage(
                site or "?",
                int(flat_bytes),
                int(dev.nbytes),
                packed_bytes=int(dev.nbytes) if packed else None,
            )
    charged = int(charged_bytes) if charged_bytes is not None else int(dev.nbytes)

    def _evict(wr, key=key):
        # Only drop the entry this weakref installed: a dead array's id can be
        # reused by a new array before the deferred callback runs.
        global _bytes
        with _lock:
            ent_now = _cache.get(key)
            if ent_now is not None and ent_now[0] is wr:
                _cache.pop(key, None)
                _bytes -= int(ent_now[2])
                _note_bytes()

    try:
        ref = weakref.ref(host, _evict)
    except TypeError:
        return dev  # non-weakref-able subclass: skip caching
    with _lock:
        hit = _cache.get(key)  # re-read: another thread may have inserted
        if hit is not None:
            if hit[0]() is host:
                return hit[1]  # raced: reuse the first upload, drop ours
            _bytes -= int(hit[2])  # displaced stale entry leaves accounting
        _cache[key] = (ref, dev, charged, encoded)
        _bytes += charged
        _note_bytes()
        _evict_over_budget(key)
    return dev
