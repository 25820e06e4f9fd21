"""Append-only per-partition scan/stats cache: incremental retraining's
answer to "don't re-read 30 days of data to learn about one new hour".

The driver's preprocess stage scans EVERY partition file on EVERY run —
vocabulary, row count, max per-row nnz, and (fused) the colStats
moments. For an hourly retrain over appended data that cost is O(total
history) when the new information is O(one partition). This module
applies the content-addressed schedule-cache pattern
(`ops/schedule_cache.py`) to the DATA artifacts instead: one cache
entry per partition file, keyed by a spot digest of the file's bytes,
holding exactly the per-partition reductions the scan needs. A cached
scan then touches only partitions without a valid entry — which for an
append-only directory is precisely the new ones. The ``scanned`` /
``cached`` counters (and the ``registry.stats_cache`` fault seam) make
"touches only new partitions" a COUNTED claim the tier-1 tests assert,
not a hope.

Exactness: the per-partition reductions are integers (rows, max live
nnz), a key SET, and float64 moment partials.

- ``index_map``/``StreamStats`` from a cached scan are EXACTLY the
  uncached ones: key sets union losslessly and ``IndexMap.build`` sorts
  (order-independent by construction); rows add; max-nnz maxes.
- The summary path merges per-partition float64 moment partials in
  sorted-file order. Against the fused single-pass scan this regroups
  the additions (per-file subtotals first), so moments can differ by
  f64 rounding — the same class of noise the multi-host all-reduce
  already accepts. The bitwise-pinned retrain invariants (no-drift
  alignment, publish parity) never flow through the summary.

Corruption protocol: an entry that fails to decode (or an injected
CORRUPT at the seam) is quarantined to ``*.corrupt`` via the
reliability layer — accounted, never silently trusted — and the
partition is rescanned.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from hashlib import blake2b
from typing import Dict, List, Optional, Tuple

import numpy as np

from photon_ml_tpu.reliability.faults import InjectedCorruption
from photon_ml_tpu.reliability.retry import (
    SeamFailure,
    io_call,
    quarantine_artifact,
)

__all__ = [
    "STATS_CACHE_SEAM",
    "STATS_CACHE_VERSION",
    "ScanCacheStats",
    "PartitionStatsCache",
    "cached_scan_stream",
    "cached_scan_stream_with_summary",
]

STATS_CACHE_SEAM = "registry.stats_cache"

# Bump when the entry layout or the per-partition reduction semantics
# change: versioned keys simply miss and rescan.
STATS_CACHE_VERSION = 1

_SPOT_BYTES = 64 * 1024


def _partition_key(path: str) -> str:
    """Content key of one partition file: size + first/last 64 KiB.
    Append-only directories never rewrite a partition in place, so a
    same-key file is the same partition; a rewritten file (size or edge
    bytes changed) misses and rescans."""
    st = os.stat(path)
    h = blake2b(digest_size=16)
    h.update(str(STATS_CACHE_VERSION).encode())
    h.update(b"\0")
    h.update(str(st.st_size).encode())
    h.update(b"\0")
    with open(path, "rb") as f:
        h.update(f.read(_SPOT_BYTES))
        if st.st_size > _SPOT_BYTES:
            f.seek(max(st.st_size - _SPOT_BYTES, 0))
            h.update(f.read(_SPOT_BYTES))
    return h.hexdigest()


@dataclass
class ScanCacheStats:
    """Per-call accounting: the "only new partitions" counters."""

    partitions: int = 0
    scanned: int = 0       # partitions actually re-read
    cached: int = 0        # partitions served from the cache
    stored: int = 0
    quarantined: int = 0

    def as_dict(self) -> Dict[str, int]:
        return dict(self.__dict__)


@dataclass
class _PartitionEntry:
    rows: int
    max_live: int
    keys: List[str]
    # per-key float64 moment partials (s1, s2, l1, nnz, mx, mn) + the
    # partition's positive-weight row count, for the fused-summary path.
    # has_moments distinguishes "partials not collected" (a scan-only
    # entry — the summary path must rescan) from "collected, all zero".
    has_moments: bool = False
    real_rows: float = 0.0
    moments: Dict[str, Tuple[float, float, float, float, float, float]] = (
        field(default_factory=dict)
    )


class PartitionStatsCache:
    """One directory of ``<key>.json`` entries (append-only)."""

    def __init__(self, cache_dir: str):
        self.cache_dir = os.path.abspath(cache_dir)
        self.stats = ScanCacheStats()

    def _entry_path(self, key: str) -> str:
        return os.path.join(
            self.cache_dir, f"v{STATS_CACHE_VERSION}", f"{key}.json"
        )

    def load(self, path: str, key: str) -> Optional[_PartitionEntry]:
        entry_path = self._entry_path(key)
        if not os.path.isfile(entry_path):
            return None

        def _load():
            with open(entry_path) as f:
                data = json.load(f)
            if data.get("version") != STATS_CACHE_VERSION or (
                data.get("key") != key
            ):
                raise ValueError(
                    f"stats-cache entry {entry_path} version/key mismatch"
                )
            return _PartitionEntry(
                rows=int(data["rows"]),
                max_live=int(data["max_live"]),
                keys=list(data["keys"]),
                has_moments=bool(data.get("has_moments", False)),
                real_rows=float(data.get("real_rows", 0.0)),
                moments={
                    k: tuple(v) for k, v in data.get("moments", {}).items()
                },
            )

        try:
            return io_call(STATS_CACHE_SEAM, _load, detail=entry_path)
        except (InjectedCorruption, ValueError, KeyError, TypeError):
            # poisoned entry: quarantine (accounted) and rescan the
            # partition — corrupt cache state must cost one re-read,
            # never a wrong model
            quarantine_artifact(entry_path, STATS_CACHE_SEAM)
            self.stats.quarantined += 1
            return None
        except SeamFailure:
            # the cache is an accelerator, not a dependency: an
            # exhausted read budget falls back to the rescan
            return None

    def store(self, path: str, key: str, entry: _PartitionEntry) -> None:
        from photon_ml_tpu.reliability.artifacts import atomic_write_json

        entry_path = self._entry_path(key)
        payload = {
            "version": STATS_CACHE_VERSION,
            "key": key,
            "source": os.path.basename(path),
            "rows": entry.rows,
            "max_live": entry.max_live,
            "keys": entry.keys,
            "has_moments": entry.has_moments,
            "real_rows": entry.real_rows,
            "moments": {k: list(v) for k, v in entry.moments.items()},
        }

        def _store():
            os.makedirs(os.path.dirname(entry_path), exist_ok=True)
            atomic_write_json(entry_path, payload)

        try:
            io_call(STATS_CACHE_SEAM, _store, detail=entry_path)
            self.stats.stored += 1
        except SeamFailure:
            return  # store failures cost the next run a rescan, nothing else


def _scan_partition(
    fmt, path: str, *, with_moments: bool
) -> _PartitionEntry:
    """One partition's reductions via the format's own scan hooks —
    exactly ``stream_scan``'s per-file semantics (selected keys, zero
    values kept in widths, intercept excluded here and re-added by the
    caller), plus the fused-summary moment accumulation when asked."""
    index_map, stats = fmt.stream_scan([path])
    from photon_ml_tpu.utils.index_map import intercept_key

    keys = sorted(k for k, _ in index_map.items() if k != intercept_key())
    max_live = stats.max_nnz - (1 if fmt.add_intercept else 0)
    entry = _PartitionEntry(
        rows=stats.num_rows, max_live=max_live, keys=keys
    )
    if with_moments:
        entry.real_rows, entry.moments = _moment_partials(fmt, path)
        entry.has_moments = True
    return entry


def _moment_partials(fmt, path: str):
    """Raw float64 per-key partials of one partition: the fused scan's
    in-loop accumulation, stopped before finalize."""
    real_rows = 0.0
    s: Dict[str, List[float]] = {}

    def slot(key):
        m = s.get(key)
        if m is None:
            m = [0.0, 0.0, 0.0, 0.0, -np.inf, np.inf]
            s[key] = m
        return m

    from photon_ml_tpu.io.avro_codec import read_avro_records

    decoded = getattr(fmt, "decode_file", lambda p: None)(path)
    if decoded is not None:
        m_rec = decoded.num_records
        sel = np.asarray([
            fmt.selected is None or x in fmt.selected
            for x in decoded.strings
        ]) if len(decoded.strings) else np.zeros(0, bool)
        wgt = (
            decoded.f64("weight")
            if "weight" in decoded.plan.num_slots
            else np.ones(m_rec)
        )
        wgt = np.where(np.isnan(wgt), 1.0, wgt)
        real = wgt > 0
        real_rows = float(real.sum())
        row_ptr, key_ids, values = decoded.bag("features")
        if len(key_ids):
            widths = np.diff(row_ptr)
            row_of = np.repeat(np.arange(m_rec, dtype=np.int64), widths)
            keep = sel[key_ids] & real[row_of] & (values != 0)
            for kid, v in zip(key_ids[keep], values[keep]):
                m = slot(decoded.strings[int(kid)])
                v = float(v)
                m[0] += v
                m[1] += v * v
                m[2] += abs(v)
                m[3] += 1.0
                m[4] = max(m[4], v)
                m[5] = min(m[5], v)
    else:
        for record in read_avro_records([path]):
            wgt_v = record.get("weight")
            w = 1.0 if wgt_v is None else float(wgt_v)
            real = w > 0
            real_rows += 1.0 if real else 0.0
            for key, value in fmt._record_pairs(record):
                if real and value != 0:
                    m = slot(key)
                    m[0] += value
                    m[1] += value * value
                    m[2] += abs(value)
                    m[3] += 1.0
                    m[4] = max(m[4], value)
                    m[5] = min(m[5], value)
    return real_rows, {k: tuple(v) for k, v in s.items()}


def _gather_entries(
    paths, fmt, cache: PartitionStatsCache, *, with_moments: bool
) -> List[Tuple[str, _PartitionEntry]]:
    files = fmt.stream_files(paths)
    out = []
    cache.stats = ScanCacheStats()
    for path in files:
        cache.stats.partitions += 1
        key = _partition_key(path)
        entry = cache.load(path, key)
        if entry is not None and (not with_moments or entry.has_moments):
            cache.stats.cached += 1
        else:
            cache.stats.scanned += 1
            entry = _scan_partition(fmt, path, with_moments=with_moments)
            cache.store(path, key, entry)
        out.append((path, entry))
    return out


def cached_scan_stream(paths, fmt, cache_dir: str, *, index_map=None):
    """Drop-in for ``io.streaming.scan_stream`` over an append-only
    directory: returns the IDENTICAL ``(index_map, StreamStats)`` while
    re-reading only partitions without a valid cache entry. Accounting
    in ``cache.stats`` (also returned for the caller's metrics)."""
    from photon_ml_tpu.io.streaming import StreamStats
    from photon_ml_tpu.utils.index_map import IndexMap

    cache = PartitionStatsCache(cache_dir)
    entries = _gather_entries(paths, fmt, cache, with_moments=False)
    keys = set()
    num_rows = 0
    max_live = 0
    for _path, e in entries:
        num_rows += e.rows
        max_live = max(max_live, e.max_live)
        if index_map is None:
            keys.update(e.keys)
    if index_map is None:
        index_map = IndexMap.build(
            iter(keys), add_intercept=fmt.add_intercept
        )
    max_nnz = max(max_live + (1 if fmt.add_intercept else 0), 1)
    return (
        index_map,
        StreamStats(num_rows=num_rows, max_nnz=max_nnz),
        cache.stats,
    )


def cached_scan_stream_with_summary(
    paths, fmt, cache_dir: str, *, index_map=None
):
    """Cached twin of ``stream_scan_with_summary``: vocabulary + shape
    stats + colStats summary from per-partition partials, re-reading
    only uncached partitions. Returns
    ``(index_map, StreamStats, summary, ScanCacheStats)``."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.stats import finalize_summary
    from photon_ml_tpu.io.streaming import StreamStats
    from photon_ml_tpu.utils.index_map import IndexMap, intercept_key

    cache = PartitionStatsCache(cache_dir)
    entries = _gather_entries(paths, fmt, cache, with_moments=True)
    keys = set()
    num_rows = 0
    max_live = 0
    real_rows = 0.0
    for _path, e in entries:
        num_rows += e.rows
        max_live = max(max_live, e.max_live)
        real_rows += e.real_rows
        if index_map is None:
            keys.update(e.keys)
    if index_map is None:
        index_map = IndexMap.build(
            iter(keys), add_intercept=fmt.add_intercept
        )
    dim = index_map.size
    s1 = np.zeros(dim)
    s2 = np.zeros(dim)
    l1 = np.zeros(dim)
    nnz = np.zeros(dim)
    mx = np.full(dim, -np.inf)
    mn = np.full(dim, np.inf)
    # merge partials in sorted-file order (the _gather order), so the
    # result is deterministic run to run
    for _path, e in entries:
        for key, (p1, p2, pl1, pn, pmx, pmn) in e.moments.items():
            j = index_map.get_index(key)
            if j < 0:
                continue
            s1[j] += p1
            s2[j] += p2
            l1[j] += pl1
            nnz[j] += pn
            mx[j] = max(mx[j], pmx)
            mn[j] = min(mn[j], pmn)
    icept = (
        index_map.get_index(intercept_key()) if fmt.add_intercept else -1
    )
    if icept >= 0 and real_rows > 0:
        s1[icept] = s2[icept] = l1[icept] = real_rows
        nnz[icept] = real_rows
        mx[icept] = mn[icept] = 1.0
    summary = finalize_summary(
        jnp.float32(real_rows),
        jnp.asarray(s1, jnp.float32),
        jnp.asarray(s2, jnp.float32),
        jnp.asarray(l1, jnp.float32),
        jnp.asarray(nnz, jnp.float32),
        jnp.asarray(mx, jnp.float32),
        jnp.asarray(mn, jnp.float32),
    )
    max_nnz = max(max_live + (1 if fmt.add_intercept else 0), 1)
    return (
        index_map,
        StreamStats(num_rows=num_rows, max_nnz=max_nnz),
        summary,
        cache.stats,
    )
