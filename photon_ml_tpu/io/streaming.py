"""Streaming (>host-RAM) GLM input: chunked Avro decode into fixed-shape
device batches.

Reference: the reference streams Avro partitions lazily into RDD rows
(io/GLMSuite.scala:98-131) and relies on Spark's MEMORY_AND_DISK persist —
datasets larger than aggregate executor memory re-read from disk on every
pass. The one-host analog here: every optimizer evaluation streams the
input files through a FIXED-shape staging batch (one XLA compilation,
reused for every chunk of every evaluation), so peak host memory is
bounded by one decoded file + one staged chunk regardless of dataset
size. Multi-host runs split files per process with
``parallel.multihost.process_shard`` before constructing the stream.

Full-batch semantics are exact: chunk partials of (value, gradient) are
accumulated on device, so streaming L-BFGS walks the same iterate
sequence as the in-memory path (fp32 accumulation-order noise aside).
The cost model matches Spark's spilled-cache mode: one disk pass per
objective evaluation (including line-search trials).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, List, Optional, Tuple

import numpy as np

from photon_ml_tpu.data.batch import SparseBatch
from photon_ml_tpu.utils.index_map import IndexMap


@dataclass(frozen=True)
class StreamStats:
    """One-pass scan results needed to fix the staging-batch shape."""

    num_rows: int
    max_nnz: int  # per-row nonzeros INCLUDING the intercept slot


def scan_stream(
    paths, fmt, *, index_map: Optional[IndexMap] = None
) -> Tuple[IndexMap, StreamStats]:
    """One bounded-memory pass collecting the vocabulary, the row count,
    and the max per-row nnz (incl. intercept) that fix the staging batch
    — dispatched to the input format's streaming protocol
    (``fmt.stream_scan``): Avro scans one decoded file at a time, LibSVM
    one text line at a time. With a prebuilt ``index_map`` (the
    FeatureIndexingJob store — required for multi-host streaming, where
    no single process sees the whole vocabulary) the key collection is
    skipped and only the shape stats are scanned."""
    return fmt.stream_scan(paths, index_map=index_map)


def scan_stream_with_summary(paths, fmt, *, index_map=None):
    """Fused scan: ONE pass collecting the vocabulary, the shape stats AND
    the colStats feature summary — formats without the fused hook (LibSVM)
    fall back to the classic two passes (scan, then streamed summary).
    Returns ``(index_map, StreamStats, summary)``; single-process only
    (the multi-host driver path shards files and all-reduces moments
    through :func:`streaming_summary` instead)."""
    fused = getattr(fmt, "stream_scan_with_summary", None)
    if fused is not None:
        return fused(paths, index_map=index_map)
    index_map, stats = scan_stream(paths, fmt, index_map=index_map)
    summary, _ = streaming_summary(paths, fmt, index_map, stats)
    return index_map, stats, summary


def _file_rows(fmt, path, index_map: IndexMap):
    """One file's decoded row stream behind the ``chunk_read`` seam: the
    whole-file decode is the retryable unit (re-decoding a file is
    idempotent). Formats with the split decode hook (Avro) retry the
    actual column decode; line-at-a-time formats (LibSVM) only cover
    stream construction — their per-line reads are not restartable
    mid-file, so a mid-stream error propagates (and the seam accounting
    still names the file)."""
    from photon_ml_tpu.reliability.retry import io_call

    decode = getattr(fmt, "decode_payload", None)
    rows_from = getattr(fmt, "stream_rows_from_payload", None)
    if decode is not None and rows_from is not None:
        payload = io_call("chunk_read", decode, path, detail=path)
        return rows_from(payload, path, index_map)
    return io_call(
        "chunk_read", fmt.stream_rows, path, index_map, detail=path
    )


def _pipelined_file_rows(files, fmt, index_map: IndexMap):
    """reader->decode stage of the populate pipeline: a worker thread
    decodes file i+1 (``fmt.decode_payload`` — the expensive whole-file
    native column decode) while the caller stages file i's rows. Bounded
    double-buffering: at most one decoded payload queued + one being
    staged + one in flight on the worker. Formats without the split
    decode hook (LibSVM is line-at-a-time) fall back to the serial
    ``stream_rows``. Decodes run behind the ``chunk_read`` seam on the
    worker thread — an injected/transient decode fault retries THERE,
    invisible to the consumer."""
    from photon_ml_tpu.reliability.retry import io_call

    decode = getattr(fmt, "decode_payload", None)
    rows_from = getattr(fmt, "stream_rows_from_payload", None)
    if decode is None or rows_from is None:
        for path in files:
            yield from _file_rows(fmt, path, index_map)
        return

    def decoded():
        for path in files:
            yield path, io_call("chunk_read", decode, path, detail=path)

    for path, payload in _prefetched(decoded(), depth=1):
        yield from rows_from(payload, path, index_map)


def iter_chunks(
    paths,
    fmt,
    index_map: IndexMap,
    *,
    rows_per_chunk: int,
    nnz_width: int,
    pipeline: Optional[bool] = None,
) -> Iterator[SparseBatch]:
    """Stream fixed-shape [rows_per_chunk, nnz_width] SparseBatch chunks
    (weight-0 padding rows in the final chunk). Every chunk has the SAME
    shape, so one jitted partial-objective serves the whole stream.

    ``pipeline``: decode-ahead the NEXT file on a worker thread while
    this thread stages the current one (reader->decode->stage overlap,
    parallel/overlap.py); None follows the global overlap setting AND
    requires a multi-core host — on one core the extra thread cannot
    overlap anything and its switching overhead measurably loses (round-6
    A/B), while the existing chunk-level prefetch
    already recovers the recoverable idle. The serial path is
    row-for-row identical."""
    import os

    import jax.numpy as jnp

    if pipeline is None:
        from photon_ml_tpu.parallel.overlap import overlap_enabled

        pipeline = overlap_enabled() and (os.cpu_count() or 1) > 1
    # a multi-host process can own a ZERO-file shard (process_shard with
    # more processes than files) — it must yield no chunks and still join
    # every collective, not raise
    files = fmt.stream_files(paths) if paths else []
    R, W = rows_per_chunk, nnz_width
    ix_buf = np.zeros((R, W), np.int32)
    v_buf = np.zeros((R, W), np.float32)
    lab_buf = np.zeros((R,), np.float32)
    off_buf = np.zeros((R,), np.float32)
    wgt_buf = np.zeros((R,), np.float32)
    fill = 0

    def emit():
        # COPIES are load-bearing: jnp.asarray on the CPU backend can
        # alias numpy memory zero-copy and dispatch is async, so handing
        # out a view of the reused staging buffers would let the next
        # chunk's refill race the consumer's read of this one.
        return SparseBatch(
            indices=jnp.asarray(ix_buf.copy()),
            values=jnp.asarray(v_buf.copy()),
            labels=jnp.asarray(lab_buf.copy()),
            offsets=jnp.asarray(off_buf.copy()),
            weights=jnp.asarray(wgt_buf.copy()),
        )

    rows = (
        _pipelined_file_rows(files, fmt, index_map)
        if pipeline
        else (
            row
            for path in files
            for row in _file_rows(fmt, path, index_map)
        )
    )
    for ix, vs, lab, off, wgt in rows:
        if len(ix) > W:
            raise ValueError(
                f"row has {len(ix)} nonzeros > staging width {W}; "
                "re-scan the stream or raise nnz_width"
            )
        ix_buf[fill, : len(ix)] = ix
        ix_buf[fill, len(ix):] = 0
        v_buf[fill, : len(vs)] = vs
        v_buf[fill, len(vs):] = 0.0
        lab_buf[fill] = lab
        off_buf[fill] = off
        wgt_buf[fill] = wgt
        fill += 1
        if fill == R:
            yield emit()
            fill = 0
    if fill:
        ix_buf[fill:] = 0
        v_buf[fill:] = 0.0
        lab_buf[fill:] = 0.0
        off_buf[fill:] = 0.0
        wgt_buf[fill:] = 0.0  # weight-0 rows are inert in every objective
        yield emit()


def _prefetched(source: Iterator, depth: int = 2) -> Iterator:
    """Decode-ahead: a worker thread keeps up to ``depth`` staged chunks
    queued while the consumer's device compute runs — the IO/compute
    overlap Spark gets from its task pipeline. (On a single-core host the
    thread adds nothing; on real multi-core hosts decode hides behind the
    objective evaluation.)

    Abandoning the generator (consumer raises mid-pass) cancels the
    worker: its puts poll a stop flag, so no thread or open decode leaks
    across failed evaluations."""
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    sentinel = object()
    stop = threading.Event()
    errors: List[BaseException] = []

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            # decode_ahead seam: accounts the worker-thread handoff (and
            # gives chaos plans a handle on the thread itself). The
            # retryable IO underneath it is covered by the chunk_read /
            # spill_read seams the source generator crosses.
            from photon_ml_tpu.reliability.faults import inject

            inject("decode_ahead")
            for item in source:
                if not _put(item):
                    return
        except BaseException as e:  # re-raised on the consumer side
            errors.append(e)
        finally:
            _put(sentinel)

    threading.Thread(target=worker, daemon=True).start()
    try:
        while True:
            item = q.get()
            if item is sentinel:
                if errors:
                    raise errors[0]
                return
            yield item
    finally:
        stop.set()


def shard_stream_files(paths, fmt):
    """Cross-process-consistent shard of the format's input files: the
    GLOBAL sort (inside ``fmt.stream_files``) before the round-robin
    split is load-bearing — every host must agree on the file order or
    the shards overlap. One definition shared by the streaming trainer,
    the driver's summary/validation passes, and tests."""
    from photon_ml_tpu.parallel.multihost import process_shard

    return process_shard(fmt.stream_files(paths))


def shard_avro_files(paths):
    """Back-compat alias: shard the default Avro format's files."""
    from photon_ml_tpu.io.input_format import AvroInputDataFormat

    return shard_stream_files(paths, AvroInputDataFormat())


_MOMENTS_JIT = None


def _sparse_moments_jit():
    """Module-level jitted sparse-moments wrapper (dim static): ONE
    compile cache shared across every streaming_summary call, instead of
    a fresh jit(lambda) — and a fresh XLA compilation — per scan."""
    global _MOMENTS_JIT
    if _MOMENTS_JIT is None:
        import jax

        from photon_ml_tpu.data.stats import sparse_moments

        _MOMENTS_JIT = jax.jit(sparse_moments, static_argnums=(1,))
    return _MOMENTS_JIT


def streaming_summary(
    paths,
    fmt,
    index_map: IndexMap,
    stats: StreamStats,
    *,
    rows_per_chunk: int = 65536,
    reservoir_rows: int = 0,
    seed: int = 0,
):
    """One bounded-memory pass computing the FEATURE SUMMARY over a >RAM
    stream (the colStats/summarization stage, BasicStatistics.scala:42 —
    every reference driver stage is a pass over an RDD; this is that pass
    over chunks), plus an optional uniform RESERVOIR SAMPLE of rows
    returned as an in-memory SparseBatch (algorithm R over the stream) —
    the bounded-memory stand-in for diagnostics stages that genuinely
    need row-level resampling (bootstrap).

    Returns ``(summary, sample_batch_or_None)``. Multi-host: moments
    reduce across processes; the reservoir stays process-local (used only
    by the coordinator's diagnostics) — i.e. it is drawn from the
    coordinator's 1/P round-robin file shard, not the full set. The
    round-robin split interleaves date/source-partitioned files, which
    keeps the sample roughly representative; exact global sampling would
    need a cross-host exchange that diagnostics do not warrant.
    """
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.stats import finalize_summary
    from photon_ml_tpu.parallel import overlap

    dim = index_map.size
    jitted_moments = _sparse_moments_jit()

    def moments_fn(b):
        return jitted_moments(b, dim)
    acc = None
    K = int(reservoir_rows)
    rng = np.random.default_rng(seed)
    W = stats.max_nnz
    res = (
        {
            "ix": np.zeros((K, W), np.int32),
            "v": np.zeros((K, W), np.float32),
            "lab": np.zeros(K, np.float32),
            "off": np.zeros(K, np.float32),
            "wgt": np.zeros(K, np.float32),
        }
        if K
        else None
    )
    seen = 0
    for chunk in iter_chunks(
        paths, fmt, index_map, rows_per_chunk=rows_per_chunk, nnz_width=W
    ):
        m = moments_fn(chunk)
        if acc is None:
            acc = list(m)
        else:
            for i in range(5):  # n, s1, s2, l1, nnz are sums
                acc[i] = acc[i] + m[i]
            acc[5] = jnp.maximum(acc[5], m[5])
            acc[6] = jnp.minimum(acc[6], m[6])
        if res is not None:
            wgt = np.asarray(chunk.weights)
            real = np.nonzero(wgt > 0)[0]
            m = len(real)
            if m:
                # vectorized algorithm R (exact): per-row independent
                # acceptance draws + random slots; numpy fancy assignment
                # applies duplicates in order, so the LAST accepted row
                # wins a contested slot — identical to the sequential
                # algorithm. One rng call per chunk, not per row.
                t = seen + 1 + np.arange(m)  # global 1-based row ranks
                fill_mask = t <= K
                slots = np.where(fill_mask, t - 1, 0)
                u = rng.random(m)
                accept = fill_mask | (u < K / t)
                rand_slots = rng.integers(0, K, size=m)
                slots = np.where(fill_mask, slots, rand_slots)
                sel = real[accept]
                dst = slots[accept]
                res["ix"][dst] = np.asarray(chunk.indices)[sel]
                res["v"][dst] = np.asarray(chunk.values)[sel]
                res["lab"][dst] = np.asarray(chunk.labels)[sel]
                res["off"][dst] = np.asarray(chunk.offsets)[sel]
                res["wgt"][dst] = wgt[sel]
                seen += m
    if acc is None:
        if jax.process_count() <= 1:
            raise ValueError(f"no rows found under {paths!r}")
        # a process can own ZERO file shards when processes outnumber
        # files — it still joins the cross-host reduction with inert
        # moments
        big = jnp.float32(jnp.inf)
        acc = [
            jnp.float32(0.0),
            jnp.zeros((dim,), jnp.float32),
            jnp.zeros((dim,), jnp.float32),
            jnp.zeros((dim,), jnp.float32),
            jnp.zeros((dim,), jnp.float32),
            jnp.full((dim,), -big),
            jnp.full((dim,), big),
        ]
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        for i in range(5):
            acc[i] = jnp.asarray(
                multihost_utils.process_allgather(acc[i]).sum(axis=0)
            )
        acc[5] = jnp.asarray(
            multihost_utils.process_allgather(acc[5]).max(axis=0)
        )
        acc[6] = jnp.asarray(
            multihost_utils.process_allgather(acc[6]).min(axis=0)
        )
        if int(overlap.device_get(acc[0])) == 0:
            # same contract as single-process: .avro files that exist but
            # hold zero rows must not produce a benign-looking summary
            # (mean 0 / variance 1) and train garbage normalization
            raise ValueError(f"no rows found under {paths!r} on any host")
    summary = finalize_summary(*acc)
    sample = None
    if res is not None:
        k_eff = min(seen, K)
        sample = SparseBatch(
            indices=jnp.asarray(res["ix"][:k_eff]),
            values=jnp.asarray(res["v"][:k_eff]),
            labels=jnp.asarray(res["lab"][:k_eff]),
            offsets=jnp.asarray(res["off"][:k_eff]),
            weights=jnp.asarray(res["wgt"][:k_eff]),
        )
    return summary, sample


# Live spill scratch directories, swept at interpreter exit. __del__ alone
# is not a cleanup contract: a driver exception that keeps the objective
# alive in a traceback, or an exit while generators still hold frames,
# skips finalizers and leaks multi-GB scratch. Every spill dir registers
# here at creation and unregisters on close(); the atexit sweep removes
# whatever is left. SIGTERM is covered when the process shuts down through
# the normal exit path (the preemption guard's iteration-boundary stop);
# a hard kill cannot run ANY handler — PHOTON_SPILL_DIR + an external
# scratch sweeper remain the belt-and-braces for that.
_LIVE_SPILL_DIRS: set = set()


def _sweep_spill_dirs() -> None:
    import shutil

    for d in list(_LIVE_SPILL_DIRS):
        _LIVE_SPILL_DIRS.discard(d)
        shutil.rmtree(d, ignore_errors=True)


def register_spill_dir(path: str) -> None:
    """Track a scratch directory for the atexit sweep (shared by every
    disk-spill store: GLM chunk cache, GAME chunk/score/bucket stores)."""
    import atexit

    if not _LIVE_SPILL_DIRS:
        atexit.register(_sweep_spill_dirs)
    _LIVE_SPILL_DIRS.add(path)


def unregister_spill_dir(path: str) -> None:
    _LIVE_SPILL_DIRS.discard(path)


def make_spill_dir(prefix: str, spill_dir: Optional[str] = None) -> str:
    """Create + register a scratch directory. On hosts with a tmpfs /tmp
    the default scratch is RAM-backed — point spill_dir (or
    PHOTON_SPILL_DIR) at real disk for genuinely >RAM datasets."""
    import os
    import tempfile

    base = spill_dir or os.environ.get("PHOTON_SPILL_DIR")
    path = tempfile.mkdtemp(prefix=prefix, dir=base)
    register_spill_dir(path)
    return path


def stream_budget_rows(
    budget_bytes: int, bytes_per_row: int, *, default_rows: int = 65536,
    min_rows: int = 8,
) -> int:
    """Rows-per-chunk under an explicit host-memory byte budget
    (--stream-memory-budget): the staging chunk is the unit every
    streaming stage holds resident, so its row count is budget // row
    bytes, floored at ``min_rows`` so degenerate budgets still make
    progress (the contract is then 'one minimal chunk'). budget <= 0
    keeps the historical default chunk sizing."""
    if budget_bytes is None or budget_bytes <= 0:
        return default_rows
    return max(min_rows, budget_bytes // max(1, bytes_per_row))


def sparse_row_bytes(nnz_width: int) -> int:
    """Staged bytes per row of one sparse chunk: int32 index + float32
    value per slot, plus label/offset/weight."""
    return max(1, nnz_width) * 8 + 12


def budgeted_rows(max_rows: int, budget_bytes: int, bytes_per_row: int) -> int:
    """Row count of a bounded in-memory sample (diagnostics reservoirs)
    under a byte budget: wide rows scale the count DOWN instead of
    allocating multiple GB on the host — the streaming paths' bounded-
    memory contract. Shared by the GLM driver's
    reservoir (sparse_row_bytes rows) and the GAME driver's
    (game.streaming.game_row_bytes rows)."""
    return max(1, min(max_rows, budget_bytes // max(1, bytes_per_row)))


class _DiskChunkStore:
    """Fixed-shape staged chunks spilled to a local scratch directory —
    the disk half of Spark's persist(MEMORY_AND_DISK)
    (constants/StorageLevel.scala): evaluation 2..N re-reads the staged
    raw arrays (one sequential memmap pass) instead of re-decoding Avro."""

    _FIELDS = ("ix", "v", "lab", "off", "wgt")

    def __init__(
        self, rows_per_chunk: int, nnz_width: int,
        spill_dir: Optional[str] = None,
    ):
        import os

        self.R, self.W = rows_per_chunk, nnz_width
        self.dir = make_spill_dir("photon-stream-spill-", spill_dir)
        self.count = 0
        self._writers = {
            f: open(os.path.join(self.dir, f + ".bin"), "wb")
            for f in self._FIELDS
        }

    def append(self, batch: SparseBatch) -> None:
        from photon_ml_tpu.reliability.retry import io_call

        arrays = {
            "ix": np.asarray(batch.indices, np.int32),
            "v": np.asarray(batch.values, np.float32),
            "lab": np.asarray(batch.labels, np.float32),
            "off": np.asarray(batch.offsets, np.float32),
            "wgt": np.asarray(batch.weights, np.float32),
        }
        for f, a in arrays.items():
            data = a.tobytes()
            w = self._writers[f]
            # seek to the chunk's fixed offset per attempt: a retry after
            # a partial write overwrites in place instead of appending
            # garbage (every chunk field has a fixed record size)
            off = self.count * len(data)

            def _write(w=w, data=data, off=off):
                w.seek(off)
                w.write(data)

            io_call(
                "spill_write", _write,
                detail=f"{self.dir}/{f}.bin[{self.count}]",
            )
        self.count += 1

    def finalize(self) -> None:
        for f in self._writers.values():
            f.close()

    def chunks(self) -> Iterator[SparseBatch]:
        import os

        import jax.numpy as jnp

        R, W, n = self.R, self.W, self.count
        mm = {
            "ix": np.memmap(
                os.path.join(self.dir, "ix.bin"), np.int32, "r", shape=(n, R, W)
            ),
            "v": np.memmap(
                os.path.join(self.dir, "v.bin"), np.float32, "r", shape=(n, R, W)
            ),
            "lab": np.memmap(
                os.path.join(self.dir, "lab.bin"), np.float32, "r", shape=(n, R)
            ),
            "off": np.memmap(
                os.path.join(self.dir, "off.bin"), np.float32, "r", shape=(n, R)
            ),
            "wgt": np.memmap(
                os.path.join(self.dir, "wgt.bin"), np.float32, "r", shape=(n, R)
            ),
        }
        from photon_ml_tpu.reliability.retry import io_call

        for i in range(n):
            # spill_read seam: materializing one chunk from the memmaps
            # is idempotent, so transient read errors retry in place
            arrs = io_call(
                "spill_read",
                lambda i=i: {f: np.array(mm[f][i]) for f in self._FIELDS},
                detail=f"{self.dir}[{i}]",
            )
            yield SparseBatch(
                indices=jnp.asarray(arrs["ix"]),
                values=jnp.asarray(arrs["v"]),
                labels=jnp.asarray(arrs["lab"]),
                offsets=jnp.asarray(arrs["off"]),
                weights=jnp.asarray(arrs["wgt"]),
            )

    def close(self) -> None:
        import shutil

        self.finalize()
        unregister_spill_dir(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)

    def __del__(self):  # scratch must not outlive the objective
        try:
            self.close()
        except Exception:
            pass


# -- shared tiled-chunk fold programs ----------------------------------------
#
# The tiled cached path folds every chunk inside ONE jitted lax.scan over
# the chunk-stacked TiledSparseBatch. Module-level (objective passed as a
# pytree argument) so every StreamingGLMObjective instance with the same
# chunk structure shares one persistent compile cache — these replace
# per-instance constructor jit(lambda)s.

_TILED_FOLDS = {}


def _tiled_fold_jit(which: str):
    global _TILED_FOLDS
    if which in _TILED_FOLDS:
        return _TILED_FOLDS[which]
    import jax
    import jax.numpy as jnp

    def _scan(stacked, fold):
        def body(carry, tb):
            return jax.tree.map(jnp.add, carry, fold(tb)), None

        init = jax.tree.map(
            jnp.zeros_like,
            jax.eval_shape(fold, jax.tree.map(lambda x: x[0], stacked)),
        )
        return jax.lax.scan(body, init, stacked)[0]

    if which == "vg":

        @jax.jit
        def fn(objective, w, stacked):
            return _scan(
                stacked, lambda tb: objective.value_and_gradient(w, tb, 0.0)
            )
    elif which == "hv":

        @jax.jit
        def fn(objective, w, d, stacked):
            return _scan(
                stacked, lambda tb: objective.hessian_vector(w, d, tb, 0.0)
            )
    else:

        @jax.jit
        def fn(objective, w, stacked):
            return _scan(
                stacked, lambda tb: objective.hessian_diagonal(w, tb, 0.0)
            )

    _TILED_FOLDS[which] = fn
    return fn


class StreamingGLMObjective:
    """GLMObjective facade whose (value, gradient) stream the input from
    disk per evaluation — full-batch semantics with bounded memory.

    The per-chunk partial (l2 = 0) is one fixed-shape jitted program;
    the L2 term is added once at the end. Feed this to the host-driven
    L-BFGS/OWL-QN (optim.host_lbfgs) — the in-jit while_loop optimizers
    cannot trace through disk IO.

    persist(MEMORY_AND_DISK) semantics (GLMSuite.scala:98-131 +
    StorageLevel.scala): the FIRST evaluation populates a cache of the
    staged fixed-shape chunks — device-resident up to ``cache_bytes``,
    the remainder spilled as raw arrays to local scratch — so evaluation
    2..N never re-decodes Avro. ``cache_bytes=0`` disables caching (one
    decode pass per evaluation, the round-3 behavior); ``prefetch``
    decode-aheads one chunk on a worker thread.

    FAST-KERNEL CACHED PATH (``kernel="auto"|"tiled"`` on TPU): staged
    chunks have FIXED structure after the populate pass — exactly what
    the tiled Pallas kernels' static schedules need — so once the cache
    exists, per-chunk tile schedules are built ONCE (padded to one common
    shape so a single compiled program serves every chunk) and evaluation
    2..N dispatches the gather/scatter-free bilinear kernels
    asynchronously chunk after chunk, accumulating on device. The
    reference pays no kernel penalty for persisted-on-disk data
    (GLMSuite.scala:98-131 + ValueAndGradientAggregator.scala:235-250);
    after this, neither do we. Tiled chunks are device-resident up to
    ``tiled_cache_bytes``; chunks past the budget stay on the scatter
    partial.
    """

    def __init__(
        self,
        paths,
        fmt,
        index_map: IndexMap,
        stats: StreamStats,
        task,
        *,
        rows_per_chunk: int = 65536,
        cache_bytes: int = 2 << 30,
        prefetch: bool = True,
        spill_dir: Optional[str] = None,
        kernel: str = "auto",
        tiled_cache_bytes: int = 4 << 30,
        tile_params=None,
        norm=None,
        tile_cache_dir: Optional[str] = None,
    ):
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.ops.objective import GLMObjective

        self.paths = paths
        self.fmt = fmt
        self.index_map = index_map
        self.stats = stats
        self.rows_per_chunk = int(min(rows_per_chunk, max(stats.num_rows, 8)))
        self.nnz_width = stats.max_nnz
        self.dim = index_map.size
        self.cache_bytes = int(cache_bytes)
        self.prefetch = prefetch
        self.spill_dir = spill_dir
        self._mem_cache: List[SparseBatch] = []
        self._disk_cache: Optional[_DiskChunkStore] = None
        self._cached = False
        from photon_ml_tpu.ops.normalization import identity_context

        self._loss = loss_for_task(task)
        self.norm = norm if norm is not None else identity_context()
        # per-chunk partials run the SHARED module-level jits
        # (ops.objective.partial_value_and_gradient and friends): the
        # objective is a pytree argument, so every instance with the
        # same structure/chunk shape hits one persistent compile cache.
        self._objective = GLMObjective(self._loss, self.dim, self.norm)
        if kernel not in ("auto", "tiled", "scatter"):
            raise ValueError(f"unknown kernel {kernel!r}")
        from photon_ml_tpu.utils.backend import effective_platform

        self._use_tiled = kernel == "tiled" or (
            kernel == "auto" and effective_platform() == "tpu"
        )
        self.tiled_cache_bytes = int(tiled_cache_bytes)
        self.tile_params = tile_params
        # persistent schedule-cache dir for the per-chunk tiled builds
        # (ops/schedule_cache.py); None falls back to the process config /
        # PHOTON_TILE_CACHE_DIR. Staged chunks have fixed content after
        # the populate pass, so a rerun over the same files hits the
        # content-addressed artifacts chunk by chunk.
        self.tile_cache_dir = tile_cache_dir
        self._tiled_chunk_count: Optional[int] = None
        self._tiled_stacked = None  # chunk-stacked TiledSparseBatch pytree
        self._tiled_objective = None

    # -- tiled cached path --------------------------------------------------

    def _build_tiled_chunks(self) -> None:
        """Convert cached staged chunks to tiled batches, once.

        Every chunk shares the staging shape [R, W], so all schedules are
        padded to ONE static (steps, spill) shape — a single compiled
        tiled program then serves the whole stream with no per-chunk
        recompilation. Build cost is one pass of the native counting-sort
        builder per chunk (threaded; structure is fixed for the rest of
        training, the persisted-RDD analog)."""
        from concurrent.futures import ThreadPoolExecutor


        from photon_ml_tpu.ops import tiled_sparse as ts

        params0 = self.tile_params or ts.TileParams()
        win = params0.window
        R = self.rows_per_chunk
        r_pad = max(((R + win - 1) // win) * win, win)
        d_pad = max(((self.dim + win - 1) // win) * win, win)
        z_blocks, g_blocks = r_pad // win, d_pad // win

        # ONE chunk at a time — the COO staging of a chunk is dropped
        # before the next decodes, so host memory holds at most the KEPT
        # schedules (bounded by tiled_cache_bytes) + one in-flight chunk;
        # the >RAM streaming contract survives the fast-kernel upgrade.
        params = None
        built = []  # (z, g, lab, off, wgt) for kept chunks only
        budget = self.tiled_cache_bytes
        from photon_ml_tpu.ops.schedule_cache import cache_scope

        with cache_scope(self.tile_cache_dir), ThreadPoolExecutor(2) as pool:
            for batch in self.chunks():
                rows, feats, vals, _n = ts._sparse_coo(batch)
                if params is None:
                    # chunks share the staging shape; the first chunk's
                    # occupancy fixes the grid-step width for all
                    # (resolved() divides by the tile count itself)
                    params = params0.resolved(
                        len(vals), z_blocks * g_blocks
                    )
                fz = pool.submit(
                    ts._build_schedule_np, rows, feats, vals,
                    params=params, sort_by_feature_block=False,
                    num_out_blocks=z_blocks,
                )
                g = ts._build_schedule_np(
                    rows, feats, vals, params=params,
                    sort_by_feature_block=True, num_out_blocks=g_blocks,
                )
                z = fz.result()
                del rows, feats, vals
                nbytes = (
                    sum(a.nbytes for a in z) + 2 * sum(a.nbytes for a in g)
                )
                if nbytes > budget:
                    # remaining chunks stay on the scatter partial
                    break
                budget -= nbytes
                built.append((
                    z, g,
                    np.asarray(batch.labels),
                    np.asarray(batch.offsets),
                    np.asarray(batch.weights),
                ))
        if not built:
            self._tiled_chunk_count = 0
            return
        # pad every kept schedule to ONE static shape so a single
        # compiled program serves all chunks
        gz = max(b[0][0].shape[0] for b in built)
        gg = max(b[1][0].shape[0] for b in built)
        sz = max(b[0][8].shape[0] for b in built)
        sg = max(b[1][8].shape[0] for b in built)
        meta = ts._TiledMeta(
            params=params, num_rows=r_pad, dim=d_pad,
            num_real_rows=R, real_dim=self.dim,
        )
        import jax.numpy as jnp

        def pad_rows(a):
            out = np.zeros(r_pad, np.float32)
            out[: a.shape[0]] = a
            return out

        # ALL cached chunks evaluate in ONE dispatch: leaves stacked along
        # a leading chunk axis (stacked HOST-side — one device copy, no
        # per-chunk device duplicates) and folded by lax.scan — per-chunk
        # python dispatches leave the device idle between kernels that
        # are themselves shorter than a dispatch
        n_chunks = len(built)
        padded = [
            (
                ts._pad_schedule_np(z, gz, z_blocks, sz),
                ts._pad_schedule_np(g, gg, g_blocks, sg),
                lab, off, wgt,
            )
            for z, g, lab, off, wgt in built
        ]
        del built

        def lead(items):
            # ALWAYS stacked with a leading chunk axis (even at 1 chunk)
            # so the shared module-level scan programs below see one
            # uniform structure across instances
            return jnp.asarray(np.stack(list(items)))

        self._tiled_stacked = ts.TiledSparseBatch(
            meta=meta,
            z_sched=ts._Schedule(
                *(lead(p[0][i] for p in padded) for i in range(9))
            ),
            g_sched=ts._Schedule(
                *(lead(p[1][i] for p in padded) for i in range(9))
            ),
            g_vals_sq=lead(p[1][5] ** 2 for p in padded),
            labels=lead(pad_rows(p[2]) for p in padded),
            offsets=lead(pad_rows(p[3]) for p in padded),
            weights=lead(pad_rows(p[4]) for p in padded),
        )
        del padded
        from photon_ml_tpu.utils.backend import effective_platform

        self._tiled_objective = ts.TiledGLMObjective(
            self._loss, self.dim, self.norm,
            interpret=effective_platform() == "cpu",
        )
        self._tiled_chunk_count = n_chunks

    def _ensure_tiled(self) -> bool:
        if not (self._use_tiled and self._cached):
            return False
        if self._tiled_chunk_count is None:
            self._build_tiled_chunks()
        return self._tiled_chunk_count > 0

    def _overflow_chunks(self) -> Iterator[SparseBatch]:
        """Cached chunks past the tiled-cache budget (scatter fallback)."""
        import itertools

        yield from itertools.islice(
            self.chunks(), self._tiled_chunk_count, None
        )

    def _chunk_nbytes(self) -> int:
        return self.rows_per_chunk * (self.nnz_width * 8 + 12)

    def chunks(self) -> Iterator[SparseBatch]:
        if self._cached:
            yield from self._mem_cache
            if self._disk_cache is not None:
                # spill-tier reads get the same IO/compute overlap as the
                # populate pass
                spill = self._disk_cache.chunks()
                yield from (
                    _prefetched(spill) if self.prefetch else spill
                )
            return
        source = iter_chunks(
            self.paths, self.fmt, self.index_map,
            rows_per_chunk=self.rows_per_chunk, nnz_width=self.nnz_width,
        )
        if self.prefetch:
            source = _prefetched(source)
        if self.cache_bytes <= 0:
            yield from source
            return
        budget = max(1, self.cache_bytes // max(1, self._chunk_nbytes()))
        mem: List[SparseBatch] = []
        disk: Optional[_DiskChunkStore] = None
        for batch in source:
            if len(mem) < budget:
                mem.append(batch)
            else:
                if disk is None:
                    disk = _DiskChunkStore(
                        self.rows_per_chunk, self.nnz_width, self.spill_dir
                    )
                disk.append(batch)
            yield batch
        if disk is not None:
            disk.finalize()
        self._mem_cache = mem
        self._disk_cache = disk
        self._cached = True

    def _reduce_hosts(self, vec):
        """Cross-host sum of a streamed partial (the treeAggregate combine
        over DCN); no-op single-process."""
        import jax
        import jax.numpy as jnp

        if jax.process_count() <= 1:
            return vec
        from jax.experimental import multihost_utils

        return jnp.asarray(
            multihost_utils.process_allgather(vec).sum(axis=0), jnp.float32
        )

    def hessian_vector(self, w, direction, l2_weight=0.0):
        """Streamed H(w) @ d: one pass over the cached staged chunks —
        the reference's exact second-order pattern (one cluster aggregate
        per CG step, HessianVectorAggregator.scala:137-152). Rides the
        tiled chunk cache when built."""
        import jax.numpy as jnp

        from photon_ml_tpu.ops.objective import partial_hessian_vector

        hv = jnp.zeros((self.dim,), jnp.float32)
        if self._ensure_tiled():
            hv = hv + _tiled_fold_jit("hv")(
                self._tiled_objective, w, direction, self._tiled_stacked
            )
            chunks = self._overflow_chunks()
        else:
            chunks = self.chunks()
        for batch in chunks:
            hv = hv + partial_hessian_vector(
                self._objective, w, direction, batch
            )
        hv = self._reduce_hosts(hv)
        return hv + l2_weight * direction

    def hessian_diagonal(self, w, l2_weight=0.0):
        """Streamed Hessian diagonal (the variance pass,
        DistributedOptimizationProblem.scala:79-93): one pass over the
        cached staged chunks."""
        import jax.numpy as jnp

        from photon_ml_tpu.ops.objective import partial_hessian_diagonal

        diag = jnp.zeros((self.dim,), jnp.float32)
        if self._ensure_tiled():
            diag = diag + _tiled_fold_jit("hd")(
                self._tiled_objective, w, self._tiled_stacked
            )
            chunks = self._overflow_chunks()
        else:
            chunks = self.chunks()
        for batch in chunks:
            diag = diag + partial_hessian_diagonal(self._objective, w, batch)
        return self._reduce_hosts(diag) + l2_weight

    def value_and_gradient(self, w, l2_weight=0.0):
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu.ops.objective import partial_value_and_gradient

        value = jnp.float32(0.0)
        grad = jnp.zeros((self.dim,), jnp.float32)
        if self._ensure_tiled():
            # cached fast path: EVERY tiled chunk folds inside one
            # jitted lax.scan dispatch (no per-chunk dispatch gaps)
            v, g = _tiled_fold_jit("vg")(
                self._tiled_objective, w, self._tiled_stacked
            )
            value = value + v
            grad = grad + g
            for batch in self._overflow_chunks():
                v, g = partial_value_and_gradient(self._objective, w, batch)
                value = value + v
                grad = grad + g
        else:
            for batch in self.chunks():
                v, g = partial_value_and_gradient(self._objective, w, batch)
                value = value + v
                grad = grad + g
        if jax.process_count() > 1:
            # cross-host reduction of the loss partials (the treeAggregate
            # combine step over DCN): each process streamed only ITS file
            # shard; the regularization term is added once, after
            from jax.experimental import multihost_utils

            packed = jnp.concatenate([value[None], grad])
            gathered = multihost_utils.process_allgather(packed)
            total = gathered.sum(axis=0)
            value = jnp.float32(total[0])
            grad = jnp.asarray(total[1:], jnp.float32)
        value = value + 0.5 * l2_weight * jnp.vdot(w, w)
        return value, grad + l2_weight * w


class FeatureShardedStreamingObjective:
    """Streaming x feature-sharded composition: the >host-RAM dataset AND
    the >single-chip-HBM coefficient vector at once — the north-star
    combination the round-5 verdict named as the open frontier.

    Rows stream through the SAME staged-chunk pipeline as
    :class:`StreamingGLMObjective` (decode once, fixed-shape chunks,
    mem/disk cache), but every staged chunk is RE-LAID-OUT per feature
    block on the (data, model) mesh (feature_shard_sparse_batch) — the
    per-chunk analog of the reference's hash-partitioned feature
    vocabulary. Each objective evaluation folds one sharded program per
    chunk (value replicated, gradient sharded over "model"); TRON runs
    one streamed Hv pass per CG step, exactly the host_tron driver's
    one-aggregate-per-CG-step pattern.

    Staged chunks have FIXED content after the populate pass, so each
    chunk's sharded layout is built ONCE and kept device-resident up to
    ``sharded_cache_bytes``; chunks past the budget re-shard from the
    staged arrays on every pass (the spilled-cache cost model). On a
    CPU backend "device-resident" is host RAM, so both budgets count
    against the host-memory contract.

    Scope (validated by the driver): single process, no normalization
    (the shift/factor extras are not threaded through the per-chunk
    entry points yet), sparse layout (the tiled per-chunk schedules ride
    the PR-1 cache through StreamingGLMObjective on the unsharded path).
    """

    def __init__(
        self,
        paths,
        fmt,
        index_map: IndexMap,
        stats: StreamStats,
        task,
        mesh,
        *,
        rows_per_chunk: int = 65536,
        cache_bytes: int = 2 << 30,
        sharded_cache_bytes: int = 2 << 30,
        prefetch: bool = True,
        spill_dir: Optional[str] = None,
    ):
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        if MODEL_AXIS not in mesh.axis_names or DATA_AXIS not in mesh.axis_names:
            raise ValueError(
                "streaming feature-sharded training needs a (data, model) "
                f"mesh, got axes {mesh.axis_names}"
            )
        self.mesh = mesh
        self.data_shards = int(mesh.shape[DATA_AXIS])
        self.model_shards = int(mesh.shape[MODEL_AXIS])
        self.dim = index_map.size
        self.block_dim = -(-self.dim // self.model_shards)
        self.d_pad = self.model_shards * self.block_dim
        self.sharded_cache_bytes = int(sharded_cache_bytes)
        # staging/cache tier only (kernel="scatter": the sharded programs
        # below do the math; the base's own partials are never dispatched)
        self._base = StreamingGLMObjective(
            paths, fmt, index_map, stats, task,
            rows_per_chunk=rows_per_chunk, cache_bytes=cache_bytes,
            prefetch=prefetch, spill_dir=spill_dir, kernel="scatter",
        )
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.ops.objective import GLMObjective
        from photon_ml_tpu.parallel.distributed import (
            feature_sharded_hessian_diagonal,
            feature_sharded_sparse_hessian_vector,
            feature_sharded_sparse_value_and_grad,
        )

        self._objective = GLMObjective(loss_for_task(task), self.dim)
        self._vg = feature_sharded_sparse_value_and_grad(
            self._objective, mesh
        )
        self._hv = feature_sharded_sparse_hessian_vector(
            self._objective, mesh
        )
        self._hd = feature_sharded_hessian_diagonal(
            self._objective, mesh, None, layout="sparse"
        )
        # per-chunk sharded layouts: None until populated; entries are
        # either a FeatureShardedSparseBatch (cached) or None (over
        # budget -> re-shard per pass)
        self._sharded: Optional[List[Optional[object]]] = None

    def _shard_chunk(self, batch):
        from photon_ml_tpu.parallel import overlap
        from photon_ml_tpu.parallel.distributed import (
            feature_shard_sparse_batch,
        )

        # counted seam: the re-staging fetch happens once per chunk per
        # pass (cached under the budget) — route it through the counter
        host = overlap.device_get(batch)
        sharded, block_dim = feature_shard_sparse_batch(
            host, self.dim, self.model_shards,
            rows_multiple=self.data_shards,
        )
        assert block_dim == self.block_dim
        return sharded

    def _sharded_chunks(self):
        """Yield one FeatureShardedSparseBatch per staged chunk; builds
        (and budget-caches) the layouts on the first pass."""
        if self._sharded is None:
            built: List[Optional[object]] = []
            budget = self.sharded_cache_bytes
            for batch in self._base.chunks():
                sb = self._shard_chunk(batch)
                nbytes = sum(
                    np.dtype(a.dtype).itemsize * int(np.prod(a.shape))
                    for a in sb
                )
                if nbytes <= budget:
                    budget -= nbytes
                    built.append(sb)
                else:
                    built.append(None)
                yield sb
            self._sharded = built
            return
        source = None
        for i, sb in enumerate(self._sharded):
            if sb is not None:
                yield sb
                continue
            if source is None:
                # over-budget tail: re-shard from the staged chunk cache
                import itertools

                source = itertools.islice(self._base.chunks(), i, None)
            yield self._shard_chunk(next(source))

    def value_and_gradient(self, w, l2_weight=0.0):
        import jax.numpy as jnp

        value = jnp.float32(0.0)
        grad = jnp.zeros((self.d_pad,), jnp.float32)
        for sb in self._sharded_chunks():
            v, g = self._vg(w, sb, jnp.float32(0.0))
            value = value + v
            grad = grad + g
        value = value + 0.5 * l2_weight * jnp.vdot(w, w)
        return value, grad + l2_weight * w

    def hessian_vector(self, w, direction, l2_weight=0.0):
        import jax.numpy as jnp

        hv = jnp.zeros((self.d_pad,), jnp.float32)
        for sb in self._sharded_chunks():
            hv = hv + self._hv(w, direction, sb, jnp.float32(0.0))
        return hv + l2_weight * direction

    def hessian_diagonal(self, w, l2_weight=0.0):
        import jax.numpy as jnp

        diag = jnp.zeros((self.d_pad,), jnp.float32)
        for sb in self._sharded_chunks():
            diag = diag + self._hd(w, sb, jnp.float32(0.0))
        return diag + l2_weight
