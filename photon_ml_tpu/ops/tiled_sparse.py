"""Tiled sparse GLM kernels: gather/scatter-free margins and gradients.

WHY: on TPU, XLA lowers random gather/scatter to serial per-element
loops, so the reference's two hot loops (margin accumulation and gradient
axpy, ValueAndGradientAggregator.scala:133-154) run far below the
hardware's streaming rate (PERF_LEDGER.jsonl, PR 28, `glmix-ads-100m.cd`:
`cd_fe_eval_ms` 589.13 on the scatter objective, 115.33 on these kernels
at `highest`). This module replaces both with a STATIC TILED layout + two
Pallas kernels whose only per-entry operations are VPU compares and MXU
matmuls:

- Entries are binned into (row-window x feature-window) tiles; windows are
  R_WIN = F_WIN = S_HI * S_LO positions wide.
- A window-local index idx in [0, WIN) decomposes as hi*S_LO + lo; the
  gather w[idx] becomes the bilinear form onehot_hi @ w2d . onehot_lo with
  w2d = w_window reshaped [S_HI, S_LO] — ONE small matmul per chunk plus
  elementwise masks, no scatter/gather anywhere.
- The z-pass streams chunks sorted by row-block (output revisiting is
  monotone -> pallas accumulates the z window in VMEM); the grad-pass
  streams the same entries sorted by feature-block.
- A DENSE column (an entry in every live row: an intercept) stays out of
  the tiles. It is not sparse: it fills whole tiles with one-hot
  expansions that read the same coefficient thousands of times, and the
  kernel's bfloat16 hi+lo split rounds that ONE coefficient the same way
  in every row, so all margins shift together and the value moves by the
  shift times sum l'(z) (5e-7-8e-7 of it at 524,288 rows x 65; PERF.md
  section 6, PR 28, 32), where every other column's rounding averages
  out over the rows. The batch builders take such columns out of the
  entries they schedule (``_split_dense_columns``: from the data alone,
  at most ``MAX_DENSE_COLUMNS``) and keep them as a float32 [K, rows]
  array beside the schedules; the objective's two passes apply it
  exactly, one fused multiply-add a row.

The schedule (tile assignment, chunking, window-local index packing) is
computed ONCE on host per dataset — full-batch GLM training re-evaluates
the same static structure hundreds of times, so the build cost amortizes
to zero. Schedules and per-row arrays are pytree leaves: pass the
TiledSparseBatch *as a jit argument* (exactly like SparseBatch), never a
closure constant — at ads scale the schedule is hundreds of MB and baking
it into the executable blows up compilation.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.normalization import NormalizationContext, identity_context
from photon_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

Array = jnp.ndarray


@dataclass(frozen=True)
class TileParams:
    # ``chunk=None`` sizes the grid-step width from the dataset's average
    # tile occupancy at build time (see ``resolved``): fewer, fuller grid
    # steps. What a step and a launch cost at the benchmark's shapes is
    # in PERF.md section 5.
    s_hi: int = 128
    s_lo: int = 64
    chunk: Optional[int] = None  # entries per grid step; None = auto
    # Spill-to-scatter threshold: a tile whose entry count modulo the
    # chunk leaves a remainder <= spill_cap routes that remainder to a
    # small XLA gather/scatter path instead of paying a nearly-empty
    # grid step (and a tile with <= spill_cap entries total spills
    # entirely). A grid step costs a few hundred spilled entries'
    # serialized gather+scatter, so the cap defaults to chunk // 16 (~260
    # at chunk 4096). None = default; 0 disables spilling.
    spill_cap: Optional[int] = None

    @property
    def window(self) -> int:
        return self.s_hi * self.s_lo

    def resolved_spill_cap(self) -> int:
        if self.spill_cap is not None:
            return self.spill_cap
        return max(0, (self.chunk or 0) // 16)

    def resolved(self, n_entries: int, n_tiles_hint: int) -> "TileParams":
        """Fix ``chunk=None`` from dataset statistics. Tiny-window test
        configs (window < 1024) fall back to the window size so toy
        schedules stay small.

        With spilling enabled and tiles in the single-chunk regime, the
        chunk is mean + 2*sqrt(mean) rounded up to a lane multiple: tile
        occupancy concentrates around the mean (Poisson-ish), so a chunk
        just past the +2-sigma tail holds ~98% of tiles in ONE ~97%-full
        step and spills only the far tail (at a mean of 4078 entries a
        tile: chunk 4224, 2.3k spilled entries where pow2 4096 spills
        104k). Multi-chunk tiles (mean > 4096) keep the pow2 rule — the
        remainder logic already spills or pads their tails."""
        if self.chunk is not None:
            return self
        import dataclasses

        avg = max(1, n_entries // max(n_tiles_hint, 1))
        lo = min(1024, self.window)
        spilling = self.spill_cap is None or self.spill_cap > 0
        if spilling and avg <= 4096:
            # entries live on lanes: round up to a multiple of 128
            c = -(-int(avg + 2.0 * np.sqrt(avg)) // 128) * 128
            c = max(lo, min(4608, c))
        else:
            c = 1 << int(np.round(np.log2(avg)))
            c = max(lo, min(4096, c))
        return dataclasses.replace(self, chunk=c)


class _Schedule(NamedTuple):
    """One pass's static schedule: chunked entries sorted by output block.

    All fields are arrays (the NamedTuple is a pytree — jit-argument safe).
    Entry blocks are 2-D rows [G, L]: TPU HBM tiling pads the trailing two
    dims to (8, 128), so [G, L, 1] would waste 128x HBM (observed: 54 GB
    for a 528 MB schedule) and [G, 1, L] 8x, while [G, L] is compact. In
    the kernel each [1, L] row broadcasts against sublane-iota; a
    [8, L//8] -> [L] reshape would be an unsupported Mosaic relayout.

    ``spill_*``: the tile remainders routed around the kernel (see
    TileParams.spill_cap) as SCHEDULE-LOCAL flat coordinates (output /
    input position in this pass's padded out/in space). Zero-padded to a
    lane multiple; padding slots carry val 0 at coordinate 0 — inert.
    """

    step_out: Array  # int32 [G] output block id per step
    step_in: Array  # int32 [G] input-window block id per step
    step_init: Array  # int32 [G] 1 iff first step of its output block
    out_pos: Array  # int32 [G, L] window-local OUTPUT index in [0, WIN)
    in_pos: Array  # int32 [G, L] window-local INPUT index in [0, WIN)
    vals: Array  # float32 [G, L] entry values (0 for padding slots)
    spill_out: Array  # int32 [S] flat output coordinate
    spill_in: Array  # int32 [S] flat input coordinate
    spill_vals: Array  # float32 [S] (0 for padding)

    @property
    def num_steps(self) -> int:
        return self.step_out.shape[0]

    def apply_spill(
        self, out_flat: Array, src_flat: Array,
        vals: Optional[Array] = None,
    ) -> Array:
        """out_flat[spill_out] += spill_vals * src_flat[spill_in] — the
        scatter cleanup completing the kernel's chunked partial sums.
        ``vals`` overrides the entry values (the hessian-diagonal pass
        squares them)."""
        if self.spill_vals.shape[0] == 0:
            return out_flat
        v = self.spill_vals if vals is None else vals
        contrib = v * jnp.take(src_flat, self.spill_in)
        return out_flat.at[self.spill_out].add(contrib)


import threading as _threading

_TILE_LIB_LOCK = _threading.Lock()
_tile_lib_handle = None  # None = untried, False = unavailable


def _tile_lib():
    """ctypes handle to native/tile_schedule.cpp (compiled on demand, see
    utils/native_build.py); False when the toolchain/library is
    unavailable — callers fall back to the numpy builder."""
    global _tile_lib_handle
    if _tile_lib_handle is not None:
        return _tile_lib_handle
    import ctypes
    import subprocess

    from photon_ml_tpu.utils.native_build import library_path

    with _TILE_LIB_LOCK:
        if _tile_lib_handle is not None:
            return _tile_lib_handle
        try:
            lib = ctypes.CDLL(library_path("tile_schedule", opt="-O3"))
            i64 = ctypes.c_int64
            p_i64 = ctypes.POINTER(i64)
            p_i32 = ctypes.POINTER(ctypes.c_int32)
            p_f32 = ctypes.POINTER(ctypes.c_float)
            lib.ts_plan.restype = i64
            lib.ts_plan.argtypes = [
                p_i64, p_i64, p_f32, i64, i64, i64, i64, i64, p_i64, p_i64,
            ]
            lib.ts_fill.restype = i64
            lib.ts_fill.argtypes = [
                p_i64, p_i64, p_f32, i64, i64, i64, i64, i64, i64, i64,
                p_i32, p_i32, p_i32, p_i32, p_i32, p_f32,
                p_i32, p_i32, p_f32,
            ]
            _tile_lib_handle = lib
        except (OSError, subprocess.CalledProcessError):
            _tile_lib_handle = False
    return _tile_lib_handle


def _build_schedule_native(
    rows: np.ndarray,
    feats: np.ndarray,
    vals: np.ndarray,
    *,
    params: TileParams,
    sort_by_feature_block: bool,
    num_out_blocks: int,
) -> Optional[Tuple[np.ndarray, ...]]:
    """Counting-sort schedule build in C++ (~0.3 s vs ~4 s numpy at the ads
    shape; ctypes releases the GIL, so the z/grad passes overlap for real).
    Returns None when the native library is unavailable or the tile space
    is too large for counting sort."""
    lib = _tile_lib()
    if not lib:
        return None
    import ctypes

    if sort_by_feature_block:
        oc, ic = feats, rows
    else:
        oc, ic = rows, feats
    oc = np.ascontiguousarray(oc, dtype=np.int64)
    ic = np.ascontiguousarray(ic, dtype=np.int64)
    v = np.ascontiguousarray(vals, dtype=np.float32)
    n = oc.shape[0]
    L = params.chunk
    win = params.window

    def p(a, t):
        return a.ctypes.data_as(ctypes.POINTER(t))

    i64, i32, f32 = ctypes.c_int64, ctypes.c_int32, ctypes.c_float
    cap = params.resolved_spill_cap()
    # flat spill coordinates must fit int32 — same (conservative,
    # block-rounded) bound as the numpy builder so both produce
    # identically shaped schedules
    if cap and n and (
        (int(oc.max()) // win) * win + win >= 2**31
        or (int(ic.max()) // win) * win + win >= 2**31
    ):
        cap = 0
    steps_out = ctypes.c_int64()
    spilled_out = ctypes.c_int64()
    rc = lib.ts_plan(
        p(oc, i64), p(ic, i64), p(v, f32), n, win, L, cap, num_out_blocks,
        ctypes.byref(steps_out), ctypes.byref(spilled_out),
    )
    if rc != 0:
        return None
    G = steps_out.value
    S = spilled_out.value
    G8 = ((G + 7) // 8) * 8
    step_out = np.zeros(G, np.int32)
    step_in = np.zeros(G, np.int32)
    step_init = np.zeros(G, np.int32)
    o_pos = np.zeros((G8, L), np.int32)
    i_pos = np.zeros((G8, L), np.int32)
    sv = np.zeros((G8, L), np.float32)
    sp_out = np.zeros(S, np.int32)
    sp_in = np.zeros(S, np.int32)
    sp_vals = np.zeros(S, np.float32)
    rc = lib.ts_fill(
        p(oc, i64), p(ic, i64), p(v, f32), n, win, L, cap,
        num_out_blocks, G, S,
        p(step_out, i32), p(step_in, i32), p(step_init, i32),
        p(o_pos, i32), p(i_pos, i32), p(sv, f32),
        p(sp_out, i32), p(sp_in, i32), p(sp_vals, f32),
    )
    if rc != 0:
        return None
    sp_out, sp_in, sp_vals = _pad_spill_np(sp_out, sp_in, sp_vals)
    return (
        step_out, step_in, step_init, o_pos, i_pos, sv,
        sp_out, sp_in, sp_vals,
    )


def _build_schedule_np(
    rows: np.ndarray,
    feats: np.ndarray,
    vals: np.ndarray,
    *,
    params: TileParams,
    sort_by_feature_block: bool,
    num_out_blocks: int,
    digest: Optional[str] = None,
) -> Tuple[np.ndarray, ...]:
    """Schedule build -> (step_out, step_in, step_init, o_pos, i_pos, sv)
    numpy arrays. Tries the persistent content-addressed disk cache first
    (ops/schedule_cache.py — a hit returns mmap-backed arrays and skips
    the build entirely), then the native counting-sort builder; the numpy
    path below is the fallback oracle (vectorized repeat/cumsum/scatter —
    no per-entry Python loops).

    An entry whose value is 0 is no entry (it takes no slot): how the
    batch builders take a dense column's entries out without compacting
    three arrays (``_split_dense_columns``).

    ``digest``: precomputed content digest of (rows, feats, vals) so
    callers building BOTH passes from one triple hash it once."""
    from photon_ml_tpu.ops import schedule_cache as _sc

    cache_dir = _sc.resolve_cache_dir()
    cache_key = None
    if cache_dir is not None:
        if digest is None:
            digest = _sc.content_digest(rows, feats, vals)
        cache_key = _sc.schedule_key(
            digest, params, sort_by_feature_block, num_out_blocks
        )
        cached = _sc.load_schedule(cache_dir, cache_key)
        if cached is None and not _sc.is_cache_writer():
            # multi-host: the coordinator builds and writes; everyone
            # else waits for its artifact (local build only on timeout)
            cached = _sc.wait_and_load(cache_dir, cache_key)
        if cached is not None:
            return cached
    import time as _time

    t_build = _time.perf_counter()
    native = _build_schedule_native(
        rows, feats, vals, params=params,
        sort_by_feature_block=sort_by_feature_block,
        num_out_blocks=num_out_blocks,
    )
    entries = int(np.count_nonzero(vals))
    if native is not None:
        return _finish_schedule_build(
            native, t_build, cache_dir, cache_key, entries
        )
    if entries != len(vals):
        live = np.flatnonzero(vals)
        rows, feats, vals = rows[live], feats[live], vals[live]
    win = params.window
    L = params.chunk
    # int32 entry coordinates when they fit (half the sort/gather traffic);
    # feature ids can exceed int32 at the 10B-coefficient scale
    if len(rows) and int(rows.max()) < 2**31 and int(feats.max()) < 2**31:
        rows = rows.astype(np.int32, copy=False)
        feats = feats.astype(np.int32, copy=False)
    rb = rows // win
    fb = feats // win
    # Single combined-key stable argsort (numpy uses radix sort for ints —
    # ~2x faster than the equivalent two-key lexsort at 16.7M entries).
    if sort_by_feature_block:
        key = fb.astype(np.int64) * (int(rb.max(initial=0)) + 1) + rb
        order = np.argsort(key, kind="stable")
        out_blocks, in_blocks = fb[order], rb[order]
        out_pos, in_pos = feats[order] % win, rows[order] % win
    else:
        key = rb.astype(np.int64) * (int(fb.max(initial=0)) + 1) + fb
        order = np.argsort(key, kind="stable")
        out_blocks, in_blocks = rb[order], fb[order]
        out_pos, in_pos = rows[order] % win, feats[order] % win
    v = vals[order]
    n_ent = len(v)
    cap = params.resolved_spill_cap()
    sp_out = np.zeros(0, np.int32)
    sp_in = np.zeros(0, np.int32)
    sp_vals = np.zeros(0, np.float32)

    if n_ent:
        # tile boundaries: chunk entries so no chunk crosses a tile
        # boundary; the sort key IS the tile id, already ordered
        tile_key = key[order]
        tile_starts = np.nonzero(
            np.concatenate([[True], tile_key[1:] != tile_key[:-1]])
        )[0]
        tile_ends = np.concatenate([tile_starts[1:], [n_ent]])
        sizes_t = tile_ends - tile_starts
        if cap and (
            int(out_blocks.max(initial=0)) * win + win >= 2**31
            or int(in_blocks.max(initial=0)) * win + win >= 2**31
        ):
            cap = 0  # flat spill coordinates must fit int32
        # spill rule (see TileParams.spill_cap): whole tiny tiles spill;
        # otherwise a small remainder past the last full chunk spills —
        # the spilled entries are each tile's TAIL in stable order
        full = sizes_t // L
        rem = sizes_t % L
        spill_all = sizes_t <= cap
        spill_tail = (~spill_all) & (rem > 0) & (rem <= cap) & (full >= 1)
        n_spill_t = np.where(
            spill_all, sizes_t, np.where(spill_tail, rem, 0)
        )
        kept_t = sizes_t - n_spill_t
        n_chunks = -(-kept_t // L)  # 0 for fully spilled tiles
        if int(n_spill_t.sum()):
            pos_in_tile = np.arange(n_ent) - np.repeat(tile_starts, sizes_t)
            is_spill = pos_in_tile >= np.repeat(kept_t, sizes_t)
            sp_out = (
                out_blocks[is_spill].astype(np.int64) * win
                + out_pos[is_spill]
            ).astype(np.int32)
            sp_in = (
                in_blocks[is_spill].astype(np.int64) * win
                + in_pos[is_spill]
            ).astype(np.int32)
            sp_vals = v[is_spill].astype(np.float32)
            keep = ~is_spill
            out_blocks, in_blocks = out_blocks[keep], in_blocks[keep]
            out_pos, in_pos, v = out_pos[keep], in_pos[keep], v[keep]
            n_ent = len(v)
            tile_starts = np.concatenate(
                [[0], np.cumsum(kept_t)[:-1]]
            ).astype(tile_starts.dtype)
            tile_ends = tile_starts + kept_t
        live = n_chunks > 0
        tile_starts, tile_ends = tile_starts[live], tile_ends[live]
        n_chunks = n_chunks[live]
        G_data = int(n_chunks.sum())
        rep_start = np.repeat(tile_starts, n_chunks)
        rep_end = np.repeat(tile_ends, n_chunks)
        first = np.concatenate([[0], np.cumsum(n_chunks)[:-1]])
        ordinal = np.arange(G_data) - np.repeat(first, n_chunks)
        chunk_start = rep_start + ordinal * L
        chunk_end = np.minimum(chunk_start + L, rep_end)
        so_data = out_blocks[rep_start].astype(np.int32)
        si_data = in_blocks[chunk_start].astype(np.int32)
        sizes = chunk_end - chunk_start
        entry_step = np.repeat(np.arange(G_data), sizes)
        slot = np.arange(n_ent) - np.repeat(chunk_start, sizes)
    else:
        G_data = 0
        so_data = np.zeros(0, np.int32)
        si_data = np.zeros(0, np.int32)

    # Every output block needs at least one step: the kernel only writes
    # blocks named by step_out (out_ref starts as UNINITIALIZED memory on
    # TPU — interpret mode zero-fills, hiding this), so an output window
    # with no entries would otherwise return garbage. Append zero-entry
    # init steps for the missing blocks; the stable sort below slots them
    # into out-block order so VMEM accumulation stays monotone. Data steps
    # are already out-block-sorted (entries were lexsorted by out block),
    # so the stable merge preserves their entry order.
    present = np.zeros(num_out_blocks, bool)
    if G_data:
        present[so_data] = True
    missing = np.nonzero(~present)[0].astype(np.int32)

    G = G_data + len(missing)
    so_all = np.concatenate([so_data, missing])
    si_all = np.concatenate([si_data, np.zeros(len(missing), np.int32)])
    perm = np.argsort(so_all, kind="stable")
    step_out = so_all[perm]
    step_in = si_all[perm]
    step_init = np.ones(G, np.int32)
    step_init[1:] = (step_out[1:] != step_out[:-1]).astype(np.int32)

    # pad the entry-row axis to a multiple of 8: the kernel reads entry
    # rows in (8, L) blocks (sublane tiling); padded rows never execute
    G8 = ((G + 7) // 8) * 8
    o_pos = np.zeros((G8, L), np.int32)
    i_pos = np.zeros((G8, L), np.int32)
    sv = np.zeros((G8, L), np.float32)
    if n_ent:
        inv = np.empty(G_data, np.int64)
        inv[perm[perm < G_data].astype(np.int64)] = np.nonzero(
            perm < G_data
        )[0]
        dest_row = inv[entry_step]
        o_pos[dest_row, slot] = out_pos
        i_pos[dest_row, slot] = in_pos
        sv[dest_row, slot] = v
    sp_out, sp_in, sp_vals = _pad_spill_np(sp_out, sp_in, sp_vals)
    return _finish_schedule_build(
        (
            step_out, step_in, step_init, o_pos, i_pos, sv,
            sp_out, sp_in, sp_vals,
        ),
        t_build, cache_dir, cache_key, entries,
    )


def _finish_schedule_build(arrays, t0, cache_dir, key, entries):
    """Record the build in the cache stats/profiling stream, count its
    ``entries`` by the path that applies them and persist the artifact
    (writer process only) when the disk tier is active."""
    import time as _time

    from photon_ml_tpu.ops import schedule_cache as _sc

    _sc.record_build_seconds(_time.perf_counter() - t0)
    # (the spilled tail is zero-padded; an entry that spilled is not 0)
    spilled = int(np.count_nonzero(arrays[8]))
    counter = _entries_counter()
    counter.inc(entries - spilled, path="kernel")
    counter.inc(spilled, path="spill")
    if key is not None and _sc.is_cache_writer():
        _sc.store_schedule(cache_dir, key, arrays)
    return arrays


def _pad_spill_np(sp_out, sp_in, sp_vals, pad_to: Optional[int] = None):
    """Zero-pad spill arrays to a lane multiple (or exactly ``pad_to``);
    padding entries carry val 0 at coordinate 0 — inert adds."""
    s = len(sp_vals)
    target = ((s + 127) // 128) * 128 if pad_to is None else pad_to
    if target < s:
        raise ValueError(f"pad_to={target} < spill size {s}")
    if target != s:
        sp_out = np.concatenate([sp_out, np.zeros(target - s, np.int32)])
        sp_in = np.concatenate([sp_in, np.zeros(target - s, np.int32)])
        sp_vals = np.concatenate(
            [sp_vals, np.zeros(target - s, np.float32)]
        )
    return sp_out, sp_in, sp_vals


def _pad_schedule_np(
    arrs: Tuple[np.ndarray, ...], pad_steps_to: int, num_out_blocks: int,
    pad_spill_to: Optional[int] = None,
) -> Tuple[np.ndarray, ...]:
    """Pad a schedule's step axis to ``pad_steps_to`` with inert zero-entry
    steps on the LAST output block (keeps out-block order monotone; the
    last block always exists — init steps guarantee every block has one)
    and its spill axis to ``pad_spill_to``. Needed so per-device-shard
    schedules share one static shape under shard_map."""
    (
        step_out, step_in, step_init, o_pos, i_pos, sv,
        sp_out, sp_in, sp_vals,
    ) = arrs
    G = step_out.shape[0]
    if pad_steps_to < G:
        raise ValueError(f"pad_steps_to={pad_steps_to} < num steps {G}")
    extra = pad_steps_to - G
    if extra:
        step_out = np.concatenate(
            [step_out, np.full(extra, num_out_blocks - 1, np.int32)]
        )
        step_in = np.concatenate([step_in, np.zeros(extra, np.int32)])
        step_init = np.concatenate([step_init, np.zeros(extra, np.int32)])
    G8 = ((pad_steps_to + 7) // 8) * 8
    L = o_pos.shape[1]
    if G8 > o_pos.shape[0]:
        pad_rows = G8 - o_pos.shape[0]
        o_pos = np.concatenate([o_pos, np.zeros((pad_rows, L), np.int32)])
        i_pos = np.concatenate([i_pos, np.zeros((pad_rows, L), np.int32)])
        sv = np.concatenate([sv, np.zeros((pad_rows, L), np.float32)])
    if pad_spill_to is not None:
        sp_out, sp_in, sp_vals = _pad_spill_np(
            sp_out, sp_in, sp_vals, pad_to=pad_spill_to
        )
    return (
        step_out, step_in, step_init, o_pos, i_pos, sv,
        sp_out, sp_in, sp_vals,
    )


# How many dense columns a batch keeps beside its schedules: one sublane
# tile of the [K, rows] side array. A further one stays in the tiles.
MAX_DENSE_COLUMNS = 8


def _split_dense_columns(
    rows: np.ndarray,
    feats: np.ndarray,
    vals: np.ndarray,
    num_rows: int,
    num_cols: int,
    limit: int,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """COO triple -> (vals, dense_cols, dense_vals): the values with the
    dense columns' entries set to 0, which to the schedule builders is no
    entry (the 168M-entry triple is not compacted), those columns' ids
    (int32 [K], K <= ``limit``) and their values a row (float32 [K,
    num_rows], 0 where a row holds no entry at all). With K = 0 ``vals``
    comes back as it went in (the same object), else as a copy.

    A column is dense when it holds exactly one entry in every LIVE row (a
    row with at least one entry; the callers have dropped weight-0 rows
    and zero values), so its entry count equals the live-row count: read
    off ``np.bincount``, from the entries alone.

    Host work ahead of every schedule build, so it is a few memory-bound
    passes, each split over threads (numpy releases the GIL in them; at
    most 256 MB of per-thread column counts)."""
    from concurrent.futures import ThreadPoolExecutor

    total = len(vals)
    none = vals, np.zeros(0, np.int32), np.zeros((0, num_rows), np.float32)
    if limit <= 0 or not total:
        return none
    threads = max(1, min(8, (1 << 25) // max(num_cols, 1)))
    edges = np.linspace(0, total, threads + 1).astype(np.int64)
    parts = [slice(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:])]
    with ThreadPoolExecutor(threads) as pool:

        def counted(ids, length):
            return sum(pool.map(
                lambda part: np.bincount(ids[part], minlength=length), parts
            ))

        counts = counted(feats, num_cols)
        top = int(counts.max())
        # no column outnumbers the live rows short of a repeated (row,
        # column) pair, so a column in all ``num_rows`` rows says every row
        # is live and saves the pass over ``rows``; the distinct-row check
        # below holds either reading to account
        live = top if top == num_rows else int(
            np.count_nonzero(counted(rows, num_rows))
        )
        cols, sides, taken = [], [], []
        for col in np.flatnonzero(counts == live):
            at = np.concatenate(list(pool.map(
                lambda part: part.start + np.flatnonzero(feats[part] == col),
                parts,
            )))
            side = np.zeros(num_rows, np.float32)
            side[rows[at]] = vals[at]
            if int(np.count_nonzero(side)) != live:
                continue  # a row holds it twice, so another row lacks it
            cols.append(col)
            sides.append(side)
            taken.append(at)
            if len(cols) == limit:
                break
    if not cols:
        return none
    vals = vals.copy()
    vals[np.concatenate(taken)] = 0.0
    return vals, np.asarray(cols, np.int32), np.stack(sides)


def _count_dense_entries(
    dense_vals: np.ndarray, builds_before: int, cells: int
) -> None:
    """``photon_tiled_entries_total{path="dense"}``, beside what
    ``_finish_schedule_build`` counts: each schedule BUILT since
    ``builds_before`` (two a cell of rows when nothing came from the disk
    cache) adds the dense entries of its rows, as it added its own."""
    from photon_ml_tpu.ops import schedule_cache as _sc

    built = min(_sc.stats().builds - builds_before, 2 * cells)
    entries = int(np.count_nonzero(dense_vals))
    if built > 0 and entries:
        _entries_counter().inc(entries * built // cells, path="dense")


def _entries_counter():
    from photon_ml_tpu.obs.registry import default_registry

    return default_registry().counter(
        "photon_tiled_entries_total",
        "entries of built tile schedules by the path that applies them: "
        "kernel (grid steps), spill (the scatter beside them), dense (a "
        "dense column's float32 side term)",
    )


def _build_schedule(
    rows: np.ndarray,
    feats: np.ndarray,
    vals: np.ndarray,
    *,
    params: TileParams,
    sort_by_feature_block: bool,
    num_out_blocks: int,
    digest: Optional[str] = None,
    entries: int,
    dense_columns: int,
) -> _Schedule:
    """``_build_schedule_np`` onto the device, under its span, which says
    what the batch builder scheduled (``entries``) and what it kept beside
    the schedules (``dense_columns``)."""
    from photon_ml_tpu.obs.trace import span
    from photon_ml_tpu.ops import schedule_cache as _sc

    builds = _sc.stats().builds
    with span(
        "tiled.schedule_build", entries=entries, dense_columns=dense_columns
    ) as sp:
        arrays = _build_schedule_np(
            rows, feats, vals, params=params,
            sort_by_feature_block=sort_by_feature_block,
            num_out_blocks=num_out_blocks, digest=digest,
        )
        sp.set(cache="miss" if _sc.stats().builds > builds else "hit")
        return _upload_schedule(arrays)


def _upload_schedule(arrays) -> _Schedule:
    """A built schedule's host arrays onto the device, one at a time (an
    iterable may make each only when asked), under ``tiled.upload``
    (``bytes``)."""
    from photon_ml_tpu.obs.trace import span

    with span("tiled.upload") as sp:
        on_device, total = [], 0
        for a in arrays:
            total += int(a.nbytes)
            on_device.append(jnp.asarray(a))
        sp.set(bytes=total)
        return _Schedule(*on_device)


def _dense_split_spanned(rows, feats, vals, num_rows, num_cols, limit):
    """``_split_dense_columns`` under ``tiled.dense_split`` (``entries``
    it read, ``dense_columns`` it found)."""
    from photon_ml_tpu.obs.trace import span

    with span("tiled.dense_split", entries=len(vals)) as sp:
        out = _split_dense_columns(
            rows, feats, vals, num_rows, num_cols, limit
        )
        sp.set(dense_columns=len(out[1]))
    return out


class TiledSparseBatch(NamedTuple):
    """Statically tiled sparse batch (replaces SparseBatch on the hot path).

    Row space is padded to num_row_blocks * window; feature space to
    num_feat_blocks * window. ``labels/offsets/weights`` live in padded row
    space (weight 0 padding). A NamedTuple pytree: ints are leaves too, but
    they are concrete python ints, so jit sees them as static weak-typed
    scalars only if hashable — instead we keep them in ``meta`` as a static
    aux via the _TiledMeta wrapper below.
    """

    meta: "_TiledMeta"
    z_sched: _Schedule
    g_sched: _Schedule
    g_vals_sq: Array  # [G2, L] squared values for hessian_diagonal
    labels: Array
    offsets: Array
    weights: Array
    dense_cols: Optional[Array] = None  # int32 [K]
    dense_vals: Optional[Array] = None  # float32 [K, num_rows]

    # convenience passthroughs (static python ints)
    @property
    def params(self) -> TileParams:
        return self.meta.params

    @property
    def num_rows(self) -> int:
        return self.meta.num_rows

    @property
    def dim(self) -> int:
        return self.meta.dim

    @property
    def num_real_rows(self) -> int:
        return self.meta.num_real_rows

    @property
    def real_dim(self) -> int:
        return self.meta.real_dim

    @property
    def num_row_blocks(self) -> int:
        return self.meta.num_rows // self.meta.params.window

    @property
    def num_feat_blocks(self) -> int:
        return self.meta.dim // self.meta.params.window


@jax.tree_util.register_static
@dataclass(frozen=True)
class _TiledMeta:
    """Static (hashable) shape metadata for TiledSparseBatch.

    ``data_shards > 1`` marks a mesh layout: every array leaf carries
    ``data_shards`` per-shard segments concatenated along axis 0 (all
    per-shard shapes equal), and the shape fields describe ONE shard —
    the view each device sees inside shard_map with the batch's leaves
    split over the data axis. Such a batch is only meaningful under that
    shard_map; single-device code must use ``data_shards == 1`` batches.
    """

    params: TileParams
    num_rows: int  # padded (per data shard)
    dim: int  # padded
    num_real_rows: int  # global real row count
    real_dim: int
    data_shards: int = 1


def build_tiled_batch(
    rows: np.ndarray,
    feats: np.ndarray,
    vals: np.ndarray,
    labels: np.ndarray,
    offsets: np.ndarray,
    weights: np.ndarray,
    dim: int,
    *,
    params: TileParams = TileParams(),
    max_dense_columns: int = MAX_DENSE_COLUMNS,
) -> TiledSparseBatch:
    """COO triples + per-row arrays -> tiled batch. Entries with zero value
    are dropped (they contribute nothing); up to ``max_dense_columns``
    dense columns go beside the schedules (``_split_dense_columns``), the
    tile parameters resolved from what is left. The whole build is the
    span ``tiled.batch_build`` on the calling thread, which waits for the
    two schedules' ``tiled.schedule_build`` on a pool's threads."""
    from photon_ml_tpu.obs.trace import span

    with span("tiled.batch_build", rows=int(labels.shape[0]), shards=1):
        return _build_tiled_batch(
            rows, feats, vals, labels, offsets, weights, dim,
            params=params, max_dense_columns=max_dense_columns,
        )


def _build_tiled_batch(
    rows, feats, vals, labels, offsets, weights, dim, *, params,
    max_dense_columns,
) -> TiledSparseBatch:
    nz = vals != 0
    if not nz.all():
        rows, feats, vals = rows[nz], feats[nz], vals[nz]
    win = params.window
    n = labels.shape[0]
    n_pad = max(((n + win - 1) // win) * win, win)
    d_pad = max(((dim + win - 1) // win) * win, win)
    vals, dense_cols, dense_vals = _dense_split_spanned(
        rows, feats, vals, n, dim, max_dense_columns
    )
    entries = len(vals) - int(np.count_nonzero(dense_vals))
    params = params.resolved(entries, (n_pad // win) * (d_pad // win))

    # the two passes are independent and numpy's sorts/gathers release the
    # GIL — overlap them (halves the dominant host cost of cold training)
    from concurrent.futures import ThreadPoolExecutor

    from photon_ml_tpu.obs.trace import bound_to_current_span
    from photon_ml_tpu.ops import schedule_cache as _sc

    # both passes key off the same COO triple: hash it once, up front
    digest = (
        _sc.content_digest(rows, feats, vals)
        if _sc.resolve_cache_dir() is not None else None
    )
    builds = _sc.stats().builds
    build = bound_to_current_span(_build_schedule)
    with ThreadPoolExecutor(2) as pool:
        fz = pool.submit(
            build, rows, feats, vals, params=params,
            sort_by_feature_block=False, num_out_blocks=n_pad // win,
            digest=digest, entries=entries, dense_columns=len(dense_cols),
        )
        fg = pool.submit(
            build, rows, feats, vals, params=params,
            sort_by_feature_block=True, num_out_blocks=d_pad // win,
            digest=digest, entries=entries, dense_columns=len(dense_cols),
        )
        z_sched = fz.result()
        g_sched = fg.result()
    _count_dense_entries(dense_vals, builds, 1)
    lab = np.zeros(n_pad, np.float32)
    lab[:n] = labels
    off = np.zeros(n_pad, np.float32)
    off[:n] = offsets
    wgt = np.zeros(n_pad, np.float32)
    wgt[:n] = weights
    return TiledSparseBatch(
        meta=_TiledMeta(
            params=params,
            num_rows=n_pad,
            dim=d_pad,
            num_real_rows=n,
            real_dim=dim,
        ),
        z_sched=z_sched,
        g_sched=g_sched,
        g_vals_sq=g_sched.vals**2,
        labels=jnp.asarray(lab),
        offsets=jnp.asarray(off),
        weights=jnp.asarray(wgt),
        **_dense_leaves(dense_cols, dense_vals, n_pad, 1),
    )


def _dense_leaves(
    dense_cols: np.ndarray, dense_vals: np.ndarray, rows_a_shard: int,
    shards: int,
) -> dict:
    """``_split_dense_columns``' K ids and [K, n] values as the batch's two
    leaves: rows zero-padded to ``shards * rows_a_shard``, then one [K,
    rows_a_shard] segment a shard along axis 0 (global row r sits at
    position r in both layouts). Nothing where K = 0."""
    k, n = dense_vals.shape
    if not k:
        return {}
    padded = np.zeros((k, shards * rows_a_shard), np.float32)
    padded[:, :n] = dense_vals
    by_shard = padded.reshape(k, shards, rows_a_shard).transpose(1, 0, 2)
    return dict(
        dense_cols=jnp.asarray(np.tile(dense_cols, shards)),
        dense_vals=jnp.asarray(by_shard.reshape(shards * k, rows_a_shard)),
    )


def bucket_spill(batch: TiledSparseBatch) -> TiledSparseBatch:
    """``batch`` with each schedule's spilled tail zero-padded from its
    lane multiple up to a multiple of a 16th–32nd of its own length
    (padding slots carry val 0 at coordinate 0: inert adds, < 7% more of a
    pass that is itself a few percent of an evaluation).

    The tail's length is part of the compiled fit's shape, and it follows
    the ROW ORDER: rows that land in other 8,192-row blocks (input files
    read in another order) move tile counts across the spill rule by a
    few hundred entries, and every such run compiled the fit anew (4.6 s
    at 524,288 x 65) with the persistent compile cache warm. Bucketed,
    near-equal tails share one program. Single-device layout only: a mesh
    layout's tail is one padded segment a shard."""
    if batch.meta.data_shards != 1:
        return batch

    def padded(sched: _Schedule) -> _Schedule:
        s = int(sched.spill_vals.shape[0])
        step = max(128, 1 << max(s.bit_length() - 5, 0))
        extra = -s % step
        if not extra:
            return sched
        return sched._replace(
            spill_out=jnp.pad(sched.spill_out, (0, extra)),
            spill_in=jnp.pad(sched.spill_in, (0, extra)),
            spill_vals=jnp.pad(sched.spill_vals, (0, extra)),
        )

    return batch._replace(
        z_sched=padded(batch.z_sched), g_sched=padded(batch.g_sched)
    )


def tiled_batch_from_sparse(
    batch, dim: int, *, params: TileParams = TileParams(),
    max_dense_columns: int = MAX_DENSE_COLUMNS,
):
    """Convenience: SparseBatch (padded ELL) -> TiledSparseBatch."""
    rows, feats, vals, _ = _sparse_coo(batch)
    return build_tiled_batch(
        rows, feats, vals,
        np.asarray(batch.labels), np.asarray(batch.offsets),
        np.asarray(batch.weights),
        dim, params=params, max_dense_columns=max_dense_columns,
    )


def _sparse_coo(batch) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """SparseBatch -> filtered COO triples (+ real row count): zero values
    and weight-0 (padding) rows dropped. Row-major order. Where nothing is
    dropped (no padding, no explicit zeros) no entry is gathered: at 34M
    entries the masked copies were most of a schedule build's host time.
    Under ``tiled.coo`` (``entries``): the batch's arrays pulled to the
    host and a row id made for every entry, 5 s of 168M entries."""
    from photon_ml_tpu.obs.trace import span

    with span("tiled.coo") as sp:
        out = _sparse_coo_unspanned(batch)
        sp.set(entries=len(out[2]))
    return out


def _sparse_coo_unspanned(batch):
    indices = np.asarray(batch.indices)
    values = np.asarray(batch.values)
    weights = np.asarray(batch.weights)
    n, k = indices.shape
    keep = values != 0
    live = weights > 0
    if not live.all():
        keep &= live[:, None]
    if keep.all():
        rows = np.repeat(np.arange(n, dtype=np.int64), k)
        return (
            rows, indices.reshape(-1).astype(np.int64),
            values.reshape(-1).astype(np.float32, copy=False), n,
        )
    return (
        np.nonzero(keep)[0].astype(np.int64, copy=False),
        indices[keep].astype(np.int64),
        values[keep].astype(np.float32, copy=False), n,
    )


def pad_row_vector(vec, total: int) -> Array:
    """One row vector zero-padded to ``total`` float32 rows. A vector that
    is already on the device is padded THERE: in coordinate descent the
    offsets are the device-resident residual, and pulling them to the host
    would block on the scoring queued before the solve and move the vector
    both ways, a synchronous readback outside the counted
    ``overlap.device_get`` seam."""
    if isinstance(vec, jax.Array):
        vec = vec.astype(jnp.float32)
        return jnp.pad(vec, (0, total - vec.shape[0]))
    out = np.zeros(total, np.float32)
    out[: len(vec)] = np.asarray(vec)
    return jnp.asarray(out)


def _padded_row_meta(batch, total: int):
    return (
        pad_row_vector(batch.labels, total),
        pad_row_vector(batch.offsets, total),
        pad_row_vector(batch.weights, total),
    )


def _concat_cell_schedules(
    local_rows: np.ndarray,
    local_feats: np.ndarray,
    vals: np.ndarray,
    cell_of: np.ndarray,
    n_cells: int,
    *,
    params: TileParams,
    z_out_blocks: int,
    g_out_blocks: int,
) -> Tuple[_Schedule, _Schedule, np.ndarray]:
    """Per-cell z/grad schedules padded to ONE static shape and
    concatenated along the step axis (cells in ``cell_of`` order) so a
    shard_map split hands each device its own schedule. Returns
    (z_sched, g_sched, g_vals numpy) — callers square g_vals for the
    hessian-diagonal pass."""
    from concurrent.futures import ThreadPoolExecutor

    def _cell_pair(c):
        m = cell_of == c
        lr, lf, vl = local_rows[m], local_feats[m], vals[m]
        return (
            _build_schedule_np(
                lr, lf, vl, params=params, sort_by_feature_block=False,
                num_out_blocks=z_out_blocks,
            ),
            _build_schedule_np(
                lr, lf, vl, params=params, sort_by_feature_block=True,
                num_out_blocks=g_out_blocks,
            ),
        )

    with ThreadPoolExecutor(min(8, n_cells)) as pool:
        pairs = list(pool.map(_cell_pair, range(n_cells)))
        z_parts = [p[0] for p in pairs]
        g_parts = [p[1] for p in pairs]
        gz = max(p[0].shape[0] for p in z_parts)
        gg = max(p[0].shape[0] for p in g_parts)
        sz = max(p[8].shape[0] for p in z_parts)
        sg = max(p[8].shape[0] for p in g_parts)
        # the per-cell pad-to-common-shape copies were the last serial
        # stretch of the sharded build — numpy concatenate releases the
        # GIL, so they overlap on the same pool
        z_parts = list(pool.map(
            lambda p: _pad_schedule_np(p, gz, z_out_blocks, sz), z_parts
        ))
        g_parts = list(pool.map(
            lambda p: _pad_schedule_np(p, gg, g_out_blocks, sg), g_parts
        ))
    # (a generator: one concatenated host copy alive at a time)
    z_sched = _upload_schedule(
        np.concatenate([p[i] for p in z_parts]) for i in range(9)
    )
    g_sched = _upload_schedule(
        np.concatenate([p[i] for p in g_parts]) for i in range(9)
    )
    return z_sched, g_sched, np.concatenate([p[5] for p in g_parts])


# photon: sharding(axes=[data], in=?, out=[data])
def build_sharded_tiled_batch(
    batch,
    dim: int,
    n_shards: int,
    *,
    params: TileParams = TileParams(),
    mesh=None,
    axis: Optional[str] = None,
    max_dense_columns: int = MAX_DENSE_COLUMNS,
) -> TiledSparseBatch:
    """SparseBatch -> mesh-layout TiledSparseBatch: the fast kernel AND
    data parallelism simultaneously (the reference's hot loop property,
    ValueAndGradientAggregator.scala:235-250).

    Rows split into ``n_shards`` contiguous ranges (each padded to the tile
    window); each range gets its OWN z/grad schedule built in its local row
    space; all schedules are padded to one static shape and concatenated
    along axis 0. Under shard_map with the batch's leaves split over the
    data axis, every device then sees exactly a single-shard
    TiledSparseBatch (the meta describes the per-shard view) and runs the
    unmodified Pallas kernels; the objective's ``axis_name`` psums do the
    cross-device reduction. With ``mesh`` given, leaves are placed with
    rows/steps sharded over ``axis`` (default "data"). A dense column of
    the WHOLE batch goes beside the schedules as in ``build_tiled_batch``,
    each device holding its own rows' segment.
    """
    from photon_ml_tpu.obs.trace import span

    with span(
        "tiled.batch_build", rows=int(batch.labels.shape[0]),
        shards=n_shards,
    ):
        return _build_sharded_tiled_batch(
            batch, dim, n_shards, params=params, mesh=mesh, axis=axis,
            max_dense_columns=max_dense_columns,
        )


def _build_sharded_tiled_batch(
    batch, dim, n_shards, *, params, mesh, axis, max_dense_columns
) -> TiledSparseBatch:
    from photon_ml_tpu.obs.trace import span
    from photon_ml_tpu.ops import schedule_cache as _sc

    win = params.window
    rows, feats, vals, n = _sparse_coo(batch)
    rows_per = -(-n // n_shards)
    R = max(((rows_per + win - 1) // win) * win, win)
    d_pad = max(((dim + win - 1) // win) * win, win)
    vals, dense_cols, dense_vals = _dense_split_spanned(
        rows, feats, vals, n, dim, max_dense_columns
    )
    entries = len(vals) - int(np.count_nonzero(dense_vals))
    params = params.resolved(
        entries, n_shards * (R // win) * (d_pad // win)
    )
    shard_of = rows // R
    local_rows = rows - shard_of * R

    builds = _sc.stats().builds
    with span(
        "tiled.schedule_build", entries=entries,
        dense_columns=len(dense_cols), shards=n_shards,
    ) as sp:
        z_sched, g_sched, g_vals = _concat_cell_schedules(
            local_rows, feats, vals, shard_of, n_shards,
            params=params, z_out_blocks=R // win,
            g_out_blocks=d_pad // win,
        )
        sp.set(cache="miss" if _sc.stats().builds > builds else "hit")
    _count_dense_entries(dense_vals, builds, n_shards)
    g_vals_sq = jnp.asarray(g_vals**2)
    lab, off, wgt = _padded_row_meta(batch, n_shards * R)
    out = TiledSparseBatch(
        meta=_TiledMeta(
            params=params, num_rows=R, dim=d_pad, num_real_rows=n,
            real_dim=dim, data_shards=n_shards,
        ),
        z_sched=z_sched,
        g_sched=g_sched,
        g_vals_sq=g_vals_sq,
        labels=lab,
        offsets=off,
        weights=wgt,
        **_dense_leaves(dense_cols, dense_vals, R, n_shards),
    )
    if mesh is not None:
        with span("tiled.upload", bytes=sum(
            int(a.nbytes) for a in jax.tree.leaves(out)
        )):
            out = _place_data_sharded(out, mesh, axis or DATA_AXIS)
    return out


@jax.tree_util.register_static
@dataclass(frozen=True)
class _FeatureShardedTiledMeta:
    """Static metadata for FeatureShardedTiledBatch: shapes describe ONE
    (data shard x feature block) cell — the per-device view."""

    params: TileParams
    rows_per_shard: int  # padded rows per data shard
    block_dim: int  # padded features per model block (multiple of window)
    num_real_rows: int
    real_dim: int
    data_shards: int
    model_shards: int


class FeatureShardedTiledBatch(NamedTuple):
    """The 10B-coefficient layout on the FAST kernel: a SparseBatch
    re-laid-out for a 2-D (data x model) mesh with one tiled schedule per
    (data shard, feature block) cell.

    Each cell's z-schedule produces that feature block's PARTIAL margins
    for its row shard (psum over "model" completes them); its g-schedule
    produces the block-local gradient (psum over "data" completes it) —
    same collective pattern as parallel.distributed's scatter-based sparse
    layout, but running the Pallas bilinear kernels instead of XLA's
    gather/scatter loops.

    Schedule leaves concatenate cells along axis 0 in data-major,
    model-minor order, all cells padded to one static shape, so shard_map
    splits them with ``P((data, model))``. Row metadata is sharded over
    "data" only (replicated across feature blocks). Global feature id
    f lives at w[(f // block_dim) * block_dim + f % block_dim] — blocks
    are contiguous ranges, so w[:real_dim] are the real coefficients.
    """

    meta: _FeatureShardedTiledMeta
    z_sched: _Schedule
    g_sched: _Schedule
    labels: Array
    offsets: Array
    weights: Array


# photon: sharding(axes=[data,model], in=?, out=[data+model])
def feature_shard_tiled_batch(
    batch,
    dim: int,
    data_shards: int,
    model_shards: int,
    *,
    params: TileParams = TileParams(),
    mesh=None,
    data_axis: str = DATA_AXIS,
    model_axis: str = MODEL_AXIS,
) -> Tuple[FeatureShardedTiledBatch, int]:
    """SparseBatch -> (FeatureShardedTiledBatch, block_dim).

    ``block_dim`` (features per model block) is rounded up to a multiple of
    the tile window so every block's local feature space is tile-aligned;
    the sharded coefficient vector has length model_shards * block_dim.
    With ``mesh`` given, leaves are placed with schedules sharded over
    (data, model) and row metadata over data.
    """
    win = params.window
    rows, feats, vals, n = _sparse_coo(batch)
    rows_per = -(-n // data_shards)
    R = max(((rows_per + win - 1) // win) * win, win)
    block_dim = -(-dim // model_shards)
    block_dim = max(((block_dim + win - 1) // win) * win, win)
    params = params.resolved(
        len(vals),
        data_shards * model_shards * (R // win) * (block_dim // win),
    )

    ds_of = rows // R
    local_rows = rows - ds_of * R
    mb_of = feats // block_dim
    local_feats = feats - mb_of * block_dim
    cell_of = ds_of * model_shards + mb_of

    z_sched, g_sched, _ = _concat_cell_schedules(
        local_rows, local_feats, vals, cell_of,
        data_shards * model_shards, params=params,
        z_out_blocks=R // win, g_out_blocks=block_dim // win,
    )
    lab, off, wgt = _padded_row_meta(batch, data_shards * R)
    out = FeatureShardedTiledBatch(
        meta=_FeatureShardedTiledMeta(
            params=params, rows_per_shard=R, block_dim=block_dim,
            num_real_rows=n, real_dim=dim, data_shards=data_shards,
            model_shards=model_shards,
        ),
        z_sched=z_sched,
        g_sched=g_sched,
        labels=lab,
        offsets=off,
        weights=wgt,
    )
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        cell_sh = NamedSharding(mesh, P((data_axis, model_axis)))
        row_sh = NamedSharding(mesh, P(data_axis))
        out = FeatureShardedTiledBatch(
            meta=out.meta,
            z_sched=_Schedule(*(
                jax.device_put(a, cell_sh) for a in out.z_sched
            )),
            g_sched=_Schedule(*(
                jax.device_put(a, cell_sh) for a in out.g_sched
            )),
            labels=jax.device_put(out.labels, row_sh),
            offsets=jax.device_put(out.offsets, row_sh),
            weights=jax.device_put(out.weights, row_sh),
        )
    return out, block_dim


def tiled_block_local_vg(loss, batch: FeatureShardedTiledBatch,
                         data_axis: str, model_axis: str, l2,
                         *, shift=None, factor=None,
                         interpret: bool = False, mxu: str = "bf16x2w"):
    """Block-local (value, grad) closure over ONE device's cell of a
    FeatureShardedTiledBatch (call inside shard_map). The distributed.py
    fit entry points wrap this with the unmodified L-BFGS/OWL-QN.

    ``shift``/``factor``: this feature block's slice of the lazy
    normalization vectors (NormalizationContext.scala:119-157 applied
    inside the aggregator): margins use w_eff = factor * w and subtract
    the psum'd shift.w_eff scalar; gradients un-shift with the data-psum'd
    prefactor — normalization shards trivially along the feature axis."""
    meta = batch.meta
    p = meta.params
    win = p.window

    def vg(w_block):
        w_eff = w_block if factor is None else w_block * factor
        w2d = w_eff.reshape((meta.block_dim // win, p.s_hi, p.s_lo))
        z_partial = _bilinear_pass_auto(
            batch.z_sched, w2d, meta.rows_per_shard // win, p,
            interpret=interpret, mxu=mxu,
            name=MARGIN_KERNEL,
        ).reshape(-1)
        z_partial = batch.z_sched.apply_spill(z_partial, w_eff)
        if shift is not None:
            z_partial = z_partial - jnp.vdot(shift, w_eff)
        z = jax.lax.psum(z_partial, model_axis) + batch.offsets
        c = batch.weights * loss.d1(z, batch.labels)
        value = jax.lax.psum(
            jnp.sum(batch.weights * loss.value(z, batch.labels)), data_axis
        )
        c2d = c.reshape((meta.rows_per_shard // win, p.s_hi, p.s_lo))
        g_local = _bilinear_pass_auto(
            batch.g_sched, c2d, meta.block_dim // win, p,
            interpret=interpret, mxu=mxu,
            name=GRADIENT_KERNEL,
        ).reshape(-1)
        g_local = batch.g_sched.apply_spill(g_local, c)
        grad_block = jax.lax.psum(g_local, data_axis)
        if shift is not None or factor is not None:
            prefactor = jax.lax.psum(jnp.sum(c), data_axis)
            if shift is not None:
                grad_block = grad_block - shift * prefactor
            if factor is not None:
                grad_block = grad_block * factor
        w_sq = jax.lax.psum(jnp.vdot(w_block, w_block), model_axis)
        return value + 0.5 * l2 * w_sq, grad_block + l2 * w_block

    return vg


def tiled_block_local_hvp_factory(
    loss, batch: FeatureShardedTiledBatch,
    data_axis: str, model_axis: str, l2,
    *, shift=None, factor=None,
    interpret: bool = False, mxu: str = "bf16x2w",
):
    """Block-local Hessian-vector FACTORY over one device's cell of a
    FeatureShardedTiledBatch (call inside shard_map) — the tiled twin of
    parallel.distributed._sparse_block_hvp_factory
    (HessianVectorAggregator.scala:137-152). The Hv pass reuses the
    z-schedule for the direction expansion and the g-schedule for the
    accumulation — same static layout, different contraction — so the
    reference's hottest distributed loop (one Hv per CG step,
    TRON.scala:259-341) runs at full kernel speed. The w-only pieces
    (margins psum, second-derivative coefficients) are computed once per
    outer TRON iteration."""
    meta = batch.meta
    p = meta.params
    win = p.window

    def _z(x_block):
        # x_block is already in EFFECTIVE space (callers apply factor);
        # the shift correction is one block-local scalar folded into the
        # model-axis psum
        x2d = x_block.reshape((meta.block_dim // win, p.s_hi, p.s_lo))
        part = _bilinear_pass_auto(
            batch.z_sched, x2d, meta.rows_per_shard // win, p,
            interpret=interpret, mxu=mxu,
            name=MARGIN_KERNEL,
        ).reshape(-1)
        part = batch.z_sched.apply_spill(part, x_block)
        if shift is not None:
            part = part - jnp.vdot(shift, x_block)
        return part

    def _eff(x_block):
        return x_block if factor is None else x_block * factor

    def factory(w_block):
        z = jax.lax.psum(_z(_eff(w_block)), model_axis) + batch.offsets
        d2c = batch.weights * loss.d2(z, batch.labels)

        def hvp(d_block):
            zd = jax.lax.psum(_z(_eff(d_block)), model_axis)
            c = d2c * zd
            c2d = c.reshape((meta.rows_per_shard // win, p.s_hi, p.s_lo))
            h_local = _bilinear_pass_auto(
                batch.g_sched, c2d, meta.block_dim // win, p,
                interpret=interpret, mxu=mxu,
                name=GRADIENT_KERNEL,
            ).reshape(-1)
            h_local = batch.g_sched.apply_spill(h_local, c)
            h_block = jax.lax.psum(h_local, data_axis)
            if shift is not None or factor is not None:
                prefactor = jax.lax.psum(jnp.sum(c), data_axis)
                if shift is not None:
                    h_block = h_block - shift * prefactor
                if factor is not None:
                    h_block = h_block * factor
            return h_block + l2 * d_block

        return hvp

    return factory


def tiled_block_local_hdiag(
    loss, batch: FeatureShardedTiledBatch,
    data_axis: str, model_axis: str, l2,
    *, shift=None, factor=None,
    interpret: bool = False, mxu: str = "bf16x2w",
):
    """Block-local Hessian-DIAGONAL closure over one device's cell — the
    variance computation of DistributedOptimizationProblem.scala:79-93 on
    the feature-sharded layout. Hdiag is block-local by construction
    (diag_j only touches feature j's entries), so it shards trivially:
    one g-pass with squared values psum'd over "data" (plus the S1/S0
    shifted-space terms when normalization is active)."""
    meta = batch.meta
    p = meta.params
    win = p.window

    def hdiag(w_block):
        w_eff = w_block if factor is None else w_block * factor
        w2d = w_eff.reshape((meta.block_dim // win, p.s_hi, p.s_lo))
        z_partial = _bilinear_pass_auto(
            batch.z_sched, w2d, meta.rows_per_shard // win, p,
            interpret=interpret, mxu=mxu,
            name=MARGIN_KERNEL,
        ).reshape(-1)
        z_partial = batch.z_sched.apply_spill(z_partial, w_eff)
        if shift is not None:
            z_partial = z_partial - jnp.vdot(shift, w_eff)
        z = jax.lax.psum(z_partial, model_axis) + batch.offsets
        c = batch.weights * loss.d2(z, batch.labels)
        c2d = c.reshape((meta.rows_per_shard // win, p.s_hi, p.s_lo))

        def g_pass(vals, spill_vals):
            out = _bilinear_pass_auto(
                batch.g_sched, c2d, meta.block_dim // win, p,
                vals=vals, interpret=interpret, mxu=mxu,
                name=GRADIENT_KERNEL,
            ).reshape(-1)
            return batch.g_sched.apply_spill(out, c, vals=spill_vals)

        s2 = jax.lax.psum(
            g_pass(batch.g_sched.vals**2, batch.g_sched.spill_vals**2),
            data_axis,
        )
        if shift is not None:
            s1 = jax.lax.psum(g_pass(None, None), data_axis)
            s0 = jax.lax.psum(jnp.sum(c), data_axis)
            diag = s2 - 2.0 * shift * s1 + (shift**2) * s0
        else:
            diag = s2
        if factor is not None:
            diag = diag * factor**2
        return diag + l2

    return hdiag


def _place_data_sharded(batch: TiledSparseBatch, mesh, axis: str):
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda a: jax.device_put(a, sharding), batch)


# In-memory conversion caches for ensure_tiled / ensure_tiled_sharded: a
# caller that wraps the SAME indices/values/weights arrays in a fresh
# SparseBatch per call (the GAME coordinate-descent pattern — only
# offsets change between sweeps) must not pay the multi-second schedule
# rebuild + host pull every call. Keyed by array identity; LRU-bounded
# because each entry pins a tiled batch in HBM. TWO separate caches (one
# per conversion flavor): a process interleaving
# single-device and sharded conversions — GAME with several FE shards
# plus a GLM grid — previously thrashed one shared 2-entry dict and
# silently rebuilt every sweep. Both sit in front of the persistent disk
# tier (ops/schedule_cache.py), which absorbs genuine evictions and
# process restarts.
from photon_ml_tpu.ops.schedule_cache import ScheduleLRU as _ScheduleLRU

_TILED_CACHE_MAX = 2
_SHARDED_CACHE_MAX = 2
_TILED_CACHE = _ScheduleLRU(_TILED_CACHE_MAX)
_SHARDED_CACHE = _ScheduleLRU(_SHARDED_CACHE_MAX)


def ensure_tiled(  # photon: entropy(id-keyed tiling memo; weakref-pinned, never serialized)
    batch,
    dim: int,
    *,
    params: Optional[TileParams] = None,
) -> TiledSparseBatch:
    """Idempotent single-device tiled conversion with the same
    identity-keyed LRU pattern as ensure_tiled_sharded (but its OWN
    bounded cache, so the two conversion flavors cannot evict each
    other): a SparseBatch sharing indices/values/weights with a previous
    call (the GAME coordinate-descent pattern — only offsets change
    between sweeps) reuses the cached schedules and only re-pads the row
    metadata."""
    if isinstance(batch, TiledSparseBatch):
        return batch
    key = (
        id(batch.indices), id(batch.values), id(batch.weights),
        dim, params,
    )
    hit = _TILED_CACHE.get(key)
    if hit is not None:
        (ix_ref, v_ref, w_ref), cached = hit
        if (
            ix_ref is batch.indices
            and v_ref is batch.values
            and w_ref is batch.weights
        ):
            meta = cached.meta
            lab, off, wgt = _padded_row_meta(batch, meta.num_rows)
            return cached._replace(labels=lab, offsets=off, weights=wgt)
        _TILED_CACHE.pop(key)  # stale id collision
    out = tiled_batch_from_sparse(
        batch, dim, params=params or TileParams()
    )
    _TILED_CACHE.put(
        key, ((batch.indices, batch.values, batch.weights), out),
    )
    return out


# photon: sharding(axes=[data], in=?, out=[data])
def ensure_tiled_sharded(  # photon: entropy(id-keyed tiling memo; weakref-pinned, never serialized)
    batch,
    dim: int,
    mesh,
    axis: str = DATA_AXIS,
    *,
    params: Optional[TileParams] = None,
) -> TiledSparseBatch:
    """Idempotent mesh-layout conversion (the tiled analog of
    parallel.mesh.ensure_data_sharded): SparseBatch -> sharded tiled build;
    an already-matching TiledSparseBatch passes through (so a lambda grid
    or coordinate-descent loop pays the schedule build + transfer once).
    A SparseBatch sharing indices/values/weights with a previous call
    reuses the cached schedules — only the row metadata (labels/offsets/
    weights, the parts a CD sweep changes) is re-padded and re-placed."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = int(mesh.shape[axis])
    if isinstance(batch, TiledSparseBatch):
        if batch.meta.data_shards != n:
            raise ValueError(
                f"TiledSparseBatch was laid out for {batch.meta.data_shards} "
                f"data shard(s) but the mesh's {axis!r} axis has {n}; "
                "rebuild from the SparseBatch with build_sharded_tiled_batch"
            )
        if getattr(batch.labels, "sharding", None) == NamedSharding(mesh, P(axis)):
            return batch
        return _place_data_sharded(batch, mesh, axis)
    key = (
        id(batch.indices), id(batch.values), id(batch.weights),
        dim, n, id(mesh), axis, params,
    )
    hit = _SHARDED_CACHE.get(key)
    if hit is not None:
        (ix_ref, v_ref, w_ref), cached = hit
        if (
            ix_ref is batch.indices
            and v_ref is batch.values
            and w_ref is batch.weights
        ):
            meta = cached.meta
            lab, off, wgt = _padded_row_meta(
                batch, meta.data_shards * meta.num_rows
            )
            row_sh = NamedSharding(mesh, P(axis))
            return cached._replace(
                labels=jax.device_put(lab, row_sh),
                offsets=jax.device_put(off, row_sh),
                weights=jax.device_put(wgt, row_sh),
            )
        _SHARDED_CACHE.pop(key)  # stale id collision
    out = build_sharded_tiled_batch(
        batch, dim, n, params=params or TileParams(), mesh=mesh, axis=axis
    )
    _SHARDED_CACHE.put(
        key, ((batch.indices, batch.values, batch.weights), out),
    )
    return out


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------


def _bilinear_pass_kernel(
    # scalar prefetch
    step_out_ref, step_in_ref, step_init_ref,
    # per-step entry blocks [1, L]
    in_pos_ref, out_pos_ref, vals_ref,
    # gathered-from window [1, S_HI, S_LO] (w2d for z-pass, c2d for grad)
    src_ref,
    # output window accumulator [1, S_HI, S_LO]
    out_ref,
    *,
    s_hi: int,
    s_lo: int,
    chunk: int,
    mxu: str,
    onehot: str = "compare",
):
    """One grid step: expand src at in_pos, multiply by vals,
    bilinear-scatter into the out_pos output window.

    Entries live on LANES ([1, L] rows); one-hots are sublane-iota
    compares, so each one-hot is [S, L] with the entry axis last and both
    matmuls contract without any transpose relayout.
    """
    g = pl.program_id(0)
    L = chunk
    # Entry blocks are [8, L] (8 steps' rows — sublane dim must tile by 8);
    # select this step's row with a sublane one-hot mask + reduce (dynamic
    # sublane slicing would relayout; the mask is cheap VPU work).
    r = jax.lax.rem(g, 8)
    row_sel = (
        jax.lax.broadcasted_iota(jnp.int32, (8, L), 0) == r
    )
    ip_full = jnp.sum(
        jnp.where(row_sel, in_pos_ref[...], 0), axis=0, keepdims=True
    )  # [1, L] int32, window-local = hi * s_lo + lo
    op_full = jnp.sum(
        jnp.where(row_sel, out_pos_ref[...], 0), axis=0, keepdims=True
    )
    v_full = jnp.sum(
        jnp.where(row_sel, vals_ref[...], 0.0), axis=0, keepdims=True
    )  # [1, L] float32

    def _split(x):
        # hi + lo bf16 terms of an f32 array (~16 mantissa bits kept)
        hi_part = x.astype(jnp.bfloat16)
        lo_part = (x - hi_part.astype(jnp.float32)).astype(jnp.bfloat16)
        return hi_part, lo_part

    def _expand(idx, s, width, dt):
        """Positional expansion: [1, width] window-local indices ->
        [s, width] one-hot rows.

        ``onehot="compare"`` (default): sublane-iota equality compare —
        the round-2 build, one [s, width] VPU compare + select chain.

        ``onehot="mxu"``: the round-3 "pack the one-hot build itself
        onto the MXU" lever. 1 - (i - ix)^2 comes from ONE tiny
        [s, 3] x [3, width] matmul over packed features [1, ix, ix^2]
        (lhs rows [1 - i^2, 2i, -1]); a single relu blends it to the
        exact 0/1 indicator, since integer mismatches give d >= 1.
        f32 HIGHEST keeps ix^2 exact (< 2^14 << 2^24 mantissa range) —
        one-hot EXACTNESS, which the bf16 split relies on, survives.
        Trades the [s, width] compare chain for a matmul + one
        elementwise pass; whether Mosaic schedules it better than the
        compare has no ledger row yet (ROADMAP S1b)."""
        if onehot == "mxu":
            # Mosaic's iota is integer-only: count in int32, then cast
            i_col = jax.lax.broadcasted_iota(
                jnp.int32, (s, 1), 0
            ).astype(jnp.float32)
            lhs = jnp.concatenate(
                [1.0 - i_col * i_col, 2.0 * i_col, -jnp.ones_like(i_col)],
                axis=1,
            )  # [s, 3]
            idx_f = idx.astype(jnp.float32)
            rhs = jnp.concatenate(
                [jnp.ones_like(idx_f), idx_f, idx_f * idx_f], axis=0
            )  # [3, width]
            d = jax.lax.dot_general(
                lhs, rhs, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )  # [s, width] = 1 - (i - ix)^2
            return jnp.maximum(d, 0.0).astype(dt)
        iota = jax.lax.broadcasted_iota(jnp.int32, (s, width), 0)
        return (idx == iota).astype(dt)

    def _bf16_dot(lhs, rhs, dims):
        """Single-pass MXU product of bf16 operands, f32 accumulation.
        The precision is pinned: under a global
        ``jax.default_matmul_precision("highest")`` (a reference check's
        setting) an unpinned bf16 dot asks Mosaic for an fp32 contraction
        of bf16 operands, which it refuses to compile."""
        return jax.lax.dot_general(
            lhs, rhs, dims,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )

    # gather -> contrib -> scatter over the L entry lanes ->
    # update [S_HI, S_LO]
    ih = ip_full // s_lo
    il = ip_full - ih * s_lo
    oh = op_full // s_lo
    ol = op_full - oh * s_lo
    dims_in = (((0,), (0,)), ((), ()))
    dims_out = (((1,), (1,)), ((), ()))

    if mxu == "bf16x2w":
        # One-hot matrices are 0/1 — EXACT in bf16. Only the data
        # operand carries mantissa, so instead of Precision.HIGHEST (6
        # bf16 MXU passes for f32 x f32) the data side is split into
        # two bf16 terms (hi + lo, ~16 mantissa bits, ~1e-5 rel error),
        # and the TWO half-width matmuls that would take fuse into ONE
        # full-width matmul by packing the hi and lo terms into the
        # otherwise idle half of the MXU tile (s_lo = 64 uses 64 of 128
        # sublanes/lanes).
        oh_in_hi = _expand(ih, s_hi, L, jnp.bfloat16)  # [S_HI, L]

        # gather: pack [hi | lo] along the lane axis -> [S_HI, 2*S_LO]
        s1, s2 = _split(src_ref[0])
        src_cat = jnp.concatenate([s1, s2], axis=1)
        a_cat = _bf16_dot(
            src_cat, oh_in_hi, dims_in
        )  # [2*S_LO, L]: rows [0,S_LO) = hi terms, [S_LO,2*S_LO) = lo
        # fold the halves first (sublane slice at a multiple of 8) so
        # the mask-reduce runs at [S_LO, L] instead of [2*S_LO, L]
        a = a_cat[:s_lo] + a_cat[s_lo:]
        oh_in_lo = _expand(il, s_lo, L, jnp.float32)
        src_g = jnp.sum(a * oh_in_lo, axis=0, keepdims=True)  # [1, L]
        contrib = v_full * src_g

        # scatter: RHS rows [0,S_LO) carry onehot*c_hi, [S_LO,2*S_LO)
        # carry onehot*c_lo -> one [S_HI, 2*S_LO] product; the two lane
        # halves fold with an exact VPU add. The RHS is built from ONE
        # [S_LO, L] one-hot compare + a sublane concat (round 2 used a
        # [2*S_LO, L] compare + arithmetic 0/1 blend — twice the VPU
        # compare work for the same matrix).
        c1, c2 = _split(contrib)
        oh_out_hi = _expand(oh, s_hi, L, jnp.bfloat16)
        oh_out_lo = _expand(ol, s_lo, L, jnp.bfloat16)
        rhs = jnp.concatenate(
            [oh_out_lo * c1, oh_out_lo * c2], axis=0
        )  # [2*S_LO, L]
        update_wide = _bf16_dot(
            oh_out_hi, rhs, dims_out
        )  # [S_HI, 2*S_LO]
        update = update_wide[:, :s_lo] + update_wide[:, s_lo:]
    else:  # "highest": full f32 emulation, slower, ~1e-7 rel error
        oh_in_hi = _expand(ih, s_hi, L, jnp.float32)
        oh_in_lo = _expand(il, s_lo, L, jnp.float32)
        a = jax.lax.dot_general(
            src_ref[0], oh_in_hi, dims_in,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        src_g = jnp.sum(a * oh_in_lo, axis=0, keepdims=True)
        contrib = v_full * src_g
        oh_out_hi = _expand(oh, s_hi, L, jnp.float32)
        oh_out_lo = _expand(ol, s_lo, L, jnp.float32)
        update = jax.lax.dot_general(
            oh_out_hi, oh_out_lo * contrib, dims_out,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    @pl.when(step_init_ref[g] == 1)
    def _():
        out_ref[0] = update

    @pl.when(step_init_ref[g] != 1)
    def _():
        out_ref[0] = out_ref[0] + update


# The two directions of the bilinear pass, as the device trace names them.
MARGIN_KERNEL = "photon_tiled_margin"  # rows <- coefficients
GRADIENT_KERNEL = "photon_tiled_gradient"  # coefficients <- rows


def _grid_bilinear_pass(
    sched: _Schedule,
    src_bank: Array,  # [G, num_in_blocks, S_HI, S_LO]
    num_out_blocks: int,
    params: TileParams,
    vals: Optional[Array] = None,
    *,
    name: str,
) -> Array:
    """Grid-batched schedule application: ONE fused data pass serves every
    grid member (the λ-grid batching lever, ISSUE 5 / Podracer-style
    batched while_loops, arxiv 2104.06272).

    The per-member bilinear kernel computes out = A @ src where A is the
    sparse operator the schedule encodes; with a coefficient BANK the
    (n×d) sparse matvec becomes the (n×d) @ (d×G) blocked product. Here
    that product is one flat gather + one segment scatter-add over the
    schedule's flat (block*window + pos) coordinates with the grid axis
    riding the trailing (lane) dimension — every entry's schedule lookup,
    the dominant traffic, is paid once for the whole grid instead of once
    per λ. Flat coordinates must fit int32 (same bound the spill router
    enforces); the grid path's memory-budget gate keeps d_pad far below
    that.

    Returns [G, num_out_blocks, S_HI, S_LO]; spill entries are applied by
    the caller's ``apply_spill`` (take + scatter-add, which batches
    natively under vmap).
    """
    win = params.window
    S = sched.num_steps
    G = src_bank.shape[0]
    # the grid variant of the kernel's direction, under its own name
    with jax.named_scope(name + "_grid"):
        flat_in = (
            sched.step_in[:, None] * win + sched.in_pos[:S]
        ).reshape(-1)
        flat_out = (
            sched.step_out[:, None] * win + sched.out_pos[:S]
        ).reshape(-1)
        v = (sched.vals if vals is None else vals)[:S].reshape(-1)
        src_flat = src_bank.reshape(G, -1).T  # [num_in_blocks * win, G]
        contrib = v[:, None] * jnp.take(src_flat, flat_in, axis=0)
        out = jnp.zeros((num_out_blocks * win, G), src_flat.dtype)
        out = out.at[flat_out].add(contrib)
        return out.T.reshape(G, num_out_blocks, params.s_hi, params.s_lo)


def _bilinear_pass_auto(
    sched: _Schedule,
    src: Array,
    num_out_blocks: int,
    params: TileParams,
    *,
    vals: Optional[Array] = None,
    interpret: bool = False,
    mxu: str = "bf16x2w",
    onehot: str = "compare",
    name: str,
) -> Array:
    """:func:`_run_bilinear_pass` that stays ``jax.vmap``-able.

    Unbatched calls lower to the Pallas kernel unchanged. Under vmap
    (the batched λ-grid path vmaps the optimizers over a coefficient
    bank) a ``custom_vmap`` rule swaps in :func:`_grid_bilinear_pass`:
    one fused pass for the whole bank instead of per-member kernel
    launches — pallas_call's scalar-prefetch grid has no batching rule,
    and even if it did, G separate passes is exactly what the grid path
    exists to avoid. Only the ``src`` operand may be batched; the
    schedule and entry values are shared across the grid by construction.
    """
    import jax.custom_batching

    @jax.custom_batching.custom_vmap
    def run(sched_, src_, vals_):
        return _run_bilinear_pass(
            sched_, src_, num_out_blocks, params, vals=vals_,
            interpret=interpret, mxu=mxu, onehot=onehot, name=name,
        )

    @run.def_vmap
    def _rule(axis_size, in_batched, sched_, src_, vals_):
        sched_b, src_b, vals_b = in_batched
        if any(jax.tree_util.tree_leaves(sched_b)) or vals_b:
            raise NotImplementedError(
                "grid batching supports a batched coefficient/row operand "
                "only; the tile schedule is shared across the grid"
            )
        if not src_b:
            out = run(sched_, src_, vals_)
            return (
                jnp.broadcast_to(out, (axis_size,) + out.shape), True
            )
        return (
            _grid_bilinear_pass(
                sched_, src_, num_out_blocks, params, vals=vals_,
                name=name,
            ),
            True,
        )

    return run(sched, src, sched.vals if vals is None else vals)


def _run_bilinear_pass(
    sched: _Schedule,
    src: Array,  # [num_in_blocks, S_HI, S_LO]
    num_out_blocks: int,
    params: TileParams,
    *,
    vals: Optional[Array] = None,
    interpret: bool = False,
    mxu: str = "bf16x2w",
    onehot: str = "compare",
    name: str,
) -> Array:
    """-> [num_out_blocks, S_HI, S_LO] accumulated output. ``name`` is
    the kernel's name on the device (the pass's direction: its callers
    say ``photon_tiled_margin`` or ``photon_tiled_gradient``)."""
    G = sched.num_steps
    L = params.chunk
    entry_spec = pl.BlockSpec((8, L), lambda g, so, si, st: (g // 8, 0))
    src_spec = pl.BlockSpec(
        (1, params.s_hi, params.s_lo), lambda g, so, si, st: (si[g], 0, 0)
    )
    out_spec = pl.BlockSpec(
        (1, params.s_hi, params.s_lo), lambda g, so, si, st: (so[g], 0, 0)
    )
    kernel = partial(
        _bilinear_pass_kernel,
        s_hi=params.s_hi,
        s_lo=params.s_lo,
        chunk=L,
        mxu=mxu,
        onehot=onehot,
    )
    in_specs = [entry_spec, entry_spec, entry_spec, src_spec]
    operands = (
        sched.step_out, sched.step_in, sched.step_init,
        sched.in_pos, sched.out_pos,
        sched.vals if vals is None else vals,
        src,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(G,),
        in_specs=in_specs,
        out_specs=out_spec,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (num_out_blocks, params.s_hi, params.s_lo), jnp.float32
        ),
        interpret=interpret,
        name=name,
    )(*operands)
    return out


@dataclass(frozen=True)
class TiledGLMObjective:
    """GLMObjective-compatible fused objective over TiledSparseBatch data.

    Same math and signature contract as
    photon_ml_tpu.ops.objective.GLMObjective (sum-weighted loss, L2 added
    once, lazy shift/factor normalization, psum over ``axis_name`` if set),
    with the margins/gradient passes running the tiled Pallas kernels
    instead of gather/scatter. Methods take the batch as an argument (pass
    it through jit — it is a pytree).

    Scoring rides the margin pass too: :meth:`scores` is the same kernel
    launch for ORIGINAL-space coefficients, with no normalisation and no
    offsets (``models.glm.compute_scores``' numbers). It sees what the
    schedule holds: an entry of a row that ``_sparse_coo`` built out
    (weight 0) scores 0 here, so a caller whose rows were not all live at
    build time keeps the gather for them.
    """

    loss: object
    dim: int  # real (unpadded) coefficient dimension
    norm: NormalizationContext = None
    axis_name: Optional[str] = None
    interpret: bool = False
    # "bf16x2w" (default): hi+lo bf16 data split with both half-width
    # matmuls fused into one full-width MXU tile (~1e-5 rel err a product,
    # fastest); "highest": full f32 emulation (~1e-7), a variant for tests
    # and ``benchmark/proof.py`` that no driver selects. The split's
    # rounding is independent from column to column and averages out over
    # the rows, except for a column every row reads: such a DENSE column
    # is not in the schedules at all (module docstring), its term is
    # float32 under either variant, which is what lets the GAME driver's
    # fixed effect run the default.
    mxu: str = "bf16x2w"
    # Positional-expansion algorithm: "compare" (sublane-iota equality,
    # the round-2 build) or "mxu" (squared-distance matmul + relu — the
    # round-3 "pack the one-hot build onto the MXU" lever; exact 0/1
    # output either way, see _bilinear_pass_kernel._expand).
    onehot: str = "compare"

    def __post_init__(self):
        if self.norm is None:
            object.__setattr__(self, "norm", identity_context())
        if self.mxu not in ("bf16x2w", "highest"):
            # a typo must not silently fall through to the "highest"
            # branch (slower, different numerics)
            raise ValueError(f"unknown mxu variant {self.mxu!r}")
        if self.onehot not in ("compare", "mxu"):
            raise ValueError(f"unknown onehot variant {self.onehot!r}")

    def _psum(self, x):
        if self.axis_name is None:
            return x
        return jax.lax.psum(x, self.axis_name)

    def _pad(self, w: Array, batch: TiledSparseBatch) -> Array:
        if w.shape[0] == batch.dim:
            return w
        return jnp.zeros((batch.dim,), w.dtype).at[: w.shape[0]].set(w)

    def _z_pass(self, w_padded: Array, batch: TiledSparseBatch) -> Array:
        """raw row-sums [num_rows]: the tiled bilinear product, the spilled
        entries and the batch's dense columns."""
        b = batch
        p = b.params
        w2d = w_padded.reshape((b.num_feat_blocks, p.s_hi, p.s_lo))
        raw = _bilinear_pass_auto(
            b.z_sched, w2d, b.num_row_blocks, p,
            interpret=self.interpret, mxu=self.mxu, onehot=self.onehot,
            name=MARGIN_KERNEL,
        ).reshape(-1)
        with jax.named_scope("objective.spill"):
            raw = b.z_sched.apply_spill(raw, w_padded)
        if b.dense_vals is None:
            return raw
        # the dense columns, exactly: elementwise float32 and a sum over K,
        # no matmul for the caller's default precision to round
        with jax.named_scope("objective.dense"):
            w_dense = jnp.take(w_padded, b.dense_cols)
            return raw + jnp.sum(b.dense_vals * w_dense[:, None], axis=0)

    def _grad_pass(
        self, c_rows: Array, batch: TiledSparseBatch,
        vals: Optional[Array] = None,
        spill_vals: Optional[Array] = None,
    ) -> Array:
        b = batch
        p = b.params
        c2d = c_rows.reshape((b.num_row_blocks, p.s_hi, p.s_lo))
        g = _bilinear_pass_auto(
            b.g_sched, c2d, b.num_feat_blocks, p,
            vals=vals, interpret=self.interpret, mxu=self.mxu, onehot=self.onehot,
            name=GRADIENT_KERNEL,
        ).reshape(-1)
        with jax.named_scope("objective.spill"):
            g = b.g_sched.apply_spill(g, c_rows, vals=spill_vals)
        if b.dense_vals is None:
            return g
        with jax.named_scope("objective.dense"):
            # (``vals``: the hessian-diagonal pass squares the values)
            dense = b.dense_vals if vals is None else b.dense_vals**2
            return g.at[b.dense_cols].add(jnp.sum(dense * c_rows, axis=1))

    # -- margins -----------------------------------------------------------

    def margins(self, coef: Array, batch: TiledSparseBatch) -> Array:
        """z_i = x_eff_i . w_eff + offset_i in padded row space."""
        with jax.named_scope("objective.margins"):
            w_eff = self.norm.effective_coefficients(coef)
            raw = self._z_pass(self._pad(w_eff, batch), batch)
            return raw - self.norm.shift_dot(w_eff) + batch.offsets

    def scores(self, coef: Array, batch: TiledSparseBatch) -> Array:
        """x_i . coef in padded row space, for ORIGINAL-space ``coef``: the
        raw row sums of the margin pass (kernel ``photon_tiled_margin`` +
        the spilled entries + the dense columns' side term), what
        ``models.glm.compute_scores`` returns for the rows the batch
        holds. No normalisation, no offsets and no ``psum``: under
        ``shard_map`` each device scores its own rows.
        (:meth:`margins` takes NORMALISED-space coefficients.)"""
        with jax.named_scope("objective.scores"):
            return self._z_pass(self._pad(coef, batch), batch)

    # -- value / gradient --------------------------------------------------

    def value(self, coef: Array, batch: TiledSparseBatch, l2_weight=0.0) -> Array:
        z = self.margins(coef, batch)
        val = jnp.sum(batch.weights * self.loss.value(z, batch.labels))
        val = self._psum(val)
        return val + 0.5 * l2_weight * jnp.dot(coef, coef)

    def value_and_gradient(
        self, coef: Array, batch: TiledSparseBatch, l2_weight=0.0
    ) -> Tuple[Array, Array]:
        d_in = coef.shape[0]
        z = self.margins(coef, batch)
        with jax.named_scope("objective.loss"):
            lv = self.loss.value(z, batch.labels)
            ld = self.loss.d1(z, batch.labels)
            c = batch.weights * ld
            value_sum = jnp.sum(batch.weights * lv)
            prefactor_sum = jnp.sum(c)
        with jax.named_scope("objective.gradient"):
            vector_sum = self._grad_pass(c, batch)[:d_in]
        value_sum, vector_sum, prefactor_sum = self._psum(
            (value_sum, vector_sum, prefactor_sum)
        )
        with jax.named_scope("objective.gradient"):
            grad = self.norm.unshift_gradient(vector_sum, prefactor_sum)
            value = value_sum + 0.5 * l2_weight * jnp.dot(coef, coef)
            return value, grad + l2_weight * coef

    def gradient(self, coef: Array, batch: TiledSparseBatch, l2_weight=0.0) -> Array:
        return self.value_and_gradient(coef, batch, l2_weight)[1]

    # -- second order ------------------------------------------------------

    def hessian_vector(
        self, coef: Array, direction: Array, batch: TiledSparseBatch,
        l2_weight=0.0,
    ) -> Array:
        d_in = coef.shape[0]
        w_eff = self.norm.effective_coefficients(coef)
        d_eff = self.norm.effective_coefficients(direction)
        z = (
            self._z_pass(self._pad(w_eff, batch), batch)
            - self.norm.shift_dot(w_eff) + batch.offsets
        )
        zd = (
            self._z_pass(self._pad(d_eff, batch), batch)
            - self.norm.shift_dot(d_eff)
        )
        c = batch.weights * self.loss.d2(z, batch.labels) * zd
        vector_sum = self._grad_pass(c, batch)[:d_in]
        prefactor_sum = jnp.sum(c)
        vector_sum, prefactor_sum = self._psum((vector_sum, prefactor_sum))
        hv = self.norm.unshift_gradient(vector_sum, prefactor_sum)
        return hv + l2_weight * direction

    def hessian_diagonal(
        self, coef: Array, batch: TiledSparseBatch, l2_weight=0.0
    ) -> Array:
        d_in = coef.shape[0]
        z = self.margins(coef, batch)
        c = batch.weights * self.loss.d2(z, batch.labels)
        s2 = self._grad_pass(
            c, batch, vals=batch.g_vals_sq,
            spill_vals=batch.g_sched.spill_vals**2,
        )[:d_in]
        if self.norm.shift is not None:
            # shifted space needs S1 = sum c x and S0 = sum c as well
            s1 = self._grad_pass(c, batch)[:d_in]
            s0 = jnp.sum(c)
            s0, s1, s2 = self._psum((s0, s1, s2))
            diag = s2 - 2.0 * self.norm.shift * s1 + (self.norm.shift**2) * s0
        else:
            diag = self._psum(s2)
        if self.norm.factor is not None:
            diag = diag * self.norm.factor**2
        return diag + l2_weight

    # -- convenience -------------------------------------------------------

    def with_axis(self, axis_name: Optional[str]) -> "TiledGLMObjective":
        return TiledGLMObjective(
            self.loss, self.dim, self.norm, axis_name, self.interpret,
            self.mxu, self.onehot,
        )


# A pytree: the normalization vectors are leaves, everything else static
# aux — so the objective passes straight through jit as an ARGUMENT and
# equal-structure objectives share one persistent compile cache (the
# shared module-level jits in io/streaming.py ride on this).
jax.tree_util.register_dataclass(
    TiledGLMObjective,
    data_fields=["norm"],
    meta_fields=["loss", "dim", "axis_name", "interpret", "mxu", "onehot"],
)
