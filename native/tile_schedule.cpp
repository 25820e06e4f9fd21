// Native tiled-schedule builder for the Pallas sparse GLM kernels.
//
// Replaces the numpy schedule build in photon_ml_tpu/ops/tiled_sparse.py
// (_build_schedule_np) on the hot path: numpy's stable argsort of the
// 16.7M-entry tile keys holds the GIL and costs ~3-4 s per pass at the ads
// shape; tile ids take only num_out_blocks x num_in_blocks distinct values,
// so a stable COUNTING sort does the whole grouping in two O(n) passes
// (~0.15 s). The schedule semantics are identical to the numpy builder —
// its tests are the oracle (tests/test_tiled_sparse.py).
//
// Entry layout contract (mirrors _Schedule in tiled_sparse.py):
//   step_out[G], step_in[G], step_init[G]   int32
//   o_pos[G8*L], i_pos[G8*L]                int32 (window-local positions)
//   sv[G8*L]                                float32 (0 for padding slots)
// where G8 = ceil(G/8)*8 and the caller zero-initializes the outputs.
//
// Two-call protocol (stateless, no handle lifetime to manage):
//   ts_plan(...)  -> 0 + (steps, spilled) (or <0: numpy fallback)
//   ts_fill(...)  -> 0 ok / <0 error; fills the caller's arrays
//
// The pass is role-symmetric: the z-pass calls with (out=rows, in=feats),
// the gradient pass with (out=feats, in=rows) — same code path.
//
// An entry whose value is 0 is no entry: both calls pass over it (it takes
// no slot and no spill place). That is how a caller takes entries out
// without compacting three arrays: the batch builders zero a dense
// column's values (tiled_sparse._split_dense_columns).

#include <cstdint>
#include <cstring>
#include <vector>

namespace {

struct TileDims {
  int64_t n_in_blocks;
  int64_t n_tiles;
};

// Counting sort is only a win while the tile space is comparable to the
// entry count; past 4x entries (min 1M) the histogram dominates and the
// caller's numpy builder (comparison sort) is the right tool. Also keeps
// the per-call allocations bounded (~8 bytes/tile x 4 vectors).
int64_t max_tiles(int64_t n) {
  int64_t floor_tiles = int64_t(1) << 20;
  int64_t rel = 4 * n;
  return rel > floor_tiles ? rel : floor_tiles;
}

// Returns n_tiles <= 0 when any coordinate is negative or an out
// coordinate falls outside the declared output-block space — the caller
// then falls back to the numpy builder's Python-level error instead of
// this code indexing the histogram out of bounds.
TileDims tile_dims(const int64_t* out_coord, const int64_t* in_coord,
                   int64_t n, int64_t win, int64_t num_out_blocks) {
  int64_t max_in = 0;
  bool bad = false;
  for (int64_t i = 0; i < n; ++i) {
    if (in_coord[i] > max_in) max_in = in_coord[i];
    if (in_coord[i] < 0 || out_coord[i] < 0 ||
        out_coord[i] / win >= num_out_blocks) {
      bad = true;
    }
  }
  TileDims d;
  d.n_in_blocks = n ? (max_in / win + 1) : 1;
  d.n_tiles = bad ? -1 : num_out_blocks * d.n_in_blocks;
  return d;
}

}  // namespace

// Spill rule shared by the planning and fill passes (mirrors
// _build_schedule_np): a tile of c entries keeps n_chunks full chunks and
// routes `spill` tail entries to the caller's scatter path. `cap` <= 0
// disables spilling.
struct TilePlan {
  int64_t n_chunks;
  int64_t spill;
};

static TilePlan tile_plan(int64_t c, int64_t chunk, int64_t cap) {
  TilePlan p;
  int64_t full = c / chunk;
  int64_t rem = c % chunk;
  if (cap > 0 && c <= cap) {
    p.n_chunks = 0;
    p.spill = c;
  } else if (cap > 0 && rem > 0 && rem <= cap && full >= 1) {
    p.n_chunks = full;
    p.spill = rem;
  } else {
    p.n_chunks = full + (rem ? 1 : 0);
    p.spill = 0;
  }
  return p;
}

extern "C" {

// Plan a schedule: *steps = grid steps (data chunks + zero-entry init
// steps for output blocks with none), *spilled = spill entry count.
// Returns 0, or -1 when the tile space is too large for a counting sort
// (caller falls back to the numpy builder).
int64_t ts_plan(const int64_t* out_coord, const int64_t* in_coord,
                const float* vals, int64_t n, int64_t win, int64_t chunk,
                int64_t cap, int64_t num_out_blocks, int64_t* steps_out,
                int64_t* spilled_out) try {
  TileDims d = tile_dims(out_coord, in_coord, n, win, num_out_blocks);
  if (d.n_tiles <= 0 || d.n_tiles > max_tiles(n)) return -1;
  std::vector<int64_t> counts(static_cast<size_t>(d.n_tiles), 0);
  for (int64_t i = 0; i < n; ++i) {
    if (vals[i] == 0.0f) continue;
    int64_t t = (out_coord[i] / win) * d.n_in_blocks + in_coord[i] / win;
    ++counts[static_cast<size_t>(t)];
  }
  int64_t steps = 0;
  int64_t spilled = 0;
  for (int64_t ob = 0; ob < num_out_blocks; ++ob) {
    bool present = false;
    const int64_t* row = counts.data() + ob * d.n_in_blocks;
    for (int64_t ib = 0; ib < d.n_in_blocks; ++ib) {
      if (!row[ib]) continue;
      TilePlan p = tile_plan(row[ib], chunk, cap);
      spilled += p.spill;
      if (p.n_chunks) {
        present = true;
        steps += p.n_chunks;
      }
    }
    if (!present) ++steps;  // zero-entry init step
  }
  *steps_out = steps;
  *spilled_out = spilled;
  return 0;
} catch (...) {
  // bad_alloc etc. must not cross the ctypes boundary (std::terminate);
  // <0 routes the caller to the numpy fallback
  return -1;
}

// Fill a schedule. Outputs must be zero-initialized by the caller and sized
// step_out/step_in/step_init: [G]; o_pos/i_pos/sv: [ceil(G/8)*8 * chunk];
// sp_out/sp_in/sp_vals: [expected_spill]. Returns 0, or -1 on tile-space
// overflow / plan mismatch.
int64_t ts_fill(const int64_t* out_coord, const int64_t* in_coord,
                const float* vals, int64_t n, int64_t win, int64_t chunk,
                int64_t cap, int64_t num_out_blocks, int64_t expected_steps,
                int64_t expected_spill,
                int32_t* step_out, int32_t* step_in, int32_t* step_init,
                int32_t* o_pos, int32_t* i_pos, float* sv,
                int32_t* sp_out, int32_t* sp_in, float* sp_vals) try {
  TileDims d = tile_dims(out_coord, in_coord, n, win, num_out_blocks);
  if (d.n_tiles <= 0 || d.n_tiles > max_tiles(n)) return -1;
  std::vector<int64_t> counts(static_cast<size_t>(d.n_tiles), 0);
  for (int64_t i = 0; i < n; ++i) {
    if (vals[i] == 0.0f) continue;
    int64_t t = (out_coord[i] / win) * d.n_in_blocks + in_coord[i] / win;
    ++counts[static_cast<size_t>(t)];
  }

  // Walk tiles in (out block, in block) order, assigning each non-empty
  // tile its run of chunk steps (per the spill rule) and each OUT BLOCK
  // with no chunked tile one init step; record where each tile's KEPT
  // entries start in step space (step_base), how many it keeps (kept), and
  // where its spilled tail lands in the spill arrays (spill_base).
  std::vector<int64_t> step_base(static_cast<size_t>(d.n_tiles), 0);
  std::vector<int64_t> kept(static_cast<size_t>(d.n_tiles), 0);
  std::vector<int64_t> spill_base(static_cast<size_t>(d.n_tiles), 0);
  int64_t step = 0;
  int64_t spilled = 0;
  for (int64_t ob = 0; ob < num_out_blocks; ++ob) {
    bool first_of_block = true;
    for (int64_t ib = 0; ib < d.n_in_blocks; ++ib) {
      size_t t = static_cast<size_t>(ob * d.n_in_blocks + ib);
      int64_t c = counts[t];
      if (!c) continue;
      TilePlan p = tile_plan(c, chunk, cap);
      kept[t] = c - p.spill;
      spill_base[t] = spilled;
      spilled += p.spill;
      if (!p.n_chunks) continue;
      step_base[t] = step;
      if (step + p.n_chunks > expected_steps) return -1;  // plan mismatch
      for (int64_t j = 0; j < p.n_chunks; ++j) {
        step_out[step] = static_cast<int32_t>(ob);
        step_in[step] = static_cast<int32_t>(ib);
        step_init[step] = (first_of_block && j == 0) ? 1 : 0;
        ++step;
      }
      first_of_block = false;
    }
    if (first_of_block) {  // no chunked entries in this output block
      if (step >= expected_steps) return -1;  // plan mismatch
      step_out[step] = static_cast<int32_t>(ob);
      step_in[step] = 0;
      step_init[step] = 1;
      ++step;
    }
  }
  if (step != expected_steps || spilled != expected_spill) return -1;

  // Stable scatter: each entry lands at its tile's running cursor; the
  // first `kept` go to chunk slots, the tail to the spill arrays (both
  // orderings match the numpy builder exactly).
  std::vector<int64_t> cursor(static_cast<size_t>(d.n_tiles), 0);
  for (int64_t i = 0; i < n; ++i) {
    if (vals[i] == 0.0f) continue;
    int64_t ob = out_coord[i] / win;
    int64_t ib = in_coord[i] / win;
    size_t t = static_cast<size_t>(ob * d.n_in_blocks + ib);
    int64_t q = cursor[t]++;
    if (q < kept[t]) {
      int64_t row = step_base[t] + q / chunk;
      int64_t slot = row * chunk + q % chunk;
      o_pos[slot] = static_cast<int32_t>(out_coord[i] % win);
      i_pos[slot] = static_cast<int32_t>(in_coord[i] % win);
      sv[slot] = vals[i];
    } else {
      int64_t s = spill_base[t] + (q - kept[t]);
      sp_out[s] = static_cast<int32_t>(out_coord[i]);
      sp_in[s] = static_cast<int32_t>(in_coord[i]);
      sp_vals[s] = vals[i];
    }
  }
  return 0;
} catch (...) {
  return -1;
}

}  // extern "C"
