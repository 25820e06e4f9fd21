"""Benchmark: fused GLM objective throughput (examples/sec/chip).

Default (the driver contract): runs the L-BFGS hot kernel — fused margins
-> loss derivatives -> gradient — at an ads-scale shape and prints ONE
JSON line. Since round 2 the benched path is the tiled Pallas kernel pair
(photon_ml_tpu.ops.tiled_sparse, gather/scatter-free); the scatter/gather
GLMObjective is kept as the correctness oracle and its value is
cross-checked inline.

``--suite``: the BASELINE.md matrix — end-to-end time-to-converge +
quality metrics per config (a1a-shaped logistic grid, Criteo-shaped
TRON/elastic-net, hinge+box, GLMix ~100M coef, GAME ~1B coef), one JSON
line per config plus a trailing summary line; results also written to
BASELINE_RESULTS.json. The public datasets themselves are not in the
image (zero egress), so each config runs on a fixed-seed synthetic
dataset with the SAME shape/sparsity — stated in the output — which
measures the machine, not the corpus.

Measurement protocol: the microbench kernel is timed with an in-jit
fori_loop with a loop-carried dependency, differencing two loop lengths
to cancel the dispatch constant, each timing closed by a value readback.
Suite configs time whole host-visible fits (compile excluded by a warm
run where stated). A timing is a device number only on the chip: with no
accelerator the default run and ``--suite`` exit non-zero.

The reference publishes no numbers (SURVEY §6, BASELINE.md); vs_baseline
is computed against our own round-1 scatter/gather measurement
(round 1: 1,116,299 examples/s/chip at this exact shape).
"""

import json
import os
import sys
import time

import numpy as np

ROUND1_EXAMPLES_PER_SEC = 1_116_299  # round 1, same shape/protocol

# The roofline constants in main() (197 bf16 TFLOP/s, 819 GB/s HBM: Google
# Cloud "TPU v5e") hold for this device kind only.
ROOFLINE_DEVICE_KIND = "TPU v5 lite"


def _require_chip():
    """The timed entry points measure the device: with no accelerator
    they stop instead of timing the CPU under a device metric's name."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        sys.exit(f"bench.py: no accelerator: jax.devices() = {devices}")
    return devices[0]


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from photon_ml_tpu.utils.backend import enable_compilation_cache

    device = _require_chip()
    if device.device_kind != ROOFLINE_DEVICE_KIND:
        sys.exit(
            f"bench.py: roofline constants are for {ROOFLINE_DEVICE_KIND!r}, "
            f"not {device.device_kind!r}"
        )
    enable_compilation_cache()

    from photon_ml_tpu.data.batch import SparseBatch
    from photon_ml_tpu.ops.losses import LOGISTIC
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.tiled_sparse import (
        TiledGLMObjective,
        build_tiled_batch,
    )

    rng = np.random.default_rng(0)
    n, k, d = 1 << 18, 64, 1 << 20  # 262k examples x 64 nnz, 1M features
    indices = rng.integers(0, d, size=(n, k), dtype=np.int64)
    values = rng.normal(size=(n, k)).astype(np.float32)
    labels = (rng.uniform(size=n) > 0.5).astype(np.float32)

    rows_flat = np.repeat(np.arange(n, dtype=np.int64), k)
    t0 = time.time()
    tb = build_tiled_batch(
        rows_flat,
        indices.reshape(-1),
        values.reshape(-1),
        labels,
        np.zeros(n, np.float32),
        np.ones(n, np.float32),
        d,
    )
    schedule_build_s = time.time() - t0

    # Persistent schedule-cache cold vs warm at the same shape
    # (ops/schedule_cache.py): cold pays build + artifact store, warm
    # pays content hash + mmap load only — the number the λ-grid /
    # repeated-driver-run story rides on.
    import shutil
    import tempfile

    from photon_ml_tpu.ops import schedule_cache as _sc

    cache_tmp = tempfile.mkdtemp(prefix="photon-tile-cache-bench-")
    try:
        with _sc.cache_scope(cache_tmp):
            t0 = time.perf_counter()
            build_tiled_batch(
                rows_flat, indices.reshape(-1), values.reshape(-1),
                labels, np.zeros(n, np.float32), np.ones(n, np.float32), d,
            )
            schedule_build_s_cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            build_tiled_batch(
                rows_flat, indices.reshape(-1), values.reshape(-1),
                labels, np.zeros(n, np.float32), np.ones(n, np.float32), d,
            )
            schedule_build_s_warm = time.perf_counter() - t0
        schedule_cache_stats = _sc.stats().as_dict()
    finally:
        shutil.rmtree(cache_tmp, ignore_errors=True)
    obj = TiledGLMObjective(LOGISTIC, d)

    def make_loop(o):
        @jax.jit
        def loop(m, w0, tb):
            def body(i, carry):
                w, acc = carry
                v, g = o.value_and_gradient(w, tb, 0.1)
                return (w - 1e-9 * g, acc + v)

            return lax.fori_loop(0, m, body, (w0, jnp.float32(0.0)))

        return loop

    loop = make_loop(obj)
    w0 = jnp.zeros((d,), jnp.float32)
    iters = 11

    def timed(loop_fn, batch, m):
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            out = loop_fn(m, w0, batch)
            _ = float(out[1])
            best = min(best, time.perf_counter() - t0)
        return best

    def measure(loop_fn, batch):
        _ = timed(loop_fn, batch, 1)  # compile + warm
        return (
            timed(loop_fn, batch, iters) - timed(loop_fn, batch, 1)
        ) / (iters - 1)

    # best of two full measurements
    dt = min(measure(loop, tb), measure(loop, tb))
    examples_per_sec = n / dt

    # Kernel-chapter close-out A/B: the MXU-packed one-hot expansion
    # (onehot="mxu", the round-3 "pack the one-hot build onto the MXU"
    # lever) against the compare build, same schedules, back-to-back.
    loop_moh = make_loop(TiledGLMObjective(LOGISTIC, d, onehot="mxu"))
    dt_moh = min(measure(loop_moh, tb), measure(loop_moh, tb))

    # correctness oracle: one scatter/gather evaluation at the same point
    oracle = GLMObjective(LOGISTIC, d)
    sb = SparseBatch(
        indices=jnp.asarray(indices.astype(np.int32)),
        values=jnp.asarray(values),
        labels=jnp.asarray(labels),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )
    w_probe = jnp.asarray(
        rng.normal(size=d).astype(np.float32) * 0.01
    )
    v_tiled, _ = jax.jit(obj.value_and_gradient)(w_probe, tb, 0.1)
    v_oracle, _ = jax.jit(oracle.value_and_gradient)(w_probe, sb, 0.1)
    oracle_rel_err = abs(float(v_tiled) - float(v_oracle)) / abs(
        float(v_oracle)
    )

    # Same fused eval under a 1-device mesh: the tiled kernels run
    # UNMODIFIED inside shard_map (per-shard schedules + psum) — the
    # "fast AND distributed simultaneously" property, recorded so the
    # artifact shows no mesh penalty (round 2 silently fell back to the
    # ~10x-slower scatter objective here).
    from functools import partial as _partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from photon_ml_tpu.ops.tiled_sparse import ensure_tiled_sharded
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

    mesh = make_mesh((1,), (DATA_AXIS,), devices=jax.devices()[:1])
    # tb already has the 1-shard layout: pass-through + device_put only
    # (building from sb would re-pull the device batch and rebuild both
    # schedules)
    tb_mesh = ensure_tiled_sharded(tb, d, mesh)
    obj_mesh = obj.with_axis(DATA_AXIS)

    @jax.jit
    def mesh_loop(m, w0_, tb_):
        @_partial(
            shard_map, mesh=mesh, in_specs=(P(), P(DATA_AXIS), P()),
            out_specs=(P(), P()), check_vma=False,
        )
        def vg(w, b, l2):
            return obj_mesh.value_and_gradient(w, b, l2)

        def body(i, carry):
            w, acc = carry
            v, g = vg(w, tb_, jnp.float32(0.1))
            return (w - 1e-9 * g, acc + v)

        return lax.fori_loop(0, m, body, (w0_, jnp.float32(0.0)))

    mesh_dt = min(measure(mesh_loop, tb_mesh), measure(mesh_loop, tb_mesh))

    # Roofline: distance to the machine's ceilings, not to round 1.
    # Three bounds for THIS schedule geometry:
    # - mxu_floor_ms: pure-MXU time if only the kernel's matmuls ran —
    #   each grid step issues 2 fused full-width bf16 matmul pairs of
    #   [128, 128] x [128, L] (gather + scatter sides), ~197 bf16
    #   TFLOP/s on a v5e-class chip.
    # - dispatched_step_bound_ms: the measured-step cost model from
    #   round 4 — ~2.0 us per grid step (MXU + the one-hot
    #   VPU chain Mosaic will not overlap) + ~15 ns per spilled entry.
    #   This is the bound parameter tuning cannot beat; going below it
    #   needs a different expansion algorithm or a Mosaic change.
    # - hbm_bytes_bound_ms: schedule + row traffic at ~819 GB/s.
    steps_total = tb.z_sched.num_steps + tb.g_sched.num_steps
    L = tb.params.chunk
    spills = int(tb.z_sched.spill_vals.shape[0]) + int(
        tb.g_sched.spill_vals.shape[0]
    )
    # per grid step: one gather matmul [128,128]x[128,L] + one scatter
    # matmul [128,L]x[L,128] (bf16x2w fuses the hi/lo split into these
    # full-width tiles), 128*128*L MACs each
    macs_per_step = 2 * 128 * 128 * L
    mxu_floor_ms = steps_total * macs_per_step * 2 / 197e12 * 1e3  # FLOPs
    # measured round-4 dispatched cost: 16.4 ms / 8192 total steps =
    # ~2.0 us per grid step (MXU + the one-hot VPU chain Mosaic will not
    # overlap) — the bound parameter tuning cannot beat
    dispatched_bound_ms = steps_total * 2.0e-3 + spills * 15e-6
    sched_bytes = sum(
        int(np.asarray(a).nbytes)
        for s_ in (tb.z_sched, tb.g_sched)
        for a in (s_.out_pos, s_.in_pos, s_.vals)
    )
    hbm_bytes_bound_ms = sched_bytes / 819e9 * 1e3

    # host-device overlap A/B (CPU-scaled shape; the full config-5 A/B
    # runs via dev-scripts/bench_overlap.sh / `bench.py --overlap-ab --full`)
    overlap_result = overlap_ab()
    streaming_game = _streaming_game_config("streaming_game")["detail"]

    result = {
        "metric": "fused_value_and_gradient_examples_per_sec_per_chip",
        "value": round(examples_per_sec),
        "unit": "examples/sec/chip",
        "vs_baseline": round(examples_per_sec / ROUND1_EXAMPLES_PER_SEC, 2),
        "overlap": overlap_result["detail"],
        "streaming_game": streaming_game,
        "detail": {
            "kernel": "tiled_pallas_" + obj.mxu,
            "n": n,
            "nnz_per_row": k,
            "dim": d,
            "ms_per_eval": round(dt * 1e3, 3),
            "ms_per_eval_mxu_onehot": round(dt_moh * 1e3, 3),
            "ms_per_eval_1dev_mesh": round(mesh_dt * 1e3, 3),
            "schedule_build_s": round(schedule_build_s, 1),
            "schedule_build_s_cold": round(schedule_build_s_cold, 2),
            "schedule_build_s_warm": round(schedule_build_s_warm, 2),
            "schedule_cache_warm_speedup": round(
                schedule_build_s_cold / max(schedule_build_s_warm, 1e-9), 1
            ),
            "schedule_cache": schedule_cache_stats,
            "oracle_value_rel_err": oracle_rel_err,
            "baseline": "round-1 scatter/gather kernel, same shape",
            "roofline": {
                "measured_ms": round(dt * 1e3, 3),
                "dispatched_step_bound_ms": round(dispatched_bound_ms, 2),
                "x_off_dispatched_bound": round(
                    dt * 1e3 / dispatched_bound_ms, 2
                ),
                "mxu_floor_ms": round(mxu_floor_ms, 2),
                "hbm_bytes_bound_ms": round(hbm_bytes_bound_ms, 2),
                "grid_steps_per_eval": int(steps_total),
                "spilled_entries_per_eval": spills,
                "model": (
                    "2.0us/grid-step (r4 measured: 16.4ms / 8192 steps) "
                    "+ 15ns/spill; MXU floor at 197 bf16 TFLOP/s; HBM at "
                    "819 GB/s"
                ),
            },
            "device": str(jax.devices()[0]),
        },
    }
    print(json.dumps(result))
    return result


def overlap_ab(full: bool = False):
    """Host-device overlap A/B (parallel/overlap.py): the config-5-shaped
    GAME coordinate-descent step with overlap on vs off, plus the
    streaming cold-populate pipeline accounting (wall vs host-decode vs
    device-consume). ``full`` uses the BASELINE config-5 scale (chip-class
    hosts); the default is the same SHAPE (FE + two multi-bucket RE banks
    through the real CoordinateDescent) scaled for a CPU host.

    What the A/B exercises: deferred readbacks (one batched device_get
    per iteration instead of per-bank tracker + per-coordinate reg-term
    pulls — each a synchronous round trip), prefetched host prep under
    device solves, and async artifact IO. On a single-core CPU-only host
    the expectation is PARITY (the eliminated costs are async-device
    latencies that do not exist there); the serial path must not be
    faster.
    """
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game import (
        CoordinateDescent,
        FeatureShardConfiguration,
        FixedEffectCoordinate,
        RandomEffectCoordinate,
        RandomEffectDataConfiguration,
        RandomEffectOptimizationProblem,
        build_game_dataset,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.ops.losses import LOGISTIC
    from photon_ml_tpu.optim.config import (
        OptimizerConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_ml_tpu.optim.problem import create_glm_problem
    from photon_ml_tpu.parallel import overlap
    from photon_ml_tpu.task import TaskType

    rng = np.random.default_rng(0)
    if full:
        n, dg, n_users, n_items = 1 << 17, 1 << 16, 60_000, 40_000
    else:
        n, dg, n_users, n_items = 16_384, 4_096, 2_000, 1_200
    kg, ku = 16, 6
    # Skewed entity frequencies (Zipf-ish) land the RE datasets in
    # MULTIPLE capacity-class buckets — the per-bucket dispatch/readback
    # structure the overlap layer targets (config 5 runs 24 + 16 buckets).
    users = np.minimum(
        (rng.pareto(1.2, size=n) * n_users / 20).astype(np.int64), n_users - 1
    )
    items = np.minimum(
        (rng.pareto(1.2, size=n) * n_items / 20).astype(np.int64), n_items - 1
    )
    gix = rng.integers(0, dg, size=(n, kg))
    gv = rng.normal(size=(n, kg)).astype(np.float32)
    uv = rng.normal(size=(n, ku)).astype(np.float32)
    z = gv.sum(axis=1) * 0.1 + uv.sum(axis=1) * 0.2
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    recs = [
        {
            "uid": f"r{i}",
            "response": float(y[i]),
            "userId": f"u{users[i]}",
            "itemId": f"i{items[i]}",
            "features": [
                {"name": str(int(j)), "term": "", "value": float(v)}
                for j, v in zip(gix[i], gv[i])
            ],
            "userFeatures": [
                {"name": f"f{j}", "term": "", "value": float(uv[i][j])}
                for j in range(ku)
            ],
        }
        for i in range(n)
    ]
    shards = [
        FeatureShardConfiguration("globalShard", ["features"], add_intercept=True),
        FeatureShardConfiguration("userShard", ["userFeatures"], add_intercept=True),
    ]
    ds = build_game_dataset(recs, shards, ["userId", "itemId"])
    del recs

    def build_cd():
        red_u = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("userId", "userShard")
        )
        red_i = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("itemId", "userShard")
        )
        coords = {
            "fixed": FixedEffectCoordinate(
                name="fixed",
                dataset=ds,
                problem=create_glm_problem(
                    TaskType.LOGISTIC_REGRESSION,
                    ds.shards["globalShard"].dim,
                    config=OptimizerConfig(max_iter=25),
                    regularization=RegularizationContext(
                        RegularizationType.L2
                    ),
                ),
                feature_shard_id="globalShard",
                reg_weight=0.5,
            ),
            "perUser": RandomEffectCoordinate(
                name="perUser", dataset=ds, re_dataset=red_u,
                problem=RandomEffectOptimizationProblem(
                    LOGISTIC, OptimizerConfig(max_iter=15),
                    RegularizationContext(RegularizationType.L2),
                    reg_weight=1.0,
                ),
            ),
            "perItem": RandomEffectCoordinate(
                name="perItem", dataset=ds, re_dataset=red_i,
                problem=RandomEffectOptimizationProblem(
                    LOGISTIC, OptimizerConfig(max_iter=15),
                    RegularizationContext(RegularizationType.L2),
                    reg_weight=1.0,
                ),
            ),
        }
        n_buckets = len(red_u.buckets) + len(red_i.buckets)
        return CoordinateDescent(
            coords, ds, TaskType.LOGISTIC_REGRESSION,
            update_sequence=["fixed", "perUser", "perItem"],
        ), n_buckets

    cd, n_buckets = build_cd()
    with overlap.overlap_scope(True):
        cd.run(1)  # compile + device caches (both modes share programs)

    def step_time(enabled):
        best = float("inf")
        for _ in range(2):
            with overlap.overlap_scope(enabled):
                t0 = time.perf_counter()
                cd.run(1)
                best = min(best, time.perf_counter() - t0)
        return best

    # alternate to keep host-load drift out of the comparison
    t_on = step_time(True)
    t_off = step_time(False)
    t_on = min(t_on, step_time(True))
    t_off = min(t_off, step_time(False))
    with overlap.overlap_scope(True):
        overlap.reset_readback_stats()
        cd.run(1)
        readbacks_on = overlap.readback_stats()
    with overlap.overlap_scope(False):
        overlap.reset_readback_stats()
        cd.run(1)
        readbacks_off = overlap.readback_stats()

    # -- streaming cold-populate pipeline accounting ------------------------
    from photon_ml_tpu.io import schemas
    from photon_ml_tpu.io.avro_codec import write_container
    from photon_ml_tpu.io.input_format import AvroInputDataFormat
    from photon_ml_tpu.io.streaming import (
        StreamingGLMObjective,
        iter_chunks,
        scan_stream,
    )

    tmp = tempfile.mkdtemp(prefix="photon-overlap-bench-")
    try:
        r = np.random.default_rng(1)
        n_files, rows_per_file, ds_d, ks = (
            (8, 125_000, 200_000, 16) if full else (6, 8_000, 20_000, 12)
        )
        for fi in range(n_files):
            sx = r.integers(0, ds_d, size=(rows_per_file, ks))
            sv = r.normal(size=(rows_per_file, ks))
            lab = (r.uniform(size=rows_per_file) > 0.5).astype(float)
            write_container(
                f"{tmp}/p{fi}.avro",
                schemas.TRAINING_EXAMPLE_AVRO,
                [
                    {
                        "uid": f"{fi}-{i}",
                        "label": float(lab[i]),
                        "features": [
                            {"name": str(int(j)), "term": "", "value": float(v)}
                            for j, v in zip(sx[i], sv[i])
                        ],
                        "offset": 0.0,
                        "weight": 1.0,
                    }
                    for i in range(rows_per_file)
                ],
            )
        fmt = AvroInputDataFormat()
        index_map, stats = scan_stream([tmp], fmt)

        def populate_wall(overlapped):
            with overlap.overlap_scope(overlapped):
                sobj = StreamingGLMObjective(
                    [tmp], fmt, index_map, stats,
                    TaskType.LOGISTIC_REGRESSION,
                    rows_per_chunk=16_384, kernel="scatter",
                    prefetch=overlapped,
                )
                w = jnp.zeros((sobj.dim,), jnp.float32)
                t0 = time.perf_counter()
                v, _ = sobj.value_and_gradient(w, 0.1)
                _ = float(v)
                wall = time.perf_counter() - t0
                # device-consume per pass: the cached eval (no decode)
                t0 = time.perf_counter()
                v, _ = sobj.value_and_gradient(w, 0.1)
                _ = float(v)
                consume = time.perf_counter() - t0
            return wall, consume

        populate_wall(True)  # compile the partial program once
        wall_piped, consume_s = populate_wall(True)
        wall_serial, _ = populate_wall(False)
        wall_piped = min(wall_piped, populate_wall(True)[0])
        wall_serial = min(wall_serial, populate_wall(False)[0])
        # host decode+stage alone: drain the chunk iterator, no compute
        t0 = time.perf_counter()
        for _chunk in iter_chunks(
            [tmp], fmt, index_map,
            rows_per_chunk=16_384, nnz_width=stats.max_nnz, pipeline=False,
        ):
            pass
        decode_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    one_core = os.cpu_count() == 1 if hasattr(os, "cpu_count") else False
    return {
        "metric": "overlap_ab",
        "value": round(t_off / t_on, 3),
        "unit": "x speedup (GAME CD step, overlap on vs off)",
        "detail": {
            "scale": "config-5 full" if full else "config-5-shaped, CPU-scaled",
            "game_step": {
                "rows": n,
                "fe_dim": int(ds.shards["globalShard"].dim),
                "re_entities": [n_users, n_items],
                "re_buckets_total": n_buckets,
                "step_s_overlap_on": round(t_on, 3),
                "step_s_overlap_off": round(t_off, 3),
                "speedup": round(t_off / t_on, 3),
                "readbacks_per_step_on": readbacks_on,
                "readbacks_per_step_off": readbacks_off,
            },
            "streaming_populate": {
                "files": n_files,
                "rows": n_files * rows_per_file,
                "cold_populate_wall_s_pipelined": round(wall_piped, 3),
                "cold_populate_wall_s_serial": round(wall_serial, 3),
                "host_decode_stage_s": round(decode_s, 3),
                "device_consume_s": round(consume_s, 3),
                "bound_max_decode_consume_s": round(
                    max(decode_s, consume_s), 3
                ),
                "bound_sum_s": round(decode_s + consume_s, 3),
                # the acceptance inequality, with a 15%+50ms epsilon:
                # multicore/chip hosts must meet the max() bound; a
                # single-core host can only meet the sum() bound (no
                # second core to run the decode under the consume)
                "wall_within_max_bound": bool(
                    wall_piped
                    <= max(decode_s, consume_s) * 1.15 + 0.05
                ),
                "wall_within_sum_bound": bool(
                    wall_piped <= (decode_s + consume_s) * 1.15 + 0.05
                ),
            },
            "host": {
                "cpu_count": os.cpu_count(),
                "note": (
                    "single-core host: compute/compute overlap is "
                    "physically unavailable; the pipelined wall is bounded "
                    "by decode+consume, and the GAME A/B gate is parity "
                    "(>=1.15x applies on chip-attached hosts, where the "
                    "eliminated readbacks and dispatch gaps exist)"
                    if one_core
                    else "multi-core host"
                ),
            },
        },
    }


# ---------------------------------------------------------------------------
# BASELINE.md suite
# ---------------------------------------------------------------------------


def _synth_sparse(rng, n, d, k, *, task="logistic", noise=0.5):
    """Fixed-seed synthetic sparse problem with a planted model."""
    w_true = (rng.normal(size=d) * (rng.uniform(size=d) < 0.2)).astype(
        np.float32
    )
    return _regen_with_model(rng, n, d, k, w_true, task, noise=noise)


def _glm_fit_config(
    name,
    *,
    task,
    optimizer,
    reg_type,
    lambdas,
    n,
    d,
    k,
    n_val=0,
    max_iter=None,
    box_bound=None,
    elastic_net_alpha=None,
    kernel="auto",
    seed=0,
    shape_note="",
):
    """Train a lambda grid end-to-end; report warm time-to-converge +
    validation quality (the BASELINE.json metrics contract)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.evaluation import (
        area_under_roc_curve,
        root_mean_squared_error,
    )
    from photon_ml_tpu.models.glm import compute_margins, compute_means
    from photon_ml_tpu.optim.common import BoxConstraints
    from photon_ml_tpu.task import TaskType
    from photon_ml_tpu.training import train_generalized_linear_model
    from photon_ml_tpu.optim import OptimizerType, RegularizationType

    rng = np.random.default_rng(seed)
    task_t = TaskType.parse(task)
    gen_task = {
        "LOGISTIC_REGRESSION": "logistic",
        "LINEAR_REGRESSION": "linear",
        "POISSON_REGRESSION": "poisson",
        "SMOOTHED_HINGE_LOSS_LINEAR_SVM": "hinge",
    }[task_t.name]
    batch, w_true = _synth_sparse(rng, n, d, k, task=gen_task)
    vbatch = None
    if n_val:
        # held-out set drawn from the SAME planted model
        vbatch, _ = _regen_with_model(
            np.random.default_rng(seed + 1), n_val, d, k, w_true, gen_task
        )
    box = None
    if box_bound is not None:
        box = BoxConstraints(
            lower=jnp.full((d,), -box_bound, jnp.float32),
            upper=jnp.full((d,), box_bound, jnp.float32),
        )

    # Resolve + prebuild the tiled schedule OUTSIDE the timed fit: the
    # schedule is static per dataset (the index-build analog), so
    # time-to-converge should not re-pay it per lambda grid.
    from photon_ml_tpu.optim.problem import resolve_kernel

    kernel = resolve_kernel(kernel, batch)
    schedule_build_s = 0.0
    if kernel == "tiled":
        from photon_ml_tpu.ops.tiled_sparse import tiled_batch_from_sparse

        # untimed: pull the synthetic device-resident batch to host first —
        # a real driver builds schedules from host-loaded data, so the
        # D2H copy of this harness's synthetic arrays must not be billed
        # to the schedule build
        host_batch = jax.device_get(batch)  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
        t0 = time.perf_counter()
        batch = tiled_batch_from_sparse(host_batch, d)
        schedule_build_s = time.perf_counter() - t0

    kwargs = dict(
        optimizer_type=OptimizerType.parse(optimizer),
        regularization_type=RegularizationType.parse(reg_type),
        regularization_weights=lambdas,
        elastic_net_alpha=elastic_net_alpha,
        max_iter=max_iter,
        box=box,
        kernel=kernel,
    )

    def fit():
        t0 = time.perf_counter()
        models, results = train_generalized_linear_model(
            batch, task_t, d, **kwargs
        )
        # force completion host-side
        for r in results.values():
            _ = int(r.iterations)
        return models, results, time.perf_counter() - t0

    _, _, cold_s = fit()  # compile
    models, results, warm_s = fit()  # time-to-converge, compile excluded

    total_iters = sum(int(r.iterations) for r in results.values())
    quality = {}
    if vbatch is not None:
        lam_best, best = None, None
        for lam, model in models.items():
            margins = compute_margins(model.means, vbatch)
            if task_t == TaskType.LOGISTIC_REGRESSION or (
                task_t == TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM
            ):
                score = float(
                    area_under_roc_curve(
                        margins, vbatch.labels, vbatch.weights
                    )
                )
                better = best is None or score > best
            else:
                means = compute_means(task_t, model.means, vbatch)
                score = float(
                    root_mean_squared_error(
                        means, vbatch.labels, vbatch.weights
                    )
                )
                better = best is None or score < best
            if better:
                best, lam_best = score, lam
        quality = {
            "metric": (
                "AUC"
                if task_t
                in (
                    TaskType.LOGISTIC_REGRESSION,
                    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
                )
                else "RMSE"
            ),
            "best_value": best,
            "best_lambda": lam_best,
        }
    return {
        "config": name,
        "metric": "time_to_converge_s",
        "value": round(warm_s, 3),
        "unit": "s (lambda grid, warm)",
        "detail": {
            "task": task_t.name,
            "optimizer": optimizer,
            "regularization": reg_type,
            "lambdas": lambdas,
            "n": n,
            "dim": d,
            "nnz_per_row": k,
            "examples_per_sec": round(n * total_iters / warm_s)
            if warm_s > 0
            else None,
            "total_iterations": total_iters,
            "cold_s": round(cold_s, 3),
            "kernel": kernel,
            "schedule_build_s": round(schedule_build_s, 2),
            "validation": quality,
            "data": shape_note or "fixed-seed synthetic, planted model",
        },
    }


def _feature_sharded_tron_config(name, *, n, d, k, lam=1.0, seed=0):
    """Config 2a on the feature-sharded TILED path under a 1-device
    (data, model) mesh: measures what the sharded TRON composition costs
    on one chip (the distributed-path analog of the headline's
    ms_per_eval_1dev_mesh check) — the tiled Hv factory riding the z/g
    schedules inside shard_map (TRON.scala:259-341 +
    HessianVectorAggregator.scala:137-152). Multi-chip scaling itself is
    the mesh's job (MULTICHIP_WEAK_SCALING.md)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.tiled_sparse import feature_shard_tiled_batch
    from photon_ml_tpu.parallel.distributed import (
        feature_sharded_tiled_fit_tron,
    )
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
    from photon_ml_tpu.task import TaskType

    rng = np.random.default_rng(seed)
    batch, _ = _synth_sparse(rng, n, d, k, task="linear")
    host_batch = jax.device_get(batch)  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
    mesh = make_mesh(
        (1, 1), (DATA_AXIS, MODEL_AXIS), devices=jax.devices()[:1]
    )
    t0 = time.perf_counter()
    sharded, block_dim = feature_shard_tiled_batch(
        host_batch, d, 1, 1, mesh=mesh
    )
    schedule_build_s = time.perf_counter() - t0
    objective = GLMObjective(
        loss_for_task(TaskType.LINEAR_REGRESSION), d
    )
    fit = feature_sharded_tiled_fit_tron(
        objective, mesh, sharded.meta, max_iter=15, tol=1e-5
    )

    def run():
        t0 = time.perf_counter()
        res = fit(
            jnp.zeros((block_dim,), jnp.float32), sharded, jnp.float32(lam)
        )
        iters = int(res.iterations)
        return iters, time.perf_counter() - t0

    _, cold_s = run()
    iters, warm_s = run()
    return {
        "config": name,
        "metric": "time_to_converge_s",
        "value": round(warm_s, 3),
        "unit": "s (one lambda, warm)",
        "detail": {
            "task": "LINEAR_REGRESSION",
            "optimizer": "TRON",
            "path": "feature-sharded tiled (1x1 mesh, shard_map)",
            "n": n,
            "dim": d,
            "nnz_per_row": k,
            "examples_per_sec": round(n * iters / warm_s) if warm_s else None,
            "total_iterations": iters,
            "cold_s": round(cold_s, 3),
            "kernel": "tiled",
            "schedule_build_s": round(schedule_build_s, 2),
            "data": "synthetic at Criteo-sample shape, sharded-path cost check",
        },
    }


def _game_fe_sharded_config(name, *, n=1 << 18, d=1 << 20, k=64, seed=0):
    """Config-4-shaped GAME FIXED EFFECT solved through FixedEffectCoordinate
    under a 1x1 (data, model) mesh — proves the feature-sharded GAME FE
    composition (round-5 wiring: FixedEffectCoordinate._update_model_
    feature_sharded) costs nothing on one chip vs the same coordinate's
    replicated solve. Match: the reference runs the GAME FE distributed by
    construction at huge dimension (cli/game/training/Driver.scala:357-363,
    717-719)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.coordinate import FixedEffectCoordinate
    from photon_ml_tpu.game.data import GameDataset, ShardData
    from photon_ml_tpu.optim.config import (
        OptimizerConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_ml_tpu.optim.problem import create_glm_problem
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
    from photon_ml_tpu.task import TaskType

    rng = np.random.default_rng(seed)
    batch, _ = _synth_sparse(rng, n, d, k)
    host = jax.device_get(batch)  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
    from photon_ml_tpu.utils.index_map import IdentityIndexMap

    shard = ShardData(
        indices=np.asarray(host.indices),  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
        values=np.asarray(host.values),  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
        index_map=IdentityIndexMap(d),
        intercept_index=None,
    )
    ds = GameDataset(
        uids=[""] * n,
        labels=np.asarray(host.labels),  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
        offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        shards={"global": shard},
        entity_codes={},
        entity_indexes={},
        num_real_rows=n,
    )
    mesh = make_mesh(
        (1, 1), (DATA_AXIS, MODEL_AXIS), devices=jax.devices()[:1]
    )
    out = {}
    for label, m in (("sharded_1x1", mesh), ("replicated", None)):
        coord = FixedEffectCoordinate(
            name="fe",
            dataset=ds,
            problem=create_glm_problem(
                TaskType.LOGISTIC_REGRESSION, d,
                config=OptimizerConfig(max_iter=50),
                regularization=RegularizationContext(RegularizationType.L2),
                kernel="tiled",
            ),
            feature_shard_id="global",
            reg_weight=1.0,
            mesh=m,
        )

        def step(model):
            t0 = time.perf_counter()
            model, res = coord.update_model(model)
            _ = float(jnp.sum(model.model.means))  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
            return model, time.perf_counter() - t0

        model, cold_s = step(coord.initialize_model())
        # one more warm-up: the first warm-started call traces a second
        # program variant (fresh-coefficients vs warm-start shardings)
        model, _ = step(model)
        model, warm_s = step(model)
        out[label] = {"warm_s": round(warm_s, 3), "cold_s": round(cold_s, 3)}
    ratio = out["sharded_1x1"]["warm_s"] / max(out["replicated"]["warm_s"], 1e-9)
    return {
        "config": name,
        "metric": "game_fe_sharded_vs_replicated_warm_ratio",
        "value": round(ratio, 3),
        "unit": "x (1.0 = zero composition cost)",
        "detail": {
            "n": n, "dim": d, "nnz_per_row": k,
            **{f"{k_}_{m}": v for k_, d_ in out.items() for m, v in d_.items()},
            "path": "FixedEffectCoordinate feature-sharded (1x1 mesh) vs "
                    "replicated, tiled kernel both sides",
            "data": "synthetic at BASELINE config-4 FE shape",
        },
    }


def _streaming_config(name, *, n_files=8, rows_per_file=125_000, d=200_000,
                      k=16, seed=0):
    """Streaming (>RAM-shaped) path: full-batch (value, gradient) with
    chunked Avro decode. Measures evaluation 1 (decode + cache populate)
    vs evaluation 2+ (staged-chunk cache, zero Avro decode — the
    persist(MEMORY_AND_DISK) semantics landed round 4) and reports the
    cache speedup. Dataset size is a harness-budget stand-in; the path's
    memory bound is one decoded file + one staged chunk regardless of
    scale (tests/test_streaming.py pins bounded RSS)."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    from photon_ml_tpu.io import schemas
    from photon_ml_tpu.io.avro_codec import write_container
    from photon_ml_tpu.io.input_format import AvroInputDataFormat
    from photon_ml_tpu.io.streaming import StreamingGLMObjective, scan_stream
    from photon_ml_tpu.task import TaskType

    rng = np.random.default_rng(seed)
    tmp = tempfile.mkdtemp(prefix="photon-stream-bench-")
    try:
        w_true = rng.normal(size=d).astype(np.float32) * 0.2
        gen_t = 0.0
        t0 = time.perf_counter()
        for fi in range(n_files):
            ix = rng.integers(0, d, size=(rows_per_file, k))
            vs = rng.normal(size=(rows_per_file, k)).astype(np.float32)
            z = (w_true[ix] * vs).sum(axis=1)
            y = (rng.uniform(size=rows_per_file) < 1 / (1 + np.exp(-z)))
            recs = [
                {
                    "uid": f"{fi}-{i}",
                    "label": float(y[i]),
                    "features": [
                        {"name": str(int(j)), "term": "", "value": float(v)}
                        for j, v in zip(ix[i], vs[i])
                    ],
                    "offset": 0.0,
                    "weight": 1.0,
                }
                for i in range(rows_per_file)
            ]
            write_container(
                f"{tmp}/part-{fi:03d}.avro",
                schemas.TRAINING_EXAMPLE_AVRO,
                recs,
            )
        gen_t = time.perf_counter() - t0
        fmt = AvroInputDataFormat()
        t0 = time.perf_counter()
        index_map, stats = scan_stream([tmp], fmt)
        scan_s = time.perf_counter() - t0
        obj = StreamingGLMObjective(
            [tmp], fmt, index_map, stats, TaskType.LOGISTIC_REGRESSION
        )
        w = jnp.zeros((obj.dim,), jnp.float32)

        def one_eval():
            t0 = time.perf_counter()
            v, g = obj.value_and_gradient(w, 0.1)
            _ = float(v) + float(jnp.sum(g))  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
            return time.perf_counter() - t0

        eval1_s = one_eval()  # decode + cache populate (+ compile)
        eval_rt_s = min(one_eval() for _ in range(3))  # cached + readback

        # Cached-eval DEVICE rate with the closing readback amortized
        # over a chain; chained evals keep a real data dependency.
        def eval_chain(m):
            t0 = time.perf_counter()
            w_ = w
            for _ in range(m):
                v, g = obj.value_and_gradient(w_, 0.1)
                w_ = w_ - 1e-9 * g
            _ = float(v) + float(jnp.sum(g))  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
            return time.perf_counter() - t0

        t1 = min(eval_chain(1) for _ in range(2))
        t7 = min(eval_chain(7) for _ in range(2))
        eval2_s = max((t7 - t1) / 6, 1e-9)
        n = stats.num_rows
        return {
            "config": name,
            "metric": "streaming_examples_per_sec_cached_eval",
            "value": round(n / eval2_s),
            "unit": "examples/sec (full value+grad pass)",
            "detail": {
                "n": n,
                "dim": obj.dim,
                "nnz_per_row": k,
                "n_files": n_files,
                "eval1_s_decode": round(eval1_s, 2),
                "eval2_s_cached": round(eval2_s, 3),
                "eval_s_cached_with_readback": round(eval_rt_s, 3),
                "kernel_path": (
                    "tiled_scan" if obj._tiled_chunk_count else "scatter"
                ),
                "cache_speedup": round(eval1_s / eval2_s, 1),
                "scan_s": round(scan_s, 2),
                "examples_per_sec_decode_eval": round(n / eval1_s),
                "data_gen_s": round(gen_t, 1),
                "data": "synthetic Avro written to scratch; streamed per eval",
            },
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _streaming_game_config(name, *, n_files=3, rows_per_file=6000,
                           n_users=400, d_g=24, d_u=8, num_iterations=2,
                           budget_bytes=2 << 20, seed=0):
    """Out-of-core GAME fit A/B (game/streaming.py): streamed coordinate
    descent over spilled chunks vs the in-memory CD on the same files.
    Emits examples_per_s + peak_rss_bytes (the budget contract made
    observable) + the objective parity — the round artifact's
    ``streaming_game`` section. Gates live in
    dev-scripts/bench_streaming_game.sh (host-class-aware: throughput
    >= 0.8x in-memory on multi-core hosts, objective parity everywhere,
    RSS delta bounded)."""
    import shutil
    import tempfile

    from photon_ml_tpu.game.config import (
        FeatureShardConfiguration,
        FixedEffectDataConfiguration,
        ProjectorType,
        RandomEffectDataConfiguration,
    )
    from photon_ml_tpu.io import schemas
    from photon_ml_tpu.io.avro_codec import write_container
    from photon_ml_tpu.optim.config import GLMOptimizationConfiguration
    from photon_ml_tpu.task import TaskType
    from photon_ml_tpu.utils.profiling import peak_rss_bytes

    schema = {
        "name": "GameExample", "type": "record",
        "fields": [
            {"name": "uid", "type": ["null", "string"], "default": None},
            {"name": "response", "type": "double"},
            {"name": "metadataMap",
             "type": ["null", {"type": "map", "values": "string"}],
             "default": None},
            {"name": "features",
             "type": {"type": "array", "items": schemas.FEATURE_AVRO}},
            {"name": "userFeatures",
             "type": {"type": "array", "items": "FeatureAvro"}},
        ],
    }
    rng = np.random.default_rng(seed)
    tmp = tempfile.mkdtemp(prefix="photon-game-stream-bench-")
    try:
        w_g = np.linspace(-1, 1, d_g)
        w_u = np.random.default_rng(7).normal(size=(n_users, d_u)) * 0.5
        t0 = time.perf_counter()
        for fi in range(n_files):
            recs = []
            for i in range(rows_per_file):
                u = int(rng.integers(0, n_users))
                xg = rng.normal(size=d_g)
                xu = rng.normal(size=d_u)
                z = float(xg @ w_g + xu @ w_u[u])
                recs.append({
                    "uid": f"{fi}-{i}",
                    "response": float(
                        1 / (1 + np.exp(-z)) > rng.uniform()
                    ),
                    "metadataMap": {"userId": f"user{u}"},
                    "features": [
                        {"name": f"g{j}", "term": "", "value": float(xg[j])}
                        for j in range(d_g)
                    ],
                    "userFeatures": [
                        {"name": f"u{j}", "term": "", "value": float(xu[j])}
                        for j in range(d_u)
                    ],
                })
            write_container(f"{tmp}/part-{fi:03d}.avro", schema, recs)
            del recs
        gen_s = time.perf_counter() - t0

        shards = [
            FeatureShardConfiguration("globalShard", ["features"]),
            FeatureShardConfiguration("userShard", ["userFeatures"]),
        ]
        fe_data = {"global": FixedEffectDataConfiguration("globalShard")}
        re_data = {
            "per-user": RandomEffectDataConfiguration(
                "userId", "userShard",
                projector_type=ProjectorType.IDENTITY,
            )
        }
        combo = {
            "global": GLMOptimizationConfiguration.parse(
                "20,1e-6,0.5,1,TRON,L2"
            ),
            "per-user": GLMOptimizationConfiguration.parse(
                "20,1e-6,1.0,1,LBFGS,L2"
            ),
        }
        n = n_files * rows_per_file

        # -- streamed fit (FIRST: its RSS delta excludes the in-memory
        # staging below) --------------------------------------------------
        from photon_ml_tpu.game.streaming import train_streaming_game

        rss_before = peak_rss_bytes()
        t0 = time.perf_counter()
        res, extras = train_streaming_game(
            [tmp], shards, fe_data, re_data, combo,
            TaskType.LOGISTIC_REGRESSION,
            num_iterations=num_iterations,
            memory_budget_bytes=budget_bytes,
        )
        stream_s = time.perf_counter() - t0
        rss_after = peak_rss_bytes()

        # -- in-memory reference ------------------------------------------
        from photon_ml_tpu.game.coordinate import (
            FixedEffectCoordinate,
            RandomEffectCoordinate,
        )
        from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
        from photon_ml_tpu.game.data import build_game_dataset_from_files
        from photon_ml_tpu.game.random_effect import (
            RandomEffectOptimizationProblem,
        )
        from photon_ml_tpu.game.random_effect_data import (
            build_random_effect_dataset,
        )
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.optim.problem import create_glm_problem

        task = TaskType.LOGISTIC_REGRESSION
        t0 = time.perf_counter()
        ds = build_game_dataset_from_files([tmp], shards, ["userId"])
        red = build_random_effect_dataset(ds, re_data["per-user"])
        coords = {
            "global": FixedEffectCoordinate(
                name="global", dataset=ds,
                problem=create_glm_problem(
                    task, ds.shards["globalShard"].dim,
                    config=combo["global"].optimizer_config,
                    regularization=combo["global"].regularization,
                    intercept_index=(
                        ds.shards["globalShard"].intercept_index
                    ),
                ),
                feature_shard_id="globalShard",
                reg_weight=combo["global"].reg_weight,
            ),
            "per-user": RandomEffectCoordinate(
                name="per-user", dataset=ds, re_dataset=red,
                problem=RandomEffectOptimizationProblem(
                    loss_for_task(task),
                    combo["per-user"].optimizer_config,
                    combo["per-user"].regularization,
                    reg_weight=combo["per-user"].reg_weight,
                ),
            ),
        }
        ref = CoordinateDescent(coords, ds, task).run(num_iterations)
        mem_s = time.perf_counter() - t0

        obj_rel = abs(
            res.objective_history[-1] - ref.objective_history[-1]
        ) / abs(ref.objective_history[-1])
        ex_s = round(n * num_iterations / stream_s)
        ex_m = round(n * num_iterations / mem_s)
        return {
            "config": name,
            "metric": "streaming_game_examples_per_sec",
            "value": ex_s,
            "unit": "examples/sec (full CD pass, streamed)",
            "detail": {
                "n": n,
                "num_iterations": num_iterations,
                "num_chunks": extras["store"].count,
                "rows_per_chunk": extras["rows_per_chunk"],
                "memory_budget_bytes": budget_bytes,
                "examples_per_s": ex_s,
                "in_memory_examples_per_s": ex_m,
                "throughput_ratio": round(ex_s / max(ex_m, 1), 3),
                "stream_fit_s": round(stream_s, 2),
                "in_memory_fit_s": round(mem_s, 2),
                "peak_rss_bytes": rss_after,
                "rss_delta_bytes": rss_after - rss_before,
                "objective_rel_diff": float(obj_rel),
                "data_gen_s": round(gen_s, 1),
                "host": {"cpu_count": os.cpu_count()},
                "data": "synthetic GAME Avro written to scratch",
            },
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _pod_game_config(name, *, n=16384, E=2048, d=32, k=8, iters=3, seed=0):
    """Pod-scale GAME A/B (game/pod.py): entity-hash-sharded RE bank
    update + two-hop routed scoring vs the replicated bucket path on the
    SAME in-memory dataset, at every available shard count.

    Emits the weak-scaling accounting the round artifact carries:
    per-device bank + optimizer-state bytes (replicated vs sharded at
    N = all visible devices), a weak-scaling table where total
    coefficients GROW with N while per-device bytes stay flat, parity
    (bank/score max-abs-diff vs the replicated update), routed-path
    readback count (must be 0 — the overlap.device_get seam), and
    update+score throughput both ways. Gates live in
    dev-scripts/bench_pod_game.sh (host-class-aware: bytes + parity +
    zero-readback everywhere; the throughput-scaling gate is chip-only —
    virtual CPU devices EMULATE collectives on one core, so sharded
    wall-clock on this container measures emulation, not ICI)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.config import (
        ProjectorType,
        RandomEffectDataConfiguration,
    )
    from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
    from photon_ml_tpu.game.pod import (
        EntityShardSpec,
        PodRandomEffectProblem,
        ShardedREBank,
        per_device_bytes,
    )
    from photon_ml_tpu.game.random_effect import (
        RandomEffectOptimizationProblem,
        score_random_effect,
    )
    from photon_ml_tpu.game.random_effect_data import (
        build_random_effect_dataset,
    )
    from photon_ml_tpu.ops.losses import LOGISTIC
    from photon_ml_tpu.optim.config import (
        OptimizerConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_ml_tpu.parallel import overlap
    from photon_ml_tpu.parallel.mesh import entity_mesh
    from photon_ml_tpu.utils.index_map import IndexMap, feature_key

    rng = np.random.default_rng(seed)
    codes = rng.integers(0, E, size=n).astype(np.int32)
    ix = rng.integers(0, d, size=(n, k)).astype(np.int32)
    v = rng.normal(size=(n, k)).astype(np.float32)
    lab = (rng.uniform(size=n) > 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    off = (rng.normal(size=n) * 0.1).astype(np.float32)
    imap = IndexMap.build(
        (feature_key(f"f{i}", "") for i in range(d)), add_intercept=False
    )
    ds = GameDataset(
        uids=[str(i) for i in range(n)],
        labels=lab, offsets=off, weights=w,
        shards={"s": ShardData(ix, v, imap, None)},
        entity_codes={"user": codes},
        entity_indexes={
            "user": EntityIndex.build("user", [f"e{i:06d}" for i in range(E)])
        },
        num_real_rows=n,
    )
    red = build_random_effect_dataset(
        ds,
        RandomEffectDataConfiguration(
            random_effect_type="user", feature_shard_id="s",
            projector_type=ProjectorType.IDENTITY,
        ),
    )
    resid = jnp.asarray(off)

    def make_problem():
        return RandomEffectOptimizationProblem(
            LOGISTIC, OptimizerConfig(max_iter=5),
            RegularizationContext(RegularizationType.L2), reg_weight=1.0,
        )

    def run_replicated():
        problem = make_problem()
        bank = jnp.zeros((red.num_entities, red.local_dim), jnp.float32)
        bank, _, var = problem.update_bank(
            bank, red, residual_offsets=resid, with_variances=True
        )
        scores = score_random_effect(bank, red)
        jax.block_until_ready((bank, var, scores))  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
        t0 = time.perf_counter()
        for _ in range(iters):
            bank, _, var = problem.update_bank(
                bank, red, residual_offsets=resid, with_variances=True
            )
            scores = score_random_effect(bank, red)
        jax.block_until_ready((bank, var, scores))  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
        return bank, var, scores, (time.perf_counter() - t0) / iters

    ref_bank, ref_var, ref_scores, rep_s = run_replicated()
    replicated_state_bytes = int(ref_bank.nbytes) + int(ref_var.nbytes)

    n_dev = len(jax.devices())
    mesh = entity_mesh(n_dev)
    pod = PodRandomEffectProblem(make_problem(), mesh)
    view = pod.pod_view(red)
    bank = pod.init_bank(red)
    bank, _, var = pod.update_bank(
        bank, red, residual_offsets=resid, with_variances=True,
        defer_tracker=True,
    )
    scores = pod.score(bank, red)
    jax.block_until_ready((bank.data, var.data, scores))  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
    overlap.reset_readback_stats()
    t0 = time.perf_counter()
    for _ in range(iters):
        bank, _, var = pod.update_bank(
            bank, red, residual_offsets=resid, with_variances=True,
            defer_tracker=True,
        )
        scores = pod.score(bank, red)
    jax.block_until_ready((bank.data, var.data, scores))  # photon: allow(hidden-host-sync) — timing harness syncs deliberately
    pod_s = (time.perf_counter() - t0) / iters
    routed_readbacks = overlap.readback_stats()

    bank_diff, score_diff = (
        float(x) for x in overlap.device_get((
            jnp.max(jnp.abs(bank.to_global() - ref_bank)),
            jnp.max(jnp.abs(scores - ref_scores)),
        ))
    )
    sharded_state_bytes = per_device_bytes(bank, var)

    # weak scaling: total coefficients GROW with the shard count while
    # per-device bank+optimizer bytes stay ~flat (the "hundreds of
    # billions of coefficients" shape, PAPER.md, at toy scale)
    weak = []
    for ns in (1, 2, 4, 8):
        if ns > n_dev:
            continue
        spec = EntityShardSpec(ns, E * ns)
        m = entity_mesh(ns)
        b = ShardedREBank.zeros(m, spec, d)
        vb = ShardedREBank.zeros(m, spec, d)
        weak.append({
            "shards": ns,
            "entities": E * ns,
            "coefficients": E * ns * d,
            "per_device_state_bytes": per_device_bytes(b, vb),
        })

    return {
        "config": name,
        "metric": "pod_game_per_device_state_bytes",
        "value": sharded_state_bytes,
        "unit": f"bytes/device at {n_dev} entity shards (bank + variances)",
        "detail": {
            "n": n, "entities": E, "dim": d, "n_shards": n_dev,
            "replicated_state_bytes": replicated_state_bytes,
            "sharded_per_device_state_bytes": sharded_state_bytes,
            "bytes_ratio": round(
                sharded_state_bytes / max(replicated_state_bytes, 1), 4
            ),
            "per_device_data_bytes": view.per_device_data_bytes(),
            "bank_max_abs_diff": bank_diff,
            "score_max_abs_diff": score_diff,
            "routed_readbacks": routed_readbacks,
            "replicated_step_s": round(rep_s, 4),
            "sharded_step_s": round(pod_s, 4),
            "throughput_ratio": round(rep_s / max(pod_s, 1e-9), 3),
            "weak_scaling": weak,
            "host": {
                "cpu_count": os.cpu_count(),
                "devices": n_dev,
                "platform": jax.devices()[0].platform,
            },
        },
    }


def _unified_mesh_config(name, *, n=4096, E=512, d=16, k=6, iters=2,
                         seed=0):
    """Unified (grid × entity) mesh A/B (game/unified.py): the whole
    G-member λ-grid over an entity-sharded GAME model as ONE
    jitted/shard_mapped program vs the sequential-composed legacy sweep
    (G per-λ pod CD runs on the same entity mesh).

    Emits the round artifact's contract + wall accounting: per-λ
    objective/bank parity vs the sequential pod oracle, the unified
    sweep's readback count (must equal the CD iteration count — ONE
    batched readback per iteration covers every member), relowerings on
    a warmed same-shape run with DIFFERENT λ values (must be 0), the
    P(grid, entity) per-device bank bytes, and wall-clock both ways.
    Gates live in dev-scripts/bench_unified_mesh.sh (host-class-aware:
    parity + readback/lowering contracts everywhere; the >= 1.2x
    wall-clock gate at G >= 4 is multi-core/chip-only — a 1-core host
    runs every virtual device sequentially, so the one-program win is
    dispatch overhead only and the figure is recorded, not gated)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.config import (
        ProjectorType,
        RandomEffectDataConfiguration,
    )
    from photon_ml_tpu.game.coordinate import (
        FixedEffectCoordinate,
        PodRandomEffectCoordinate,
    )
    from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
    from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
    from photon_ml_tpu.game.random_effect import (
        RandomEffectOptimizationProblem,
    )
    from photon_ml_tpu.game.random_effect_data import (
        build_random_effect_dataset,
    )
    from photon_ml_tpu.game.unified import run_game_grid
    from photon_ml_tpu.ops.losses import LOGISTIC
    from photon_ml_tpu.optim.config import (
        OptimizerConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_ml_tpu.optim.problem import create_glm_problem
    from photon_ml_tpu.parallel import overlap
    from photon_ml_tpu.parallel.mesh import entity_mesh
    from photon_ml_tpu.parallel.unified_mesh import resolve_mesh
    from photon_ml_tpu.task import TaskType
    from photon_ml_tpu.utils.index_map import IndexMap, feature_key

    rng = np.random.default_rng(seed)
    codes = rng.integers(0, E, size=n).astype(np.int32)
    ix = rng.integers(0, d, size=(n, k)).astype(np.int32)
    v = rng.normal(size=(n, k)).astype(np.float32)
    lab = (rng.uniform(size=n) > 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    off = (rng.normal(size=n) * 0.1).astype(np.float32)
    imap = IndexMap.build(
        (feature_key(f"f{i}", "") for i in range(d)), add_intercept=False
    )
    ds = GameDataset(
        uids=[str(i) for i in range(n)],
        labels=lab, offsets=off, weights=w,
        shards={"s": ShardData(ix, v, imap, None)},
        entity_codes={"user": codes},
        entity_indexes={
            "user": EntityIndex.build("user", [f"e{i:06d}" for i in range(E)])
        },
        num_real_rows=n,
    )
    red = build_random_effect_dataset(
        ds,
        RandomEffectDataConfiguration(
            random_effect_type="user", feature_shard_id="s",
            projector_type=ProjectorType.IDENTITY,
        ),
    )
    task = TaskType.LOGISTIC_REGRESSION
    fe_problem = create_glm_problem(
        task, ds.shards["s"].dim, config=OptimizerConfig(max_iter=5)
    )

    def re_problem(lam=1.0):
        return RandomEffectOptimizationProblem(
            LOGISTIC, OptimizerConfig(max_iter=5),
            RegularizationContext(RegularizationType.L2), reg_weight=lam,
        )

    lambdas = [0.1, 0.5, 1.0, 2.0]
    n_dev = len(jax.devices())
    n_ent = 2 if n_dev >= 2 else 1
    plan = resolve_mesh(grid_size=len(lambdas), entity_shards=n_ent)

    def run_unified(lams, num_iterations):
        return run_game_grid(
            plan, ds, red, fe_problem, re_problem(), lams,
            feature_shard_id="s", fe_reg_weight=0.1,
            num_iterations=num_iterations,
        )

    def run_sequential(lams, num_iterations):
        out = []
        for lam in lams:
            coords = {
                "fixed": FixedEffectCoordinate(
                    name="fixed", dataset=ds, problem=fe_problem,
                    feature_shard_id="s", reg_weight=0.1,
                ),
                "per-user": PodRandomEffectCoordinate(
                    name="per-user", dataset=ds, re_dataset=red,
                    problem=re_problem(lam), mesh=entity_mesh(n_ent),
                ),
            }
            out.append(CoordinateDescent(coords, ds, task).run(
                num_iterations
            ))
        return out

    # warm both program families, then time
    run_unified(lambdas, 1)
    run_sequential(lambdas, 1)
    t0 = time.perf_counter()
    res = run_unified(lambdas, iters)
    uni_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    refs = run_sequential(lambdas, iters)
    seq_s = time.perf_counter() - t0

    bank_diff = 0.0
    obj_rel = 0.0
    for gi, ref in enumerate(refs):
        got = np.asarray(res.re_bank.member_global(gi))
        want_bank = np.asarray(ref.model.models["per-user"].bank)
        bank_diff = max(bank_diff, float(np.max(np.abs(got - want_bank))))
        got_obj = np.asarray([h[gi] for h in res.objective_history])
        want_obj = np.asarray(ref.objective_history)
        obj_rel = max(obj_rel, float(np.max(
            np.abs(got_obj - want_obj) / np.maximum(np.abs(want_obj), 1e-9)
        )))

    with overlap.overlap_scope(True):
        overlap.reset_readback_stats()
        run_unified(lambdas, iters)
        readbacks = overlap.readback_stats()

    import jax._src.test_util as jtu
    with jtu.count_jit_and_pmap_lowerings() as count:
        run_unified([0.2, 0.7, 1.5, 3.0], iters)
    relowerings = int(count[0])

    return {
        "config": name,
        "metric": "unified_mesh_speedup",
        "value": round(seq_s / max(uni_s, 1e-9), 3),
        "unit": (
            f"sequential/unified wall ratio, G={len(lambdas)} x "
            f"{n_ent} entity shards x {iters} CD iterations"
        ),
        "detail": {
            "n": n, "entities": E, "dim": d,
            "grid_size": len(lambdas),
            "entity_shards": plan.entity_shards,
            "grid_rows": plan.grid_rows,
            "cd_iterations": iters,
            "unified_wall_s": round(uni_s, 4),
            "sequential_wall_s": round(seq_s, 4),
            "speedup": round(seq_s / max(uni_s, 1e-9), 3),
            "bank_max_abs_diff": bank_diff,
            "objective_max_rel_diff": obj_rel,
            "unified_readbacks": readbacks,
            "relowerings_warm": relowerings,
            "per_device_bank_bytes": res.re_bank.per_device_bytes(),
            "host": {
                "cpu_count": os.cpu_count(),
                "devices": n_dev,
                "platform": jax.devices()[0].platform,
            },
        },
    }


def _reliability_config(name, *, n_chunks=8, rows=65536, k=16,
                        passes=10, seed=0):
    """Reliability-layer overhead A/B (round 11): the spill-read/write
    hot path (staged-chunk cache re-reads, the evaluation-2+ currency of
    every streaming objective) timed with the seams ACTIVE (inject +
    policy lookup + counters per chunk, no plan installed) vs BYPASSED
    (PHOTON_RELIABILITY_BYPASS=1 — io_call degenerates to a direct
    call). Gate (dev-scripts/chaos.sh): overhead < 2% with injection
    disabled — the layer must be free when nothing is failing."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import SparseBatch
    from photon_ml_tpu.io.streaming import _DiskChunkStore
    from photon_ml_tpu.reliability import reliability_metrics
    from photon_ml_tpu.reliability.retry import io_call

    rng = np.random.default_rng(seed)
    store = _DiskChunkStore(rows, k)
    try:
        for _ in range(n_chunks):
            store.append(SparseBatch(
                indices=jnp.asarray(
                    rng.integers(0, 1000, size=(rows, k)).astype(np.int32)
                ),
                values=jnp.asarray(
                    rng.normal(size=(rows, k)).astype(np.float32)
                ),
                labels=jnp.zeros((rows,), jnp.float32),
                offsets=jnp.zeros((rows,), jnp.float32),
                weights=jnp.ones((rows,), jnp.float32),
            ))
        store.finalize()

        def sweep():
            t0 = time.perf_counter()
            n = 0
            for b in store.chunks():
                n += int(b.indices.shape[0])
            return time.perf_counter() - t0

        sweep()  # warm page cache + compile-free path
        sweep_s = min(sweep() for _ in range(passes))
        # A whole-sweep A/B cannot resolve the seam cost here: one
        # io_call is ~5 us and a sweep is ~25 ms of memcpy whose run-to-
        # run variance on a shared 1-core host is +-10% — two orders
        # above the signal. So measure the PER-CALL seam overhead
        # directly (tight no-op loop, seams active minus bypassed) and
        # scale by the seam crossings per sweep; the fraction is derived
        # but every term is measured.
        def noop():
            return None

        M = 20_000

        def per_call_s(env):
            if env:
                os.environ["PHOTON_RELIABILITY_BYPASS"] = "1"
            else:
                os.environ.pop("PHOTON_RELIABILITY_BYPASS", None)
            try:
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(M):
                        io_call("spill_read", noop)
                    best = min(best, (time.perf_counter() - t0) / M)
                return best
            finally:
                os.environ.pop("PHOTON_RELIABILITY_BYPASS", None)

        seam_call_s = per_call_s(False)
        bypass_call_s = per_call_s(True)
        per_call_overhead_s = max(seam_call_s - bypass_call_s, 0.0)
        calls_per_sweep = n_chunks  # one spill_read crossing per chunk
        overhead = per_call_overhead_s * calls_per_sweep / max(
            sweep_s, 1e-9
        )
        return {
            "config": name,
            "metric": "reliability_overhead_frac",
            "value": round(overhead, 5),
            "unit": "fraction of the spill-read sweep (no fault plan)",
            "detail": {
                "n_chunks": n_chunks,
                "rows_per_chunk": rows,
                "sweep_s": round(sweep_s, 4),
                "seam_call_us": round(seam_call_s * 1e6, 2),
                "bypass_call_us": round(bypass_call_s * 1e6, 2),
                "per_call_overhead_us": round(per_call_overhead_s * 1e6, 2),
                "calls_per_sweep": calls_per_sweep,
                "seam_calls": reliability_metrics()["faults"]["calls"],
            },
        }
    finally:
        store.close()


def _grid_batched_config(name, *, n=20_000, d=2_000, k=16,
                         lambdas=(100.0, 30.0, 10.0, 3.0, 1.0, 0.3, 0.1,
                                  0.03),
                         max_iter=40, seed=0):
    """Batched λ-grid A/B (ISSUE 5 / training.train_grid_batched): the
    warm-started sequential regularization path vs ONE vmapped grid
    program over the same data — wall-clock (cold incl. compile AND
    warm), jit lowerings counted per path, per-λ objective parity, and
    the readback count for the whole grid's result scalars. Gates live
    in dev-scripts/bench_grid.sh (host-class-aware: >= 1.3x warm at
    G >= 4 on multi-core/chip hosts; parity-only on a 1-core container,
    where the batched program and the sequential loop serialize onto the
    same core)."""
    import jax._src.test_util as jtu
    import jax.numpy as jnp

    from photon_ml_tpu import training
    from photon_ml_tpu.data.batch import SparseBatch
    from photon_ml_tpu.optim import problem as problem_mod
    from photon_ml_tpu.optim.config import RegularizationType
    from photon_ml_tpu.parallel import overlap
    from photon_ml_tpu.task import TaskType

    rng = np.random.default_rng(seed)
    indices = rng.integers(0, d, size=(n, k)).astype(np.int32)
    values = rng.normal(size=(n, k)).astype(np.float32)
    w_true = np.zeros(d, np.float32)
    w_true[: d // 10] = rng.normal(size=d // 10)
    z = (w_true[indices] * values).sum(axis=1)
    labels = (
        1.0 / (1.0 + np.exp(-z)) > rng.uniform(size=n)
    ).astype(np.float32)
    batch = SparseBatch(
        indices=jnp.asarray(indices),
        values=jnp.asarray(values),
        labels=jnp.asarray(labels),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )
    lambdas = [float(x) for x in lambdas]
    kw = dict(
        regularization_type=RegularizationType.L2,
        regularization_weights=lambdas,
        max_iter=max_iter,
    )

    def timed(fn):
        t0 = time.perf_counter()
        models, results = fn()
        # force completion through the SAME single batched fetch the
        # driver uses — wall-clock includes the readback round(s)
        scalars = training.grid_result_scalars(results)
        return time.perf_counter() - t0, scalars

    def run_seq(ls=None):
        return training.train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d, warm_start=True,
            **{**kw, "regularization_weights": ls or lambdas},
        )

    def run_bat(ls=None):
        return training.train_grid_batched(
            batch, TaskType.LOGISTIC_REGRESSION, d,
            **{**kw, "regularization_weights": ls or lambdas},
        )

    regrid = [lam * 1.5 for lam in lambdas]  # same shape, new λ values
    out = {}
    for label, fn in (("sequential", run_seq), ("batched", run_bat)):
        problem_mod._FIT_CACHE.clear()
        with jtu.count_jit_and_pmap_lowerings() as cnt:
            cold_s, scalars = timed(fn)
        lowerings = cnt[0]
        warm_s, _ = timed(fn)  # fit program cached: steady-state cost
        # the 1-compile contract, measured: a DIFFERENT grid of the same
        # shape must lower 0 new programs (λ is a traced argument)
        with jtu.count_jit_and_pmap_lowerings() as cnt2:
            fn(regrid)
        overlap.reset_readback_stats()
        _, results = fn()
        training.grid_result_scalars(results)
        out[label] = {
            "cold_s": round(cold_s, 3),
            "warm_s": round(warm_s, 3),
            "jit_lowerings_cold": int(lowerings),
            "jit_lowerings_regrid": int(cnt2[0]),
            "scalar_readback_rounds": overlap.readback_stats(),
            "objectives": {
                str(lam): scalars[lam][1] for lam in lambdas
            },
            "iterations": {
                str(lam): scalars[lam][0] for lam in lambdas
            },
        }
    parity = max(
        abs(out["batched"]["objectives"][key]
            - out["sequential"]["objectives"][key])
        / max(abs(out["sequential"]["objectives"][key]), 1e-12)
        for key in out["sequential"]["objectives"]
    )
    speedup_warm = out["sequential"]["warm_s"] / max(
        out["batched"]["warm_s"], 1e-9
    )
    speedup_cold = out["sequential"]["cold_s"] / max(
        out["batched"]["cold_s"], 1e-9
    )
    return {
        "config": name,
        "metric": "grid_batched_warm_speedup",
        "value": round(speedup_warm, 3),
        "unit": "x (sequential warm wall / batched warm wall)",
        "detail": {
            "n": n, "d": d, "nnz_per_row": k, "G": len(lambdas),
            "max_iter": max_iter,
            "sequential": out["sequential"],
            "batched": out["batched"],
            "speedup_warm": round(speedup_warm, 3),
            "speedup_cold": round(speedup_cold, 3),
            "objective_parity_rel_max": float(parity),
            "host": {"cpu_count": os.cpu_count()},
            "data": "synthetic logistic (planted sparse model)",
        },
    }


def _serving_config(name, *, seed=0):
    """Online scoring service bench (ISSUE 7 / photon_ml_tpu.serving):
    a synthetic GAME bank at config-5-class model shapes (FE 1M dims +
    600k-user RE bank on chip-attached hosts; scaled down on the CPU
    container, stated in the output) served through the real stack —
    device bank, AOT shape ladder, micro-batcher — under two loads:

    - **single-request closed loop**: one request in flight, every
      dispatch shape 1 — the latency floor (p50/p99 reported);
    - **saturating open loop**: N submitter threads, continuous
      batching coalesces to the ladder — the QPS headline.

    Both phases run with jax's lowering counter active: the request
    path must lower ZERO programs after the AOT warmup (the
    fixed-shape contract). Gates live in dev-scripts/bench_serving.sh
    (p99 bound + zero recompiles everywhere; QPS chip-attached only).
    """
    import jax
    import jax._src.test_util as jtu

    from photon_ml_tpu.parallel import overlap
    from photon_ml_tpu.serving import (
        MicroBatcher,
        ScoreRequest,
        ServingMetrics,
        ServingPrograms,
        bank_from_arrays,
    )

    on_chip = any(p.platform != "cpu" for p in jax.devices())
    if on_chip:
        d_fixed, n_users, d_user = 1 << 20, 600_000, 1000
        k_fixed, k_user = 64, 32
        n_closed, n_open, concurrency = 2_000, 20_000, 32
        shape_note = "config-5 FE/RE shapes (1M dims, 600k users x 1000)"
    else:
        d_fixed, n_users, d_user = 1 << 17, 20_000, 64
        k_fixed, k_user = 32, 16
        n_closed, n_open, concurrency = 300, 4_000, 8
        shape_note = "CPU-scaled shapes (131k dims, 20k users x 64)"

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    bank = bank_from_arrays(
        fixed=[(
            "global", "g",
            rng.standard_normal(d_fixed, dtype=np.float32) * 0.1,
        )],
        random=[(
            "per-user", "userId", "u",
            rng.standard_normal((n_users, d_user), dtype=np.float32) * 0.1,
            [f"user{i}" for i in range(n_users)],
        )],
        shard_widths={"g": k_fixed, "u": k_user},
    )
    stage_s = time.perf_counter() - t0
    programs = ServingPrograms()
    t0 = time.perf_counter()
    programs.ensure_compiled(bank)
    warmup_s = time.perf_counter() - t0

    def make_requests(n):
        gi = rng.integers(0, d_fixed, size=(n, k_fixed)).astype(np.int32)
        gv = rng.standard_normal((n, k_fixed), dtype=np.float32)
        ui = rng.integers(0, d_user, size=(n, k_user)).astype(np.int32)
        uv = rng.standard_normal((n, k_user), dtype=np.float32)
        users = rng.integers(0, n_users, size=n)
        # raw ids, like production traffic: the dispatch loop pays the
        # per-batch id->row resolve, so the measured latency includes it
        return [
            ScoreRequest(
                uid=str(i),
                indices={"g": gi[i], "u": ui[i]},
                values={"g": gv[i], "u": uv[i]},
                entity_ids={"userId": f"user{int(users[i])}"},
            )
            for i in range(n)
        ]

    compiles_before = programs.stats()["compile_count"]
    out = {}
    with jtu.count_jit_and_pmap_lowerings() as lowerings:
        # -- closed loop: the single-request latency floor ------------------
        closed_metrics = ServingMetrics()
        reqs = make_requests(n_closed)
        overlap.reset_readback_stats()
        with MicroBatcher(
            lambda: bank, programs, closed_metrics
        ) as batcher:
            for r in reqs:
                batcher.score(r)
        snap = closed_metrics.snapshot()
        out["closed"] = {
            "requests": snap["requests"],
            "p50_ms": snap["latency_p50_ms"],
            "p99_ms": snap["latency_p99_ms"],
            "mean_ms": snap["latency_mean_ms"],
            "qps": snap["qps"],
            "dispatches": snap["dispatches"],
            "readbacks": overlap.readback_stats(),
        }

        # -- open loop: saturating concurrent submitters --------------------
        import threading

        open_metrics = ServingMetrics()
        reqs = make_requests(n_open)
        it = iter(reqs)
        lock = threading.Lock()
        overlap.reset_readback_stats()

        def worker():
            while True:
                with lock:
                    r = next(it, None)
                if r is None:
                    return
                batcher.score(r)

        with MicroBatcher(lambda: bank, programs, open_metrics) as batcher:
            threads = [
                threading.Thread(target=worker)
                for _ in range(concurrency)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            open_wall_s = time.perf_counter() - t0
        snap = open_metrics.snapshot()
        out["open"] = {
            "requests": snap["requests"],
            "concurrency": concurrency,
            "qps": round(n_open / open_wall_s, 1),
            "p50_ms": snap["latency_p50_ms"],
            "p99_ms": snap["latency_p99_ms"],
            "dispatches": snap["dispatches"],
            "readbacks": overlap.readback_stats(),
            "batch_occupancy_mean": snap["batch_occupancy_mean"],
            "pad_waste_frac": snap["pad_waste_frac"],
            "shape_counts": snap["shape_counts"],
        }

    stats = programs.stats()
    return {
        "config": name,
        "metric": "serving_p99_ms_single_request",
        "value": out["closed"]["p99_ms"],
        "unit": "ms (closed-loop p99; open-loop QPS in detail)",
        "detail": {
            "device": str(jax.devices()[0]),
            "host": {"cpu_count": os.cpu_count(), "on_chip": on_chip},
            "shape_note": shape_note,
            "model": {
                "d_fixed": d_fixed, "n_users": n_users, "d_user": d_user,
                "k_fixed": k_fixed, "k_user": k_user,
                "bank_bytes": bank.device_bytes(),
            },
            "ladder": list(programs.ladder),
            "stage_s": round(stage_s, 3),
            "aot_warmup_s": round(warmup_s, 3),
            "aot_programs": stats["compiled_programs"],
            "closed": out["closed"],
            "open": out["open"],
            # the fixed-shape contract, measured over BOTH phases
            "request_path_lowerings": int(lowerings[0]),
            "recompiles_after_warmup": (
                stats["compile_count"] - compiles_before
            ),
            "cold_dispatch_compiles": stats["cold_dispatch_compiles"],
            "data": "synthetic bank + synthetic request trace",
        },
    }


def _overload_config(name, *, seed=0):
    """Serving-under-fire bench (ISSUE 8): an open-loop flood PAST
    capacity through the admission-controlled micro-batcher.

    Unlike ``10_serving``'s closed-loop submitters (which self-pace to
    the service rate), this section fires ``n_flood`` requests with a
    tight ``deadline_ms`` from ``flood_threads`` threads as fast as
    they can — deliberately more offered load than the device can
    absorb. The service's job is NOT to finish them all; it is to

    - give EVERY submitted request exactly one terminal outcome
      (scored, SHED, DEADLINE_EXCEEDED) — counted here, gated by
      ``dev-scripts/bench_overload.sh``;
    - keep the ADMITTED requests' p99 bounded (shedding is what buys
      this: an unbounded queue converts overload into unbounded p99);
    - lower ZERO programs on the request path while overloaded;
    - then drain a parting burst inside ``drain_timeout_s`` with no
      hung futures (the SIGTERM protocol, timed).
    """
    import threading

    import jax
    import jax._src.test_util as jtu

    from photon_ml_tpu.serving import (
        DeadlineExceeded,
        MicroBatcher,
        RequestShed,
        ScoreRequest,
        ServingError,
        ServingMetrics,
        ServingPrograms,
        bank_from_arrays,
    )

    on_chip = any(p.platform != "cpu" for p in jax.devices())
    if on_chip:
        d_fixed, n_users, d_user = 1 << 20, 600_000, 1000
        k_fixed, k_user = 64, 32
        n_flood, flood_threads = 20_000, 64
        deadline_ms, max_queue = 5.0, 8192
        shape_note = "config-5 FE/RE shapes (1M dims, 600k users x 1000)"
    else:
        d_fixed, n_users, d_user = 1 << 15, 2_000, 32
        k_fixed, k_user = 16, 8
        n_flood, flood_threads = 3_000, 16
        deadline_ms, max_queue = 25.0, 2048
        shape_note = "CPU-scaled shapes (32k dims, 2k users x 32)"
    drain_timeout_s = float(
        os.environ.get("PHOTON_OVERLOAD_DRAIN_TIMEOUT_S", "5")
    )
    drain_burst = 256

    rng = np.random.default_rng(seed)
    bank = bank_from_arrays(
        fixed=[(
            "global", "g",
            rng.standard_normal(d_fixed, dtype=np.float32) * 0.1,
        )],
        random=[(
            "per-user", "userId", "u",
            rng.standard_normal((n_users, d_user), dtype=np.float32) * 0.1,
            [f"user{i}" for i in range(n_users)],
        )],
        shard_widths={"g": k_fixed, "u": k_user},
    )
    programs = ServingPrograms()
    programs.ensure_compiled(bank)

    def make_requests(n, deadline):
        gi = rng.integers(0, d_fixed, size=(n, k_fixed)).astype(np.int32)
        gv = rng.standard_normal((n, k_fixed), dtype=np.float32)
        ui = rng.integers(0, d_user, size=(n, k_user)).astype(np.int32)
        uv = rng.standard_normal((n, k_user), dtype=np.float32)
        users = rng.integers(0, n_users, size=n)
        return [
            ScoreRequest(
                uid=str(i),
                indices={"g": gi[i], "u": ui[i]},
                values={"g": gv[i], "u": uv[i]},
                entity_ids={"userId": f"user{int(users[i])}"},
                deadline_ms=deadline,
            )
            for i in range(n)
        ]

    metrics = ServingMetrics()
    compiles_before = programs.stats()["compile_count"]
    outcomes = {}
    out_lock = threading.Lock()

    def note(outcome):
        with out_lock:
            outcomes[outcome] = outcomes.get(outcome, 0) + 1

    with jtu.count_jit_and_pmap_lowerings() as lowerings:
        batcher = MicroBatcher(
            lambda: bank, programs, metrics, max_queue=max_queue
        )
        reqs = make_requests(n_flood, deadline_ms)
        it = iter(reqs)
        it_lock = threading.Lock()
        futures = []
        fut_lock = threading.Lock()

        def flood():
            # TRUE open loop: submit as fast as admission allows, never
            # wait for results — offered load exceeds capacity by
            # construction
            while True:
                with it_lock:
                    r = next(it, None)
                if r is None:
                    return
                try:
                    fut = batcher.submit(r)
                except RequestShed:
                    note("shed")
                    continue
                except ServingError as e:
                    note(f"error:{e.code}")
                    continue
                with fut_lock:
                    futures.append(fut)

        threads = [
            threading.Thread(target=flood) for _ in range(flood_threads)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        flood_submit_s = time.perf_counter() - t0
        for fut in futures:
            try:
                fut.result(timeout=60.0)
                note("ok")
            except DeadlineExceeded:
                note("deadline_exceeded")
            except ServingError as e:
                note(f"error:{e.code}")
        flood_wall_s = time.perf_counter() - t0

        # -- drain phase: a parting burst, then the bounded SIGTERM
        # drain — zero hung futures inside the budget ------------------
        burst = make_requests(drain_burst, None)
        burst_futs = []
        burst_refused = 0
        for r in burst:
            try:
                burst_futs.append(batcher.submit(r))
            except ServingError:
                burst_refused += 1
        report = batcher.drain(drain_timeout_s)
        burst_terminal = sum(1 for f in burst_futs if f.done())

    snap = metrics.snapshot()
    stats = programs.stats()
    terminal = sum(outcomes.values())
    refused = outcomes.get("shed", 0) + outcomes.get("deadline_exceeded", 0)
    shed_rate = round(refused / n_flood, 6)
    return {
        "config": name,
        "metric": "overload_shed_rate",
        "value": shed_rate,
        "unit": "refused/submitted under 0-pacing flood (details gated)",
        "detail": {
            "device": str(jax.devices()[0]),
            "host": {"cpu_count": os.cpu_count(), "on_chip": on_chip},
            "shape_note": shape_note,
            "deadline_ms": deadline_ms,
            "max_queue": max_queue,
            "flood": {
                "submitted": n_flood,
                "threads": flood_threads,
                "submit_wall_s": round(flood_submit_s, 3),
                "wall_s": round(flood_wall_s, 3),
                "outcomes": dict(sorted(outcomes.items())),
                "terminal": terminal,
                "ok": outcomes.get("ok", 0),
                "refused": refused,
                "shed_rate": shed_rate,
                "sheds_by_reason": snap["sheds"],
                "deadline_expired_at_dispatch": snap["deadline_expired"],
                "admitted_p50_ms": snap.get("latency_p50_ms"),
                "admitted_p99_ms": snap.get("latency_p99_ms"),
                "dispatches": snap["dispatches"],
                "batch_occupancy_mean": snap["batch_occupancy_mean"],
            },
            "drain": {
                **report.to_dict(),
                "burst": drain_burst,
                "burst_admitted": len(burst_futs),
                "burst_refused": burst_refused,
                "burst_terminal": burst_terminal,
                "budget_s": drain_timeout_s,
            },
            "request_path_lowerings": int(lowerings[0]),
            "recompiles_after_warmup": (
                stats["compile_count"] - compiles_before
            ),
            "cold_dispatch_compiles": stats["cold_dispatch_compiles"],
            "data": "synthetic bank + synthetic open-loop flood",
        },
    }


SHARD_CHILD_FLAG = "--shard-routing-child"


def _shard_routing_shapes():
    # What this section asserts is host-plane (conservation, cache hits,
    # 0 lowerings) and valid on the CPU: the shard-server children run
    # there whatever the parent holds, since a chip belongs to one
    # process at a time and the parent may already have it.
    return {
        "E": 2_000, "d_g": 1 << 14, "d_u": 32,
        "k_g": 16, "k_u": 8,
        "n_flood": int(os.environ.get("PHOTON_ROUTING_FLOOD", "1200")),
        "threads": 8, "n_kill": 400,
        "zipf_a": 1.3, "payload_pool": 4,
        "note": "CPU-scaled shapes (16k dims, 2k users x 32)",
    }


def _shard_routing_ids(E):
    return [f"user{i:06d}" for i in range(E)]


def _shard_routing_arrays(seed, shapes):
    rng = np.random.default_rng(seed)
    fe = rng.standard_normal(shapes["d_g"]).astype(np.float32) * 0.1
    re = (
        rng.standard_normal((shapes["E"], shapes["d_u"]))
        .astype(np.float32) * 0.1
    )
    return fe, re


def _shard_routing_shard_configs():
    from photon_ml_tpu.game.config import FeatureShardConfiguration

    return [
        FeatureShardConfiguration("g", ["features"]),
        FeatureShardConfiguration("u", ["userFeatures"]),
    ]


def _shard_routing_child(cfg_text):
    """One shard-server subprocess for the 14_shard_routing fleet:
    builds its 1/N slice of the SAME deterministic synthetic bank the
    parent knows (seed -> arrays, no artifact on disk), serves the
    routing control plane (topology + two-step swap via a synthetic
    stager keyed by seed), publishes its port, and on SIGTERM drains
    and writes its program-cache stats — the parent gates 0 request-
    path lowerings per shard on exactly that file."""
    import signal
    import threading

    from photon_ml_tpu.reliability import atomic_write_json
    from photon_ml_tpu.serving import (
        ServingModel,
        ServingPrograms,
        ShardServer,
        bank_from_arrays,
    )
    from photon_ml_tpu.utils.index_map import IndexMap

    cfg = json.loads(cfg_text)
    shapes = cfg["shapes"]
    s, n = int(cfg["shard"]), int(cfg["count"])
    ids = _shard_routing_ids(shapes["E"])
    imaps = {
        "g": IndexMap({f"g{j}\t": j for j in range(shapes["d_g"])}),
        "u": IndexMap({f"u{j}\t": j for j in range(shapes["d_u"])}),
    }
    widths = {"g": shapes["k_g"], "u": shapes["k_u"]}

    def build(seed):
        fe, re = _shard_routing_arrays(seed, shapes)
        return bank_from_arrays(
            fixed=[("global", "g", fe)],
            random=[("per-user", "userId", "u", re, ids)],
            shard_widths=widths,
            index_maps=imaps,
            entity_shard=(s, n),
        )

    sm = ServingModel(
        build(cfg["seed"]),
        ServingPrograms(tuple(cfg.get("ladder", (1, 8, 64)))),
        partial=True,
        entity_shard=(s, n),
    )

    def stager(obj):
        return sm.prepare_swap_bank(build(int(obj["model_dir"])))

    srv = ShardServer(
        sm,
        _shard_routing_shard_configs(),
        (s, n),
        stager=stager,
        has_response=False,
    ).start()
    out = cfg["out"]
    os.makedirs(out, exist_ok=True)
    atomic_write_json(
        os.path.join(out, "frontend.json"),
        {"port": srv.port, "pid": os.getpid(), "shard": s, "count": n},  # photon: entropy(discovery artifact; pid names the live shard process)
    )
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_a: stop.set())
    while not stop.wait(timeout=0.2):
        pass
    report = srv.close(drain_timeout_s=5.0)
    atomic_write_json(
        os.path.join(out, "metrics.json"),
        {
            "programs": sm.programs.stats(),
            "serving": srv.metrics.snapshot(),
            "drain": report.to_dict(),
        },
    )


def _shard_routing_config(name, *, seed=0):
    """Planet-scale serving bench (ISSUE 12): aggregate QPS vs shard
    count through the scatter/gather router over REAL shard-server
    subprocesses, under a zipf (head-skewed) open-loop replay.

    Per fleet size N in {1, 2, 4}: spawn N shard-server processes
    (each holding 1/N of the RE bank, partial-score mode), connect the
    router, flood it from ``threads`` submitter threads over a zipf
    entity draw whose payloads repeat (the hot-entity cache's food),
    and record aggregate QPS, fan-out p50/p99, cache hit rate and
    outcome conservation. At N=4 a second, smaller flood runs with one
    shard SIGKILLed mid-fleet: its entities must degrade FE-only
    (named, counted) — never a failed run. Children then SIGTERM-drain
    and report their program caches: the parent records 0 request-path
    lowerings per shard. Gates in dev-scripts/bench_shard_routing.sh
    (scaling gate multi-core/chip only — on a 1-core container N
    processes share one core and the ratio is recorded, not gated).
    """
    import signal
    import subprocess
    import tempfile
    import threading

    from photon_ml_tpu.serving import (
        RoutingPolicy,
        ShardRouter,
        ServingError,
    )

    shapes = _shard_routing_shapes()
    ids = _shard_routing_ids(shapes["E"])
    rng = np.random.default_rng(seed)
    # zipf head draw + a small payload pool per entity: head entities
    # repeat identical (entity, features) pairs — deterministic score
    # paths the cache may legally absorb
    zipf = rng.zipf(shapes["zipf_a"], size=shapes["n_flood"] * 2)
    entity_draw = (zipf - 1) % shapes["E"]
    pool = {}

    def record_for(i, j, variant=0):
        # ``variant`` switches to a disjoint payload universe: the kill
        # leg uses variant=1 so its records MISS the cache by
        # construction and the dead shard's entities must hit the wire
        key = (int(i), int(j) % shapes["payload_pool"], int(variant))
        rec = pool.get(key)
        if rec is None:
            import zlib

            # crc32, not hash(): flood payloads must be identical
            # across the parent and the relaunched child processes
            # (PYTHONHASHSEED differs), or cache-hit accounting drifts
            seed = zlib.crc32(
                f"{key[0]}:{key[1]}:{key[2]}".encode("utf-8")
            )
            prng = np.random.default_rng(seed & 0x7FFFFFFF)
            rec = {
                "uid": f"q{key[0]}-{key[1]}-{key[2]}",
                "metadataMap": {"userId": ids[key[0]]},
                "features": [
                    {"name": f"g{int(g)}", "term": "",
                     "value": float(prng.standard_normal())}
                    for g in prng.integers(
                        0, shapes["d_g"], size=shapes["k_g"] // 2
                    )
                ],
                "userFeatures": [
                    {"name": f"u{int(u)}", "term": "",
                     "value": float(prng.standard_normal())}
                    for u in prng.integers(
                        0, shapes["d_u"], size=shapes["k_u"] // 2
                    )
                ],
                "offset": 0.0,
            }
            pool[key] = rec
        return rec

    base = tempfile.mkdtemp(prefix="photon-shard-routing-")
    child_env = dict(os.environ, JAX_PLATFORMS="cpu")

    def spawn_fleet(n_shards):
        procs = []
        for s in range(n_shards):
            out = os.path.join(base, f"n{n_shards}-shard{s}")
            cfg = json.dumps({
                "shard": s, "count": n_shards, "seed": seed,
                "shapes": shapes, "out": out, "ladder": [1, 8, 64],
            })
            procs.append((out, subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 SHARD_CHILD_FLAG, cfg],
                env=child_env,
                stdout=subprocess.DEVNULL,
                stderr=subprocess.STDOUT,
            )))
        ports = []
        for out, p in procs:
            fj = os.path.join(out, "frontend.json")
            deadline = time.perf_counter() + 180
            while not os.path.exists(fj):
                if p.poll() is not None:
                    raise RuntimeError(
                        f"shard child died during boot ({out})"
                    )
                if time.perf_counter() > deadline:
                    raise RuntimeError("shard child boot timeout")
                time.sleep(0.2)
            ports.append(json.load(open(fj))["port"])
        return procs, ports

    def flood(router, n_requests, offset, threads, variant=0):
        it = iter(range(n_requests))
        it_lock = threading.Lock()
        counts = {}
        c_lock = threading.Lock()

        def note(key):
            with c_lock:
                counts[key] = counts.get(key, 0) + 1

        def worker():
            while True:
                with it_lock:
                    i = next(it, None)
                if i is None:
                    return
                rec = record_for(
                    entity_draw[offset + i],
                    entity_draw[offset + i] + i,
                    variant,
                )
                try:
                    out = router.score_record(rec)
                    note("degraded" if out.degraded else "ok")
                except ServingError as e:
                    note(f"error:{e.code}")

        ts = [threading.Thread(target=worker) for _ in range(threads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return counts, time.perf_counter() - t0

    fleets = {}
    kill_leg = None
    for n_shards in (1, 2, 4):
        procs, ports = spawn_fleet(n_shards)
        router = ShardRouter(
            [("127.0.0.1", pt) for pt in ports],
            entity_ids={"userId": ids},
            shard_configs=_shard_routing_shard_configs(),
            policy=RoutingPolicy(subrequest_timeout_s=5.0),
            cache_entries=int(os.environ.get(
                "PHOTON_ROUTING_CACHE_ENTRIES", "8192"
            )),
        )
        try:
            router.connect()
            # tiny warmup so the flood never measures ladder selection
            flood(router, 16, 0, 4)
            counts, wall = flood(
                router, shapes["n_flood"], 16, shapes["threads"]
            )
            snap = router.metrics.snapshot()
            cache = router.cache.snapshot()
            terminal = sum(counts.values())
            fleets[str(n_shards)] = {
                "outcomes": dict(sorted(counts.items())),
                "terminal": terminal,
                "submitted": shapes["n_flood"],
                "wall_s": round(wall, 3),
                "qps": round(terminal / wall, 1) if wall > 0 else None,
                "fanout_p50_ms": snap.get("latency_p50_ms"),
                "fanout_p99_ms": snap.get("latency_p99_ms"),
                "fanout_mean": snap["fanout_mean"],
                "subrequests": snap["subrequests"],
                "hedges": snap["hedges"],
                "cache": cache,
                "cache_hit_rate": round(
                    cache["hits"] / max(cache["hits"] + cache["misses"], 1),
                    4,
                ),
            }
            if n_shards == 4:
                # the kill leg: SIGKILL one shard mid-fleet, flood
                # again — its entities degrade (FE-only, named), the
                # run never fails
                procs[3][1].send_signal(signal.SIGKILL)
                procs[3][1].wait(timeout=30)
                counts, wall = flood(
                    router, shapes["n_kill"], shapes["n_flood"] // 2,
                    shapes["threads"], variant=1,
                )
                kill_leg = {
                    "killed_shard": 3,
                    "outcomes": dict(sorted(counts.items())),
                    "terminal": sum(counts.values()),
                    "submitted": shapes["n_kill"],
                    "wall_s": round(wall, 3),
                    "degraded": counts.get("degraded", 0),
                    "errors": sum(
                        v for k, v in counts.items()
                        if k.startswith("error")
                    ),
                    "health": [h.snapshot() for h in router.health],
                }
        finally:
            router.close()
            shard_stats = []
            for out, p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for out, p in procs:
                if p.poll() is None:
                    try:
                        p.wait(timeout=60)
                    except subprocess.TimeoutExpired:
                        p.kill()
                mp = os.path.join(out, "metrics.json")
                if os.path.exists(mp):
                    m = json.load(open(mp))
                    shard_stats.append({
                        "shard": os.path.basename(out),
                        "cold_dispatch_compiles": (
                            m["programs"]["cold_dispatch_compiles"]
                        ),
                        "compiled_programs": (
                            m["programs"]["compiled_programs"]
                        ),
                        "dispatches": m["serving"]["dispatches"],
                    })
            fleets.setdefault(str(n_shards), {})["shards"] = shard_stats

    q1 = fleets["1"]["qps"] or 1.0
    q4 = fleets["4"]["qps"] or 0.0
    scaling = round(q4 / q1, 3)
    return {
        "config": name,
        "metric": "routing_qps_scaling_4x_over_1x",
        "value": scaling,
        "unit": "aggregate QPS ratio N=4 / N=1 (details gated)",
        "detail": {
            "host": {"cpu_count": os.cpu_count()},
            "shape_note": shapes["note"],
            "zipf_a": shapes["zipf_a"],
            "fleets": fleets,
            "kill_leg": kill_leg,
            "scaling_4_over_1": scaling,
            "scaling_2_over_1": round(
                (fleets["2"]["qps"] or 0.0) / q1, 3
            ),
            "data": (
                "synthetic sharded banks (subprocess fleet) + zipf "
                "open-loop replay through the router"
            ),
        },
    }


def _obs_config(name, *, seed=0):
    """Unified-telemetry overhead A/B (ISSUE 13): the SAME closed-loop
    request stream through the real micro-batcher with the obs plane
    OFF (tracing disabled, no registry views — the shipped default)
    vs ON (span tracing + live metrics registry views + flight
    recorder), alternating passes, median-of-passes per arm.

    The contract being priced: tracing must stay affordable enough to
    leave on in production. Gates its caller applied (CPU-era):
    <2% request-path overhead on this host class (multi-core/chip; the
    1-core container number is recorded honestly), 0 request-path
    lowerings in BOTH arms, readbacks == dispatches unchanged, and
    trace COMPLETENESS — every dispatch of the traced arm produced a
    serving.dispatch span, every request a serving.score span."""
    import jax
    import jax._src.test_util as jtu

    from photon_ml_tpu.obs.flight_recorder import reset_flight_recorder
    from photon_ml_tpu.obs.registry import MetricsRegistry
    from photon_ml_tpu.obs.trace import tracer, tracing_scope
    from photon_ml_tpu.parallel import overlap
    from photon_ml_tpu.serving import (
        MicroBatcher,
        ScoreRequest,
        ServingMetrics,
        ServingPrograms,
        bank_from_arrays,
    )

    on_chip = any(p.platform != "cpu" for p in jax.devices())
    if on_chip:
        d_fixed, n_users, d_user = 1 << 18, 100_000, 128
        k_fixed, k_user = 32, 16
        n_req, passes = 2_000, 3
    else:
        d_fixed, n_users, d_user = 1 << 15, 5_000, 32
        k_fixed, k_user = 16, 8
        n_req, passes = 400, 5

    rng = np.random.default_rng(seed)
    bank = bank_from_arrays(
        fixed=[(
            "global", "g",
            rng.standard_normal(d_fixed, dtype=np.float32) * 0.1,
        )],
        random=[(
            "per-user", "userId", "u",
            rng.standard_normal((n_users, d_user), dtype=np.float32) * 0.1,
            [f"user{i}" for i in range(n_users)],
        )],
        shard_widths={"g": k_fixed, "u": k_user},
    )
    programs = ServingPrograms()
    programs.ensure_compiled(bank)

    def make_requests(trace_ids: bool):
        gi = rng.integers(0, d_fixed, size=(n_req, k_fixed)).astype(np.int32)
        gv = rng.standard_normal((n_req, k_fixed), dtype=np.float32)
        ui = rng.integers(0, d_user, size=(n_req, k_user)).astype(np.int32)
        uv = rng.standard_normal((n_req, k_user), dtype=np.float32)
        users = rng.integers(0, n_users, size=n_req)
        return [
            ScoreRequest(
                uid=str(i),
                indices={"g": gi[i], "u": ui[i]},
                values={"g": gv[i], "u": uv[i]},
                entity_ids={"userId": f"user{int(users[i])}"},
                # the traced arm carries wire context like frontend
                # traffic does, so the per-request span path is priced
                trace_id=f"t-{i}" if trace_ids else None,
                parent_span=f"s-{i}" if trace_ids else None,
            )
            for i in range(n_req)
        ]

    def one_pass(obs_on: bool) -> float:
        reqs = make_requests(trace_ids=obs_on)
        metrics = ServingMetrics()
        registry = None
        if obs_on:
            registry = MetricsRegistry()
            registry.register_view("serving", metrics.snapshot)
        with tracing_scope(obs_on):
            with MicroBatcher(lambda: bank, programs, metrics) as mb:
                t0 = time.perf_counter()
                for r in reqs:
                    mb.score(r)
                wall = time.perf_counter() - t0
            if obs_on:
                registry.snapshot()  # one live scrape per pass
        return wall, metrics.snapshot()

    # The deterministic micro (see below) is measured BOTH here — on
    # the warm but still-clean heap — and again after the A/B: the
    # min is the operation's cost, the spread is allocator state.
    def span_record_micro(n_micro=20_000, reps=3) -> float:
        import gc

        from photon_ml_tpu.obs.trace import record_span as _rs

        gc.collect()
        best = float("inf")
        for _ in range(reps):
            with tracing_scope(True):
                t0 = time.perf_counter()
                for _i in range(n_micro):
                    _rs(
                        "serving.dispatch", 0.0, 1.0, shape=8,
                        occupancy=8, generation=1, partial=False,
                        traces=[("t", "s", False)] * 8,
                    )
                best = min(
                    best, (time.perf_counter() - t0) / n_micro * 1e6
                )
            tracer().clear()
        return best

    # warmup (both paths touched once, excluded from the medians)
    one_pass(False)
    one_pass(True)
    span_record_us = span_record_micro()

    walls = {False: [], True: []}
    snaps = {False: None, True: None}
    reset_flight_recorder()
    tracer().clear()
    overlap.reset_readback_stats()
    readbacks_before = overlap.readback_stats()
    with jtu.count_jit_and_pmap_lowerings() as lowerings:
        for _ in range(passes):
            for arm in (False, True):  # alternating, same stream shape
                wall, snap = one_pass(arm)
                walls[arm].append(wall)
                snaps[arm] = snap
    readbacks = overlap.readback_stats() - readbacks_before

    # trace completeness over the traced passes (expansion happens
    # HERE, off the request path — the hot loop recorded one span per
    # dispatch carrying its traced-request contexts)
    from photon_ml_tpu.obs.flight_recorder import flight_recorder
    from photon_ml_tpu.obs.trace import expand_spans

    spans = expand_spans(tracer().snapshot())
    dispatch_spans = [s for s in spans if s.name == "serving.dispatch"]
    score_spans = [s for s in spans if s.name == "serving.score"]
    conservation = flight_recorder().check_conservation()

    # Paired estimator: the container's absolute speed drifts far more
    # across the run than the effect under test, so each off-pass is
    # compared only to the on-pass that ran right after it (alternating
    # arms above) and the MEDIAN pairwise ratio is the overhead.
    ratios = sorted(
        on / off for off, on in zip(walls[False], walls[True])
    )
    overhead = ratios[len(ratios) // 2] - 1.0
    off_s = float(min(walls[False]))
    on_s = float(min(walls[True]))

    # Deterministic twin of the A/B: the obs plane's ENTIRE
    # request-path addition is one record_span per dispatch (+ one
    # tuple per traced request); measure that call in isolation and
    # divide by the measured per-request wall. On hosts whose
    # scheduling noise exceeds the effect (this 1-core container
    # swings +-20% pass to pass), THIS number was the gated one —
    # the A/B stays recorded honestly either way.
    span_record_us = min(span_record_us, span_record_micro())
    per_request_us = off_s / n_req * 1e6
    implied_overhead = span_record_us / per_request_us
    traced_dispatches = passes * snaps[True]["dispatches"]
    return {
        "config": name,
        "metric": "obs_request_path_overhead_frac",
        "value": round(overhead, 5),
        "unit": "frac (tracing+metrics on vs off, closed loop)",
        "detail": {
            "device": str(jax.devices()[0]),
            "host": {"cpu_count": os.cpu_count(), "on_chip": on_chip},
            "requests_per_pass": n_req,
            "passes_per_arm": passes,
            "off_wall_s": [round(w, 4) for w in walls[False]],
            "on_wall_s": [round(w, 4) for w in walls[True]],
            "pairwise_ratios": [round(r, 4) for r in ratios],
            "off_qps": round(n_req / off_s, 1),
            "on_qps": round(n_req / on_s, 1),
            "span_record_us_per_dispatch": round(span_record_us, 3),
            "per_request_us": round(per_request_us, 2),
            "implied_overhead_frac": round(implied_overhead, 5),
            "request_path_lowerings": int(lowerings[0]),
            "readbacks": readbacks,
            "dispatches": (
                passes * (
                    snaps[False]["dispatches"] + snaps[True]["dispatches"]
                )
            ),
            "traced_dispatches": traced_dispatches,
            "dispatch_spans": len(dispatch_spans),
            "score_spans": len(score_spans),
            "traced_requests": passes * n_req,
            "conservation": conservation,
            "data": "synthetic bank + synthetic closed-loop trace",
        },
    }


def _fleet_obs_config(name, *, seed=0):
    """Fleet-observability overhead A/B (ISSUE 15): the SAME closed-loop
    routed request stream through a REAL 2-shard TCP fleet with the
    fleet-obs plane OFF (tracing disabled, no collector — the shipped
    default) vs ON (span tracing + the live FleetCollector draining
    every member's ring over fresh connections + router conservation
    attribution), alternating passes.

    The contract being priced: the collector must stay affordable
    enough to leave on against a production fleet. Gates its caller
    applied (CPU-era): <2% request-path overhead on
    multi-core/chip hosts (the 1-core container number is recorded
    honestly under a noise ceiling), 0 request-path lowerings in BOTH
    arms, fleet conservation balanced (router admitted == Σ
    shard-attributed + router-local over per-member books), and merge
    COMPLETENESS — every traced request's router.request root reached
    the collector and the stitched fleet trace verifies."""
    import jax
    import jax._src.test_util as jtu

    from photon_ml_tpu.game.config import FeatureShardConfiguration
    from photon_ml_tpu.obs.fleet import (
        FleetCollector,
        fleet_check_conservation,
        verify_fleet_trace,
    )
    from photon_ml_tpu.obs.flight_recorder import FlightRecorder
    from photon_ml_tpu.obs.trace import start_span, tracer, tracing_scope
    from photon_ml_tpu.serving import (
        RoutingPolicy,
        ServingModel,
        ServingPrograms,
        ShardRouter,
        ShardServer,
        bank_from_arrays,
    )
    from photon_ml_tpu.utils.index_map import IndexMap

    on_chip = any(p.platform != "cpu" for p in jax.devices())
    E, d_g, d_u = 128, 256, 16
    n_req, passes = 300, 5
    k = 8
    rng = np.random.default_rng(seed)
    ids = sorted(f"user{i:06d}" for i in range(E))
    fe_w = rng.standard_normal(d_g).astype(np.float32)
    re_w = rng.standard_normal((E, d_u)).astype(np.float32)
    imaps = {
        "g": IndexMap({f"g{j}\t": j for j in range(d_g)}),
        "u": IndexMap({f"u{j}\t": j for j in range(d_u)}),
    }
    shard_cfgs = [
        FeatureShardConfiguration("g", ["features"]),
        FeatureShardConfiguration("u", ["userFeatures"]),
    ]
    shard_books = [FlightRecorder(1 << 14) for _ in range(2)]
    servers = []
    for s in range(2):
        bank = bank_from_arrays(
            fixed=[("global", "g", fe_w)],
            random=[("per-user", "userId", "u", re_w, ids)],
            shard_widths={"g": k, "u": k},
            index_maps=imaps,
            entity_shard=(s, 2),
        )
        sm = ServingModel(
            bank, ServingPrograms((1, 8)), partial=True,
            entity_shard=(s, 2),
        )
        servers.append(ShardServer(
            sm, shard_cfgs, (s, 2), has_response=False,
            recorder=shard_books[s],
        ).start())
    router_book = FlightRecorder(1 << 14)
    router = ShardRouter(
        [("127.0.0.1", srv.port) for srv in servers],
        entity_ids={"userId": ids},
        shard_configs=shard_cfgs,
        policy=RoutingPolicy(subrequest_timeout_s=10.0),
        cache_entries=0,  # price the WIRE path, not cache replay
        recorder=router_book,
    )
    router.connect()
    # one remote member is the whole in-process fleet's tracer (every
    # span reaches the collector exactly once, over real TCP), so the
    # poll path carries the full span stream
    collector = FleetCollector(
        [("fleet", "127.0.0.1", servers[0].port)],
        poll_s=0.05,
    )

    def make_records(n):
        out = []
        gj = rng.integers(0, d_g, size=(n, 3))
        uj = rng.integers(0, d_u, size=(n, 2))
        gv = rng.standard_normal((n, 3))
        uv = rng.standard_normal((n, 2))
        for i in range(n):
            out.append({
                "uid": f"q{i}",
                "metadataMap": {"userId": ids[i % E]},
                "features": [
                    {"name": f"g{int(gj[i, j])}", "term": "",
                     "value": float(gv[i, j])}
                    for j in range(3)
                ],
                "userFeatures": [
                    {"name": f"u{int(uj[i, j])}", "term": "",
                     "value": float(uv[i, j])}
                    for j in range(2)
                ],
            })
        return out

    records = make_records(n_req)

    def one_pass(obs_on: bool) -> float:
        if obs_on:
            collector.start()
        with tracing_scope(obs_on):
            t0 = time.perf_counter()
            for rec in records:
                router.score_record(rec)
            wall = time.perf_counter() - t0
        if obs_on:
            # drain the tail so completeness is exact per pass
            collector.stop(final_poll=True)
        return wall

    try:
        tracer().clear()
        one_pass(False)  # warmup: every program + connection touched
        one_pass(True)
        tracer().clear()
        router_book.reset()
        for b in shard_books:
            b.reset()
        # fresh collector for the measured phase: the warmup pass's
        # spans must not ride the completeness accounting
        collector = FleetCollector(
            [("fleet", "127.0.0.1", servers[0].port)],
            poll_s=0.05,
        )
        walls = {False: [], True: []}
        with jtu.count_jit_and_pmap_lowerings() as lowerings:
            for _ in range(passes):
                for arm in (False, True):
                    walls[arm].append(one_pass(arm))
        # -- merge completeness + fleet conservation -----------------------
        stitched = collector.stitched_spans()
        verdict = verify_fleet_trace(stitched)
        roots = [
            s for s in stitched if s["name"] == "router.request"
        ]
        conservation = fleet_check_conservation(
            router_book.check_conservation(),
            {
                f"shard{i}": {
                    "conservation": shard_books[i].check_conservation(),
                    "complete": True,
                    "shard_indices": [i],
                }
                for i in range(2)
            },
        )
        status = collector.member_status()["fleet"]
    finally:
        router.close()
        for srv in servers:
            srv.close()
    ratios = sorted(
        on / off for off, on in zip(walls[False], walls[True])
    )
    overhead = ratios[len(ratios) // 2] - 1.0
    off_s = float(min(walls[False]))
    per_request_us = off_s / n_req * 1e6
    # Deterministic twin of the A/B: the fleet plane's entire
    # request-path addition in the ROUTER process is two conservation
    # notes + two span record/ends per request (the collector runs on
    # its own thread; its cost rides the A/B only). Priced in
    # isolation — best of several repetitions, because the cost is
    # deterministic and the min strips scheduler interference — and
    # divided by the measured per-request wall.
    import gc

    micro_rec = FlightRecorder(1 << 12)
    n_micro = 20_000
    gc.collect()
    conservation_us = float("inf")
    span_us = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n_micro):
            micro_rec.note_admitted()
            micro_rec.note_terminal("ok", generation=1,
                                    attribution="shard:0")
        conservation_us = min(
            conservation_us, (time.perf_counter() - t0) / n_micro * 1e6
        )
        with tracing_scope(True):
            t0 = time.perf_counter()
            for _ in range(n_micro):
                start_span("router.request", uid="q").end()
                start_span("router.subrequest", shard=0).end()
            span_us = min(
                span_us, (time.perf_counter() - t0) / n_micro * 1e6
            )
        tracer().clear()
    implied = (conservation_us + span_us) / per_request_us
    return {
        "config": name,
        "metric": "fleet_obs_request_path_overhead_frac",
        "value": round(overhead, 5),
        "unit": "frac (fleet tracing+collector+attribution on vs off)",
        "detail": {
            "device": str(jax.devices()[0]),
            "host": {"cpu_count": os.cpu_count(), "on_chip": on_chip},
            "shards": 2,
            "requests_per_pass": n_req,
            "passes_per_arm": passes,
            "off_wall_s": [round(w, 4) for w in walls[False]],
            "on_wall_s": [round(w, 4) for w in walls[True]],
            "pairwise_ratios": [round(r, 4) for r in ratios],
            "off_qps": round(n_req / off_s, 1),
            "per_request_us": round(per_request_us, 2),
            "conservation_note_us": round(conservation_us, 3),
            "span_pair_us": round(span_us, 3),
            "implied_overhead_frac": round(implied, 5),
            "request_path_lowerings": int(lowerings[0]),
            "collector": {
                "polls": status["polls"],
                "errors": status["errors"],
                "spans": status["spans"],
                "ring_dropped": status["ring_dropped"],
                "clock_offset_uncertainty_s": (
                    status["clock_offset_uncertainty_s"]
                ),
            },
            "traced_requests": passes * n_req,
            "router_request_roots": len(roots),
            "stitch_ok": verdict["ok"],
            "stitch_violations": verdict["violations"][:5],
            "score_leaves": verdict["score_leaves"],
            "conservation": conservation,
            "data": "synthetic 2-shard TCP fleet, closed-loop router",
        },
    }


def _wire_config(name, *, seed=0):
    """photon-wire A/B (ISSUE 17): the SAME closed-loop routed request
    stream through a REAL 2-shard TCP fleet over the JSON-lines data
    plane vs the length-prefixed binary plane (negotiated at
    ``connect()``), paired-alternating passes per house rules.

    The contract being priced: binary framing + raw-float codecs must
    cut per-request marshalling cost WITHOUT perturbing a single bit of
    the routed margins. Gates in dev-scripts/bench_wire.sh: bitwise
    parity between arms on every pass, binary micro codec cost below
    the JSON micro cost (best-of-reps, measured pre+post the A/B), 0
    request-path lowerings in BOTH arms, fleet conservation balanced
    over the shared ledger, and the binary trace drain COMPLETE (every
    traced request's router.request root reached the collector, 0 ring
    drops). The QPS speedup gate is multi-core/chip-only; the 1-core
    container ratio is recorded honestly.

    A writer-coalescing burst leg pipelines a flood of score frames on
    ONE connection per protocol and reports the walls plus the
    ``coalesced_responses`` counter delta (responses that shared a
    sendall with a predecessor) — gated > 0 in bench_wire.sh."""
    import gc
    import socket

    import jax
    import jax._src.test_util as jtu

    from photon_ml_tpu.game.config import FeatureShardConfiguration
    from photon_ml_tpu.obs.fleet import (
        FleetCollector,
        fleet_check_conservation,
    )
    from photon_ml_tpu.obs.flight_recorder import FlightRecorder
    from photon_ml_tpu.obs.trace import tracer, tracing_scope
    from photon_ml_tpu.serving import (
        PartialScore,
        RoutingPolicy,
        ServingModel,
        ServingPrograms,
        ShardRouter,
        ShardServer,
        bank_from_arrays,
    )
    from photon_ml_tpu.serving import wire
    from photon_ml_tpu.serving.programs import term_entries
    from photon_ml_tpu.utils.index_map import IndexMap

    on_chip = any(p.platform != "cpu" for p in jax.devices())
    E, d_g, d_u = 128, 256, 16
    n_req, passes = 300, 5
    # shard widths sized for criteo-width rows (26 + 13 features)
    widths = {"g": 32, "u": 16}
    rng = np.random.default_rng(seed)
    ids = sorted(f"user{i:06d}" for i in range(E))
    fe_w = rng.standard_normal(d_g).astype(np.float32)
    re_w = rng.standard_normal((E, d_u)).astype(np.float32)
    imaps = {
        "g": IndexMap({f"g{j}\t": j for j in range(d_g)}),
        "u": IndexMap({f"u{j}\t": j for j in range(d_u)}),
    }
    shard_cfgs = [
        FeatureShardConfiguration("g", ["features"]),
        FeatureShardConfiguration("u", ["userFeatures"]),
    ]
    shard_books = [FlightRecorder(1 << 14) for _ in range(2)]
    servers = []
    for s in range(2):
        bank = bank_from_arrays(
            fixed=[("global", "g", fe_w)],
            random=[("per-user", "userId", "u", re_w, ids)],
            shard_widths=widths,
            index_maps=imaps,
            entity_shard=(s, 2),
        )
        sm = ServingModel(
            bank, ServingPrograms((1, 8)), partial=True,
            entity_shard=(s, 2),
        )
        servers.append(ShardServer(
            sm, shard_cfgs, (s, 2), has_response=False,
            recorder=shard_books[s],
        ).start())
    term_names = tuple(e[1] for e in term_entries(bank.spec))
    # ONE shared router ledger: both arms' requests land in the same
    # book, so the fleet conservation join prices the TOTAL stream
    router_book = FlightRecorder(1 << 14)

    def make_router(wire_mode):
        return ShardRouter(
            [("127.0.0.1", srv.port) for srv in servers],
            entity_ids={"userId": ids},
            shard_configs=shard_cfgs,
            policy=RoutingPolicy(subrequest_timeout_s=10.0),
            cache_entries=0,  # price the WIRE path, not cache replay
            recorder=router_book,
            wire=wire_mode,
        )

    routers = {"json": make_router("json"), "binary": make_router("binary")}
    negotiated = {}
    for arm, r in routers.items():
        negotiated[arm] = r.connect()["wire"]
    assert negotiated == {"json": "json", "binary": "binary"}, negotiated

    # criteo-width records (39 features/row, the paper's serving
    # shape): the wire plane is priced on realistic rows, where
    # per-float text encode/decode is the marshalling tall pole
    n_g_feat, n_u_feat = 26, 13

    def make_records(n):
        out = []
        gj = rng.integers(0, d_g, size=(n, n_g_feat))
        uj = rng.integers(0, d_u, size=(n, n_u_feat))
        gv = rng.standard_normal((n, n_g_feat))
        uv = rng.standard_normal((n, n_u_feat))
        for i in range(n):
            out.append({
                "uid": f"q{i}",
                "metadataMap": {"userId": ids[i % E]},
                "features": [
                    {"name": f"g{int(gj[i, j])}", "term": "",
                     "value": float(gv[i, j])}
                    for j in range(n_g_feat)
                ],
                "userFeatures": [
                    {"name": f"u{int(uj[i, j])}", "term": "",
                     "value": float(uv[i, j])}
                    for j in range(n_u_feat)
                ],
            })
        return out

    records = make_records(n_req)

    def one_pass(arm):
        router = routers[arm]
        lats = []
        scores = []
        t0 = time.perf_counter()
        for rec in records:
            t = time.perf_counter()
            scores.append(float(router.score_record(rec)))
            lats.append(time.perf_counter() - t)
        wall = time.perf_counter() - t0
        return wall, lats, scores

    # -- deterministic marshalling micro (best-of-reps, pre AND post the
    # A/B per the estimator house rules: the codec cost is
    # deterministic, the min strips scheduler interference, and
    # measuring again after the flood catches state-dependent drift) ---
    micro_req = records[0]
    # the response micro prices EXACTLY what this fleet exchanges: a
    # gather answer with this bank's term entries, carrying f32-exact
    # doubles (what scores ARE) whose shortest-round-trip reprs are
    # long — the per-float text cost the JSON path pays on every answer
    micro_partial = PartialScore.from_vector(
        float(np.float32(0.128437)), term_names,
        rng.standard_normal(len(term_names)).astype(np.float32),
        generation=1,
    )
    micro_head = {
        "uid": "q0", "status": "ok", "partial": True, "generation": 1,
        "degraded": False,
    }
    micro_resp_bin = dict(micro_head)
    micro_resp_bin["_wire_partial"] = micro_partial
    n_micro = 5_000

    def micro_codec():
        """us per request+response encode/decode round-trip, per arm.
        The JSON response is built from the PartialScore per iteration
        — the frontend materializes the terms dict on every gather
        answer; the binary arm ships the vector straight through."""
        gc.collect()
        best = {"json": float("inf"), "binary": float("inf")}
        buf = bytearray()
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n_micro):
                line = json.dumps(micro_req).encode() + b"\n"
                json.loads(line)
                r = dict(micro_head)
                nm, vec = micro_partial.term_vector()
                r["fe"] = micro_partial.fe
                r["terms"] = dict(zip(nm, vec.tolist()))
                rline = json.dumps(r).encode() + b"\n"
                json.loads(rline)
            best["json"] = min(
                best["json"], (time.perf_counter() - t0) / n_micro * 1e6
            )
            dec = wire.FrameDecoder()
            t0 = time.perf_counter()
            for _ in range(n_micro):
                del buf[:]
                wire.append_score_request(buf, micro_req)
                wire.append_response(buf, micro_resp_bin)
                for mtype, payload in dec.feed(bytes(buf)):
                    wire.decode_message(mtype, payload)
            best["binary"] = min(
                best["binary"], (time.perf_counter() - t0) / n_micro * 1e6
            )
        return best

    try:
        micro_pre = micro_codec()
        tracer().clear()
        for arm in ("json", "binary"):
            one_pass(arm)  # warmup: every program + connection touched
        router_book.reset()
        for b in shard_books:
            b.reset()
        walls = {"json": [], "binary": []}
        lats = {"json": [], "binary": []}
        scores = {"json": [], "binary": []}
        collector = FleetCollector(
            [("fleet", "127.0.0.1", servers[0].port)],
            poll_s=0.05,
            wire="binary",
        )
        with jtu.count_jit_and_pmap_lowerings() as lowerings:
            for _ in range(passes):
                for arm in ("json", "binary"):
                    w, ls, sc = one_pass(arm)
                    walls[arm].append(w)
                    lats[arm].extend(ls)
                    scores[arm].append(sc)
            # -- binary trace drain: cursor-keyed span batches ride
            # MSG_TRACE_RESPONSE frames into the live collector --------
            collector.start()
            with tracing_scope(True):
                for rec in records:
                    routers["binary"].score_record(rec)
            collector.stop(final_poll=True)
        # bitwise parity: every pass of each arm must reproduce pass 0
        # of the JSON arm EXACTLY (float equality, no tolerance)
        ref = scores["json"][0]
        parity_ok = all(
            scores[arm][p] == ref
            for arm in ("json", "binary")
            for p in range(passes)
        )
        roots = [
            s for s in collector.stitched_spans()
            if s["name"] == "router.request"
        ]
        status = collector.member_status()["fleet"]
        conservation = fleet_check_conservation(
            router_book.check_conservation(),
            {
                f"shard{i}": {
                    "conservation": shard_books[i].check_conservation(),
                    "complete": True,
                    "shard_indices": [i],
                }
                for i in range(2)
            },
        )
        # -- writer-coalescing burst: ONE connection pipelines a flood
        # of score frames at shard 0 and drains every response; the
        # writer thread must batch the backlog into few sendalls
        # (coalesced_responses counts responses that shared a syscall).
        # Runs OUTSIDE the lowerings counter: a pipelined burst forms
        # batch shapes the closed-loop A/B never did. --------------------
        n_burst = 200
        burst_payload = {}
        buf = bytearray()
        for rec in records[:n_burst]:
            wire.append_score_request(buf, rec)
        burst_payload["binary"] = bytes(buf)
        burst_payload["json"] = "".join(
            json.dumps(rec, separators=(",", ":")) + "\n"
            for rec in records[:n_burst]
        ).encode()

        def one_burst(arm):
            sock = socket.create_connection(
                ("127.0.0.1", servers[0].port), timeout=60
            )
            try:
                t0 = time.perf_counter()
                sock.sendall(burst_payload[arm])
                if arm == "binary":
                    dec = wire.FrameDecoder()
                    got = 0
                    while got < n_burst:
                        got += len(dec.feed(sock.recv(1 << 16)))
                else:
                    f = sock.makefile("rb")
                    for _ in range(n_burst):
                        f.readline()
                return time.perf_counter() - t0
            finally:
                sock.close()

        coalesced0 = servers[0].metrics.snapshot()["frontend"].get(
            "coalesced_responses", 0
        )
        burst_walls = {"json": [], "binary": []}
        for arm in ("json", "binary"):
            one_burst(arm)  # warmup: the burst batch shapes compile here
        for _ in range(3):
            for arm in ("json", "binary"):
                burst_walls[arm].append(one_burst(arm))
        coalesced = servers[0].metrics.snapshot()["frontend"].get(
            "coalesced_responses", 0
        ) - coalesced0
        micro_post = micro_codec()
    finally:
        for r in routers.values():
            r.close()
        for srv in servers:
            srv.close()
    micro = {
        arm: min(micro_pre[arm], micro_post[arm])
        for arm in ("json", "binary")
    }
    ratios = sorted(
        j / b for j, b in zip(walls["json"], walls["binary"])
    )
    speedup = ratios[len(ratios) // 2]
    per_req = {arm: float(min(walls[arm])) / n_req * 1e6
               for arm in ("json", "binary")}

    def p99(samples):
        return float(np.percentile(np.asarray(samples), 99) * 1e6)

    return {
        "config": name,
        "metric": "wire_json_over_binary_wall_ratio",
        "value": round(speedup, 4),
        "unit": "x (routed closed-loop, JSON wall / binary wall)",
        "detail": {
            "device": str(jax.devices()[0]),
            "host": {"cpu_count": os.cpu_count(), "on_chip": on_chip},
            "shards": 2,
            "requests_per_pass": n_req,
            "passes_per_arm": passes,
            "negotiated": negotiated,
            "json_wall_s": [round(w, 4) for w in walls["json"]],
            "binary_wall_s": [round(w, 4) for w in walls["binary"]],
            "pairwise_ratios": [round(r, 4) for r in ratios],
            "json_qps": round(n_req / min(walls["json"]), 1),
            "binary_qps": round(n_req / min(walls["binary"]), 1),
            "json_p99_us": round(p99(lats["json"]), 1),
            "binary_p99_us": round(p99(lats["binary"]), 1),
            "per_request_us": {
                arm: round(v, 2) for arm, v in per_req.items()
            },
            "micro_codec_us": {
                arm: round(micro[arm], 3) for arm in ("json", "binary")
            },
            "micro_codec_us_pre": {
                arm: round(micro_pre[arm], 3)
                for arm in ("json", "binary")
            },
            "micro_codec_us_post": {
                arm: round(micro_post[arm], 3)
                for arm in ("json", "binary")
            },
            "implied_marshalling_frac": {
                arm: round(micro[arm] / per_req[arm], 5)
                for arm in ("json", "binary")
            },
            "bitwise_parity": parity_ok,
            "request_path_lowerings": int(lowerings[0]),
            "burst": {
                "pipelined_requests": n_burst,
                "json_wall_s": [round(w, 4) for w in burst_walls["json"]],
                "binary_wall_s": [
                    round(w, 4) for w in burst_walls["binary"]
                ],
                "json_best_us_per_req": round(
                    min(burst_walls["json"]) / n_burst * 1e6, 2
                ),
                "binary_best_us_per_req": round(
                    min(burst_walls["binary"]) / n_burst * 1e6, 2
                ),
                "coalesced_responses": int(coalesced),
            },
            "trace": {
                "traced_requests": n_req,
                "router_request_roots": len(roots),
                "collector_spans": status["spans"],
                "ring_dropped": status["ring_dropped"],
                "errors": status["errors"],
            },
            "conservation": conservation,
            "data": "synthetic 2-shard TCP fleet, closed-loop router",
        },
    }


def _retrain_config(name, *, n_files=8, rows_per_file=4000, d=2000,
                    k=12, max_iter=30, seed=0):
    """Incremental retrain vs full retrain (ISSUE 10, ROADMAP metric):
    after a parent generation trains and publishes, data is appended at
    1% and 10% of the base rows and the model retrains two ways —

    - FULL: fresh uncached scan of every partition + cold solve from
      zeros (what an hourly cron without the registry pays);
    - INCREMENTAL: per-partition stats cache (only the NEW partition is
      re-read — counted) + drift-safe warm start from the parent
      generation's coefficients.

    Reported per fraction: wall-clock both ways, speedup, the
    partitions-scanned counters, and iteration counts. The correctness
    pins ride along: scanned == new-partitions-only, and the no-drift
    warm-start alignment is BITWISE the parent coefficients
    (warm_start_bitwise). Speedup gates are host-class-aware in
    dev-scripts/bench_retrain.sh (the 1-core CPU container measures the
    counters, not throughput)."""
    import shutil
    import tempfile

    from photon_ml_tpu.io import schemas
    from photon_ml_tpu.io.avro_codec import write_container
    from photon_ml_tpu.io.input_format import AvroInputDataFormat
    from photon_ml_tpu.io.model_io import save_glm_models_avro
    from photon_ml_tpu.io.streaming import scan_stream
    from photon_ml_tpu.registry import (
        ModelRegistry,
        align_coefficients,
        cached_scan_stream,
    )
    from photon_ml_tpu.task import TaskType
    from photon_ml_tpu.training import train_streaming_glm
    from photon_ml_tpu.utils.index_map import feature_key

    rng = np.random.default_rng(seed)
    tmp = tempfile.mkdtemp(prefix="photon-retrain-bench-")
    try:
        w_true = rng.normal(size=d).astype(np.float32) * 0.3
        train_dir = os.path.join(tmp, "train")
        os.makedirs(train_dir)

        def write_part(fi, rows):
            ix = rng.integers(0, d, size=(rows, k))
            vs = rng.normal(size=(rows, k)).astype(np.float32)
            z = (w_true[ix] * vs).sum(axis=1)
            y = rng.uniform(size=rows) < 1 / (1 + np.exp(-z))
            recs = [
                {
                    "uid": f"{fi}-{i}",
                    "label": float(y[i]),
                    "features": [
                        {"name": str(int(j)), "term": "",
                         "value": float(v)}
                        for j, v in zip(ix[i], vs[i])
                    ],
                    "offset": 0.0,
                    "weight": 1.0,
                }
                for i in range(rows)
            ]
            write_container(
                os.path.join(train_dir, f"part-{fi:03d}.avro"),
                schemas.TRAINING_EXAMPLE_AVRO, recs,
            )

        for fi in range(n_files):
            write_part(fi, rows_per_file)
        base_rows = n_files * rows_per_file
        fmt = AvroInputDataFormat()
        cache_dir = os.path.join(tmp, "scan-cache")

        def fit(index_map, stats, initial=None):
            models, results, _ = train_streaming_glm(
                [train_dir], TaskType.LOGISTIC_REGRESSION,
                regularization_weights=[1.0], max_iter=max_iter,
                fmt=fmt, index_map=index_map, stats=stats,
                initial=initial, prefetch=False,
            )
            (model,) = models.values()
            (result,) = results.values()
            return model, int(result.iterations)

        # parent generation: cold scan (primes the cache) + cold solve
        imap, stats, cs0 = cached_scan_stream([train_dir], fmt, cache_dir)
        parent_model, parent_iters = fit(imap, stats)
        parent_means = {
            key: float(np.asarray(parent_model.means)[i])
            for key, i in imap.items()
        }
        # publish through the REAL registry so the bench exercises the
        # lease/stage/commit path too
        cand = os.path.join(tmp, "candidate")
        os.makedirs(cand)
        save_glm_models_avro(
            {1.0: parent_model}, os.path.join(cand, "model.avro"), imap
        )
        registry = ModelRegistry(os.path.join(tmp, "registry"))
        t0 = time.perf_counter()
        gen1 = registry.publish(cand, data_ranges={"train_dir": train_dir})
        publish_s = time.perf_counter() - t0

        # no-drift alignment bitwise pin (the warm-start parity gate)
        aligned = align_coefficients(parent_means, imap)
        warm_bitwise = bool(
            np.array_equal(aligned, np.asarray(parent_model.means))
        )

        phases = {}
        next_fi = n_files
        for frac in (0.01, 0.10):
            rows_new = max(int(base_rows * frac), 1)
            write_part(next_fi, rows_new)
            next_fi += 1

            t0 = time.perf_counter()
            imap_f, stats_f = scan_stream([train_dir], fmt)
            _model_f, iters_full = fit(imap_f, stats_f)
            full_s = time.perf_counter() - t0

            t0 = time.perf_counter()
            imap_i, stats_i, cs = cached_scan_stream(
                [train_dir], fmt, cache_dir
            )
            initial = align_coefficients(parent_means, imap_i)
            _model_i, iters_inc = fit(imap_i, stats_i, initial=initial)
            inc_s = time.perf_counter() - t0

            phases[f"{int(frac * 100)}pct"] = {
                "rows_appended": rows_new,
                "full_s": round(full_s, 2),
                "incremental_s": round(inc_s, 2),
                "speedup": round(full_s / max(inc_s, 1e-9), 2),
                "iters_full": iters_full,
                "iters_incremental": iters_inc,
                "partitions": cs.partitions,
                "partitions_scanned": cs.scanned,
                "partitions_cached": cs.cached,
            }
        return {
            "config": name,
            "metric": "retrain_speedup_10pct",
            "value": phases["10pct"]["speedup"],
            "unit": "x (full retrain / incremental retrain)",
            "detail": {
                "n_base_rows": base_rows,
                "dim": d,
                "nnz_per_row": k,
                "parent_iters": parent_iters,
                "publish_s": round(publish_s, 3),
                "published_generation": gen1.generation,
                "warm_start_bitwise": warm_bitwise,
                "scan0_scanned": cs0.scanned,
                **phases,
            },
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _regen_with_model(rng, n, d, k, w_true, gen_task, noise=0.5):
    """Draw a dataset from a GIVEN planted model (shared generator for the
    train set and its held-out split)."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import SparseBatch

    indices = rng.integers(0, d, size=(n, k), dtype=np.int64)
    values = rng.normal(size=(n, k)).astype(np.float32)
    z = (w_true[indices] * values).sum(axis=1)
    if gen_task in ("logistic", "hinge"):
        p = 1.0 / (1.0 + np.exp(-z / max(noise, 1e-6)))
        labels = (rng.uniform(size=n) < p).astype(np.float32)
    elif gen_task == "linear":
        labels = (z + noise * rng.normal(size=n)).astype(np.float32)
    elif gen_task == "poisson":
        lam = np.exp(np.clip(z * 0.1, None, 3.0))
        labels = rng.poisson(lam).astype(np.float32)
    else:
        raise ValueError(gen_task)
    batch = SparseBatch(
        indices=jnp.asarray(indices.astype(np.int32)),
        values=jnp.asarray(values),
        labels=jnp.asarray(labels),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )
    return batch, w_true


def _synth_re_buckets(
    rng, n_entities, d_local, samples_per_entity, k, chunk
):
    """Synthetic bucketed random-effect data (RandomEffectBucket layout)
    with a planted per-entity model, split into `chunk`-entity buckets so
    transient optimizer state stays bounded."""
    from types import SimpleNamespace

    from photon_ml_tpu.game.random_effect_data import RandomEffectBucket

    buckets = []
    for start in range(0, n_entities, chunk):
        e = min(chunk, n_entities - start)
        s = samples_per_entity
        idx = rng.integers(0, d_local, size=(e, s, k), dtype=np.int32)
        val = rng.normal(size=(e, s, k)).astype(np.float32)
        w_ent = rng.normal(size=(e, 1, d_local)).astype(np.float32) * 0.5
        z = np.take_along_axis(
            np.broadcast_to(w_ent, (e, s, d_local)), idx, axis=2
        )
        z = (z * val).sum(axis=2)
        p = 1.0 / (1.0 + np.exp(-z))
        labels = (rng.uniform(size=(e, s)) < p).astype(np.float32)
        buckets.append(
            RandomEffectBucket(
                entity_codes=np.arange(start, start + e, dtype=np.int32),
                row_index=np.full((e, s), -1, np.int32),
                indices=idx,
                values=val,
                labels=labels,
                offsets=np.zeros((e, s), np.float32),
                weights=np.ones((e, s), np.float32),
            )
        )
    return SimpleNamespace(buckets=buckets)


def _re_bank_update(problem, bank, dataset):
    t0 = time.perf_counter()
    bank, tracker = problem.update_bank(bank, dataset)
    _ = np.asarray(bank[0, 0])  # force
    return bank, tracker, time.perf_counter() - t0


def _glmix_config(
    name,
    *,
    n_fixed,
    d_fixed,
    k_fixed,
    n_users,
    d_user,
    samples_per_user,
    k_user,
    n_items=0,
    d_item=0,
    samples_per_item=0,
    k_item=0,
    re_max_iter=30,
    re_history=5,
    chunk=25_000,
    kernel="auto",
    seed=0,
):
    """Fixed effect + entity banks: one full coordinate-descent-style pass
    (FE solve, then each RE bank update), coefficients counted honestly."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.random_effect import (
        RandomEffectOptimizationProblem,
    )
    from photon_ml_tpu.ops.losses import LOGISTIC
    from photon_ml_tpu.optim.config import (
        OptimizerConfig,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
    )
    from photon_ml_tpu.task import TaskType
    from photon_ml_tpu.training import train_generalized_linear_model

    rng = np.random.default_rng(seed)
    batch, _ = _synth_sparse(rng, n_fixed, d_fixed, k_fixed)

    from photon_ml_tpu.optim.problem import resolve_kernel

    kernel = resolve_kernel(kernel, batch)
    if kernel == "tiled":
        from photon_ml_tpu.ops.tiled_sparse import tiled_batch_from_sparse

        batch = tiled_batch_from_sparse(batch, d_fixed)

    def fixed_fit():
        t0 = time.perf_counter()
        _, results = train_generalized_linear_model(
            batch,
            TaskType.LOGISTIC_REGRESSION,
            d_fixed,
            regularization_type=RegularizationType.L2,
            regularization_weights=[1.0],
            max_iter=50,
            kernel=kernel,
        )
        iters = int(next(iter(results.values())).iterations)
        return iters, time.perf_counter() - t0

    fe_iters, _ = fixed_fit()  # compile
    fe_iters, fe_s = fixed_fit()

    re_specs = [("user", n_users, d_user, samples_per_user, k_user)]
    if n_items:
        re_specs.append(("item", n_items, d_item, samples_per_item, k_item))

    re_results = {}
    total_re_coefs = 0
    config = OptimizerConfig(
        OptimizerType.LBFGS,
        max_iter=re_max_iter,
        tolerance=1e-5,
        lbfgs_history=re_history,
    )
    for re_name, n_e, d_l, s_e, k_e in re_specs:
        data = _synth_re_buckets(rng, n_e, d_l, s_e, k_e, chunk)
        problem = RandomEffectOptimizationProblem(
            loss=LOGISTIC,
            config=config,
            regularization=RegularizationContext(RegularizationType.L2),
            reg_weight=1.0,
        )
        bank = jnp.zeros((n_e, d_l), jnp.float32)
        bank, _, _ = _re_bank_update(problem, bank, data)  # compile
        bank = jnp.zeros((n_e, d_l), jnp.float32)
        bank, tracker, re_s = _re_bank_update(problem, bank, data)
        total_re_coefs += n_e * d_l
        re_results[re_name] = {
            "entities": n_e,
            "local_dim": d_l,
            "entities_per_sec": round(n_e / re_s),
            "seconds": round(re_s, 3),
            "iterations_mean": round(tracker.iterations_mean, 2),
        }

    total_coefs = d_fixed + total_re_coefs
    step_s = fe_s + sum(r["seconds"] for r in re_results.values())
    return {
        "config": name,
        "metric": "coordinate_step_s",
        "value": round(step_s, 3),
        "unit": "s (FE solve + all RE bank updates, warm)",
        "detail": {
            "total_coefficients": total_coefs,
            "fixed_effect": {
                "n": n_fixed,
                "dim": d_fixed,
                "iterations": fe_iters,
                "seconds": round(fe_s, 3),
                "examples_per_sec": round(n_fixed * fe_iters / fe_s)
                if fe_s > 0
                else None,
            },
            "random_effects": re_results,
            "data": "fixed-seed synthetic, planted per-entity models",
        },
    }



def _mf_config(
    name,
    *,
    n_rows=138_493,
    n_cols=26_744,
    K=32,
    n_ratings=2_000_000,
    num_inner_iterations=1,
    seed=0,
):
    """Matrix factorization through the REAL MatrixFactorizationCoordinate
    at MovieLens-20M entity counts (ratings subsampled 10x to bound the
    one-time host-side structure build): one update_model call = row +
    col ALS half-steps including the on-device latent-view gathers. The
    BASELINE.json config-5 "+ MF" term."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.coordinate import MatrixFactorizationCoordinate
    from photon_ml_tpu.game.data import EntityIndex, GameDataset
    from photon_ml_tpu.game.random_effect import (
        RandomEffectOptimizationProblem,
    )
    from photon_ml_tpu.ops.losses import LINEAR
    from photon_ml_tpu.optim.config import (
        OptimizerConfig,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
    )

    rng = np.random.default_rng(seed)
    n = n_ratings
    rows = rng.integers(0, n_rows, size=n).astype(np.int32)
    cols = rng.integers(0, n_cols, size=n).astype(np.int32)
    row_true = rng.normal(0, 0.4, size=(n_rows, K)).astype(np.float32)
    col_true = rng.normal(0, 0.4, size=(n_cols, K)).astype(np.float32)
    ratings = (
        (row_true[rows] * col_true[cols]).sum(axis=1)
        + 0.3 * rng.normal(size=n)
    ).astype(np.float32)

    def eindex(prefix, count):
        ids = [f"{prefix}{i}" for i in range(count)]
        return EntityIndex(prefix, ids, {v: i for i, v in enumerate(ids)})

    dataset = GameDataset(
        uids=[""] * n,
        labels=ratings,
        offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        shards={},
        entity_codes={"userId": rows, "itemId": cols},
        entity_indexes={
            "userId": eindex("u", n_rows), "itemId": eindex("i", n_cols)
        },
        num_real_rows=n,
    )
    coord = MatrixFactorizationCoordinate(
        name="mf",
        dataset=dataset,
        row_effect_type="userId",
        col_effect_type="itemId",
        num_latent_factors=K,
        problem=RandomEffectOptimizationProblem(
            loss=LINEAR,
            config=OptimizerConfig(
                OptimizerType.LBFGS, max_iter=20, tolerance=1e-5,
                lbfgs_history=5,
            ),
            regularization=RegularizationContext(RegularizationType.L2),
            reg_weight=1.0,
        ),
        num_inner_iterations=num_inner_iterations,
    )
    model = coord.initialize_model()
    t0 = time.perf_counter()
    model, _ = coord.update_model(model)  # structure build + compile
    _ = np.asarray(model.row_latent[0, 0])
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    model, _ = coord.update_model(model)  # warm: the per-CD-iteration cost
    _ = np.asarray(model.row_latent[0, 0])
    warm_s = time.perf_counter() - t0
    return {
        "config": name,
        "metric": "mf_als_step_s",
        "value": round(warm_s, 3),
        "unit": "s (one full ALS step through the MF coordinate, warm)",
        "detail": {
            "latent_factors": K,
            "total_latent_parameters": (n_rows + n_cols) * K,
            "ratings": n,
            "first_step_s": round(first_s, 3),
            "includes": (
                "on-device latent-view gathers from the partner side's "
                "current factors (structure cached, values_override path)"
            ),
            "data": (
                "fixed-seed synthetic at MovieLens-20M entity counts "
                "(138,493 users x 26,744 movies), planted latent factors"
            ),
        },
    }


def suite(only=None):
    """BASELINE.md matrix. One JSON line per config + summary.

    ``only``: config-name prefix filter (``--only 3`` re-measures just
    config 3); filtered runs MERGE into BASELINE_RESULTS.json instead of
    rewriting it.
    """
    import os

    import jax

    from photon_ml_tpu.utils.backend import enable_compilation_cache

    device = str(_require_chip())
    enable_compilation_cache()
    results = []

    def want(name):
        return only is None or name.startswith(only)

    # 1: a1a logistic grid (README.md:217-256 tutorial shape: n=1605
    # train / 30956 test, d=123; lambdas from run_photon_ml_driver.sh).
    if want("1_a1a_logistic"):
        results.append(
            _glm_fit_config(
                "1_a1a_logistic",
                task="LOGISTIC_REGRESSION",
                optimizer="LBFGS",
                reg_type="L2",
                lambdas=[0.1, 1.0, 10.0, 100.0],
                n=1605,
                d=123,
                k=14,
                n_val=30_956,
                max_iter=50,
                kernel="scatter",  # tiny dim: schedule build not worth it
                shape_note="synthetic with a1a's exact shape (1605x123, ~14 nnz)",
            )
        )
        print(json.dumps(results[-1]), flush=True)

    # 2: Criteo-shaped linear TRON + poisson elastic-net (39 raw features
    # hashed to 1M dims, k=39 nnz).
    if want("2a_criteo_linear_tron"):
        results.append(
            _glm_fit_config(
                "2a_criteo_linear_tron",
                task="LINEAR_REGRESSION",
                optimizer="TRON",
                reg_type="L2",
                lambdas=[1.0],
                n=1 << 18,
                d=1 << 20,
                k=40,
                n_val=1 << 15,
                shape_note="synthetic at Criteo-sample shape (262k x 1M, 40 nnz)",
            )
        )
        print(json.dumps(results[-1]), flush=True)
    if want("2a_feature_sharded_tron"):
        results.append(
            _feature_sharded_tron_config(
                "2a_feature_sharded_tron",
                n=1 << 18,
                d=1 << 20,
                k=40,
            )
        )
        print(json.dumps(results[-1]), flush=True)
    if want("2b_criteo_poisson_elastic_net"):
        results.append(
            _glm_fit_config(
                "2b_criteo_poisson_elastic_net",
                task="POISSON_REGRESSION",
                optimizer="LBFGS",
                reg_type="ELASTIC_NET",
                elastic_net_alpha=0.5,
                lambdas=[0.1, 1.0],
                n=1 << 18,
                d=1 << 20,
                k=40,
                n_val=1 << 15,
                max_iter=50,
                shape_note="synthetic at Criteo-sample shape (262k x 1M, 40 nnz)",
            )
        )
        print(json.dumps(results[-1]), flush=True)

    # 3: smoothed-hinge SVM with per-coefficient box constraints.
    if want("3_hinge_box"):
        results.append(
            _glm_fit_config(
                "3_hinge_box",
                task="SMOOTHED_HINGE_LOSS_LINEAR_SVM",
                optimizer="LBFGS",
                reg_type="L2",
                lambdas=[1.0],
                n=1 << 18,
                d=1 << 17,
                k=32,
                n_val=1 << 15,
                max_iter=50,
                box_bound=0.5,
                shape_note="synthetic (262k x 131k, 32 nnz), box [-0.5, 0.5]",
            )
        )
        print(json.dumps(results[-1]), flush=True)

    # 4fs: config-4-shaped GAME FE under a 1x1 (data, model) mesh — the
    # feature-sharded GAME fixed effect composition cost check.
    if want("4fs_game_fe_sharded"):
        results.append(_game_fe_sharded_config("4fs_game_fe_sharded"))
        print(json.dumps(results[-1]), flush=True)

    # 4: GLMix fixed + per-user RE, ~101M coefficients.
    if want("4_glmix_100m"):
        results.append(
            _glmix_config(
                "4_glmix_100m",
                n_fixed=1 << 18,
                d_fixed=1 << 20,
                k_fixed=64,
                n_users=100_000,
                d_user=1000,
                samples_per_user=16,
                k_user=32,
            )
        )
        print(json.dumps(results[-1]), flush=True)

    # 5: full GAME fixed + user RE + item RE, ~1B coefficients.
    if want("5_game_1b"):
        results.append(
            _glmix_config(
                "5_game_1b",
                n_fixed=1 << 18,
                d_fixed=1 << 20,
                k_fixed=64,
                n_users=600_000,
                d_user=1000,
                samples_per_user=16,
                k_user=32,
                n_items=400_000,
                d_item=1000,
                samples_per_item=16,
                k_item=32,
            )
        )
        print(json.dumps(results[-1]), flush=True)

    if want("5b_movielens_mf"):
        results.append(_mf_config("5b_movielens_mf"))
        print(json.dumps(results[-1]), flush=True)

    # 6: streaming (>RAM-shaped) input path with the staged-chunk cache.
    if want("6_streaming"):
        results.append(_streaming_config("6_streaming"))
        print(json.dumps(results[-1]), flush=True)

    # 7: out-of-core GAME coordinate descent (streamed CD A/B vs
    # in-memory on the same files; budget-bounded RSS).
    if want("7_streaming_game"):
        results.append(_streaming_game_config("7_streaming_game"))
        print(json.dumps(results[-1]), flush=True)

    # 8: batched λ-grid training (one vmapped grid program vs the
    # warm-started sequential path; compile counts + per-λ parity).
    if want("8_grid_batched"):
        results.append(_grid_batched_config("8_grid_batched"))
        print(json.dumps(results[-1]), flush=True)

    # 9: reliability-layer overhead (round 11): seams active vs bypassed
    # on the spill-read hot path; <2% gate in dev-scripts/chaos.sh.
    if want("9_reliability"):
        results.append(_reliability_config("9_reliability"))
        print(json.dumps(results[-1]), flush=True)

    # 10: online scoring service (round 12): single-request latency +
    # saturating QPS over a device-resident bank at config-5 shapes;
    # gates in dev-scripts/bench_serving.sh.
    if want("10_serving"):
        results.append(_serving_config("10_serving"))
        print(json.dumps(results[-1]), flush=True)

    # 11: serving under fire (ISSUE 8): open-loop flood past capacity
    # through admission control — shed rate, admitted p99, bounded
    # drain; gates in dev-scripts/bench_overload.sh.
    if want("11_overload"):
        results.append(_overload_config("11_overload"))
        print(json.dumps(results[-1]), flush=True)

    # 12: pod-scale GAME (ISSUE 9): entity-sharded RE banks + two-hop
    # routed residuals vs the replicated path — per-device state bytes,
    # parity, zero routed readbacks, weak-scaling table; gates in
    # dev-scripts/bench_pod_game.sh.
    if want("12_pod_game"):
        results.append(_pod_game_config("12_pod_game"))
        print(json.dumps(results[-1]), flush=True)

    # 13: continuous retraining (ISSUE 10): incremental retrain
    # (per-partition stats cache + registry warm start) vs full retrain
    # at 1%/10% appended data — the ROADMAP metric; gates in
    # dev-scripts/bench_retrain.sh.
    if want("13_retrain"):
        results.append(_retrain_config("13_retrain"))
        print(json.dumps(results[-1]), flush=True)

    # 14: planet-scale serving (ISSUE 12): aggregate QPS vs shard count
    # through the scatter/gather router over subprocess shard-servers
    # under a zipf flood, + the SIGKILL-one-shard degradation leg;
    # gates in dev-scripts/bench_shard_routing.sh.
    if want("14_shard_routing"):
        results.append(_shard_routing_config("14_shard_routing"))
        print(json.dumps(results[-1]), flush=True)

    # 15: unified telemetry (ISSUE 13): tracing/metrics on-vs-off
    # request-path overhead A/B + trace completeness + conservation.
    if want("15_observability"):
        results.append(_obs_config("15_observability"))
        print(json.dumps(results[-1]), flush=True)

    # 16: fleet observability (ISSUE 15): collector/tracing/attribution
    # on-vs-off over a real 2-shard TCP fleet + merge completeness +
    # fleet conservation.
    if want("16_fleet_observability"):
        results.append(_fleet_obs_config("16_fleet_observability"))
        print(json.dumps(results[-1]), flush=True)

    # 17: photon-wire (ISSUE 17): binary data plane vs JSON-lines over
    # a real 2-shard TCP fleet — paired A/B, bitwise parity, micro
    # codec cost, binary trace drain; gates in dev-scripts/bench_wire.sh.
    if want("17_wire"):
        results.append(_wire_config("17_wire"))
        print(json.dumps(results[-1]), flush=True)

    # 18: unified mesh (ISSUE 20): the whole λ-grid over an
    # entity-sharded GAME model as ONE shard_mapped program vs G
    # sequential pod CD sweeps — parity, 1-readback/iteration,
    # 0-relowering, per-device bank bytes, wall both ways; gates in
    # dev-scripts/bench_unified_mesh.sh.
    if want("18_unified_mesh"):
        results.append(_unified_mesh_config("18_unified_mesh"))
        print(json.dumps(results[-1]), flush=True)

    path = "BASELINE_RESULTS.json"
    merged = {}
    if only is not None and os.path.exists(path):
        with open(path) as f:
            for r in json.load(f).get("results", []):
                merged[r["config"]] = r
    for r in results:
        merged[r["config"]] = r
    from photon_ml_tpu.reliability import atomic_write_json, reliability_metrics

    atomic_write_json(
        path,
        {
            "device": device,
            "results": list(merged.values()),
            # fault-injection/retry accounting rides in the round
            # artifact so BENCH rounds record reliability overhead
            "reliability": reliability_metrics(),
        },
    )
    summary = {
        "metric": "baseline_suite",
        "value": len(results),
        "unit": "configs",
        "vs_baseline": 1.0,
        "detail": {"device": device, "configs": [r["config"] for r in results]},
    }
    print(json.dumps(summary))


if __name__ == "__main__":
    if SHARD_CHILD_FLAG in sys.argv:
        # one shard-server subprocess of the 14_shard_routing fleet
        # (spawned by _shard_routing_config; never run by hand)
        _shard_routing_child(sys.argv[sys.argv.index(SHARD_CHILD_FLAG) + 1])
    elif "--overlap-ab" in sys.argv:
        print(json.dumps(overlap_ab(full="--full" in sys.argv)))
    elif "--grid-batched" in sys.argv:
        # dev-scripts/bench_grid.sh entry: the batched λ-grid A/B as one
        # JSON line (gates applied by the script)
        print(json.dumps(_grid_batched_config("grid_batched")))
    elif "--serving" in sys.argv:
        # dev-scripts/bench_serving.sh entry: the online-scoring bench
        # as one JSON line (gates applied by the script)
        print(json.dumps(_serving_config("serving")))
    elif "--overload" in sys.argv:
        # dev-scripts/bench_overload.sh entry: the serving-under-fire
        # flood as one JSON line (gates applied by the script)
        print(json.dumps(_overload_config("overload")))
    elif "--reliability" in sys.argv:
        # dev-scripts/chaos.sh entry: the seam-overhead A/B as one JSON
        # line (the <2% gate is applied by the script)
        print(json.dumps(_reliability_config("reliability")))
    elif "--streaming-game" in sys.argv:
        # dev-scripts/bench_streaming_game.sh entry: the streamed GAME
        # CD A/B as one JSON line (gates applied by the script)
        print(json.dumps(_streaming_game_config("streaming_game")))
    elif "--unified-mesh" in sys.argv:
        # dev-scripts/bench_unified_mesh.sh entry: the unified-mesh A/B
        # as one JSON line (gates applied by the script)
        print(json.dumps(_unified_mesh_config("unified_mesh")))
    elif "--pod-game" in sys.argv:
        # dev-scripts/bench_pod_game.sh entry: the entity-sharded GAME
        # A/B as one JSON line (gates applied by the script)
        print(json.dumps(_pod_game_config("pod_game")))
    elif "--retrain" in sys.argv:
        # dev-scripts/bench_retrain.sh entry: incremental vs full
        # retrain as one JSON line (gates applied by the script)
        print(json.dumps(_retrain_config("retrain")))
    elif "--shard-routing" in sys.argv:
        # dev-scripts/bench_shard_routing.sh entry: the scatter/gather
        # fleet bench as one JSON line (gates applied by the script)
        print(json.dumps(_shard_routing_config("shard_routing")))
    elif "--wire" in sys.argv:
        # dev-scripts/bench_wire.sh entry: the binary-vs-JSON wire A/B
        # as one JSON line (gates applied by the script)
        print(json.dumps(_wire_config("wire")))
    elif "--fleet-obs" in sys.argv:
        # the fleet-collector overhead A/B as one JSON line
        print(json.dumps(_fleet_obs_config("fleet_obs")))
    elif "--obs" in sys.argv:
        # the telemetry overhead A/B as one JSON line
        print(json.dumps(_obs_config("obs")))
    elif "--suite" in sys.argv:
        only = None
        if "--only" in sys.argv:
            i = sys.argv.index("--only") + 1
            if i >= len(sys.argv):
                sys.exit("--only requires a config-name prefix")
            only = sys.argv[i]
        suite(only=only)
    else:
        main()
