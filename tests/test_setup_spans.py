"""Set-up tells where it went: the compile stages, the dataset and
structure builds, the drivers' timers and the host's one-off costs are
spans on the ring, filed with nobody asking, each sized by its attrs."""

import threading
import time

import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.game import (
    RandomEffectDataConfiguration,
    build_game_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.utils import profiling  # noqa: F401  installs the listeners
from photon_ml_tpu.utils.logging_util import Timer

from test_mf_driver import _descent, _ratings
from test_overlap import SHARDS, _records
from test_program_tracing import _by_name


@pytest.fixture
def ring():
    assert not obs_trace.tracing_enabled()  # no switch is on in what follows
    obs_trace.tracer().clear()
    return obs_trace.tracer()


@pytest.fixture
def compile_cache(tmp_path):
    """JAX's persistent compile cache, on in a directory of the test's own
    (the test process runs with it off) and off again afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (True, str(tmp_path / "xla"), 0.0, 0)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


# ---- the compile stages -----------------------------------------------------


@pytest.mark.parametrize("stage", ["jax.trace", "jax.lower", "jax.compile"])
def test_each_compile_stage_files_a_span_naming_its_program(ring, stage):
    def a_program_of_this_test(x):
        return jnp.tanh(x) * 3.0 + float(len(stage))

    with obs_trace.span("caller") as caller:
        jax.jit(a_program_of_this_test)(jnp.ones(7)).block_until_ready()
    mine = [
        s for s in ring.drain()
        if s.attrs.get("program") == "a_program_of_this_test"
    ]
    span, = [s for s in mine if s.name == stage]
    assert span.t1 >= span.t0 and span.parent_id == caller.span_id
    # the test process runs without the persistent cache: neither a hit
    # nor a miss, and no read of it
    assert ("cache" in span.attrs) == (stage == "jax.compile")
    if stage == "jax.compile":
        assert span.attrs["cache"] == "none"
    assert not [s for s in mine if s.name == "jax.cache_read"]


def test_a_compile_says_whether_the_cache_served_it(ring, compile_cache):
    def a_cached_program(x):
        return jnp.cos(x) - 0.125

    def a_program_under_the_floor(x):
        return jnp.sin(x) + 0.375

    x = jnp.ones(5)
    jax.jit(a_cached_program)(x).block_until_ready()
    jax.clear_caches()  # the in-memory executable goes; the disk's stays
    jax.jit(a_cached_program)(x).block_until_ready()
    # (what a driver's enable_compilation_cache does with its 1 s floor)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e6)
    jax.jit(a_program_under_the_floor)(x).block_until_ready()
    spans = ring.drain()
    mine = [s for s in spans if s.attrs.get("program") == "a_cached_program"]
    first, second = [s for s in mine if s.name == "jax.compile"]
    assert (first.attrs["cache"], second.attrs["cache"]) == ("miss", "hit")
    read, = [s for s in mine if s.name == "jax.cache_read"]
    # the read is the hit's child, inside its window
    assert read.parent_id == second.span_id
    assert read.trace_id == second.trace_id
    assert second.t0 - 1e-3 <= read.t0 <= read.t1 <= second.t1 + 1e-3
    assert len([s for s in mine if s.name == "jax.lower"]) == 2
    # asked, compiled, not written back: the next process compiles it again
    third, = [
        s for s in spans if s.name == "jax.compile"
        and s.attrs["program"] == "a_program_under_the_floor"
    ]
    assert third.attrs["cache"] == "unsaved"


def test_a_pool_threads_compile_keeps_its_thread_and_its_queuer(ring):
    from concurrent.futures import ThreadPoolExecutor

    def a_pooled_program(x):
        return x * 2.0 - 11.0

    def compile_it():
        return jax.jit(a_pooled_program).lower(jnp.ones(3)).compile()

    with obs_trace.span("bank.warm_solvers") as warm:
        with ThreadPoolExecutor(1) as pool:
            pool.submit(obs_trace.bound_to_current_span(compile_it)).result()
    mine = _by_name(
        s for s in ring.drain()
        if s.attrs.get("program") == "a_pooled_program"
    )
    assert set(mine) == {"jax.trace", "jax.lower", "jax.compile"}
    for (span,) in mine.values():
        assert span.parent_id == warm.span_id
        assert span.tid != threading.get_ident()


# ---- set-up's own work ------------------------------------------------------


def test_the_random_effect_dataset_build_is_a_span_with_its_sizes(ring, rng):
    ds = build_game_dataset(_records(rng), SHARDS, ["userId"])
    red = build_random_effect_dataset(
        ds, RandomEffectDataConfiguration("userId", "userShard")
    )
    span, = [s for s in ring.drain() if s.name == "re.dataset_build"]
    assert span.attrs == {
        "type": "userId", "rows": ds.num_rows,
        "entities": red.num_entities, "buckets": len(red.buckets),
    }
    assert span.attrs["buckets"] >= 2 and span.t1 > span.t0


@pytest.fixture(scope="module")
def mf_descent(tmp_path_factory):
    from photon_ml_tpu.game import random_effect as re_mod

    # programs an earlier test of this process compiled for the same
    # shapes would leave the pool nothing to warm
    re_mod._SOLVER_CACHE.clear()
    obs_trace.tracer().clear()
    dataset, _ = _ratings()
    coords, cd = _descent(tmp_path_factory.mktemp("mf_spans"), dataset)
    built = obs_trace.tracer().drain()
    coords["mf"].prepare()
    coords["mf"].prepare()  # the structures are cached: built once a side
    return coords, _by_name(built), _by_name(obs_trace.tracer().drain())


def test_the_drivers_coordinates_are_built_under_one_span(mf_descent):
    coords, built, _ = mf_descent
    span, = built["game.build_coordinates"]
    assert span.attrs == {"coordinates": len(coords)} and len(coords) == 4


def test_each_sides_structure_build_is_a_span_with_its_sizes(mf_descent):
    coords, _, prepared = mf_descent
    row, col = prepared["mf.structure_build"]
    assert (row.attrs["side"], col.attrs["side"]) == ("row", "col")
    for span, view in zip((row, col), (
        coords["mf"]._als_structure_cache[s] for s in ("row", "col")
    )):
        assert span.attrs["classes"] == len(view.buckets) >= 1
        assert span.attrs["entities"] == sum(
            b.num_entities for b in view.buckets)
        assert span.attrs["slots"] == sum(
            b.row_index.size for b in view.buckets)
        assert span.attrs["slots"] >= view.num_active_rows > 0


def test_the_solver_pool_warms_under_a_span_its_compiles_parent_to(mf_descent):
    _, _, prepared = mf_descent
    warm, = prepared["bank.warm_solvers"]
    assert warm.attrs["programs"] >= 1 and warm.attrs["cached"] >= 0
    compiles = [
        s for s in prepared["jax.compile"] if s.parent_id == warm.span_id
    ]
    # one compile a solver program (and what small programs the thunks'
    # own staging needs), each on a pool thread, each naming its program
    banks = [s for s in compiles if s.attrs["program"].startswith("bank_")]
    assert len(banks) == warm.attrs["programs"], sorted(
        s.attrs["program"] for s in compiles)
    assert all(s.tid != warm.tid and s.attrs["program"] for s in compiles)


def test_a_timers_stage_is_the_span_driver_dot_its_name(ring):
    timer = Timer()
    with timer.time("load-train"):
        with obs_trace.span("inside"):
            pass
    with pytest.raises(RuntimeError):
        with timer.time("save-model"):
            raise RuntimeError("the stage failed")
    named = _by_name(ring.drain())
    stage, = named["driver.load-train"]
    assert named["inside"][0].parent_id == stage.span_id
    assert timer.durations["load-train"] == pytest.approx(
        stage.t1 - stage.t0, abs=1e-3)
    # a stage that raises is still timed, still filed
    assert "driver.save-model" in named and "save-model" in timer.durations


# ---- one span system: the host timings are spans ----------------------------


def test_the_host_timings_view_is_summed_from_the_ring(ring, rng, tmp_path):
    from photon_ml_tpu.obs import ObsSession
    from photon_ml_tpu.obs.registry import reset_default_registry
    from photon_ml_tpu.ops import schedule_cache
    from photon_ml_tpu.parallel import overlap

    assert not hasattr(profiling, "record_host_timing")
    assert not hasattr(profiling, "host_timings")
    with overlap.overlap_scope(True):
        pending = [overlap.Deferred(jnp.float32(i), float) for i in range(3)]
        overlap.fetch_all(pending)
        overlap.wait(overlap.submit(time.sleep, 0.01))
        overlap.drain_io()
    schedule_cache.record_build_seconds(0.25)
    schedule_cache.record_build_seconds(0.5)
    with obs_trace.span("cd.iteration"):  # not a host timing: left out
        pass
    view = obs_trace.host_timings()
    assert set(view) == {
        "overlap.fetch", "overlap.prep_wait", "overlap.io_wait",
        "schedule_cache.build_s",
    }
    assert view["schedule_cache.build_s"] == pytest.approx(0.75, abs=1e-6)
    assert view["overlap.prep_wait"] > 0
    named = _by_name(ring.snapshot())
    assert named["overlap.fetch"][0].attrs == {"arrays": 3}
    # and it is what an --obs-dir snapshot holds under the same key
    reset_default_registry()
    sess = ObsSession(str(tmp_path / "obs"), snapshot_period_s=60,
                      signal_dump=False)
    try:
        snap = sess.registry.snapshot()
    finally:
        sess.finish()
        obs_trace.set_tracing(False)
    assert snap["host_timings"] == pytest.approx(view)


def test_a_tiny_tiled_build_files_the_split_the_builds_and_the_uploads(ring, rng):
    from photon_ml_tpu.data.batch import SparseBatch
    from photon_ml_tpu.ops.tiled_sparse import (
        TileParams, build_sharded_tiled_batch, tiled_batch_from_sparse,
    )

    n, d, k = 96, 40, 3
    indices = rng.integers(0, d - 1, (n, k))
    indices[:, 0] = d - 1  # an entry in every row: a dense column
    batch = SparseBatch(
        indices=jnp.asarray(indices, jnp.int32),
        values=jnp.asarray(rng.normal(size=(n, k)) + 3.0, jnp.float32),
        labels=jnp.asarray(rng.integers(0, 2, n), jnp.float32),
        offsets=jnp.zeros(n, jnp.float32), weights=jnp.ones(n, jnp.float32),
    )
    params = TileParams(8, 8, 32, spill_cap=8)
    tiled_batch_from_sparse(batch, d, params=params)
    one = _by_name(ring.drain())
    build_sharded_tiled_batch(batch, d, 2, params=params)
    two = _by_name(ring.drain())
    for named, shards, uploads in ((one, 1, 2), (two, 2, 2)):
        build, = named["tiled.batch_build"]
        assert build.attrs == {"rows": n, "shards": shards}
        split, = named["tiled.dense_split"]
        assert split.attrs == {"entries": n * k, "dense_columns": 1}
        assert split.parent_id == build.span_id
        assert len(named["tiled.upload"]) == uploads
        builds = {s.span_id for s in named["tiled.schedule_build"]}
        assert all(
            s.parent_id in builds and s.attrs["bytes"] > 0
            for s in named["tiled.upload"]
        )
        assert all(
            s.parent_id == build.span_id and s.attrs["dense_columns"] == 1
            for s in named["tiled.schedule_build"]
        )
        # the host's build seconds lie inside the schedules' spans
        assert "schedule_cache.build_s" in named
