"""``setup_split``'s and ``late_step``'s arithmetic on hand-made rings, no
profiler and no program: every instant goes to the innermost span open on
the main thread, the shares sum to the interval, a pool thread's spans
move nothing, an empty ring says nothing; then the metric files: their
span lists share no name and leave none out."""

import io
import os
import types

import pytest

from benchmark import run
from benchmark.readers import late_step, setup_split

MAIN, POOL, PREP = 1, 2, 3


def _ring():
    # process start 0, window at 100. Main thread: generate (the
    # benchmark's) [5, 20); re.dataset_build [22, 30); cd.iteration
    # [40, 95) holding cd.update [42, 90), which holds bank.warm_solvers
    # [50, 70), a jax.trace the main thread made itself [72, 75) and its
    # compile [75, 80) with the cache's read inside [76, 79). The pool's
    # thread compiles [51, 69) under the warm-up: it moves no share. The
    # main thread's prefetch wait [43, 48) IS followed: the prep worker's
    # tiled.batch_build [42.5, 47) and, opened later on a pool thread, a
    # tiled.schedule_build [44, 46); the last second of it nobody works.
    hit = {"program": "glm_fit", "cache": "hit"}
    return [
        ("re.dataset_build", 22.0, 30.0, MAIN, {"entities": 8}),
        ("jax.trace", 52.0, 60.0, POOL, {"program": "bank_fused"}),
        ("jax.lower", 60.0, 63.0, POOL, {"program": "bank_fused"}),
        ("jax.compile", 63.0, 69.0, POOL,
         {"program": "bank_fused", "cache": "miss"}),
        ("bank.warm_solvers", 50.0, 70.0, MAIN, {"programs": 1}),
        ("jax.trace", 72.0, 75.0, MAIN, {"program": "glm_fit"}),
        ("jax.cache_read", 76.0, 79.0, MAIN, {"program": "glm_fit"}),
        ("jax.compile", 75.0, 80.0, MAIN, hit),
        ("overlap.prep_wait", 43.0, 48.0, MAIN, {}),
        ("tiled.batch_build", 42.5, 47.0, PREP, {"rows": 64}),
        ("tiled.schedule_build", 44.0, 46.0, POOL, {}),
        ("cd.update", 42.0, 90.0, MAIN, {}),
        ("cd.iteration", 40.0, 95.0, MAIN, {}),
        ("cd.iteration", 101.0, 150.0, MAIN, {}),  # the window's: cut away
    ]


BENCH = [
    ("bench.setup.generate", 5.0, 20.0),
    ("bench.setup.re_dataset", 21.0, 31.0),
    ("bench.setup.warmup", 35.0, 98.0),
    ("bench.step", 100.5, 151.0),
]


def test_every_instant_goes_to_the_innermost_main_thread_span():
    result = setup_split.split(_ring(), BENCH, 0.0, 100.0, MAIN)
    assert result["totals"] == pytest.approx({
        "bench.setup.generate": 15.0,
        "re.dataset_build": 8.0,
        "cd.iteration": (42 - 40) + (95 - 90),
        "cd.update": (43 - 42) + (50 - 48) + (72 - 70) + (90 - 80),
        "tiled.batch_build": (44 - 43) + (47 - 46),
        "tiled.schedule_build": 2.0,
        "overlap.prep_wait": 1.0,
        "bank.warm_solvers": 20.0,
        "jax.trace": 3.0,
        "jax.compile": 2.0,   # [75, 76) and [79, 80): the read is inside it
        "jax.cache_read": 3.0,
        "dark": 5 + 2 + 10 + 5,
    })
    # a partition: the shares sum to the interval, exactly
    assert sum(result["totals"].values()) == pytest.approx(100.0, abs=1e-12)
    assert result["wall"] == 100.0
    # dark, by the benchmark's own span around it, and its longest stretch
    assert result["dark_under"] == pytest.approx({
        "no bench span": 5 + 1 + 4 + 2,          # [0,5) [20,21) [31,35) [98,100)
        "bench.setup.re_dataset": 1 + 1,         # [21,22) [30,31)
        "bench.setup.warmup": 5 + 3,             # [35,40) [95,98)
    })
    assert result["dark_stretches"][0] == (
        10.0, "re.dataset_build", "cd.iteration")
    # the pool's thread: told beside the wait, in no share
    assert result["waits"] == {"bank.warm_solvers": {
        "waits": 1, "threads": 2, "seconds": 20.0,
        "did": {"jax.trace": 8.0, "jax.lower": 3.0, "jax.compile": 6.0},
    }, "overlap.prep_wait": {
        "waits": 1, "threads": 2, "seconds": 5.0,
        "did": {"tiled.batch_build": 4.0, "tiled.schedule_build": 2.0},
    }}
    assert result["programs"]["bank_fused"] == {
        "jax.trace": 8.0, "jax.lower": 3.0, "jax.compile": 6.0, "miss": 1,
    }
    assert result["programs"]["glm_fit"]["hit"] == 1
    assert result["misses"] == [("bank_fused", 6.0)]


def test_the_readers_shares_partition_and_an_empty_ring_says_nothing():
    result = setup_split.split(_ring(), BENCH, 0.0, 100.0, MAIN)
    ctx = types.SimpleNamespace(_setup_split=result)
    specs = _setup_specs()
    got = {
        name: setup_split.read(ctx, **spec["args"])
        for name, spec in specs.items()
    }
    assert got == pytest.approx({
        "setup_compile_s": 5.0, "setup_trace_lower_s": 3.0,
        "setup_schedule_build_s": 4.0, "setup_dataset_build_s": 8.0,
        "setup_structure_build_s": 0.0, "setup_warm_solvers_s": 20.0,
        "setup_first_step_s": 23.0, "setup_generate_s": 15.0,
        "setup_dark_s": 22.0,
    })
    assert sum(got.values()) == pytest.approx(result["wall"], abs=1e-9)
    # the parent of the PR that made span() always file: an empty ring,
    # and one whose only spans began inside the window
    assert setup_split.split([], BENCH, 0.0, 100.0, MAIN) is None
    late = [s for s in _ring() if s[1] > 100.0]
    assert setup_split.split(late, BENCH, 0.0, 100.0, MAIN) is None
    nothing = types.SimpleNamespace(_setup_split=None)
    assert all(
        setup_split.read(nothing, **spec["args"]) is None
        for spec in specs.values()
    )


def test_the_split_is_told_on_standard_error_with_what_missed_the_cache():
    result = setup_split.split(_ring(), BENCH, 0.0, 100.0, MAIN)
    out = io.StringIO()
    setup_split.tell(result, filed=11, dropped=0, file=out)
    told = out.getvalue()
    assert "process start to window 100 s; ring 11 spans, dropped 0" in told
    assert "setup split bank.warm_solvers: 20 s" in told
    assert "setup dark under bench.setup.warmup: 8 s" in told
    assert "setup dark stretch 10 s after re.dataset_build, before cd.iteration" in told
    assert ("setup wait bank.warm_solvers: 1 waits, 20 s of the main thread; "
            "meanwhile the other 2 threads' innermost spans (thread-seconds): "
            "jax.trace 8, jax.compile 6, jax.lower 3") in told
    assert ("setup program bank_fused: trace 8 + lower 3 + compile 6 s "
            "(cache read 0); compiles: 1 miss") in told
    assert "compiles: 1 hit, 1 miss, 0 unsaved, 0 none" in told
    assert "setup cache miss bank_fused: compiled in 6 s" in told


def test_spans_that_open_together_nest_by_their_close_and_prefixes_match():
    # an elapsed span filed with its parent's own start: the shorter wins
    pieces = setup_split.partition(
        [("outer", 0.0, 10.0), ("inner", 0.0, 4.0), ("late", 12.0, 30.0)],
        0.0, 20.0)
    assert pieces == [
        ("inner", 0.0, 4.0), ("outer", 4.0, 10.0), ("dark", 10.0, 12.0),
        ("late", 12.0, 20.0),
    ]
    assert setup_split.matches("schedule_cache.build_s", ["schedule_cache.*"])
    assert not setup_split.matches("schedule_cache", ["schedule_cache.*"])
    assert setup_split.matches("cd.update", ["*"])
    assert not setup_split.matches("cd.update", ["cd"]) and not (
        setup_split.matches("cd.update", None))


def test_the_late_step_is_the_longest_less_the_median_with_its_split():
    walls = [3.0, 3.01, 3.5, 2.99, 3.0]
    assert late_step.late(walls) == (pytest.approx(0.5), 2, 0)
    assert late_step.late([3.0]) is None and late_step.late([]) is None
    assert late_step.late([1.0, 1.0])[0] == 0.0
    # step 0 [0, 3): cd.iteration [0.1, 2.9) holding cd.update [0.5, 2.5);
    # step 1 [3, 6.5): the same and half a second more in cd.readback,
    # with a compile (a pool thread's) that closed inside it
    ring = [
        ("cd.update", 0.5, 2.5, MAIN, {}), ("cd.iteration", 0.1, 2.9, MAIN, {}),
        ("cd.update", 3.5, 5.5, MAIN, {}), ("cd.readback", 5.5, 6.0, MAIN, {}),
        ("cd.iteration", 3.1, 6.4, MAIN, {}),
        ("jax.compile", 5.6, 5.9, POOL, {"program": "re_score", "cache": "hit"}),
        ("jax.compile", 1.0, 1.2, POOL, {"program": "earlier"}),
    ]
    steps = [(0.0, 3.0), (3.0, 6.5)]
    usual, slow = late_step.step_splits(ring, steps, MAIN, (0, 1))
    assert usual == pytest.approx(
        {"dark": 0.2, "cd.iteration": 0.8, "cd.update": 2.0})
    assert slow == pytest.approx({
        "dark": 0.2, "cd.iteration": 0.8, "cd.update": 2.0, "cd.readback": 0.5})
    assert late_step.compiles_inside(ring, *steps[1]) == [
        ("jax.compile", "re_score", pytest.approx(0.3))]
    # a program that files nothing still has a late step: all of it dark
    assert late_step.step_splits([], steps, MAIN, (1,)) == [{"dark": 3.5}]


NEW = [
    "setup_compile_s", "setup_trace_lower_s", "setup_schedule_build_s",
    "setup_dataset_build_s", "setup_structure_build_s", "setup_warm_solvers_s",
    "setup_first_step_s", "setup_generate_s", "setup_dark_s",
    "setup_cache_misses", "late_step_s.cd", "late_step_s.fit",
]


def _setup_specs():
    return {
        name: run.load_json(run.HERE, "metrics", name + ".json")
        for name in NEW if name.endswith("_s")
    }


def test_the_twelve_metric_files_name_their_readers_and_their_cells():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    listed = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    cd = [c for c in cells if c.endswith(".cd")]
    for name in NEW:
        spec = run.load_json(run.HERE, "metrics", name + ".json")
        assert os.path.exists(
            os.path.join(run.HERE, "readers", spec["reader"] + ".py"))
        m = listed[name]
        assert m["better"] == "lower" and set(m["workloads"]) <= set(cells)
        if name.startswith("setup_"):
            assert (m["layer"], m["moves"]) == ("set-up", "setup_s")
    assert listed["late_step_s.cd"]["workloads"] == cd
    assert listed["late_step_s.fit"]["workloads"] == ["criteo-logistic-1m.fit"]
    assert listed["setup_dataset_build_s"]["workloads"] == cd
    assert listed["setup_cache_misses"]["unit"] == "count"
    # a partition in every cell: no two lists share a name, the catch-all
    # leaves out exactly what the others claim, and a cell that lacks a
    # metric files no span of its list (checked on the chip, PERF.md)
    specs = _setup_specs()
    claimed = [
        n for name, spec in specs.items()
        if name not in ("setup_first_step_s", "setup_dark_s")
        for n in spec["args"]["spans"]
    ]
    assert len(claimed) == len(set(claimed))
    rest = specs["setup_first_step_s"]["args"]
    assert rest["spans"] == ["*"] and sorted(rest["but"]) == sorted(claimed)
    assert specs["setup_dark_s"]["args"]["spans"] is None
