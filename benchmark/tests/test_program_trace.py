"""``program_trace``'s arithmetic without the profiler: module seconds,
the idle device time inside the program's spans by innermost span; then
the readers over it, on a trace of a program that names its work and on
one that names nothing."""

import json
import os
import types

import pytest

from benchmark import program_trace, run
from benchmark.readers import (
    device_time_per_launch, host_gap, module_time, module_time_per_counted,
    registry_ratio,
)

HERE = os.path.dirname(os.path.abspath(__file__))
PLANE = "/device:TPU:0"


def _trace():
    # one CD iteration [0, 1000): glm_fit runs [100, 600), the bank
    # [650, 700), the score [705, 715) and again [900, 910); the device
    # idles [0,100) [600,650) [700,705) [715,900) [910,1000)
    modules = [
        ["jit_glm_fit(11)", 100.0, 500.0], ["jit_bank_fused(12)", 650.0, 50.0],
        ["jit_re_score(13)", 705.0, 10.0], ["jit_re_score(13)", 900.0, 10.0],
        ["jit_add(14)", 2000.0, 5.0],
    ]
    spans = [
        ["photon.cd.iteration", 0.0, 1000.0],
        ["photon.cd.update", 50.0, 570.0],      # [50, 620)
        ["photon.fit.dispatch", 60.0, 30.0],    # [60, 90)
        ["photon.cd.update", 640.0, 40.0],      # [640, 680)
        ["photon.cd.readback", 720.0, 200.0],   # [720, 920)
    ]
    return {"modules": {PLANE: modules}, "spans": spans}


def test_module_seconds_sum_the_runs_the_pattern_matches():
    trace = _trace()
    assert program_trace.module_seconds(trace, r"^jit_glm_fit\(") == pytest.approx(500e-9)
    assert program_trace.module_seconds(trace, r"^jit_bank_") == pytest.approx(50e-9)
    assert program_trace.module_seconds(trace, r"^jit_(re_score|cd_objective)\(") == (
        pytest.approx(20e-9))
    assert program_trace.module_seconds(trace, "nothing_by_that_name") == 0.0
    two = {"modules": {PLANE: trace["modules"][PLANE], "/device:TPU:1": []}}
    assert program_trace.module_seconds(two, "glm_fit") == pytest.approx(250e-9)
    assert program_trace.module_seconds({"modules": {}}, "glm_fit") == 0.0


def test_idle_time_goes_to_the_innermost_span_that_covers_it():
    idle = program_trace.idle_inside(_trace(), "photon.cd.iteration")
    # [0,100): midpoint 50 -> cd.update [50,620) is the innermost there;
    # [600,650): 625 -> the iteration; [700,705): the iteration;
    # [715,900): 807.5 -> cd.readback; [910,1000): 955 -> the iteration
    assert idle == pytest.approx({
        "photon.cd.update": 100e-9,
        "photon.cd.iteration": (50 + 5 + 90) * 1e-9,
        "photon.cd.readback": 185e-9,
    })
    assert sum(idle.values()) == pytest.approx((1000 - 570) * 1e-9)
    assert program_trace.idle_inside(_trace(), "photon.no.such.span") == {}
    no_spans = dict(_trace(), spans=[])
    assert program_trace.idle_inside(no_spans, "photon.cd.iteration") == {}


def test_a_time_no_span_covers_is_unattributed():
    spans = _trace()["spans"]
    assert program_trace.innermost(spans, 70.0) == "photon.fit.dispatch"
    assert program_trace.innermost(spans, 1500.0) == program_trace.UNATTRIBUTED
    assert program_trace.innermost([], 70.0) == program_trace.UNATTRIBUTED


def test_the_arithmetic_on_a_recorded_trace():
    """A cut of a traced chip run of ``glmix-ads-100m.cd`` (PR 27): one CD
    step's program runs and the program's spans, as ``load`` read them;
    the numbers beside it were read off it by hand once."""
    with open(os.path.join(HERE, "data", "recorded_program_trace.json")) as f:
        rec = json.load(f)
    trace, expect = rec["trace"], rec["expect"]
    for pattern, seconds in expect["module_seconds"].items():
        assert program_trace.module_seconds(trace, pattern) == pytest.approx(seconds, rel=1e-9)
    idle = program_trace.idle_inside(trace, "photon.cd.iteration")
    assert idle == pytest.approx(expect["idle_inside"], rel=1e-9)
    runs = trace["modules"][PLANE]
    named = sum(program_trace.module_seconds(trace, p) for p in expect["module_seconds"])
    assert named == pytest.approx(sum(d for _, _, d in runs) / 1e9, rel=0.01)


# ---- the readers ------------------------------------------------------------


def _ctx(trace, steps=2):
    ctx = run.MetricContext(traced_steps=steps, trace=None)
    ctx.__dict__["_program_trace"] = trace
    return ctx


def test_the_readers_read_a_named_program_and_say_nothing_of_an_unnamed_one(capsys):
    ctx = _ctx(_trace())
    assert module_time.read(ctx, r"^jit_glm_fit\(") == pytest.approx(250e-9)
    assert host_gap.read(ctx, "photon.cd.iteration") == pytest.approx(215e-9)
    assert "host gap photon.cd.readback: 9.25e-08 s a step" in capsys.readouterr().err
    # the parent: the same harness, a program with no names and no spans
    parent = _ctx({"modules": {PLANE: [["jit_solve_one(1)", 0.0, 9.0]]},
                   "spans": []})
    assert module_time.read(parent, r"^jit_glm_fit\(") is None
    assert host_gap.read(parent, "photon.cd.iteration") is None
    assert module_time.read(_ctx(None), "glm_fit") is None  # no trace at all
    assert host_gap.read(_ctx(None), "photon.cd.iteration") is None


def test_the_counter_readers_divide_what_the_program_counted():
    from photon_ml_tpu.obs.registry import default_registry, reset_default_registry

    reset_default_registry()
    labels = {"coordinate": "global"}
    names = ("photon_optim_evaluations_total", "photon_optim_iterations_total")
    assert registry_ratio.read(None, *names, labels=labels) is None  # nothing counted
    reg = default_registry()
    reg.counter("photon_optim_solves_total").inc(3, **labels)
    reg.counter("photon_optim_iterations_total").inc(30, **labels)
    reg.counter("photon_optim_evaluations_total").inc(39, **labels)
    reg.counter("photon_optim_evaluations_total").inc(1000, coordinate="other")
    assert registry_ratio.read(None, *names, labels=labels) == pytest.approx(1.3)
    ctx = _ctx(_trace())
    # 250 ns of glm_fit a step over 13 evaluations a solve
    got = module_time_per_counted.read(
        ctx, r"^jit_glm_fit\(", "photon_optim_evaluations_total",
        "photon_optim_solves_total", labels=labels, scale=1e9)
    assert got == pytest.approx(250.0 / 13.0)
    reset_default_registry()
    assert module_time_per_counted.read(
        ctx, r"^jit_glm_fit\(", "photon_optim_evaluations_total",
        "photon_optim_solves_total", labels=labels) is None


def test_a_named_kernels_time_is_taken_per_launch():
    summary = {
        "ops": {"%photon_tiled_margin.38 custom-call:tpu_custom_call": 3.0,
                "%photon_tiled_margin.40 custom-call:tpu_custom_call": 0.6,
                "%photon_tiled_gradient.39 custom-call:tpu_custom_call": 3.8},
        "counts": {"%photon_tiled_margin.38 custom-call:tpu_custom_call": 30,
                   "%photon_tiled_margin.40 custom-call:tpu_custom_call": 6,
                   "%photon_tiled_gradient.39 custom-call:tpu_custom_call": 38},
    }
    ctx = types.SimpleNamespace(trace=summary)
    assert device_time_per_launch.read(ctx, "photon_tiled_margin", scale=1000.0) == (
        pytest.approx(100.0))
    assert device_time_per_launch.read(ctx, "photon_tiled_gradient") == pytest.approx(0.1)
    assert device_time_per_launch.read(ctx, "_body") is None  # the parent's names
    assert device_time_per_launch.read(types.SimpleNamespace(trace=None), "x") is None


def test_every_new_metric_file_names_a_reader_and_a_cell():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    new = {"cd_fe_device_s", "cd_re_device_s", "cd_score_device_s", "cd_fe_evals_per_iter",
           "cd_fe_eval_ms", "cd_host_gap_s", "fe_kernel_margin_ms", "fe_kernel_gradient_ms"}
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert new <= set(listed)
    assert [m["name"] for m in bench["per_layer"][-len(new):]] == [
        m["name"] for m in bench["per_layer"] if m["name"] in new]  # appended, in order
    for name in new:
        spec = run.load_json(run.HERE, "metrics", name + ".json")
        assert os.path.exists(os.path.join(run.HERE, "readers", spec["reader"] + ".py"))
        assert len(listed[name]["workloads"]) == 1
