"""The program names its own work: evaluation counts carried out of the
optimizer loops, host spans at the layer boundaries (one system, two
sinks: the ring and the profiler), scopes and module names on the device
programs, and the counters a CD iteration leaves in the registry."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu import training
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.obs.registry import default_registry, reset_default_registry
from photon_ml_tpu.optim.common import OptResult
from photon_ml_tpu.optim.config import RegularizationType
from photon_ml_tpu.optim.host_lbfgs import minimize_lbfgs_host, minimize_owlqn_host
from photon_ml_tpu.optim.host_tron import minimize_tron_host
from photon_ml_tpu.optim.lbfgs import minimize_lbfgs, minimize_owlqn
from photon_ml_tpu.optim.tron import minimize_tron
from photon_ml_tpu.parallel import overlap
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.utils.logging_util import PhotonLogger

from test_overlap import _cd


# ---- evaluations: counted where the work happens ----------------------------


class _Counting:
    """A value_and_grad that counts its own calls on the host, from
    inside the jitted loop: a quadratic bowl at
    ``scale`` 0, where every first trial is accepted, else a Rosenbrock
    valley that steep, where the line search has to backtrack."""

    def __init__(self, scale):
        self.calls = 0
        self.scale = scale

    def _bump(self):
        self.calls += 1

    def vg(self, w):
        jax.debug.callback(self._bump, ordered=True)
        return _quiet_vg(w, self.scale)

    def hvp(self, w, d):
        return _quiet_hvp(w, d, self.scale)


def _solve(optimizer, f):
    w0 = jnp.zeros(6, jnp.float32)
    if optimizer == "lbfgs":
        return jax.jit(lambda w: minimize_lbfgs(f.vg, w, max_iter=30))(w0)
    if optimizer == "owlqn":
        return jax.jit(lambda w: minimize_owlqn(f.vg, w, 0.01, max_iter=30))(w0)
    if optimizer == "tron":
        return jax.jit(lambda w: minimize_tron(f.vg, f.hvp, w, max_iter=30))(w0)
    if optimizer == "lbfgs_host":
        return minimize_lbfgs_host(f.vg, w0, max_iter=30)
    if optimizer == "owlqn_host":
        return minimize_owlqn_host(f.vg, w0, 0.01, max_iter=30)
    return minimize_tron_host(f.vg, f.hvp, w0, max_iter=30)


@pytest.mark.parametrize("scale", [0.0, 40.0], ids=["unit_steps", "backtracking"])
@pytest.mark.parametrize(
    "optimizer",
    ["lbfgs", "owlqn", "tron", "lbfgs_host", "owlqn_host", "tron_host"],
)
def test_evaluations_equal_the_calls_of_a_counting_value_and_grad(optimizer, scale):
    f = _Counting(scale)
    result = _solve(optimizer, f)
    jax.block_until_ready(result)
    jax.effects_barrier()
    iterations, evaluations = int(result.iterations), int(result.evaluations)
    assert iterations >= 1
    assert evaluations == f.calls, (evaluations, f.calls)
    assert evaluations >= iterations + 1  # the one at w0 included
    if "tron" in optimizer:
        assert evaluations == iterations + 1
    elif scale > 0.0:
        assert evaluations > iterations + 1  # some trial was rejected
    else:
        assert evaluations == iterations + 1


@pytest.mark.parametrize("optimizer", ["lbfgs", "owlqn", "tron"])
def test_under_vmap_the_count_freezes_with_its_member(optimizer):
    """A member that has converged keeps its own count while the batched
    loop runs on for the others: the batch reads as the members alone."""
    scales = jnp.asarray([1.0, 100.0, 7.0], jnp.float32)

    def one(scale):
        def vg(w):
            return _quiet_vg(w, scale)

        def hvp(w, d):
            return _quiet_hvp(w, d, scale)

        w0 = jnp.zeros(6, jnp.float32)
        if optimizer == "lbfgs":
            return minimize_lbfgs(vg, w0, max_iter=30)
        if optimizer == "owlqn":
            return minimize_owlqn(vg, w0, 0.01, max_iter=30)
        return minimize_tron(vg, hvp, w0, max_iter=30)

    batched = jax.jit(jax.vmap(one))(scales)
    alone = [jax.jit(one)(s) for s in scales]
    for i, r in enumerate(alone):
        assert int(batched.evaluations[i]) == int(r.evaluations)
        assert int(batched.iterations[i]) == int(r.iterations)
    assert len({int(e) for e in batched.evaluations}) > 1


def _f(w, scale):
    bowl = 0.5 * jnp.sum(jnp.arange(1, w.shape[0] + 1, dtype=w.dtype) * (w - 1.0) ** 2)
    valley = jnp.sum(scale * (w[1:] - w[:-1] ** 2) ** 2 + (1.0 - w[:-1]) ** 2)
    return jnp.where(scale > 0.0, valley, bowl)


def _quiet_vg(w, scale):
    return jax.value_and_grad(_f)(w, scale)


def _quiet_hvp(w, d, scale):
    return jax.jvp(lambda x: jax.grad(_f)(x, scale), (w,), (d,))[1]


def test_a_snapshot_without_evaluations_restores_as_not_counted():
    result = jax.jit(lambda w: minimize_lbfgs(lambda x: _quiet_vg(x, 0.0), w))(
        jnp.zeros(4, jnp.float32)
    )
    snap = training._snapshot_result_arrays(result)
    back = training._result_from_snapshot(snap)
    assert int(back.evaluations) == int(result.evaluations) > 0
    old = {k: v for k, v in snap.items() if k != "evaluations"}
    restored = training._result_from_snapshot(old)
    assert int(restored.evaluations) == -1
    assert int(restored.iterations) == int(result.iterations)


# ---- one CD run: one readback an iteration, counters, the log line -----------


class _Lines(PhotonLogger):
    """A logger that keeps its info lines."""

    def __init__(self):
        super().__init__()
        self.lines = []

    def info(self, msg, *args):
        self.lines.append(msg % args)


def test_a_cd_run_leaves_one_readback_counters_and_the_log_line(rng):
    reset_default_registry()
    with overlap.overlap_scope(True):
        cd = _cd(rng)
        cd.logger = _Lines()
        overlap.reset_readback_stats()
        result = cd.run(num_iterations=2)
        assert overlap.readback_stats() == 2  # one an iteration
    solves = result.trackers["global"]
    assert len(solves) == 2 and all(isinstance(r, OptResult) for r in solves)
    registry = default_registry()
    counted = {
        what: registry.counter(f"photon_optim_{what}_total").value(coordinate="global")
        for what in ("solves", "iterations", "evaluations")
    }
    assert counted == {
        "solves": 2,
        "iterations": sum(int(r.iterations) for r in solves),
        "evaluations": sum(int(r.evaluations) for r in solves),
    }
    assert counted["evaluations"] >= counted["iterations"] + 2
    # the bank's tracker is a Deferred of its own: no optimizer counts for it
    assert registry.counter("photon_optim_solves_total").value(coordinate="per-user") == 0
    last = solves[-1]
    assert (
        f"coordinate global: {int(last.iterations)} iterations, "
        f"{int(last.evaluations)} evaluations, {last.reason_name}, "
        "kernel=scatter"
    ) in cd.logger.lines


# ---- spans: filed once, each under the span that caused it ------------------


CD_SPANS = {
    # span -> its parent, for one CD iteration over (global, per-user)
    "cd.iteration": None,
    "cd.update": "cd.iteration",
    "cd.score": "cd.iteration",
    "cd.objective": "cd.iteration",
    "cd.readback": "cd.iteration",
    "fit.prepare": "cd.update",
    "fit.dispatch": "cd.update",
    "bank.update": "cd.update",
    "bank.route_residuals": "bank.update",
    "bank.residual": "bank.update",
    "bank.dispatch": "bank.update",
}


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def test_a_cd_iteration_files_every_span_once_under_its_parent(rng):
    with overlap.overlap_scope(True):
        cd = _cd(rng)
        cd.run(num_iterations=1)  # compiles, warms the bank's solvers
        with obs_trace.tracing_scope(True):
            obs_trace.tracer().clear()
            cd.run(num_iterations=1)
            spans = obs_trace.tracer().drain()
    named = _by_name(spans)
    by_id = {s.span_id: s for s in spans}
    assert set(CD_SPANS) <= set(named), sorted(named)
    for name, parent in CD_SPANS.items():
        for s in named[name]:
            if parent is None:
                assert s.parent_id is None
            elif not (name == "cd.score" and s.parent_id is None):
                # (the scores of the model run() starts from are taken
                # before the first iteration opens)
                assert by_id[s.parent_id].name == parent, (name, s.attrs)
    # once an iteration, or once a coordinate of it
    assert len(named["cd.iteration"]) == len(named["cd.readback"]) == 1
    assert len(named["cd.objective"]) == 1
    assert sorted(s.attrs["coordinate"] for s in named["cd.update"]) == [
        "global", "per-user"]
    # the fixed effect's update says which objective ran; a bank has none
    assert {
        s.attrs["coordinate"]: s.attrs.get("kernel") for s in named["cd.update"]
    } == {"global": "scatter", "per-user": None}
    in_iteration = [s for s in named["cd.score"] if s.parent_id is not None]
    assert sorted(s.attrs["coordinate"] for s in in_iteration) == [
        "global", "per-user"]
    assert len(named["fit.dispatch"]) == len(named["bank.update"]) == 1
    assert named["fit.dispatch"][0].attrs == {
        "kernel": "scatter", "fit_cache_hit": True}
    assert {s.attrs["kind"] for s in named["bank.dispatch"]} <= {
        "newton", "primal", "dense", "sparse"}
    assert all(
        s.attrs["entities"] > 0 and s.attrs["capacity"] > 0
        for s in named["bank.dispatch"]
    )
    # cd.update is the solve alone: no cd.score inside it, none overlapping
    for u in named["cd.update"]:
        for sc in in_iteration:
            assert sc.parent_id != u.span_id
            assert sc.t0 >= u.t1 or sc.t1 <= u.t0
    it = named["cd.iteration"][0]
    assert it.attrs["iteration"] == 1 and np.isfinite(it.attrs["objective"])
    assert it.attrs["global.evaluations"] >= it.attrs["global.iterations"] + 1
    # nothing retraced or compiled in the warm iteration
    assert "jax.compile" not in named


def test_a_cold_bank_update_files_its_warm_up_and_the_compiles(rng):
    with overlap.overlap_scope(True), obs_trace.tracing_scope(True):
        obs_trace.tracer().clear()
        cd = _cd(rng)
        # a shape no earlier test compiled: a bank of its own
        cd.coordinates["per-user"].problem._aot_cache.clear()
        cd.run(num_iterations=1)
        spans = obs_trace.tracer().drain()
    named = _by_name(spans)
    # (warmed by the prefetch worker under the fixed effect's solve, or
    # by update_bank itself: either way once)
    assert len(named["bank.warm_solvers"]) == 1
    assert named["bank.warm_solvers"][0].attrs["programs"] >= 1
    assert "jax.trace" in named and all(s.t1 >= s.t0 for s in named["jax.trace"])


@pytest.mark.parametrize("path", ["windows", "sorted", "slots"])
def test_a_warm_bank_update_runs_one_residual_program_and_compiles_nothing(
    path, monkeypatch
):
    """Under a residual a bank update turns the row vector into every
    block's offsets in ONE named program (no eager dispatch a block), on
    the windows of a grouped table, and on a shuffled one's sorted
    windows or, its entity order taken away, its slots."""
    from photon_ml_tpu.game import random_effect as re_mod
    from test_residual_windows import (
        _build, _grouped_codes, _problem, _without_runs,
    )

    codes = _grouped_codes(seed=11)
    if path != "windows":
        codes = np.random.default_rng(11).permutation(codes)
    red = _build(codes)
    if path == "slots":
        red = _without_runs(red)
    held = sum(
        b.row_index.size for b in red.buckets
        if (b.row_runs is not None) == (path == "windows")
    )
    assert held > 0.9 * sum(b.row_index.size for b in red.buckets)
    calls = []
    program_for = re_mod._residual_program

    def counting(coordinate):
        program = program_for(coordinate)

        def call(*args, **kwargs):
            calls.append(coordinate)
            return program(*args, **kwargs)

        return call

    monkeypatch.setattr(re_mod, "_residual_program", counting)
    problem = _problem()
    bank = jnp.zeros((red.num_entities, red.local_dim), jnp.float32)
    residual = jnp.ones((red.row_entity_codes.shape[0],), jnp.float32)
    name = f"trace-{path}"
    problem.update_bank(bank, red, residual_offsets=residual, coordinate=name)
    calls.clear()
    with obs_trace.tracing_scope(True):
        obs_trace.tracer().clear()
        problem.update_bank(
            bank, red, residual_offsets=residual, coordinate=name
        )
        spans = obs_trace.tracer().drain()
    assert calls == [name]
    named = _by_name(spans)
    (span,) = named["bank.residual"]
    assert span.attrs[path] == held and span.attrs["groups"] >= 3
    by_id = {s.span_id: s for s in spans}
    assert by_id[span.parent_id].name == "bank.update"
    # nothing traced, lowered or compiled: no eager operation at a block's
    # shape, and the program itself is warm
    assert not {"jax.trace", "jax.lower", "jax.compile"} & set(named)


def test_a_one_lambda_train_files_its_spans_under_the_solve(rng):
    n, d, k = 256, 64, 4
    from photon_ml_tpu.data.batch import SparseBatch

    batch = SparseBatch(
        indices=jnp.asarray(rng.integers(0, d, (n, k)), jnp.int32),
        values=jnp.asarray(rng.normal(size=(n, k)), jnp.float32),
        labels=jnp.asarray(rng.integers(0, 2, n), jnp.float32),
        offsets=jnp.zeros(n, jnp.float32), weights=jnp.ones(n, jnp.float32),
    )

    def fit():
        return training.train_generalized_linear_model(
            batch, TaskType.LOGISTIC_REGRESSION, d,
            regularization_type=RegularizationType.L2,
            regularization_weights=[1.0], max_iter=5, kernel="tiled",
        )

    with obs_trace.tracing_scope(True):
        obs_trace.tracer().clear()
        _, results = fit()
        spans = obs_trace.tracer().drain()
    named = _by_name(spans)
    by_id = {s.span_id: s for s in spans}
    for name, parent in (
        ("glm.lambda_solve", None), ("fit.prepare", "glm.lambda_solve"),
        # (the batch is tiled before the lambda sweep opens: its build is
        # a root, and the two schedules' builds, on a pool's threads,
        # parent to it)
        ("fit.dispatch", "glm.lambda_solve"), ("tiled.batch_build", None),
        ("tiled.schedule_build", "tiled.batch_build"),
        ("tiled.dense_split", "tiled.batch_build"),
        ("tiled.upload", "tiled.schedule_build"),
    ):
        assert name in named, sorted(named)
        for s in named[name]:
            got = by_id[s.parent_id].name if s.parent_id else None
            assert got == parent, (name, got)
    assert len(named["glm.lambda_solve"]) == len(named["fit.dispatch"]) == 1
    assert named["fit.dispatch"][0].attrs["kernel"] == "tiled"
    # both passes' schedules, each telling whether it was built or found
    assert len(named["tiled.schedule_build"]) == 2
    assert all(
        s.attrs["entries"] == n * k and s.attrs["cache"] in ("hit", "miss")
        for s in named["tiled.schedule_build"]
    )
    build, = named["tiled.batch_build"]
    assert build.attrs == {"rows": n, "shards": 1}
    # the pool's spans keep their own threads; the caller waits in its own
    assert all(
        s.tid != build.tid and build.t0 <= s.t0 and s.t1 <= build.t1
        for s in named["tiled.schedule_build"]
    )
    split, = named["tiled.dense_split"]
    assert split.attrs == {"entries": n * k, "dense_columns": 0}
    assert split.tid == build.tid
    assert len(named["tiled.upload"]) == 2
    assert all(s.attrs["bytes"] > 0 for s in named["tiled.upload"])
    scalars = training.grid_result_scalars(results)
    (iterations, _, _, evaluations), = scalars.values()
    assert evaluations >= iterations + 1


def test_with_tracing_off_and_no_profiler_nothing_is_filed(rng):
    """The switch is the REQUEST path's: off, ``start_span`` /
    ``record_span`` file nothing. The training side has no switch:
    ``span()`` files, parents to the open span, and is the current one."""
    assert not obs_trace.tracing_enabled()
    obs_trace.tracer().clear()
    assert obs_trace.start_span("request") is obs_trace.NULL_SPAN
    obs_trace.record_span("dispatch", 1.0, 2.0)
    assert len(obs_trace.tracer()) == 0
    assert obs_trace.current_span() is None
    with obs_trace.span("outer", x=1) as outer:
        outer.set(y=2)
        assert obs_trace.current_span().span_id == outer.span_id
        with obs_trace.span("inner") as inner:
            assert obs_trace.current_span().span_id == inner.span_id
        assert obs_trace.traced("noop.call")(lambda: 7)() == 7
        late = obs_trace.record_elapsed("elapsed", 1.0, 2.5, why="told late")
    assert obs_trace.current_span() is None
    filed = {s.name: s for s in obs_trace.tracer().drain()}
    assert sorted(filed) == ["elapsed", "inner", "noop.call", "outer"]
    assert filed["outer"].parent_id is None
    assert filed["outer"].attrs == {"x": 1, "y": 2}
    for child in ("inner", "noop.call", "elapsed"):
        assert filed[child].parent_id == outer.span_id
        assert filed[child].trace_id == outer.trace_id
    assert filed["elapsed"] is late and (late.t0, late.t1) == (1.0, 2.5)
    assert all(s.t1 >= s.t0 for s in filed.values())
    # and a whole CD iteration lands on the ring with nobody asking
    with overlap.overlap_scope(True):
        _cd(rng).run(num_iterations=1)
    named = _by_name(obs_trace.tracer().drain())
    assert set(CD_SPANS) <= set(named), sorted(named)


def test_a_pool_workers_spans_parent_to_the_span_that_queued_them():
    from concurrent.futures import ThreadPoolExecutor

    def work(_=None):
        with obs_trace.span("worker.task"):
            pass

    obs_trace.tracer().clear()
    with obs_trace.span("queued.here") as here, ThreadPoolExecutor(2) as pool:
        list(pool.map(obs_trace.bound_to_current_span(work), "ab"))
        pool.submit(work).result()  # unbound: a root of its own trace
    spans = obs_trace.tracer().drain()
    tasks = [s for s in spans if s.name == "worker.task"]
    queued, = [s for s in spans if s.name == "queued.here"]
    assert all(s.tid != queued.tid for s in tasks)  # the worker's thread
    assert [s.parent_id for s in tasks] == [here.span_id] * 2 + [None]
    assert [s.trace_id for s in tasks[:2]] == [here.trace_id] * 2
    assert tasks[2].trace_id != here.trace_id


def test_a_span_opens_a_profiler_annotation_named_for_it():
    opened = []

    class _Annotation:
        def __init__(self, name, **attrs):
            self.record = [name, dict(attrs)]

        def __enter__(self):
            opened.append(self.record)

        def __exit__(self, *exc):
            self.record.append("closed")

        def set_metadata(self, **attrs):
            self.record[1].update(attrs)

    from photon_ml_tpu.utils import profiling  # noqa: F401  installs the real one

    installed = obs_trace._ANNOTATE
    assert installed is jax.profiler.TraceAnnotation
    obs_trace.set_annotation_factory(_Annotation)
    try:
        with obs_trace.span("cd.update", coordinate="global") as s:
            s.set(done=True)
        assert opened == [
            ["photon.cd.update", {"coordinate": "global", "done": True}, "closed"]
        ]
        # the ring's span beside it, switch or no switch
        assert not obs_trace.tracing_enabled() and s.span_id is not None
    finally:
        obs_trace.set_annotation_factory(installed)


# ---- names on the device programs ------------------------------------------


def _lowered_text(fn, *args):
    return fn.lower(*args).as_text(debug_info=True)


def test_glm_fit_lowers_under_its_module_name_with_the_layers_scopes(rng):
    from photon_ml_tpu.data.batch import SparseBatch
    from photon_ml_tpu.ops.tiled_sparse import tiled_batch_from_sparse
    from photon_ml_tpu.optim.problem import create_glm_problem

    n, d, k = 128, 32, 4
    batch = SparseBatch(
        indices=jnp.asarray(rng.integers(0, d, (n, k)), jnp.int32),
        values=jnp.asarray(rng.normal(size=(n, k)), jnp.float32),
        labels=jnp.asarray(rng.integers(0, 2, n), jnp.float32),
        offsets=jnp.zeros(n, jnp.float32), weights=jnp.ones(n, jnp.float32),
    )
    args = (jnp.zeros(d), batch, jnp.float32(0.0), jnp.float32(1.0))
    problem = create_glm_problem(TaskType.LOGISTIC_REGRESSION, d)
    text = _lowered_text(problem._get_fit(False)[0], *args)
    assert "module @jit_glm_fit " in text
    for scope in ("objective.margins", "objective.loss", "objective.gradient",
                  "lbfgs.direction", "lbfgs.line_search", "lbfgs.memory"):
        assert scope in text, scope
    grid, _ = problem._get_fit(False, grid=True)
    assert "module @jit_glm_fit_grid " in _lowered_text(
        grid, jnp.zeros((2, d)), batch, jnp.zeros(2), jnp.ones(2))

    tiled = create_glm_problem(TaskType.LOGISTIC_REGRESSION, d, kernel="tiled")
    tiled_args = (args[0], tiled_batch_from_sparse(batch, d)) + args[2:]
    text = _lowered_text(tiled._get_fit(False)[0], *tiled_args)
    assert "module @jit_glm_fit " in text
    for name in ("photon_tiled_margin", "photon_tiled_gradient"):
        assert name in text, name


def test_the_newton_bank_solver_lowers_under_its_names(rng):
    from photon_ml_tpu.game.random_effect import _cached_bucket_solver
    from photon_ml_tpu.ops.losses import LOGISTIC
    from photon_ml_tpu.optim import (
        OptimizerConfig, RegularizationContext,
    )

    solvers = _cached_bucket_solver(
        LOGISTIC, OptimizerConfig(max_iter=5),
        RegularizationContext(RegularizationType.L2),
    )
    e, s, k, d = 4, 8, 3, 6
    bank = jnp.zeros((e, d), jnp.float32)
    args = (
        bank, jnp.arange(e, dtype=jnp.int32),
        jnp.asarray(rng.integers(0, d, (e, s, k)), jnp.int32),
        jnp.asarray(rng.normal(size=(e, s, k)), jnp.float32),
        jnp.ones((e, s), jnp.float32), jnp.zeros((e, s), jnp.float32),
        jnp.ones((e, s), jnp.float32), jnp.float32(0.0), jnp.float32(1.0),
    )
    text = _lowered_text(solvers.fused_newton, *args)
    assert "module @jit_bank_fused " in text and "bank_newton" in text
    for scope in ("bank.densify", "bank.newton.gram", "bank.newton.cg",
                  "bank.newton.line_search", "bank.scatter_back"):
        assert scope in text, scope
    for kind, name in (("dense", "bank_dense"), ("sparse", "bank_sparse")):
        text = _lowered_text(getattr(solvers, kind), *((bank,) + args[2:]))
        assert f"module @jit_{name} " in text


def test_trace_scopes_joins_an_operation_with_its_module_and_dumped_scope(tmp_path):
    """The scopes are in no line of the chip's trace: the operator's tool
    (dev-scripts/trace_scopes.py) reads them from XLA's text dump."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(__file__), "..", "dev-scripts", "trace_scopes.py"
    )
    spec = importlib.util.spec_from_file_location("trace_scopes", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    (tmp_path / "module_0007.jit_glm_fit.cl_1.after_optimizations.txt").write_text(
        "HloModule jit_glm_fit\n"
        '  %fusion.42 = f32[8] fusion(%a), kind=kLoop, metadata={op_name="jit(glm_fit)'
        '/while/body/lbfgs.line_search/objective.margins/take" source_file="x.py"}\n'
        "  ROOT %while.1 = (f32[8]) while(%t), body=%b\n"
    )
    scopes = tool.hlo_scopes(str(tmp_path))
    assert set(scopes) == {"jit_glm_fit"} and "%while.1" not in scopes["jit_glm_fit"]
    runs = [["jit_glm_fit(11)", 100.0, 500.0], ["jit_bank_fused(12)", 650.0, 50.0]]
    ops = [
        ["%while.1 while", 100.0, 500.0], ["%fusion.42 fusion", 120.0, 300.0],
        ["%fusion.42 fusion", 650.0, 50.0],  # the same name in another module
    ]
    top = tool.top_ops(ops, runs, scopes, top=2)
    assert [(op, module) for _, op, module, _ in top] == [
        ("%fusion.42 fusion", "jit_glm_fit(11)"), ("%while.1 while", "jit_glm_fit(11)"),
    ]
    assert top[0][0] == pytest.approx(300e-9)
    assert top[1][0] == pytest.approx(200e-9)  # the while less its child
    assert "lbfgs.line_search/objective.margins" in top[0][3] and top[1][3] == ""
