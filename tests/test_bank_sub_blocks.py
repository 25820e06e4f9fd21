"""The replicated bank stages a capacity class over its dense budget as
equal sub-blocks of ONE compiled program (the rule the pod splits a
device's share by), and says which coordinate's bank a dispatch, a module
and a counter belong to: the split against the unsplit solve, the
compile count, the table of kinds, a descent over a fixed effect and TWO
random effects through the GAME driver's own ``_build_coordinates``
against plain hand-built coordinates, and the names one step leaves."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.cli import game_training_driver as gtd
from photon_ml_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    _cached_bucket_solver,
)
from photon_ml_tpu.game.random_effect_data import build_random_effect_dataset
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.obs.registry import default_registry, reset_default_registry
from photon_ml_tpu.ops.losses import LOGISTIC
from photon_ml_tpu.optim.config import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.optim.problem import create_glm_problem
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.utils.index_map import IdentityIndexMap

USERS, ROWS_A_USER = 44, 8  # 352 rows; 44 users = 3 sub-blocks of 15, one lane padded
ITEMS, ROWS_AN_ITEM = 32, 11  # 11 rows an item pad to capacity 16
# (a local space of 16: capacity 16 is then no more than it, the dual kind)
D_RE, K_RE, D_FIXED, K_FIXED = 16, 4, 64, 6
N = USERS * ROWS_A_USER
# what one entity costs the dense Newton solver: X [S, D] and the Gram [S, S]
USER_BYTES = (ROWS_A_USER * D_RE + ROWS_A_USER * ROWS_A_USER) * 4
ITEM_BYTES = (16 * D_RE + 16 * 16) * 4

DRIVER_ARGS = [
    "--task-type", "LOGISTIC_REGRESSION",
    "--feature-shard-id-to-feature-section-keys-map",
    "globalShard:features|userShard:userFeatures|itemShard:itemFeatures",
    "--feature-shard-id-to-intercept-map",
    "globalShard:true|userShard:false|itemShard:false",
    "--fixed-effect-data-configurations", "global:globalShard,1",
    "--fixed-effect-optimization-configurations", "global:10,1e-7,1.0,1,LBFGS,L2",
    "--random-effect-data-configurations",
    "per-user:userId,itemShard,1,none,none,none,IDENTITY"
    "|per-item:itemId,userShard,1,none,none,none,IDENTITY",
    "--random-effect-optimization-configurations",
    "per-user:20,1e-5,1.0,1,LBFGS,L2|per-item:20,1e-5,1.0,1,LBFGS,L2",
    "--updating-sequence", "global,per-user,per-item",
    "--num-iterations", "2",
    # one chip's deployment: the replicated bank (on the tests' eight CPU
    # devices "auto" would shard the entity axis over a mesh)
    "--distributed", "off",
]


def _rows(seed=11):
    """Seeded GAME rows over one table: user u has rows u, u + USERS, ...
    (8 each), the item of a row comes from a permutation (11 each); the
    per-user model reads the item-side features of the row and the
    per-item model the user-side ones."""
    assert N == ITEMS * ROWS_AN_ITEM
    rng = np.random.default_rng(seed)
    f_ix = np.concatenate([
        rng.integers(0, D_FIXED, (N, K_FIXED)), np.full((N, 1), D_FIXED),
        np.zeros((N, 1), np.int64),
    ], axis=1).astype(np.int32)
    f_v = np.concatenate([
        rng.normal(size=(N, K_FIXED)), np.ones((N, 1)), np.zeros((N, 1)),
    ], axis=1).astype(np.float32)

    def side():
        ix = np.stack([rng.permutation(D_RE)[:K_RE] for _ in range(N)])
        return ShardData(
            ix.astype(np.int32), rng.normal(size=(N, K_RE)).astype(np.float32),
            IdentityIndexMap(D_RE), None,
        )

    return GameDataset(
        uids=[str(i) for i in range(N)],
        labels=(rng.uniform(size=N) < 0.5).astype(np.float32),
        offsets=np.zeros(N, np.float32),
        weights=np.ones(N, np.float32),
        shards={
            "globalShard": ShardData(
                f_ix, f_v, IdentityIndexMap(D_FIXED, add_intercept=True), D_FIXED),
            "userShard": side(), "itemShard": side(),
        },
        entity_codes={
            "userId": (np.arange(N) % USERS).astype(np.int32),
            "itemId": (rng.permutation(N) % ITEMS).astype(np.int32),
        },
        entity_indexes={
            "userId": EntityIndex.build(
                "userId", [f"user{u:04d}" for u in range(USERS)]),
            "itemId": EntityIndex.build(
                "itemId", [f"item{i:04d}" for i in range(ITEMS)]),
        },
        num_real_rows=N,
    )


def _params():
    return gtd.params_from_args(DRIVER_ARGS + [
        "--train-input-dirs", "unused", "--output-dir", "unused-out"])


def _re_datasets(dataset):
    return {
        name: build_random_effect_dataset(dataset, cfg)
        for name, cfg in _params().random_effect_data_configs.items()
    }


def _problem(budget=2 << 30, **kw):
    return RandomEffectOptimizationProblem(
        LOGISTIC, OptimizerConfig(max_iter=20, tolerance=1e-5),
        RegularizationContext(RegularizationType.L2), reg_weight=1.0,
        dense_bytes_budget=budget, **kw,
    )


@pytest.fixture
def user_bank(rng):
    dataset = _rows()
    red = _re_datasets(dataset)["per-user"]
    residual = jnp.asarray(rng.normal(size=N) * 0.3, jnp.float32)
    return red, residual, jnp.zeros((USERS, D_RE), jnp.float32)


def test_a_bucket_over_the_budget_is_the_unsplit_solve_in_three_sub_blocks(
        user_bank):
    red, residual, zero = user_bank
    whole, whole_tracker = _problem().update_bank(
        zero, red, residual_offsets=residual)
    split_problem = _problem(15 * USER_BYTES)  # 44 users: 15 + 15 + 14
    blocks = split_problem._solver_blocks(red, D_RE, split=True)
    assert [(b.kind, b.sub_blocks, b.num_real) for b in blocks] == [
        ("newton", 3, 15), ("newton", 3, 15), ("newton", 3, 14)]
    assert {b.bucket.indices.shape for b in blocks} == {(15, ROWS_A_USER, K_RE)}
    # every user in one lane of one sub-block; the one padding lane holds a
    # code past the bank, no row and no weight
    codes = np.concatenate([b.bucket.entity_codes for b in blocks])
    assert sorted(codes[codes < USERS].tolist()) == list(range(USERS))
    assert codes[-1] == USERS and (codes >= USERS).sum() == 1
    last = blocks[-1].bucket
    assert (last.row_index[-1] == -1).all() and not last.weights[-1].any()
    split, split_tracker = split_problem.update_bank(
        zero, red, residual_offsets=residual)
    # EXACT: a user's solve is its own lane of the vmapped program and reads
    # no other lane, and the stopping rule is per user, so the block it sits
    # in does not reach its arithmetic (on the CPU; on the chip a batched
    # matmul may tile by the batch, and the benchmark's check holds both to
    # the plain reference instead)
    np.testing.assert_array_equal(np.asarray(split), np.asarray(whole))
    assert split_tracker.num_entities == whole_tracker.num_entities == USERS
    assert split_tracker.iterations_mean == whole_tracker.iterations_mean
    assert split_tracker.iterations_max == whole_tracker.iterations_max
    # (the padding lane is in no count)
    assert split_tracker.reason_counts == whole_tracker.reason_counts
    assert sum(split_tracker.reason_counts.values()) == USERS


def test_the_variance_pass_walks_the_same_sub_blocks(user_bank):
    red, residual, zero = user_bank
    whole = _problem().update_bank(
        zero, red, residual_offsets=residual, with_variances=True)
    split = _problem(15 * USER_BYTES).update_bank(
        zero, red, residual_offsets=residual, with_variances=True)
    np.testing.assert_array_equal(np.asarray(split[0]), np.asarray(whole[0]))
    np.testing.assert_array_equal(np.asarray(split[2]), np.asarray(whole[2]))
    assert split[1].num_entities == USERS


def test_all_sub_blocks_run_one_compiled_program(user_bank):
    red, residual, zero = user_bank
    # a solver namespace of its own (another tolerance), so that no other
    # test's executables are in its cache
    problem = RandomEffectOptimizationProblem(
        LOGISTIC, OptimizerConfig(max_iter=20, tolerance=3e-5),
        RegularizationContext(RegularizationType.L2), reg_weight=1.0,
        dense_bytes_budget=15 * USER_BYTES,
    )
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: compiled.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None
    )
    problem.prepare(zero, red, coordinate="per-user")
    assert list(problem._aot_cache) == [
        ("scan", "newton", "per-user", (USERS, D_RE), (3, 15, ROWS_A_USER, K_RE))
    ]
    before = len(compiled)
    bank, _ = problem.update_bank(
        zero, red, residual_offsets=residual, coordinate="per-user")
    problem.update_bank(bank, red, residual_offsets=residual, coordinate="per-user")
    assert len(problem._aot_cache) == 1
    # nothing the three sub-blocks run compiled after prepare(): what the
    # updates compile are the residual gather's few elementwise helpers, once
    bank_programs = [e for e in compiled[before:]]
    assert len(bank_programs) <= 8, bank_programs
    third = len(compiled)
    problem.update_bank(bank, red, residual_offsets=residual, coordinate="per-user")
    assert len(compiled) == third  # a warm update compiles nothing


@pytest.mark.parametrize("budget, layout, split, whole", [
    # whole: the class fits, one block of the dense kind either way
    (USERS * USER_BYTES, "auto", [("newton", 1)], "newton"),
    # split: over the budget, equal sub-blocks of the dense kind; a block that
    # cannot be split (a streamed segment, the mesh) runs sparse
    (USERS * USER_BYTES - 1, "auto", [("newton", 2)] * 2, "sparse"),
    (15 * USER_BYTES, "auto", [("newton", 3)] * 3, "sparse"),
    (USER_BYTES, "auto", [("newton", USERS)] * USERS, "sparse"),
    # the layout forces sparse whatever the budget
    (2 << 30, "sparse", [("sparse", 1)], "sparse"),
    # not one entity fits: nothing dense may run
    (USER_BYTES - 1, "auto", [("sparse", 1)], "sparse"),
    # the layout forces dense: whole, whatever the budget
    (1, "dense", [("newton", 1)], "newton"),
])
def test_the_table_of_kinds(user_bank, budget, layout, split, whole):
    red, _, _ = user_bank
    problem = _problem(budget, layout=layout)
    blocks = problem._solver_blocks(red, D_RE, split=True)
    assert [(b.kind, b.sub_blocks) for b in blocks] == split
    assert sum(b.num_real for b in blocks) == USERS
    (unsplit,) = problem._solver_blocks(red, D_RE, split=False)
    assert (unsplit.kind, unsplit.sub_blocks, unsplit.bucket) == (
        whole, 1, red.buckets[0])
    assert problem._bucket_kind(red.buckets[0], D_RE) == whole


def _driver_cd(tmp_path, dataset, budgets):
    driver = gtd.GameTrainingDriver(gtd.params_from_args(DRIVER_ARGS + [
        "--train-input-dirs", str(tmp_path / "unused"),
        "--output-dir", str(tmp_path / "out"),
    ]))
    p = driver.params
    reds = _re_datasets(dataset)
    combo = gtd.expand_config_grid(
        {**p.fixed_effect_opt_configs, **p.random_effect_opt_configs})[0]
    coords = driver._build_coordinates(dataset, reds, combo)
    for name, budget in budgets.items():
        coords[name].problem.dense_bytes_budget = budget
    cd = CoordinateDescent(
        coords, dataset, p.task_type, update_sequence=p.updating_sequence,
        logger=driver.logger,
    )
    return cd, coords, reds, combo


BUDGETS = {"per-user": 15 * USER_BYTES, "per-item": 7 * ITEM_BYTES}


def test_a_descent_over_two_random_effects_matches_plain_coordinates(tmp_path):
    dataset = _rows()
    cd, coords, reds, combo = _driver_cd(tmp_path, dataset, BUDGETS)
    assert list(coords) == ["global", "per-user", "per-item"]
    assert [
        (b.kind, b.sub_blocks)
        for b in coords["per-item"].problem._solver_blocks(
            reds["per-item"], D_RE, split=True)
    ] == [("newton", 5)] * 5  # 32 items at capacity 16: 7 + 7 + 7 + 7 + 4
    got = cd.run(1)
    models = got.model.models
    # stage by stage, each stage fed what the descent itself produced (so
    # that no solve's stop, an iteration apart under a residual a rounding
    # apart, is carried into the next): plain float32 math, the scatter
    # objective and unsplit banks, hand-built
    plain_fixed = FixedEffectCoordinate(
        name="global", dataset=dataset,
        problem=create_glm_problem(
            TaskType.LOGISTIC_REGRESSION, D_FIXED + 1,
            config=combo["global"].optimizer_config,
            regularization=combo["global"].regularization,
            intercept_index=D_FIXED, kernel="scatter",
        ),
        feature_shard_id="globalShard", reg_weight=combo["global"].reg_weight,
    )
    want_fixed, _ = plain_fixed.update_model(
        plain_fixed.initialize_model(), jnp.zeros(N, jnp.float32))
    np.testing.assert_allclose(
        np.asarray(models["global"].model.means),
        np.asarray(want_fixed.model.means), atol=1e-4)
    scores = {
        name: np.asarray(coords[name].score(models[name])) for name in coords
    }

    def plain_bank(name, entities, residual):
        bank, tracker = RandomEffectOptimizationProblem(
            LOGISTIC, combo[name].optimizer_config, combo[name].regularization,
            reg_weight=combo[name].reg_weight,
        ).update_bank(
            jnp.zeros((entities, D_RE), jnp.float32), reds[name],
            residual_offsets=jnp.asarray(residual),
        )
        assert tracker.num_entities == entities
        return np.asarray(bank)

    # the user bank under the fixed effect's score, the item bank under the
    # fixed effect's AND the user bank's: the same lanes' arithmetic, whole
    # or in sub-blocks, so equal to rounding
    np.testing.assert_allclose(
        np.asarray(models["per-user"].bank),
        plain_bank("per-user", USERS, scores["global"]), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(models["per-item"].bank),
        plain_bank("per-item", ITEMS, scores["global"] + scores["per-user"]),
        atol=1e-6)
    # without the hand-off of the user's score it would be another bank
    skipped = plain_bank("per-item", ITEMS, scores["global"])
    assert np.max(np.abs(skipped - np.asarray(models["per-item"].bank))) > 1e-2
    for name, entities in (("per-user", USERS), ("per-item", ITEMS)):
        assert got.trackers[name][-1].num_entities == entities
    # the objective over all three scores and penalties (lambda 1 each)
    z = np.asarray(sum(scores.values()), np.float64)
    y = np.asarray(dataset.labels, np.float64)
    want = np.sum(np.maximum(z, 0) + np.log1p(np.exp(-np.abs(z))) - y * z) + sum(
        0.5 * np.sum(np.asarray(a, np.float64) ** 2) for a in (
            models["global"].model.means, models["per-user"].bank,
            models["per-item"].bank)
    )
    np.testing.assert_allclose(got.objective_history[0], want, rtol=1e-5)
    # and it falls: a second iteration (after the same first, every run
    # starting from zero) ends lower
    two = cd.run(2).objective_history
    assert two[0] == got.objective_history[0]
    assert two[1] < two[0] < N * np.log(2.0)


def test_one_step_names_each_coordinates_bank(tmp_path):
    dataset = _rows()
    cd, coords, reds, _ = _driver_cd(tmp_path, dataset, BUDGETS)
    reset_default_registry()
    with obs_trace.tracing_scope(True):
        obs_trace.tracer().clear()
        cd.run(1)
        spans = obs_trace.tracer().drain()
    dispatches = {
        s.attrs["coordinate"]: s.attrs for s in spans if s.name == "bank.dispatch"
    }
    assert dispatches == {
        "per-user": {"kind": "newton", "entities": USERS, "capacity": 8,
                     "sub_blocks": 3, "coordinate": "per-user"},
        "per-item": {"kind": "newton", "entities": ITEMS, "capacity": 16,
                     "sub_blocks": 5, "coordinate": "per-item"},
    }
    waits = [s.attrs["coordinate"] for s in spans if s.name == "cd.prefetch_wait"]
    assert set(waits) <= {"global", "per-user", "per-item"}
    solved = default_registry().counter("photon_bank_entities_total")
    assert solved.value(coordinate="per-user", kind="newton") == USERS
    assert solved.value(coordinate="per-item", kind="newton") == ITEMS
    assert solved.total() == USERS + ITEMS
    # the XLA modules: the coordinate's name after the program's, so that
    # "^jit_bank_" still reads every bank and a longer pattern reads one
    solvers = coords["per-item"].problem._solvers
    assert solvers is _cached_bucket_solver(
        LOGISTIC, coords["per-item"].problem.config,
        coords["per-item"].problem.regularization)
    e, s, k = 7, 16, K_RE
    args = (
        jnp.zeros((ITEMS, D_RE), jnp.float32), jnp.zeros((5, e), jnp.int32),
        jnp.zeros((5, e, s, k), jnp.int32), jnp.zeros((5, e, s, k), jnp.float32),
        jnp.zeros((5, e, s), jnp.float32), jnp.zeros((5, e, s), jnp.float32),
        jnp.zeros((5, e, s), jnp.float32), jnp.float32(0.0), jnp.float32(1.0),
    )
    for coordinate, module in (
        ("per-item", "jit_bank_fused_scan_per_item"),
        ("per-user", "jit_bank_fused_scan_per_user"),
        (None, "jit_bank_fused_scan"),
    ):
        text = solvers.fused_for("newton", coordinate, scan=True).lower(
            *args).as_text()
        assert f"module @{module} " in text, module
    single = solvers.fused_for("newton", "per-item").lower(
        args[0], *(a[0] for a in args[1:7]), *args[7:]).as_text()
    assert "module @jit_bank_fused_per_item " in single
    assert solvers.fused_for("newton") is solvers.fused_newton
