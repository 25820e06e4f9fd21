"""The pod bank decides its solver from the block a DEVICE holds, splits a
share over the dense budget into equal sub-blocks of one program, and
names its work: a coordinate-descent step at four shards through the GAME
driver's own ``_build_coordinates`` against the unsharded coordinates,
the split against the unsplit solve, the kind a shard runs where the
whole class is over the budget, and the spans, module names and counters
one step leaves."""

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.cli import game_training_driver as gtd
from photon_ml_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
from photon_ml_tpu.game.pod import PodRandomEffectProblem, _build_update_program
from photon_ml_tpu.game.random_effect import RandomEffectOptimizationProblem
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
from photon_ml_tpu.parallel.mesh import entity_mesh
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.utils.index_map import IdentityIndexMap

USERS, ROWS_A_USER, D_USER, K_USER, D_FIXED, K_FIXED = 48, 8, 12, 4, 64, 6
N_SHARDS = 4
# what one user costs the dense Newton solver: X [S, D] and the Gram [S, S]
USER_BYTES = (ROWS_A_USER * D_USER + ROWS_A_USER * ROWS_A_USER) * 4

DRIVER_ARGS = [
    "--task-type", "LOGISTIC_REGRESSION",
    "--feature-shard-id-to-feature-section-keys-map",
    "globalShard:features|userShard:userFeatures",
    "--feature-shard-id-to-intercept-map", "globalShard:true|userShard:false",
    "--fixed-effect-data-configurations", "global:globalShard,1",
    "--fixed-effect-optimization-configurations", "global:10,1e-7,1.0,1,LBFGS,L2",
    "--random-effect-data-configurations",
    "per-user:userId,userShard,1,none,none,none,IDENTITY",
    "--random-effect-optimization-configurations",
    "per-user:20,1e-5,1.0,1,LBFGS,L2",
    "--updating-sequence", "global,per-user",
    "--num-iterations", "1",
]


def _rows(seed=7):
    """Seeded GLMix rows: every user has ROWS_A_USER rows (one capacity
    class), user u's rows are rows u, u + USERS, ..."""
    rng = np.random.default_rng(seed)
    n = USERS * ROWS_A_USER
    f_ix = np.concatenate([
        rng.integers(0, D_FIXED, (n, K_FIXED)), np.full((n, 1), D_FIXED),
        np.zeros((n, 1), np.int64),
    ], axis=1).astype(np.int32)
    f_v = np.concatenate([
        rng.normal(size=(n, K_FIXED)), np.ones((n, 1)), np.zeros((n, 1)),
    ], axis=1).astype(np.float32)
    u_ix = np.stack([
        rng.permutation(D_USER)[:K_USER] for _ in range(n)
    ]).astype(np.int32)
    u_v = rng.normal(size=(n, K_USER)).astype(np.float32)
    return GameDataset(
        uids=[str(i) for i in range(n)],
        labels=(rng.uniform(size=n) < 0.5).astype(np.float32),
        offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        shards={
            "globalShard": ShardData(
                f_ix, f_v, IdentityIndexMap(D_FIXED, add_intercept=True), D_FIXED),
            "userShard": ShardData(u_ix, u_v, IdentityIndexMap(D_USER), None),
        },
        entity_codes={"userId": (np.arange(n) % USERS).astype(np.int32)},
        entity_indexes={
            "userId": EntityIndex.build(
                "userId", [f"user{u:04d}" for u in range(USERS)])
        },
        num_real_rows=n,
    )


def _driver(tmp_path, *extra):
    return gtd.GameTrainingDriver(gtd.params_from_args(DRIVER_ARGS + [
        "--train-input-dirs", str(tmp_path / "unused"),
        "--output-dir", str(tmp_path / "out"), *extra,
    ]))


def _pod_cd(tmp_path, dataset):
    """The driver's own coordinates at four entity shards."""
    driver = _driver(tmp_path, "--entity-shards", str(N_SHARDS))
    p = driver.params
    (re_name, re_cfg), = p.random_effect_data_configs.items()
    red = build_random_effect_dataset(dataset, re_cfg)
    combo = gtd.expand_config_grid(
        {**p.fixed_effect_opt_configs, **p.random_effect_opt_configs})[0]
    coords = driver._build_coordinates(dataset, {re_name: red}, combo)
    cd = CoordinateDescent(
        coords, dataset, p.task_type, update_sequence=p.updating_sequence,
        logger=driver.logger,
    )
    return cd, coords, red, combo


def _bank_problem(budget, **kw):
    return RandomEffectOptimizationProblem(
        LOGISTIC, OptimizerConfig(max_iter=20, tolerance=1e-5),
        RegularizationContext(RegularizationType.L2), reg_weight=1.0,
        dense_bytes_budget=budget, **kw,
    )


def test_a_pod_step_through_the_driver_matches_the_unsharded_coordinates(tmp_path):
    dataset = _rows()
    cd, coords, red, combo = _pod_cd(tmp_path, dataset)
    assert type(coords["per-user"]).__name__ == "PodRandomEffectCoordinate"
    got = cd.run(1)
    # plain float32 math: the scatter objective on one device, and the
    # replicated bank's step, hand-built
    plain = CoordinateDescent({
        "global": FixedEffectCoordinate(
            name="global", dataset=dataset,
            problem=create_glm_problem(
                TaskType.LOGISTIC_REGRESSION, D_FIXED + 1,
                config=combo["global"].optimizer_config,
                regularization=combo["global"].regularization,
                intercept_index=D_FIXED, kernel="scatter",
            ),
            feature_shard_id="globalShard", reg_weight=combo["global"].reg_weight,
        ),
        "per-user": RandomEffectCoordinate(
            name="per-user", dataset=dataset, re_dataset=red,
            problem=RandomEffectOptimizationProblem(
                LOGISTIC, combo["per-user"].optimizer_config,
                combo["per-user"].regularization,
                reg_weight=combo["per-user"].reg_weight,
            ),
        ),
    }, dataset, TaskType.LOGISTIC_REGRESSION,
        update_sequence=["global", "per-user"]).run(1)
    np.testing.assert_allclose(
        got.objective_history, plain.objective_history, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(got.model.models["global"].model.means),
        np.asarray(plain.model.models["global"].model.means), atol=1e-4)
    # (a user's solve stops on a relative change of 1e-5: under residuals
    # a rounding apart it may stop an iteration apart)
    np.testing.assert_allclose(
        np.asarray(got.model.models["per-user"].bank),
        np.asarray(plain.model.models["per-user"].bank), atol=1e-3)
    tracker = got.trackers["per-user"][-1]
    assert tracker.num_entities == USERS


def _pod_update(budget, red, residual):
    pod = PodRandomEffectProblem(_bank_problem(budget), entity_mesh(N_SHARDS))
    bank, tracker = pod.update_bank(
        pod.init_bank(red), red, residual_offsets=residual)
    return pod.pod_view(red), np.asarray(bank.to_global()), tracker


def test_a_share_over_the_budget_splits_into_equal_sub_blocks_of_one_solve(rng):
    dataset = _rows()
    driver_cfg = gtd.params_from_args(DRIVER_ARGS + [
        "--train-input-dirs", "unused", "--output-dir", "unused-out"])
    (_, re_cfg), = driver_cfg.random_effect_data_configs.items()
    red = build_random_effect_dataset(dataset, re_cfg)
    residual = jnp.asarray(rng.normal(size=dataset.num_rows) * 0.3, jnp.float32)
    per_shard = USERS // N_SHARDS  # 12 users a shard
    whole_view, whole, whole_tracker = _pod_update(
        per_shard * USER_BYTES, red, residual)
    split_view, split, split_tracker = _pod_update(
        4 * USER_BYTES, red, residual)  # 4 users a dense block
    assert [b.kind for b in whole_view.blocks] == ["newton"]
    assert [(b.kind, b.sub_blocks, b.num_real) for b in split_view.blocks] == [
        ("newton", 3, 16)] * 3
    assert len({b.ix.shape for b in split_view.blocks}) == 1  # one program
    np.testing.assert_allclose(split, whole, rtol=1e-6, atol=1e-7)
    assert split_tracker.num_entities == whole_tracker.num_entities == USERS
    assert split_tracker.iterations_mean == whole_tracker.iterations_mean
    # every user in exactly one (shard, sub-block, lane)
    seen = []
    for b in split_view.blocks:
        lrow, valid = np.asarray(b.lrow), np.asarray(b.valid)
        shard = np.arange(lrow.shape[0]) // (lrow.shape[0] // N_SHARDS)
        seen.append((lrow * N_SHARDS + shard)[valid])
    assert sorted(np.concatenate(seen).tolist()) == list(range(USERS))
    # and the bank is the replicated solve's
    ref, _ = _bank_problem(2 << 30).update_bank(
        jnp.zeros((USERS, D_USER), jnp.float32), red, residual_offsets=residual)
    np.testing.assert_allclose(split, np.asarray(ref), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("budget, kinds", [
    # the whole class (48 users) is over the budget, a shard's 12 are not
    (12 * USER_BYTES, ["newton"]),
    # not one user fits: nothing dense may run
    (USER_BYTES - 1, ["sparse"]),
])
def test_the_kind_is_decided_from_the_block_a_shard_holds(tmp_path, budget, kinds):
    dataset = _rows()
    cd, coords, red, _ = _pod_cd(tmp_path, dataset)
    coord = coords["per-user"]
    coord.problem.dense_bytes_budget = budget
    assert coord.problem._bucket_kind(red.buckets[0], D_USER) == "sparse"
    reset_default_registry()
    cd.run(1)
    assert [b.kind for b in coord.pod.pod_view(red).blocks] == kinds
    solved = default_registry().counter("photon_pod_entities_total")
    assert solved.value(coordinate="per-user", kind=kinds[0]) == USERS
    assert solved.total() == USERS


@pytest.mark.parametrize("shape, plan, kind", [
    ((32768, 16, 1000, False), ("newton", 33026), "newton"),  # the one-chip cell
    ((262144, 16, 1000, False), ("newton", 33026), "sparse"),  # its 4-chip twin, whole
    ((65536, 16, 1000, False), ("newton", 33026), "sparse"),  # one chip's share of it
    ((1 << 20, 16, 16, True), ("newton_id", 2097152), "newton_id"),
])
def test_one_rule_gives_the_kind_and_the_entities_a_dense_block_may_hold(
        shape, plan, kind):
    problem = _bank_problem(2 << 30)
    assert problem.dense_block_plan(*shape) == plan

    class _Bucket:
        indices = np.empty((shape[0], shape[1], 0), np.int32)
        identity_indices = shape[3]

    assert problem._bucket_kind(_Bucket, shape[2]) == kind
    assert problem._use_dense(_Bucket, shape[2]) == (kind != "sparse")
    assert _bank_problem(2 << 30, layout="sparse").dense_block_plan(*shape) == (
        "sparse", shape[0])
    assert _bank_problem(1, layout="dense").dense_block_plan(*shape) == (
        plan[0], shape[0])


def test_one_pod_step_leaves_its_spans_module_names_and_counters(tmp_path):
    dataset = _rows()
    cd, coords, red, _ = _pod_cd(tmp_path, dataset)
    reset_default_registry()
    with obs_trace.tracing_scope(True):
        obs_trace.tracer().clear()
        cd.run(1)
        spans = obs_trace.tracer().drain()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    by_id = {s.span_id: s for s in spans}
    assert by_name["pod.view_build"][0].attrs == {
        "shards": N_SHARDS, "blocks": 1, "sub_blocks": 1,
        "slots": N_SHARDS * 24,  # 96 rows a shard, a quarter to each owner
    }
    (update,) = by_name["pod.update"]
    assert update.attrs == {"kind": "newton", "sub_blocks": 1, "entities": USERS}
    (route,) = by_name["pod.route_in"]
    assert by_id[by_id[update.parent_id].parent_id].name == "cd.iteration"
    assert by_id[update.parent_id].name == by_id[route.parent_id].name == "cd.update"
    # the model run() starts from is scored too, before the iteration opens
    assert len(by_name["pod.score"]) == len(by_name["pod.replicate"]) == 2
    assert {by_id[s.parent_id].name for s in by_name["pod.score"]} == {"cd.score"}

    registry = default_registry()
    n = dataset.num_rows
    routed = registry.counter("photon_pod_routed_rows_total")
    assert routed.value(coordinate="per-user", hop="in") == n
    assert routed.value(coordinate="per-user", hop="out") == 2 * n
    # row r lies on shard r // (n / 4); its user r % USERS on shard user % 4
    rows = np.arange(n)
    crossing = int(np.sum((rows % USERS) % N_SHARDS != rows // (n // N_SHARDS)))
    assert registry.counter("photon_pod_cross_shard_rows_total").value(
        coordinate="per-user") == 3 * crossing
    assert registry.counter("photon_pod_entities_total").value(
        coordinate="per-user", kind="newton") == USERS

    # the functions handed to jax.jit name the XLA modules
    pod = coords["per-user"].pod
    view = pod.pod_view(red)
    bank = pod.init_bank(red)
    blk = view.blocks[0]
    slots = view.router.route_in(jnp.zeros(n, jnp.float32))
    one = jnp.float32(1.0)
    lowered = {
        "pod_update": _build_update_program(
            pod.base._solvers, "newton", pod.mesh, pod.axis
        ).lower(bank.data, blk.lrow, blk.valid, blk.ix, blk.v, blk.lab, blk.w,
                blk.offslot, slots, one, one),
        "pod_score": view._score.lower(
            bank.data, view._score_blocks, view._score_rest,
            view.router._send_pos),
        "pod_route_in": view.router._route_in.lower(
            view.router._pad_rows(jnp.zeros(n, jnp.float32)), view.router._send_pos),
        "pod_route_out": view.router._route_out.lower(slots, view.router._send_pos),
    }
    for name, low in lowered.items():
        assert f"module @jit_{name} " in low.as_text(), name
    from photon_ml_tpu.game import pod as pod_module

    assert {fn.__name__ for fn in pod_module._REPL_CACHE.values()} == {"pod_replicate"}
    assert {fn.__name__ for fn in pod_module._ZEROS_CACHE.values()} == {"pod_zeros"}
