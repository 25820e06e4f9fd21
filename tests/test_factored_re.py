"""Factored random effects through the GAME driver: the per-user model in a
learned projection, ``z_i' B gamma_u``, trained by alternating the users'
latent solves (one bank update an inner iteration, the latent features
made inside each block's program) and the fit of ``B`` over the same
blocks, against ``benchmark/reference_factored.py`` (float32, plain
``jax.numpy``, imports nothing of the program) at a tiny size: 48 users
x 16 rows, 40 user features at 8 a row, L = 4.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import data_factored
from benchmark import reference_factored as ref
from photon_ml_tpu.cli import game_training_driver as gtd
from photon_ml_tpu.game import factored
from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
from photon_ml_tpu.game.random_effect_data import build_random_effect_dataset
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.obs.registry import default_registry
from photon_ml_tpu.utils.index_map import IdentityIndexMap

USERS, PER_USER, DIM, NNZ, LATENT = 48, 16, 40, 8, 4
ROWS = USERS * PER_USER
CONFIG = {
    "users": USERS, "rows_per_user": PER_USER, "fixed_hashed_dim": 256,
    "fixed_nnz": 8, "user_dim": DIM, "user_nnz": NNZ, "shape_seed": 39,
    "planted": {"density": 0.5, "fixed_margin_std": 1.5,
                "user_margin_std": 1.5, "latent_dim": LATENT},
}
# a fixed iteration count on both sides (6, with a tolerance no float32
# change can meet), so that what is compared is the same path and not
# where two float32 paths stop: at the benchmark cell's 1e-5 a stop test
# sitting on its threshold moves a user by its last Newton step, ~1e-3
# of its model, and an L-BFGS fit by an iteration
RE_OPT = "6,1e-12,1.0,1,LBFGS,L2"


def _driver_and_dataset(tmp_path, re_opt=RE_OPT, inner=2):
    d = data_factored.glmix_factored_rows(CONFIG, 7)
    driver = gtd.GameTrainingDriver(gtd.params_from_args([
        "--task-type", "LOGISTIC_REGRESSION",
        "--feature-shard-id-to-feature-section-keys-map",
        "globalShard:features|userShard:userFeatures",
        "--feature-shard-id-to-intercept-map", "globalShard:true|userShard:false",
        "--fixed-effect-data-configurations", "global:globalShard,1",
        "--fixed-effect-optimization-configurations", "global:10,1e-7,1.0,1,LBFGS,L2",
        "--random-effect-data-configurations",
        "per-user:userId,userShard,1,none,none,none,IDENTITY",
        "--random-effect-optimization-configurations", f"per-user:{re_opt}",
        "--factored-random-effect-optimization-configurations",
        f"per-user:{LATENT},{inner}",
        "--updating-sequence", "global,per-user", "--num-iterations", "1",
        "--train-input-dirs", str(tmp_path / "unused"),
        "--output-dir", str(tmp_path / "out"),
        "--delete-output-dir-if-exists", "true",
    ]))
    dataset = GameDataset(
        uids=[str(i) for i in range(ROWS)], labels=d.labels,
        offsets=np.zeros(ROWS, np.float32), weights=np.ones(ROWS, np.float32),
        shards={
            "globalShard": ShardData(
                d.fixed.indices, d.fixed.values,
                IdentityIndexMap(d.fixed.dim - 1, add_intercept=True),
                d.fixed.intercept_index),
            "userShard": ShardData(
                d.user.indices, d.user.values, IdentityIndexMap(DIM), None),
        },
        entity_codes={"userId": d.user_of_row},
        entity_indexes={"userId": EntityIndex.build(
            "userId", [f"user{u:04d}" for u in range(USERS)])},
        num_real_rows=ROWS,
    )
    return driver, dataset, d


def _coordinates(driver, dataset):
    p = driver.params
    re_cfg = p.random_effect_data_configs["per-user"]
    reds = {"per-user": build_random_effect_dataset(dataset, re_cfg)}
    combo = gtd.expand_config_grid(
        {**p.fixed_effect_opt_configs, **p.random_effect_opt_configs})[0]
    return driver._build_coordinates(dataset, reds, combo), combo


def _by_user(d, a):
    order = np.argsort(d.user_of_row, kind="stable")
    return a[order].reshape((USERS, PER_USER) + a.shape[1:])


@pytest.fixture(scope="module")
def descent(tmp_path_factory):
    """One ``CoordinateDescent.run(1)`` from zero over the driver's own
    coordinates, and the reference's alternation under the residual the
    program's fixed effect leaves."""
    driver, dataset, d = _driver_and_dataset(tmp_path_factory.mktemp("fre"))
    coords, combo = _coordinates(driver, dataset)
    cd = CoordinateDescent(coords, dataset, driver.params.task_type,
                           update_sequence=["global", "per-user"])
    with jax.default_matmul_precision("highest"):
        result = cd.run(1)
    fixed = np.asarray(result.model.get_model("global").model.coefficients.means)
    fre = result.model.get_model("per-user")
    margins = np.sum(d.fixed.values * fixed[d.fixed.indices], axis=1)
    oc = combo["per-user"].optimizer_config
    prob = ref.FactoredProblem(
        _by_user(d, d.user.indices), _by_user(d, d.user.values),
        _by_user(d, d.labels), _by_user(d, margins), DIM)
    want = prob.fit(
        ref.starting_projection(DIM, LATENT), inner=2, l2=1.0, l2_projection=1.0,
        latent_max_iter=oc.max_iter, latent_tol=oc.tolerance,
        projection_max_iter=oc.max_iter, projection_tol=oc.tolerance,
    )
    return dict(
        result=result, coords=coords, dataset=dataset, data=d, prob=prob,
        want=want, fixed=fixed, margins=margins,
        bank=np.asarray(fre.bank), projection=np.asarray(fre.projection),
        scores=np.asarray(coords["per-user"].score(fre)),
    )


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_the_descent_matches_the_reference(descent):
    """Scores, projection, latent bank and objective, each against the
    reference's. Tolerances: the projection fits follow one path
    (``RE_OPT``) and agree to ~2e-7; a user's Newton solve stops where
    its float32 objective stops changing, which leaves it ~4e-5 of its
    model from the other side's (2e-4 allowed, for the rounding carried
    through two alternations); the objective is one float32 sum
    (1e-6)."""
    want, d = descent["want"], descent["data"]
    # the program's own scores, row by row, against the reference's
    assert _rel(_by_user(d, descent["scores"]), want.scores) < 2e-4
    assert _rel(descent["projection"], want.projection) < 2e-4
    assert _rel(descent["bank"], want.gamma) < 2e-4
    l2 = 1.0
    z = descent["margins"] + descent["scores"]
    objective = (
        float(np.sum(np.logaddexp(0.0, z) - d.labels * z))
        + 0.5 * l2 * float(np.sum(descent["fixed"].astype(np.float64) ** 2))
        + 0.5 * l2 * float(np.sum(descent["bank"].astype(np.float64) ** 2))
        + 0.5 * l2 * float(np.sum(descent["projection"].astype(np.float64) ** 2))
    )
    got = descent["result"].objective_history[-1]
    assert abs(got - objective) / objective < 1e-6
    # and the fit moved: its scores carry the planted per-user signal
    assert np.std(descent["scores"]) > 0.3


def test_the_tracker_holds_both_solves(descent):
    tracker = descent["result"].trackers["per-user"][-1]
    assert len(tracker.latent) == len(tracker.projection) == 2
    assert all(t.num_entities == USERS for t in tracker.latent)
    for fit in tracker.projection:
        assert fit["iterations"] >= 1 and fit["evaluations"] > fit["iterations"] - 1
        assert fit["reason"] != "?"


def test_a_zero_latent_bank_scores_zero(descent):
    coord = descent["coords"]["per-user"]
    model = coord.initialize_model()
    assert np.array_equal(np.asarray(coord.score(model)), np.zeros(ROWS))
    assert np.array_equal(np.asarray(model.score(descent["dataset"])), np.zeros(ROWS))


def test_the_model_scores_as_the_coordinate_does(descent):
    fre = descent["result"].model.get_model("per-user")
    np.testing.assert_array_equal(
        np.asarray(fre.score(descent["dataset"])), descent["scores"])


def test_the_objective_includes_the_projection_penalty(descent):
    coord = descent["coords"]["per-user"]
    fre = descent["result"].model.get_model("per-user")
    bank, projection = descent["bank"], descent["projection"]
    want = 0.5 * (np.sum(bank.astype(np.float64) ** 2)
                  + np.sum(projection.astype(np.float64) ** 2))
    assert abs(float(coord.regularization_term(fre)) - want) / want < 1e-6
    assert abs(float(coord.regularization_term_device(fre)) - want) / want < 1e-6


def test_a_tiny_dense_budget_splits_the_blocks_and_changes_nothing(tmp_path):
    """Each user's densified rows (16 x 40 floats) charged to the budget:
    at 13 users' worth the one block of 48 runs as 4 equal sub-blocks of
    one scanned program, and every number is the unsplit run's to float32
    rounding: the projection's gradient summed over four sub-blocks in
    another order, and a user's Newton solve stopping where its float32
    objective stops changing (2e-4, as against the reference)."""
    driver, dataset, _ = _driver_and_dataset(tmp_path)
    runs = {}
    for budget in (2 << 30, 13 * 4 * PER_USER * (DIM + LATENT + LATENT)):
        coords, _ = _coordinates(driver, dataset)
        coord = coords["per-user"]
        coord.problem.dense_bytes_budget = budget
        model = coord.initialize_model()
        with obs_trace.tracing_scope(True), jax.default_matmul_precision("highest"):
            obs_trace.tracer().clear()
            new, tracker = coord.update_model(model, None)
            scores = coord.score(new)
            tracker.projection  # noqa: B018  the readback
            spans = obs_trace.tracer().drain()
        subs = {s.attrs["sub_blocks"] for s in spans if s.name == "bank.dispatch"}
        runs[budget] = (subs, np.asarray(new.bank), np.asarray(new.projection),
                        np.asarray(scores))
    (subs_whole, *whole), (subs_split, *split) = runs.values()
    assert subs_whole == {1} and subs_split == {4}
    for got, want in zip(split, whole):
        assert _rel(got, want) < 2e-4


def _shapes_in(text: str, *shapes) -> list:
    return [s for s in shapes if "x".join(map(str, s)) + "x" in text]


def test_no_program_holds_the_flattened_rows(descent):
    """Neither the projection fit, the scoring pass nor the latent bank
    update holds an ``[n, k L]`` or ``[n, k, L]`` array (nor the same per
    block, ``[E, S, k L]`` or ``[E, S, k, L]``): a block's rows meet B as
    densified rows and one matmul."""
    coord = descent["coords"]["per-user"]
    fre = descent["result"].model.get_model("per-user")
    view = coord._view()
    override = factored.latent_override(fre.projection)
    groups, offsets = coord.problem.group_offsets(
        view, jnp.zeros(ROWS), override=override, coordinate="per-user")
    arrays = factored.group_arrays(coord.problem, view, groups)
    forbidden = [(ROWS, NNZ * LATENT), (ROWS, NNZ, LATENT),
                 (USERS, PER_USER, NNZ * LATENT), (USERS, PER_USER, NNZ, LATENT)]
    fit = factored._projection_program(
        "per-user", coord.problem.loss, coord.projection_problem.config, False)
    texts = {
        "projection": fit.lower(
            fre.projection.reshape(-1), fre.bank, arrays, tuple(offsets),
            jnp.float32(0), jnp.float32(1)).as_text(),
    }
    plan = factored.score_plan(view, coord.problem, staged=DIM)
    from photon_ml_tpu.game.random_effect import score_blocks

    texts["score"] = factored.fre_score.lower(
        fre.bank, fre.projection, score_blocks(coord.problem, view, plan),
        plan.rest, num_rows=ROWS).as_text()
    (members,) = groups
    ix, v, lab, w, off, codes = coord.problem._bucket_device_args(members[0].bucket)
    texts["bank"] = coord.problem._solvers.fused_for(
        members[0].kind, "per-user", values_of=override.values_of,
    ).lower(fre.bank, codes, ix, v, lab, offsets[0], w, jnp.float32(0),
            jnp.float32(1), fre.projection).as_text()
    assert plan.rest is None and members[0].kind == "primal_id"
    for name, text in texts.items():
        assert not _shapes_in(text, *forbidden), name
    assert "jit_fre_projection_fit_per_user" in texts["projection"]
    assert "jit_fre_score" in texts["score"]
    assert "jit_bank_fused_per_user" in texts["bank"]


def test_spans_and_counters_name_the_coordinate(tmp_path):
    driver, dataset, _ = _driver_and_dataset(tmp_path, "20,1e-5,1.0,1,LBFGS,L2")
    coords, _ = _coordinates(driver, dataset)
    coord = coords["per-user"]
    registry = default_registry()
    inner = registry.counter("photon_fre_inner_iterations_total")
    evals = registry.counter("photon_fre_projection_evals_total")
    inner0 = inner.value(coordinate="per-user")
    evals0 = evals.value(coordinate="per-user")
    with obs_trace.tracing_scope(True), jax.default_matmul_precision("highest"):
        obs_trace.tracer().clear()
        _, tracker = coord.update_model(coord.initialize_model(), None)
        fits = tracker.projection  # the readback fills the spans' attrs
        spans = obs_trace.tracer().drain()
    latent = [s for s in spans if s.name == "fre.latent"]
    projection = [s for s in spans if s.name == "fre.projection"]
    assert [s.attrs["inner"] for s in latent] == [1, 2]
    assert [s.attrs["inner"] for s in projection] == [1, 2]
    assert all(s.attrs["coordinate"] == "per-user" for s in latent + projection)
    for s, fit in zip(projection, fits):
        assert s.attrs["evaluations"] == fit["evaluations"] > 0
        assert s.attrs["iterations"] == fit["iterations"]
        assert s.attrs["reason"] == fit["reason"]
    # the latent bank's dispatches, under the coordinate's name
    dispatches = [s for s in spans if s.name == "bank.dispatch"]
    assert len(dispatches) == 2
    assert all(s.attrs["coordinate"] == "per-user" for s in dispatches)
    assert inner.value(coordinate="per-user") - inner0 == 2
    assert evals.value(coordinate="per-user") - evals0 == sum(
        f["evaluations"] for f in fits)


@pytest.mark.parametrize("re_opt", [
    "6,1e-12,1.0,1,TRON,L2",  # TRON with the projection's Hessian product
    "6,1e-12,1.0,1,LBFGS,L1",  # OWL-QN
])
def test_the_other_optimizers_fit_the_projection(tmp_path, re_opt):
    """The projection fit runs under the coordinate's own optimizer
    configuration, as photon-ml's factored coordinate does: each lowers
    the coordinate's objective from where the latent solves left it."""
    driver, dataset, _ = _driver_and_dataset(tmp_path, re_opt, inner=1)
    coords, _ = _coordinates(driver, dataset)
    coord = coords["per-user"]
    labels = jnp.asarray(dataset.labels)

    def objective(model):
        z = coord.score(model)
        return float(jnp.sum(coord.problem.loss.value(z, labels))
                     + coord.regularization_term(model))

    with jax.default_matmul_precision("highest"):
        start = coord.initialize_model()
        new, tracker = coord.update_model(start, None)
        (fit,) = tracker.projection
        # the latent solves alone, against the starting projection
        latent = type(start)(**{**start.__dict__, "bank": new.bank})
        assert objective(new) < objective(latent) < objective(start)
    assert fit["iterations"] >= 1 and fit["evaluations"] >= 1


def test_the_validate_refusals_stay(tmp_path):
    driver, _, _ = _driver_and_dataset(tmp_path)
    for extra in ({"entity_shards": 2}, {"streaming": True}):
        p = driver.params
        with pytest.raises(ValueError, match="factored"):
            type(p)(**{**p.__dict__, **extra}).validate()


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(pytest.main([os.path.abspath(__file__), "-q"]))
