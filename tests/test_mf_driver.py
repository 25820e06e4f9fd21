"""The matrix-factorization coordinate through the GAME driver
(``--matrix-factorization-configurations``): the flag parsed and refused
where ``validate`` says; a descent over a fixed effect, two biases and
the ALS coordinate, built by the driver's own ``_build_coordinates``,
against a plain float64 numpy coordinate descent; the model saved and
read back through ``model_io``; the one scoring program."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.cli import game_training_driver as gtd
from photon_ml_tpu.game import model as game_model
from photon_ml_tpu.game.config import MatrixFactorizationConfiguration
from photon_ml_tpu.game.coordinate import MatrixFactorizationCoordinate
from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
from photon_ml_tpu.game.model import MatrixFactorizationModel, mf_score
from photon_ml_tpu.game.model_io import load_game_model, save_game_model
from photon_ml_tpu.game.random_effect_data import build_random_effect_dataset
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.obs.registry import default_registry, reset_default_registry
from photon_ml_tpu.task import TaskType
from photon_ml_tpu.utils.index_map import IdentityIndexMap

USERS, ITEMS, K, D_FIXED = 300, 80, 4, 3
L2 = 0.5

ARGS = [
    "--task-type", "LINEAR_REGRESSION",
    "--feature-shard-id-to-feature-section-keys-map", "globalShard:features|biasShard:",
    "--feature-shard-id-to-intercept-map", "globalShard:true|biasShard:true",
    "--fixed-effect-data-configurations", "global:globalShard,1",
    "--fixed-effect-optimization-configurations", f"global:100,1e-13,{L2},1,LBFGS,L2",
    "--random-effect-data-configurations",
    "per-user:userId,biasShard,1,none,none,none,IDENTITY"
    "|per-item:itemId,biasShard,1,none,none,none,IDENTITY",
    "--random-effect-optimization-configurations",
    f"per-user:20,1e-9,{L2},1,LBFGS,L2|per-item:20,1e-9,{L2},1,LBFGS,L2"
    f"|mf:20,1e-9,{L2},1,LBFGS,L2",
    "--matrix-factorization-configurations", f"mf:userId,itemId,{K},1",
    "--updating-sequence", "global,per-user,per-item,mf",
    "--distributed", "off",
]


def _params(tmp_path, *more, args=ARGS):
    return gtd.params_from_args(list(args) + [
        "--train-input-dirs", str(tmp_path / "unused"),
        "--output-dir", str(tmp_path / "out"), *more,
    ])


def test_the_flag_is_parsed(tmp_path):
    p = _params(tmp_path)
    assert p.mf_configs == {
        "mf": MatrixFactorizationConfiguration("userId", "itemId", K, 1)}
    p.validate()
    assert MatrixFactorizationConfiguration.parse("u,i,64") == (
        MatrixFactorizationConfiguration("u", "i", 64, 1))
    two = gtd.params_from_args([
        a if a != f"mf:userId,itemId,{K},1" else "mf:userId,itemId,8,2|mf2:itemId,userId,3,1"
        for a in ARGS
    ] + ["--train-input-dirs", "x", "--output-dir", str(tmp_path / "o")])
    assert two.mf_configs["mf"].num_inner_iterations == 2
    assert two.mf_configs["mf2"].row_effect_type == "itemId"


@pytest.mark.parametrize("spec", ["userId,itemId", "userId,,4,1", "userId,itemId,0,1",
                                  "userId,itemId,4,0", "userId,itemId,4,1,9"])
def test_a_malformed_configuration_is_refused(spec):
    with pytest.raises(ValueError):
        MatrixFactorizationConfiguration.parse(spec)


@pytest.mark.parametrize("more,args,message", [
    (["--entity-shards", "2"], ARGS, "mf does not support: --entity-shards"),
    (["--streaming", "true"], ARGS, "mf does not support: --streaming"),
    ([], [a.replace(f"|mf:20,1e-9,{L2},1,LBFGS,L2", f"|mf:20,1e-9,{L2};1.0,1,LBFGS,L2")
          if "|mf:20" in a else a for a in ARGS],
     "mf does not support: a regularization-weight grid"),
    ([], [a.replace(f"|mf:20,1e-9,{L2},1,LBFGS,L2", "") for a in ARGS],
     "missing optimization config for mf"),
    ([], [a.replace("mf:userId", "per-user:userId") for a in ARGS],
     "shares its name"),
], ids=["entity-shards", "streaming", "grid", "no-optimizer", "name-clash"])
def test_validate_refuses_what_has_not_run_with_it(tmp_path, more, args, message):
    with pytest.raises(ValueError, match=message):
        _params(tmp_path, *more, args=args).validate()


def _ratings(seed=5):
    """A seeded heavy-tailed rating table: user u has 20 + (a power law)
    ratings on distinct movies drawn by popularity."""
    rng = np.random.default_rng(seed)
    counts = np.minimum(20 + (40.0 / (1 + np.arange(USERS)) ** 0.7).astype(int) * 3, 70)
    popularity = 1.0 / (1 + np.arange(ITEMS)) ** 0.8
    users, items = [], []
    for u, c in enumerate(counts):
        picked = rng.choice(ITEMS, size=c, replace=False, p=popularity / popularity.sum())
        users += [u] * c
        items += picked.tolist()
    users, items = np.asarray(users, np.int32), np.asarray(items, np.int32)
    order = rng.permutation(len(users))
    users, items = users[order], items[order]
    n = len(users)
    x = rng.normal(size=(n, D_FIXED)).astype(np.float32)
    p, q = rng.normal(size=(USERS, K)) * 0.5, rng.normal(size=(ITEMS, K)) * 0.5
    r = (
        3.5 + x @ np.array([0.3, -0.2, 0.1]) + 0.4 * rng.normal(size=USERS)[users]
        + 0.5 * rng.normal(size=ITEMS)[items] + np.sum(p[users] * q[items], axis=1)
        + 0.3 * rng.normal(size=n)
    )
    f_ix = np.tile(np.arange(D_FIXED + 1, dtype=np.int32), (n, 1))
    f_v = np.concatenate([x, np.ones((n, 1), np.float32)], axis=1)
    dataset = GameDataset(
        uids=[], labels=r.astype(np.float32), offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        shards={
            "globalShard": ShardData(
                f_ix, f_v, IdentityIndexMap(D_FIXED, add_intercept=True), D_FIXED),
            "biasShard": ShardData(
                np.zeros((n, 1), np.int32), np.ones((n, 1), np.float32),
                IdentityIndexMap(0, add_intercept=True), 0),
        },
        entity_codes={"userId": users, "itemId": items},
        entity_indexes={
            "userId": EntityIndex.build("userId", [f"u{u:04d}" for u in range(USERS)]),
            "itemId": EntityIndex.build("itemId", [f"i{i:04d}" for i in range(ITEMS)]),
        },
        num_real_rows=n,
    )
    return dataset, f_v.astype(np.float64)


def _descent(tmp_path, dataset):
    driver = gtd.GameTrainingDriver(_params(tmp_path))
    p = driver.params
    reds = {
        name: build_random_effect_dataset(dataset, cfg)
        for name, cfg in p.random_effect_data_configs.items()
    }
    combo = gtd.expand_config_grid(
        {**p.fixed_effect_opt_configs, **p.random_effect_opt_configs})[0]
    coords = driver._build_coordinates(dataset, reds, combo)
    return coords, CoordinateDescent(
        coords, dataset, p.task_type, update_sequence=p.updating_sequence,
        logger=driver.logger,
    )


def _plain_descent(dataset, x, p0, q0, passes):
    """The same descent in float64 numpy: every coordinate the exact
    ridge solution under the residual of the others, in the same order."""
    r = dataset.labels.astype(np.float64)
    users, items = dataset.entity_codes["userId"], dataset.entity_codes["itemId"]

    def ridge(X, t):
        return np.linalg.solve(X.T @ X + L2 * np.eye(X.shape[1]), X.T @ t)

    def side(solved, partner_rows, t, count):
        out = np.zeros((count, K))
        for e in range(count):
            m = solved == e
            if m.any():
                out[e] = ridge(partner_rows[m], t[m])
        return out

    w, bu, bi = np.zeros(D_FIXED + 1), np.zeros(USERS), np.zeros(ITEMS)
    p, q = p0.astype(np.float64), q0.astype(np.float64)
    objectives = []
    for _ in range(passes):
        mf = np.sum(p[users] * q[items], axis=1)
        w = ridge(x, r - bu[users] - bi[items] - mf)
        t = r - x @ w - bi[items] - mf
        bu = np.bincount(users, weights=t, minlength=USERS) / (
            np.bincount(users, minlength=USERS) + L2)
        t = r - x @ w - bu[users] - mf
        bi = np.bincount(items, weights=t, minlength=ITEMS) / (
            np.bincount(items, minlength=ITEMS) + L2)
        t = r - x @ w - bu[users] - bi[items]
        p = side(users, q[items], t, USERS)
        q = side(items, p[users], t, ITEMS)
        z = x @ w + bu[users] + bi[items] + np.sum(p[users] * q[items], axis=1)
        objectives.append(0.5 * np.sum((z - r) ** 2) + 0.5 * L2 * (
            w @ w + bu @ bu + bi @ bi + np.sum(p * p) + np.sum(q * q)))
    return w, bu, bi, p, q, objectives


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("mf")
    dataset, x = _ratings()
    coords, cd = _descent(tmp_path, dataset)
    reset_default_registry()
    with obs_trace.tracing_scope(True), jax.default_matmul_precision("highest"):
        obs_trace.tracer().clear()
        result = cd.run(2)
        spans = obs_trace.tracer().drain()
    return tmp_path, dataset, x, coords, result, spans


def test_two_passes_through_the_drivers_coordinates_match_plain_als(fitted):
    _, dataset, x, coords, result, _ = fitted
    assert list(coords) == ["global", "per-user", "per-item", "mf"]
    assert isinstance(coords["mf"], MatrixFactorizationCoordinate)
    start = coords["mf"].initialize_model()
    again = coords["mf"].initialize_model()
    # every run starts from the same factors: the coordinate's own seed
    assert np.array_equal(np.asarray(start.row_latent), np.asarray(again.row_latent))
    w, bu, bi, p, q, objectives = _plain_descent(
        dataset, x, np.asarray(start.row_latent), np.asarray(start.col_latent), 2)
    models = result.model.models
    got = (
        np.asarray(models["global"].model.coefficients.means),
        np.asarray(models["per-user"].bank)[:, 0],
        np.asarray(models["per-item"].bank)[:, 0],
        np.asarray(models["mf"].row_latent), np.asarray(models["mf"].col_latent),
    )
    for name, g, want in zip(
        ("global", "per-user", "per-item", "row", "col"), got, (w, bu, bi, p, q)
    ):
        np.testing.assert_allclose(g, want, atol=1e-5, err_msg=name)
    np.testing.assert_allclose(result.objective_history, objectives, rtol=1e-6)
    assert objectives[1] < objectives[0]


def test_the_descent_names_its_half_steps(fitted):
    _, dataset, _, coords, _, spans = fitted
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    half = [s.attrs for s in by_name["mf.half_step"]]
    assert [(h["side"], h["entities"]) for h in half] == [
        ("row", USERS), ("col", ITEMS)] * 2
    assert all(h["classes"] >= 2 and h["sub_blocks"] >= h["classes"] for h in half)
    # K = 4: every class has more ratings an entity than features
    assert {h["kind"] for h in half} == {"primal_id"}
    ids = {s.span_id: s for s in spans}
    for s in by_name["mf.half_step"]:
        parent = ids[s.parent_id]
        assert parent.name == "cd.update" and parent.attrs["coordinate"] == "mf"
    dispatches = [s for s in by_name["bank.dispatch"]
                  if s.attrs.get("coordinate", "").startswith("mf_")]
    assert {s.attrs["coordinate"] for s in dispatches} == {"mf_row", "mf_col"}
    scores = by_name["mf.score"]
    assert all(ids[s.parent_id].name == "cd.score" for s in scores)
    solved = default_registry().counter("photon_bank_entities_total")
    assert solved.value(coordinate="mf_row", kind="primal_id") == 2 * USERS
    assert solved.value(coordinate="mf_col", kind="primal_id") == 2 * ITEMS
    assert solved.value(coordinate="per-user", kind="primal_id") == 2 * USERS
    slots = default_registry().counter("photon_mf_slots_total")
    n = dataset.num_rows
    for side in ("row", "col"):
        # counted once a structure build, not once a half-step
        assert slots.value(coordinate="mf", side=side, state="rating") == n
        assert slots.value(coordinate="mf", side=side, state="padding") > 0
    text = coords["mf"].problem._solvers.fused_for(
        "primal_id", "mf_row").lower(
            jnp.zeros((2, K)), jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, 4, 0), jnp.int32), jnp.zeros((2, 4, K)),
            jnp.zeros((2, 4)), jnp.zeros((2, 4)), jnp.zeros((2, 4)),
            jnp.float32(0), jnp.float32(1),
    ).as_text()
    assert "module @jit_bank_fused_mf_row " in text


def test_the_model_is_saved_and_read_back_with_equal_scores(fitted):
    tmp_path, dataset, _, _, result, _ = fitted
    out = str(tmp_path / "saved")
    save_game_model(result.model, dataset, out)
    loaded = load_game_model(out)
    assert set(loaded.matrix_factorizations) == {"mf"}
    np.testing.assert_allclose(
        np.asarray(loaded.score(dataset, TaskType.LINEAR_REGRESSION)),
        np.asarray(result.model.score(dataset)),
        atol=1e-5)


def test_mf_score_is_the_models_score_in_chunks(monkeypatch, rng):
    """ONE program, a chunk of rows a step; the last chunk is padded and
    a row without either entity scores 0."""
    n, R, C = 1000, 30, 20
    rows = rng.integers(0, R, size=n).astype(np.int32)
    cols = rng.integers(0, C, size=n).astype(np.int32)
    rows[::17], cols[::23] = -1, -1
    p = rng.normal(size=(R, K)).astype(np.float32)
    q = rng.normal(size=(C, K)).astype(np.float32)
    dataset = GameDataset(
        uids=[], labels=np.zeros(n, np.float32), offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32), shards={},
        entity_codes={"userId": rows, "itemId": cols}, entity_indexes={},
        num_real_rows=n,
    )
    model = MatrixFactorizationModel("userId", "itemId", jnp.asarray(p), jnp.asarray(q))
    want = np.where(
        (rows >= 0) & (cols >= 0),
        np.sum(p[np.maximum(rows, 0)] * q[np.maximum(cols, 0)], axis=1), 0.0)
    monkeypatch.setattr(game_model, "MF_SCORE_CHUNK", 128)
    got = model.score(dataset)
    assert got.shape == (n,)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    r_d, c_d = game_model.mf_device_codes(dataset, "userId", "itemId")
    assert r_d.shape == (8, 128)  # 1000 rows in 8 chunks, 24 padding rows
    np.testing.assert_allclose(
        np.asarray(mf_score(model.row_latent, model.col_latent, r_d, c_d))[:n],
        np.asarray(got))
    text = mf_score.lower(model.row_latent, model.col_latent, r_d, c_d).as_text()
    assert "module @jit_mf_score " in text
    # no [n, K] array: the widest the program holds is a chunk's rows
    assert f"tensor<{n}x{K}xf32>" not in text and f"tensor<1024x{K}xf32>" not in text
