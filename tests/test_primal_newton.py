"""The bank's PRIMAL dense Newton kind (game/random_effect.bank_primal):
the normal equations in the feature space, for an entity with more
samples than features. Against the dual kind and a plain numpy solve on
blocks both can run; the plan's choice on each side of capacity = local
dim; a heavy entity that runs primal and never sparse; a values override
over the dense budget split into sub-blocks equal to the whole solve."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.game.config import (
    ProjectorType,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    ValuesOverride,
    _bucket_solver,
)
from photon_ml_tpu.game.random_effect_data import (
    RandomEffectBucket,
    RandomEffectDataset,
)
from photon_ml_tpu.obs import trace as obs_trace
from photon_ml_tpu.obs.registry import default_registry
from photon_ml_tpu.ops.losses import LINEAR, LOGISTIC
from photon_ml_tpu.ops.spd_solve import MAX_LANE_DIM, solve_path
from photon_ml_tpu.optim.config import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)

L2 = RegularizationContext(RegularizationType.L2)


def _problem(loss=LINEAR, budget=2 << 30, reg_weight=0.7, **kw):
    return RandomEffectOptimizationProblem(
        loss, OptimizerConfig(max_iter=30, tolerance=1e-7), L2,
        reg_weight=reg_weight, dense_bytes_budget=budget, **kw,
    )


def _block(rng, E, S, D, k=None, identity=False, logistic=False):
    """One block of E entities at capacity S, the last rows of some
    entities padding (weight 0)."""
    k = D if identity else (k or min(D, 3))
    if identity:
        ix = np.tile(np.arange(D, dtype=np.int32), (E, S, 1))
    else:
        ix = np.stack([
            np.stack([rng.choice(D, size=k, replace=False) for _ in range(S)])
            for _ in range(E)
        ]).astype(np.int32)
    v = rng.normal(size=(E, S, k)).astype(np.float32)
    w = np.ones((E, S), np.float32)
    w[: E // 2, S - max(S // 4, 1):] = 0.0
    v = v * w[:, :, None]
    z = rng.normal(size=(E, S)).astype(np.float32)
    lab = (z > 0).astype(np.float32) if logistic else z
    off = (0.3 * rng.normal(size=(E, S))).astype(np.float32)
    return ix, v, lab, off, w


def _dense(ix, v, D):
    E, S, k = ix.shape
    X = np.zeros((E, S, D), np.float64)
    e, s = np.meshgrid(np.arange(E), np.arange(S), indexing="ij")
    for j in range(k):
        np.add.at(X, (e, s, ix[:, :, j]), v[:, :, j])
    return X


def _ridge(ix, v, lab, off, w, D, l2):
    """The exact ridge solution of each entity's rows, float64."""
    X = _dense(ix, v, D)
    out = []
    for X_e, y, o, w_e in zip(X, lab, off, w):
        A = X_e.T @ (w_e[:, None] * X_e) + l2 * np.eye(D)
        out.append(np.linalg.solve(A, X_e.T @ (w_e * (y - o))))
    return np.stack(out)


def _solve(kind, loss, ix, v, lab, off, w, D, l2, bank=None):
    solvers = _bucket_solver(
        loss, OptimizerConfig(max_iter=30, tolerance=1e-7), L2)
    bank = jnp.zeros((ix.shape[0], D), jnp.float32) if bank is None else bank
    with jax.default_matmul_precision("highest"):
        out, iters, reasons = getattr(solvers, kind)(
            bank, *(jnp.asarray(a) for a in (ix, v, lab, off, w)),
            jnp.float32(0.0), jnp.float32(l2),
        )
    return np.asarray(out), np.asarray(iters), np.asarray(reasons)


@pytest.mark.parametrize("identity", [False, True], ids=["indexed", "identity"])
@pytest.mark.parametrize("S,D", [(12, 4), (40, 5), (6, 6)])
def test_squared_loss_primal_is_the_ridge_solution_and_the_duals(rng, identity, S, D):
    ix, v, lab, off, w = _block(rng, 7, S, D, identity=identity)
    suffix = "_id" if identity else ""
    want = _ridge(ix, v, lab, off, w, D, 0.7)
    primal, iters, _ = _solve("primal" + suffix, LINEAR, ix, v, lab, off, w, D, 0.7)
    dual, _, _ = _solve("newton" + suffix, LINEAR, ix, v, lab, off, w, D, 0.7)
    np.testing.assert_allclose(primal, want, atol=2e-5)
    np.testing.assert_allclose(primal, dual, atol=5e-5)
    # a squared loss is solved by the first step and stops on the second
    assert iters.max() <= 2


@pytest.mark.parametrize("identity", [False, True], ids=["indexed", "identity"])
def test_logistic_loss_primal_agrees_with_dual(rng, identity):
    S, D = 24, 5
    ix, v, lab, off, w = _block(rng, 9, S, D, identity=identity, logistic=True)
    suffix = "_id" if identity else ""
    primal, it_p, re_p = _solve("primal" + suffix, LOGISTIC, ix, v, lab, off, w, D, 0.5)
    dual, it_d, re_d = _solve("newton" + suffix, LOGISTIC, ix, v, lab, off, w, D, 0.5)
    # (an entity may stop an iteration apart, on the function-value test)
    np.testing.assert_allclose(primal, dual, atol=1e-3)
    assert np.abs(it_p - it_d).max() <= 1
    # and the optimum: the gradient vanishes
    X = _dense(ix, v, D)
    z = np.einsum("esd,ed->es", X, primal) + off
    g = np.einsum("esd,es->ed", X, w * (1 / (1 + np.exp(-z)) - lab)) + 0.5 * primal
    assert np.abs(g).max() < 1e-3


def test_primal_from_a_warm_start_lands_where_the_cold_one_does(rng):
    ix, v, lab, off, w = _block(rng, 5, 16, 3)
    cold, _, _ = _solve("primal", LINEAR, ix, v, lab, off, w, 3, 0.7)
    warm, _, _ = _solve(
        "primal", LINEAR, ix, v, lab, off, w, 3, 0.7,
        bank=jnp.asarray(rng.normal(size=(5, 3)).astype(np.float32)),
    )
    np.testing.assert_allclose(warm, cold, atol=2e-5)


@pytest.mark.parametrize("shape,plan", [
    # (entities, capacity, local dim, identity) -> (kind, entities a program)
    ((100, 16, 16, False), ("newton", (2 << 30) // ((16 * 16 + 16 * 16) * 4))),
    ((100, 17, 16, False), ("primal", (2 << 30) // ((17 * 16 + 16 * 16) * 4))),
    ((100, 15, 16, False), ("newton", (2 << 30) // ((15 * 16 + 15 * 15) * 4))),
    ((100, 64, 64, True), ("newton_id", (2 << 30) // (64 * 64 * 4))),
    # identity or not, the primal kind is charged the X it holds
    ((100, 128, 64, True), ("primal_id", (2 << 30) // ((128 * 64 + 64 * 64) * 4))),
    # the regimes of the cells: 16 rows of 1,000 features, a bias over a
    # heavy user's rows, a rank-64 factor over the heaviest movie's
    ((32768, 16, 1000, False), ("newton", 33026)),
    ((1, 16384, 1, False), ("primal", (2 << 30) // ((16384 + 1) * 4))),
    ((1, 131072, 64, True), ("primal_id", 63)),
])
def test_the_plan_picks_primal_where_capacity_exceeds_the_local_dim(shape, plan):
    assert _problem().dense_block_plan(*shape) == plan


def test_no_newton_no_primal():
    """The primal kind is a Newton kind: where the dual one may not run
    (no L2, TRON), the plain dense solver runs as before."""
    no_l2 = RandomEffectOptimizationProblem(
        LINEAR, OptimizerConfig(), RegularizationContext(), reg_weight=0.0)
    assert no_l2.dense_block_plan(10, 64, 4, False)[0] == "dense"
    tron = RandomEffectOptimizationProblem(
        LINEAR, OptimizerConfig(optimizer_type=OptimizerType.TRON), L2,
        reg_weight=1.0)
    assert tron.dense_block_plan(10, 64, 4, True)[0] == "dense_id"
    assert _problem(layout="sparse").dense_block_plan(10, 64, 4, False) == (
        "sparse", 10)


def _dataset(buckets, num_entities, D, n_rows):
    codes = np.full(n_rows, -1, np.int32)
    for b in buckets:
        ok = b.row_index >= 0
        codes[b.row_index[ok]] = np.broadcast_to(
            b.entity_codes[:, None], ok.shape)[ok]
    return RandomEffectDataset(
        config=RandomEffectDataConfiguration(
            random_effect_type="e", feature_shard_id="s",
            projector_type=ProjectorType.IDENTITY),
        num_entities=num_entities, local_dim=D,
        projection=np.tile(np.arange(D, dtype=np.int32), (num_entities, 1)),
        row_local_indices=np.zeros((0, D), np.int32),
        row_local_values=np.zeros((0, D), np.float32),
        row_entity_codes=codes, buckets=buckets,
        num_active_rows=int((codes >= 0).sum()), num_passive_rows=0,
    )


def test_a_heavy_entity_runs_primal_and_never_sparse(rng):
    """One entity at capacity 4,096 over 8 features: the dual kind's Gram
    would be 64 MiB an entity and under a 1 MiB budget nothing dual fits;
    the primal kind holds 128 KiB of X and an 8 x 8 system."""
    S, D = 4096, 8
    ix, v, lab, off, w = _block(rng, 2, S, D, k=3)
    rows = np.arange(2 * S, dtype=np.int32).reshape(2, S)
    bucket = RandomEffectBucket(
        entity_codes=np.arange(2, dtype=np.int32), row_index=rows,
        indices=ix, values=v, labels=lab, offsets=off, weights=w)
    ds = _dataset([bucket], 2, D, 2 * S)
    problem = _problem(budget=1 << 20)
    assert problem.dense_block_plan(2, S, D, False) == ("primal", 7)
    assert problem._bucket_kind(bucket, D) == "primal"
    solved = default_registry().counter("photon_bank_entities_total")
    before = solved.value(coordinate="heavy", kind="primal")
    with jax.default_matmul_precision("highest"):
        bank, tracker = problem.update_bank(
            jnp.zeros((2, D), jnp.float32), ds, coordinate="heavy")
    assert solved.value(coordinate="heavy", kind="primal") == before + 2
    assert solved.value(coordinate="heavy", kind="sparse") == 0
    np.testing.assert_allclose(
        np.asarray(bank), _ridge(ix, v, lab, off, w, D, 0.7), atol=2e-5)
    assert tracker.iterations_max <= 2


def _take_rows(latent, keys):
    return jnp.where(
        (keys >= 0)[..., None], jnp.take(latent, jnp.maximum(keys, 0), axis=0), 0.0)


def _override_dataset(rng, E, S, K, partners):
    """An ALS-like view: each slot's values are a partner's factor row."""
    keys = rng.integers(0, partners, size=(E, S)).astype(np.int32)
    keys[: E // 2, S - S // 4:] = -1
    ok = keys >= 0
    rows = np.where(ok, np.arange(E * S, dtype=np.int32).reshape(E, S), -1)
    bucket = RandomEffectBucket(
        entity_codes=np.arange(E, dtype=np.int32), row_index=rows,
        indices=np.zeros((E, S, 0), np.int32),
        values=np.zeros((E, S, 0), np.float32),
        labels=np.where(ok, rng.normal(size=(E, S)), 0.0).astype(np.float32),
        offsets=np.zeros((E, S), np.float32),
        weights=ok.astype(np.float32), identity_indices=True,
        override_keys=keys,
    )
    return _dataset([bucket], E, K, E * S), bucket


def test_an_override_bucket_over_the_budget_splits_into_equal_sub_blocks(rng):
    """The values override reaches the split of a bucket over the dense
    budget: the sub-blocks' values are made inside ONE scanned program, a
    sub-block at a time, and the bank equals the whole solve's."""
    E, S, K, partners = 23, 16, 4, 9
    ds, bucket = _override_dataset(rng, E, S, K, partners)
    latent = jnp.asarray(rng.normal(size=(partners, K)).astype(np.float32))
    residual = jnp.asarray((0.2 * rng.normal(size=E * S)).astype(np.float32))
    bank0 = jnp.asarray(rng.normal(size=(E, K)).astype(np.float32))
    per_entity = (S * K + K * K) * 4
    whole, split = _problem(), _problem(budget=6 * per_entity)
    assert [
        (b.kind, b.sub_blocks, b.num_real)
        for b in split._solver_blocks(ds, K, split=True)
    ] == [("primal_id", 4, 6)] * 3 + [("primal_id", 4, 5)]
    assert [b.kind for b in whole._solver_blocks(ds, K, split=True)] == [
        "primal_id"]
    override = ValuesOverride(_take_rows, latent)
    with obs_trace.tracing_scope(True), jax.default_matmul_precision("highest"):
        obs_trace.tracer().clear()
        got, _ = split.update_bank(
            bank0, ds, residual_offsets=residual,
            values_override=override, coordinate="mf_row")
        want, _ = whole.update_bank(
            bank0, ds, residual_offsets=residual,
            values_override=override, coordinate="mf_row")
        dispatches = [
            s.attrs for s in obs_trace.tracer().drain()
            if s.name == "bank.dispatch"
        ]
    assert [(d["kind"], d["sub_blocks"], d["entities"]) for d in dispatches] == [
        ("primal_id", 4, E), ("primal_id", 1, E)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # and both are the exact ridge solution on the gathered factors
    X = np.asarray(_take_rows(latent, jnp.asarray(bucket.override_keys)))
    off = np.where(
        bucket.row_index >= 0, np.asarray(residual)[np.maximum(bucket.row_index, 0)], 0.0)
    ix = np.tile(np.arange(K, dtype=np.int32), (E, S, 1))
    np.testing.assert_allclose(
        np.asarray(want),
        _ridge(ix, X, bucket.labels, off, bucket.weights, K, 0.7), atol=2e-5)
    # the scanned program's module carries the coordinate's name
    text = split._solvers.fused_for(
        "primal_id", "mf_row", scan=True, values_of=_take_rows
    ).lower(
        bank0, *split._stacked_group_args(
            ds, split._solver_blocks(ds, K, split=True),
            with_residuals=True, with_values=False)[:4],
        jnp.zeros((4, 6, S)), jnp.zeros((4, 6, S)),
        jnp.float32(0.0), jnp.float32(0.7), latent,
    ).as_text()
    assert "jit_bank_fused_scan_mf_row" in text


def test_an_override_on_the_sparse_layout_still_solves(rng):
    """An identity block under an override holds no indices; the sparse
    solver, which reads them, is handed the tiled arange."""
    E, S, K, partners = 6, 8, 3, 5
    ds, bucket = _override_dataset(rng, E, S, K, partners)
    latent = jnp.asarray(rng.normal(size=(partners, K)).astype(np.float32))
    override = ValuesOverride(_take_rows, latent)
    bank0 = jnp.zeros((E, K), jnp.float32)
    with jax.default_matmul_precision("highest"):
        sparse, _ = _problem(layout="sparse").update_bank(
            bank0, ds, values_override=override)
        dense, _ = _problem().update_bank(bank0, ds, values_override=override)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense), atol=5e-4)


def test_a_rank_64_half_step_block_is_the_ridge_solution_in_two_iterations(rng):
    """The half-step's own shape: 64 features at capacity 256, squared
    loss: the first Newton step solves it, the second confirms it."""
    S, D = 256, 64
    ix, v, lab, off, w = _block(rng, 5, S, D, identity=True)
    got, iters, _ = _solve("primal_id", LINEAR, ix, v, lab, off, w, D, 0.7)
    np.testing.assert_allclose(
        got, _ridge(ix, v, lab, off, w, D, 0.7), atol=1e-5)
    assert (iters == 2).all()


@pytest.mark.parametrize("dim,platform,path", [
    (1, "tpu", "division"), (1, "cpu", "division"),
    (2, "tpu", "lanes"), (64, "tpu", "lanes"), (MAX_LANE_DIM, "tpu", "lanes"),
    (MAX_LANE_DIM + 1, "tpu", "xla"), (1000, "tpu", "xla"),
    (2, "cpu", "xla"), (64, "cpu", "xla"),
])
def test_the_shape_and_the_platform_name_the_way_a_batch_is_solved(
        dim, platform, path):
    assert solve_path(dim, platform) == path


def test_the_kernels_bound_holds_twice_the_half_steps_rank():
    assert MAX_LANE_DIM >= 128 and MAX_LANE_DIM % 8 == 0


def _primal_bucket(rng, E, S, D):
    ix, v, lab, off, w = _block(rng, E, S, D)
    rows = np.arange(E * S, dtype=np.int32).reshape(E, S)
    return RandomEffectBucket(
        entity_codes=np.arange(E, dtype=np.int32), row_index=rows,
        indices=ix, values=v, labels=lab, offsets=off, weights=w)


def test_the_primal_systems_are_counted_under_the_solve_that_ran(rng):
    """``photon_bank_primal_systems_total``: a primal block's entities
    under the way their systems were solved (XLA's Cholesky on the CPU
    these tests run on), the span saying the same; a bias (D = 1) has no
    system to factor and counts nothing."""
    systems = default_registry().counter("photon_bank_primal_systems_total")

    def counted(coordinate):
        return {
            solve: systems.value(coordinate=coordinate, solve=solve)
            for solve in ("lanes", "xla", "division")
        }

    S = 32
    for coordinate, D, want in [
        ("factor", 4, {"lanes": 0, "xla": 3, "division": 0}),
        ("bias", 1, {"lanes": 0, "xla": 0, "division": 0}),
    ]:
        ds = _dataset([_primal_bucket(rng, 3, S, D)], 3, D, 3 * S)
        assert _problem()._bucket_kind(ds.buckets[0], D) == "primal"
        before = counted(coordinate)
        with obs_trace.tracing_scope(True):
            obs_trace.tracer().clear()
            _problem().update_bank(
                jnp.zeros((3, D), jnp.float32), ds, coordinate=coordinate)
            (dispatch,) = [
                s.attrs for s in obs_trace.tracer().drain()
                if s.name == "bank.dispatch"
            ]
        after = counted(coordinate)
        assert {k: after[k] - before[k] for k in after} == want
        assert dispatch.get("solve") == ("xla" if D > 1 else None)
