"""Unified (data × feature × entity × grid) mesh (parallel/unified_mesh.py
+ game/unified.py): a λ-grid sweep over an entity-sharded GAME model as
ONE shard_mapped program.

Parity matrix pinned here (ISSUE 20):

- unified grid CD == per-λ pod CD on the SAME entity shard count
  (objectives ~1e-6 relative; banks inside the pod fp32 envelopes);
- unified grid CD == per-λ replicated CD at N ∈ {1, 2, 4, 8} entity
  shards — the entity axis is a layout choice, not a math change;
- FixedEffectCoordinate.update_model_grid on the (data, model) mesh ==
  the cold sequential feature-sharded sweep, with and without
  down-sampling (λ-independent draw, one shared weight rewrite);
- duplicate-λ members stay BITWISE identical — the batched while_loop
  freeze mask never lets a converged member's rows drift;
- contracts: ONE batched readback per CD iteration, ZERO relowerings on
  a warmed same-shape run, and the SHARDING.md entry-point inventory is
  strictly below the pre-unification count (38; at most 32 now) — the
  unified program REPLACED per-combination entry points instead of
  adding more.

The streaming × sharded leg is covered transitively rather than by a
direct pairing: test_streaming_game.TestStreamingGameParity pins
streamed CD == in-memory CD, test_pod_game pins sharded CD ==
replicated CD and streamed × sharded == streamed × replicated through
the training driver, and this file pins unified == pod CD — the chain
closes without a bespoke streaming oracle.
"""

import os
import re
import sys

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from photon_ml_tpu.game.coordinate import (
    FixedEffectCoordinate,
    PodRandomEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
from photon_ml_tpu.game.pod import EntityShardSpec
from photon_ml_tpu.game.unified import GridShardedREBank, run_game_grid
from photon_ml_tpu.optim.config import OptimizerConfig, OptimizerType
from photon_ml_tpu.optim.problem import create_glm_problem
from photon_ml_tpu.parallel import overlap
from photon_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    ENTITY_AXIS,
    GRID_AXIS,
    MODEL_AXIS,
    entity_mesh,
    make_mesh,
)
from photon_ml_tpu.parallel.unified_mesh import resolve_mesh
from photon_ml_tpu.reliability.checkpoint import GridCheckpointer
from photon_ml_tpu.task import TaskType
from photon_ml_tpu import training

sys.path.insert(0, os.path.dirname(__file__))

from test_pod_game import _problem, _synthetic_re  # noqa: E402

LAMBDAS = [0.1, 0.5, 1.0, 2.0]
TASK = TaskType.LOGISTIC_REGRESSION


@pytest.fixture(scope="module")
def game_data():
    """Shared small GAME dataset + FE problem + per-λ replicated oracle
    cache (the replicated CD baseline is λ-keyed and reused across the
    entity-shard parametrization)."""
    ds, red = _synthetic_re(n=96, E=11)
    fe_problem = create_glm_problem(
        TASK, ds.shards["s"].dim, config=OptimizerConfig(max_iter=5)
    )
    cache = {}

    def replicated_ref(lam):
        if lam not in cache:
            coords = {
                "fixed": FixedEffectCoordinate(
                    name="fixed", dataset=ds, problem=fe_problem,
                    feature_shard_id="s", reg_weight=0.1,
                ),
                "per-user": RandomEffectCoordinate(
                    name="per-user", dataset=ds, re_dataset=red,
                    problem=_problem(reg_weight=lam),
                ),
            }
            cache[lam] = CoordinateDescent(coords, ds, TASK).run(2)
        return cache[lam]

    return ds, red, fe_problem, replicated_ref


def _run_unified(game_data, n_ent, lambdas=LAMBDAS, num_iterations=2,
                 **kw):
    ds, red, fe_problem, _ = game_data
    plan = resolve_mesh(grid_size=len(lambdas), entity_shards=n_ent)
    res = run_game_grid(
        plan, ds, red, fe_problem, _problem(), lambdas,
        feature_shard_id="s", fe_reg_weight=0.1,
        num_iterations=num_iterations, **kw,
    )
    return plan, res


# ---------------------------------------------------------------------------
# mesh-shape policy
# ---------------------------------------------------------------------------


class TestResolveMesh:
    def test_prefers_divisor_rows(self):
        # 8 devices, N=2 -> 4 usable rows; G=6 -> 3 divides, 4 doesn't.
        plan = resolve_mesh(grid_size=6, entity_shards=2)
        assert plan.grid_rows == 3
        assert plan.members_per_row == 2
        assert plan.grid_padded == 6  # no padding members
        assert tuple(plan.mesh.axis_names) == (GRID_AXIS, ENTITY_AXIS)
        assert plan.mesh.devices.shape == (3, 2)

    def test_prime_grid_falls_to_one_row(self):
        # N=4 -> 2 usable rows; G=7 is prime above 2, and 1 always
        # divides, so the policy takes 1 row x 7 members over padding.
        plan = resolve_mesh(grid_size=7, entity_shards=4)
        assert (plan.grid_rows, plan.members_per_row) == (1, 7)
        assert plan.grid_padded == 7
        padded = plan.pad_members(LAMBDAS)
        assert len(padded) == 7 and padded[4:] == [LAMBDAS[-1]] * 3

    def test_entity_shards_minus_one_takes_all_devices(self):
        plan = resolve_mesh(grid_size=4, entity_shards=-1)
        assert plan.entity_shards == len(jax.devices())
        assert plan.grid_rows == 1

    def test_per_device_accounting(self):
        per_member = 1000
        plan = resolve_mesh(
            grid_size=8, entity_shards=2, member_bank_bytes=per_member,
            budget=10_000,
        )
        # 4 rows x 2 members/row, each device holds 2 members / 2 shards
        assert plan.per_device_bank_bytes == (
            plan.members_per_row * per_member // plan.entity_shards
        )
        assert plan.fits_budget
        tight = resolve_mesh(
            grid_size=8, entity_shards=2, member_bank_bytes=per_member,
            budget=plan.per_device_bank_bytes - 1,
        )
        assert not tight.fits_budget

    def test_sharding_spec(self):
        plan = resolve_mesh(grid_size=4, entity_shards=2)
        assert plan.grid_entity_sharding().spec == P(GRID_AXIS, ENTITY_AXIS)

    def test_bad_args(self):
        with pytest.raises(ValueError):
            resolve_mesh(grid_size=0)
        with pytest.raises(ValueError):
            resolve_mesh(grid_size=2, entity_shards=99)
        with pytest.raises(ValueError):
            resolve_mesh(grid_size=2, feature_blocks=0)
        with pytest.raises(ValueError):
            resolve_mesh(grid_size=2).pad_members([])

    def test_grid_bank_bytes_entity_sharded(self):
        total = training.grid_bank_bytes(4, 64)
        for n in (2, 4, 8):
            per_dev = training.grid_bank_bytes(4, 64, entity_shards=n)
            assert per_dev == -(-total // n)  # ceil(total / N)

    def test_resolve_grid_mode_uses_per_device_figure(self):
        # A grid too big for the replicated budget fits once the bank
        # rows split over 8 entity shards.
        kw = dict(
            num_weights=16, dim=4096,
            optimizer_type=OptimizerType.LBFGS,
        )
        budget = training.grid_bank_bytes(16, 4096) // 4
        assert training.resolve_grid_mode(
            "auto", memory_budget_bytes=budget, **kw
        ) == "sequential"
        assert training.resolve_grid_mode(
            "auto", memory_budget_bytes=budget, entity_shards=8, **kw
        ) == "batched"


# ---------------------------------------------------------------------------
# grid-sharded bank
# ---------------------------------------------------------------------------


class TestGridBank:
    def test_zeros_layout_and_per_device_bytes(self):
        plan = resolve_mesh(grid_size=4, entity_shards=2)
        spec = EntityShardSpec(2, 11)
        bank = GridShardedREBank.zeros(
            plan.mesh, spec, 4, plan.grid_padded, 12
        )
        assert bank.data.shape == (plan.grid_padded, spec.bank_rows, 12)
        assert bank.data.sharding.spec == P(GRID_AXIS, ENTITY_AXIS)
        total = bank.data.size * 4
        per_dev = bank.per_device_bytes()
        assert per_dev <= total // (plan.grid_rows * plan.entity_shards)

    def test_member_globals_round_trip(self):
        plan = resolve_mesh(grid_size=3, entity_shards=2)
        spec = EntityShardSpec(2, 7)
        rng = np.random.default_rng(0)
        members = [
            rng.normal(size=(7, 5)).astype(np.float32) for _ in range(3)
        ]
        bank = GridShardedREBank.from_member_globals(
            plan.mesh, spec, 3, plan.pad_members(members)
        )
        for g in range(3):
            np.testing.assert_array_equal(
                np.asarray(bank.member_global(g)), members[g]
            )
        # padding member duplicates the last λ's rows
        assert bank.grid_padded >= 3

    def _trained_like_bank(self):
        """A non-trivial grid bank without a training run (the
        checkpoint plane only cares about bytes and placement)."""
        plan = resolve_mesh(grid_size=3, entity_shards=2)
        spec = EntityShardSpec(2, 11)
        rng = np.random.default_rng(7)
        members = [
            rng.normal(size=(11, 4)).astype(np.float32) for _ in range(3)
        ]
        return GridShardedREBank.from_member_globals(
            plan.mesh, spec, 3, plan.pad_members(members)
        )

    def test_snapshot_restore_is_bitwise_and_resharded(self, tmp_path):
        bank = self._trained_like_bank()
        ck = GridCheckpointer(str(tmp_path), {"cfg": 1})
        ck.save_grid_bank("re", bank.snapshot(), bank.layout())
        assert ck.has_grid_bank("re")
        loaded, layout = ck.load_grid_bank(
            "re", expect_layout=bank.layout()
        )
        assert layout == {k: int(v) for k, v in bank.layout().items()}
        restored = GridShardedREBank.restore(
            bank.mesh, bank.spec, bank.grid_size, loaded
        )
        np.testing.assert_array_equal(
            np.asarray(restored.data), np.asarray(bank.data)
        )
        # restore re-shards DEVICE-side back onto P(grid, entity) —
        # never a host [E, d] gather (PL012 guards the export scopes).
        assert restored.data.sharding.spec == P(GRID_AXIS, ENTITY_AXIS)

    def test_restore_guards_layout_and_shape(self, tmp_path):
        bank = self._trained_like_bank()
        ck = GridCheckpointer(str(tmp_path), {"cfg": 1})
        ck.save_grid_bank("re", bank.snapshot(), bank.layout())
        bad = dict(bank.layout())
        bad["num_shards"] = 99
        with pytest.raises(ValueError, match="num_shards"):
            ck.load_grid_bank("re", expect_layout=bad)
        with pytest.raises(ValueError, match="does not match"):
            GridShardedREBank.restore(
                bank.mesh, bank.spec, bank.grid_size,
                bank.snapshot()[:, :-1, :],
            )

    def test_missing_snapshot_is_none(self, tmp_path):
        ck = GridCheckpointer(str(tmp_path), {"cfg": 1})
        assert not ck.has_grid_bank("nope")
        assert ck.load_grid_bank("nope") is None


# ---------------------------------------------------------------------------
# parity matrix
# ---------------------------------------------------------------------------


class TestUnifiedParity:
    @pytest.mark.parametrize("n_ent", [1, 2, 4, 8])
    def test_matches_replicated_cd(self, game_data, n_ent):
        """One unified program at N entity shards == the per-λ
        replicated CD oracle. The entity axis is a layout choice."""
        _, _, _, replicated_ref = game_data
        _, res = _run_unified(game_data, n_ent)
        for gi, lam in enumerate(LAMBDAS):
            ref = replicated_ref(lam)
            got = [h[gi] for h in res.objective_history]
            np.testing.assert_allclose(
                got, ref.objective_history, rtol=1e-4,
                err_msg=f"lambda={lam} n_ent={n_ent}",
            )
            np.testing.assert_allclose(
                np.asarray(res.re_bank.member_global(gi)),
                np.asarray(ref.model.models["per-user"].bank),
                atol=2e-3, rtol=2e-3, err_msg=f"lambda={lam}",
            )
            np.testing.assert_allclose(
                np.asarray(res.fe_means(gi)),
                np.asarray(ref.model.models["fixed"].model.means),
                atol=2e-3, rtol=2e-3, err_msg=f"lambda={lam}",
            )

    def test_matches_pod_cd(self, game_data):
        """Tightest pairing: the unified grid against per-λ pod CD on
        the SAME entity mesh — identical routing, hash placement and
        reduction order, so objectives agree to ~1e-6 relative."""
        ds, red, fe_problem, _ = game_data
        _, res = _run_unified(game_data, n_ent=2)
        for gi, lam in enumerate(LAMBDAS):
            coords = {
                "fixed": FixedEffectCoordinate(
                    name="fixed", dataset=ds, problem=fe_problem,
                    feature_shard_id="s", reg_weight=0.1,
                ),
                "per-user": PodRandomEffectCoordinate(
                    name="per-user", dataset=ds, re_dataset=red,
                    problem=_problem(reg_weight=lam),
                    mesh=entity_mesh(2),
                ),
            }
            ref = CoordinateDescent(coords, ds, TASK).run(2)
            got = [h[gi] for h in res.objective_history]
            np.testing.assert_allclose(
                got, ref.objective_history, rtol=2e-4,
                err_msg=f"lambda={lam}",
            )
            np.testing.assert_allclose(
                np.asarray(res.re_bank.member_global(gi)),
                np.asarray(ref.model.models["per-user"].bank),
                atol=2e-3, rtol=2e-3, err_msg=f"lambda={lam}",
            )

    def test_duplicate_lambda_members_bitwise_identical(self, game_data):
        """Freeze-mask bit-stability: two members with the SAME λ run
        the same masked while_loop iterates, so their banks and
        objective columns are BITWISE equal — a converged member's rows
        cannot drift under other members' continued iterations."""
        _, res = _run_unified(game_data, n_ent=2,
                              lambdas=[0.5, 0.5, 2.0, 0.5])
        for h in res.objective_history:
            assert float(h[0]) == float(h[1]) == float(h[3])
        b0 = np.asarray(res.re_bank.member_global(0))
        np.testing.assert_array_equal(
            b0, np.asarray(res.re_bank.member_global(1))
        )
        np.testing.assert_array_equal(
            b0, np.asarray(res.re_bank.member_global(3))
        )
        np.testing.assert_array_equal(
            np.asarray(res.fe_means(0)), np.asarray(res.fe_means(1))
        )


# ---------------------------------------------------------------------------
# feature-sharded FE grid inside the GAME coordinate
# ---------------------------------------------------------------------------


class TestFeatureShardedGridCoordinate:
    def _coord(self, ds, fe_problem, mesh=None, **kw):
        return FixedEffectCoordinate(
            name="fixed", dataset=ds, problem=fe_problem,
            feature_shard_id="s", mesh=mesh, **kw,
        )

    def test_grid_matches_cold_sequential(self, game_data):
        """update_model_grid on the (data, model) mesh == one cold
        feature-sharded solve per λ."""
        ds, _, fe_problem, _ = game_data
        mesh = make_mesh((4, 2), (DATA_AXIS, MODEL_AXIS))
        grid = self._coord(ds, fe_problem, mesh).update_model_grid(LAMBDAS)
        assert len(grid) == len(LAMBDAS)
        for lam, (model, result) in zip(LAMBDAS, grid):
            seq_model, seq_result = self._coord(
                ds, fe_problem, mesh, reg_weight=lam
            ).update_model(None)
            assert float(result.value) == pytest.approx(
                float(seq_result.value), rel=1e-5
            ), lam
            np.testing.assert_allclose(
                np.asarray(model.model.means),
                np.asarray(seq_model.model.means),
                atol=1e-3, err_msg=f"lambda={lam}",
            )

    def test_down_sampled_grid_matches_sequential_sampled(self, game_data):
        """Down-sampling composes with the grid solve: the draw is
        λ-independent (same PRNG stream as the sequential path), so the
        whole grid solves against the same sampled batch."""
        ds, _, fe_problem, _ = game_data
        kw = dict(down_sampling_rate=0.7, sampler_seed=3)
        grid = self._coord(ds, fe_problem, **kw).update_model_grid(LAMBDAS)
        for lam, (model, result) in zip(LAMBDAS, grid):
            seq_model, seq_result = self._coord(
                ds, fe_problem, reg_weight=lam, **kw
            ).update_model(None)
            assert float(result.value) == pytest.approx(
                float(seq_result.value), rel=1e-5
            ), lam
            np.testing.assert_allclose(
                np.asarray(model.model.means),
                np.asarray(seq_model.model.means),
                atol=1e-3, err_msg=f"lambda={lam}",
            )


# ---------------------------------------------------------------------------
# program contracts
# ---------------------------------------------------------------------------


class TestUnifiedContracts:
    def test_one_batched_readback_per_iteration(self, game_data):
        """The whole G-member sweep costs ONE device->host readback per
        CD iteration — the per-iteration objective vector (and deferred
        tracker stats) travel in a single overlap.fetch_all."""
        with overlap.overlap_scope(True):
            overlap.reset_readback_stats()
            _run_unified(game_data, n_ent=2, num_iterations=3)
            assert overlap.readback_stats() == 3

    def test_zero_relowerings_when_warm(self, game_data):
        """A warmed same-shape run lowers NOTHING: every program in the
        unified sweep (route/update/score/objective) is cached at
        module scope, so iteration count and λ values are data."""
        import jax._src.test_util as jtu

        _run_unified(game_data, n_ent=2, num_iterations=1)  # warm
        with jtu.count_jit_and_pmap_lowerings() as count:
            _run_unified(game_data, n_ent=2,
                         lambdas=[0.2, 0.7, 1.5, 3.0], num_iterations=2)
        assert count() == 0, count()

    def test_sharding_inventory_shrank(self):
        """SUBTRACTIVE success metric: the unified program REPLACED
        per-combination entry points (five distributed fit builders
        collapsed to wrappers, fit/hdiag variants merged), so the PL011
        SPMD entry-point inventory lands strictly below the
        pre-unification count of 38 (36 then; 32 since ISSUE 31 deleted
        the entry points no driver reached). The pin only goes down."""
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "SHARDING.md")) as f:
            text = f.read()
        m = re.search(r"(\d+) entry point\(s\)\.", text)
        assert m, "SHARDING.md inventory line missing"
        assert int(m.group(1)) <= 32, m.group(0)
        assert "photon_ml_tpu/game/unified.py" in text
