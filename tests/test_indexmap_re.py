"""GLMix on the reference's default random-effect path: INDEX_MAP
projection, the reservoir cap with its ``count / cap`` weight, passive rows
scored through each entity's own map; against the plain reference
``benchmark/reference_indexmap.py`` (float32 ``jax.numpy``), at a small
size on the CPU.

Two levels: a hand-made table whose members are chosen to cover the
cases (over the cap, under it, one row, exactly at it, and passive rows
whose every feature lies outside the member's map), solved by the
program's own bank update; and the benchmark's cell
``glmix-ads-indexmap-131k.cd`` at a tiny size through its entry (the GAME
driver's coordinates, one ``CoordinateDescent``), its judged numbers, the
controls that have to fail them, and a warm descent that lowers nothing.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.game.config import (
    ProjectorType,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.game.coordinate import _count_scored_rows
from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    score_plan,
    score_random_effect,
)
from photon_ml_tpu.game.random_effect_data import build_random_effect_dataset
from photon_ml_tpu.obs.registry import default_registry
from photon_ml_tpu.ops.losses import LOGISTIC
from photon_ml_tpu.optim.config import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.utils.index_map import IdentityIndexMap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference  # noqa: E402
from benchmark import reference_indexmap as ri  # noqa: E402

FEATURES = 40  # the member shard's features; the intercept is id 40
INTERCEPT = FEATURES
CAP = 4
L2 = 1.0
MAX_ITER, TOL = 20, 1e-5
# rows a member: over the cap, under it, one row, at it, and over it with
# every row on features of its own (its passive rows name none of the
# features its active rows do)
ROWS = {"over": 11, "under": 3, "single": 1, "at": 4, "apart": 6}
MEMBERS = list(ROWS)


def _table(seed=0, projector=ProjectorType.INDEX_MAP, cap=CAP):
    """A member shard of 5 features and the intercept a row, the rows of
    all members shuffled together; the program's dataset over it."""
    rng = np.random.default_rng(seed)
    member_of_row = np.repeat(
        np.arange(len(ROWS), dtype=np.int32), list(ROWS.values()))
    ids = np.stack([
        rng.choice(20, 5, replace=False) for _ in member_of_row
    ]).astype(np.int32)
    apart = MEMBERS.index("apart")
    at = np.nonzero(member_of_row == apart)[0]
    ids[at] = 5 * np.arange(len(at))[:, None] + np.arange(5)[None, :]
    ix = np.concatenate([ids, np.full((len(ids), 1), INTERCEPT, np.int32)], 1)
    v = rng.normal(size=ix.shape).astype(np.float32)
    v[:, -1] = 1.0
    order = rng.permutation(len(ids))
    member_of_row, ix, v = member_of_row[order], ix[order], v[order]
    n = len(member_of_row)
    labels = (rng.uniform(size=n) < 0.5).astype(np.float32)
    ds = GameDataset(
        uids=[str(i) for i in range(n)], labels=labels,
        offsets=np.zeros(n, np.float32), weights=np.ones(n, np.float32),
        shards={"member": ShardData(
            ix, v, IdentityIndexMap(FEATURES, add_intercept=True), INTERCEPT)},
        entity_codes={"memberId": member_of_row},
        entity_indexes={"memberId": EntityIndex.build("memberId", MEMBERS)},
        num_real_rows=n,
    )
    red = build_random_effect_dataset(ds, RandomEffectDataConfiguration(
        "memberId", "member", active_data_upper_bound=cap,
        projector_type=projector,
    ), seed=seed)
    return ds, red, rng.normal(size=n).astype(np.float32)


def _problem():
    return RandomEffectOptimizationProblem(
        LOGISTIC, OptimizerConfig(max_iter=MAX_ITER, tolerance=TOL),
        RegularizationContext(RegularizationType.L2), reg_weight=L2,
    )


def _active(red):
    """The program's active set, [members, capacity]: rows and weights."""
    width = max(b.capacity for b in red.buckets)
    rows = np.full((red.num_entities, width), -1, np.int32)
    weights = np.zeros(rows.shape, np.float32)
    for b in red.buckets:
        rows[b.entity_codes, :b.capacity] = b.row_index
        weights[b.entity_codes, :b.capacity] = b.weights
    return rows, weights


@pytest.fixture(scope="module", params=[0, 1, 2])
def solved(request):
    """The program's bank update on the table and the reference's solve on
    the program's active set, both under the same offsets."""
    ds, red, offsets = _table(request.param)
    problem = _problem()
    bank, _ = problem.update_bank(
        jnp.zeros((red.num_entities, red.local_dim), jnp.float32), red,
        residual_offsets=jnp.asarray(offsets),
    )
    bank = np.asarray(bank)
    shard = ds.shards["member"]
    active, weights = _active(red)
    members = np.arange(red.num_entities)
    feats = ri.index_maps(active, shard.indices, shard.values, INTERCEPT)
    problem_ref = ri.member_rows(
        feats, active, weights, shard.indices, shard.values, ds.labels,
        offsets)
    want = ri.solve_members(problem_ref, L2, max_iter=MAX_ITER, tol=TOL)
    got_feats, got_coefs = ri.global_form(red.projection, bank)
    codes = np.asarray(ds.entity_codes["memberId"])
    return {
        "ds": ds, "red": red, "offsets": offsets, "bank": bank,
        "active": active, "weights": weights, "members": members,
        "feats": feats, "want": want, "codes": codes,
        "got_feats": got_feats, "got_coefs": got_coefs,
        "scores": np.asarray(score_random_effect(
            jnp.asarray(bank), red, problem)),
        "want_scores": ri.map_scores(
            feats, want, codes, shard.indices, shard.values),
    }


def _passive(s):
    held = np.zeros(len(s["codes"]), bool)
    held[s["active"][s["active"] >= 0]] = True
    return ~held


# ---------------------------------------------------------------------------
# the cap and the map
# ---------------------------------------------------------------------------


def test_the_reservoir_keeps_min_count_cap_rows_weighted_count_over_cap(solved):
    red, active, weights = solved["red"], solved["active"], solved["weights"]
    counts = np.bincount(solved["codes"], minlength=len(MEMBERS))
    for m, name in enumerate(MEMBERS):
        held = active[m][active[m] >= 0]
        assert len(held) == min(ROWS[name], CAP), name
        assert np.all(solved["codes"][held] == m), name
        assert len(set(held.tolist())) == len(held), name
        want = ROWS[name] / CAP if ROWS[name] > CAP else 1.0
        np.testing.assert_allclose(
            weights[m][active[m] >= 0], want, rtol=1e-6)
    assert ri.cap_rule_breaks(
        solved["members"], active, weights, solved["codes"], counts, CAP) == 0
    assert red.num_passive_rows == sum(max(c - CAP, 0) for c in ROWS.values())
    # the rule the reference holds the program to catches a wrong weight
    dropped = np.where(active >= 0, 1.0, 0.0).astype(np.float32)
    assert ri.cap_rule_breaks(
        solved["members"], active, dropped, solved["codes"], counts, CAP) == 2


def test_the_index_map_always_holds_the_intercept(solved):
    red = solved["red"]
    for m in range(red.num_entities):
        got = set(red.projection[m][red.projection[m] >= 0].tolist())
        want = set(solved["feats"][m][solved["feats"][m] != ri.PAD].tolist())
        assert INTERCEPT in got
        # the program's map is the reference's: the active rows' features
        assert got == want, MEMBERS[m]


def test_a_passive_rows_out_of_map_features_score_zero(solved):
    """The member whose passive rows name no feature of its map: each such
    row scores its intercept's coefficient alone."""
    m = MEMBERS.index("apart")
    rows = np.nonzero(_passive(solved) & (solved["codes"] == m))[0]
    assert len(rows) == ROWS["apart"] - CAP
    red = solved["red"]
    assert not np.isin(
        solved["ds"].shards["member"].indices[rows, :5], red.projection[m]
    ).any()
    local = int(np.nonzero(red.projection[m] == INTERCEPT)[0][0])
    np.testing.assert_allclose(
        solved["scores"][rows], solved["bank"][m, local], rtol=1e-6)
    assert np.all(red.row_local_values[rows, :5] == 0.0)


# ---------------------------------------------------------------------------
# against the reference
# ---------------------------------------------------------------------------


def _gap(got, want):
    return float(np.linalg.norm((got - want).astype(np.float64))
                 / max(np.linalg.norm(want.astype(np.float64)), 1e-30))


def test_the_bank_mapped_to_the_global_space_matches_the_reference(solved):
    got = ri.lookup(solved["got_feats"], solved["got_coefs"], solved["feats"])
    # every coefficient the program holds lies on the reference's map
    np.testing.assert_allclose(
        np.sum(got.astype(np.float64) ** 2),
        np.sum(solved["got_coefs"].astype(np.float64) ** 2), rtol=1e-6)
    assert _gap(got, solved["want"]) < 1e-4


@pytest.mark.parametrize("rows", ["active", "passive"])
def test_the_scores_match_the_reference(solved, rows):
    passive = _passive(solved)
    pick = passive if rows == "passive" else ~passive
    assert pick.any()
    assert _gap(solved["scores"][pick], solved["want_scores"][pick]) < 1e-4


def test_the_objective_matches_the_reference(solved):
    ds, off = solved["ds"], solved["offsets"]

    def objective(scores, coefs):
        return reference.logistic_total(off + scores, ds.labels) + 0.5 * L2 * float(
            np.sum(coefs.astype(np.float64) ** 2))

    got = objective(solved["scores"], solved["bank"])
    want = objective(solved["want_scores"], solved["want"])
    assert abs(got - want) / abs(want) < 1e-5


COUNTED = {
    "photon_re_rows_total": {"state": "passive"},
    "photon_re_capped_entities_total": {},
    "photon_re_capacity_classes_total": {},
    "photon_re_dataset_builds_total": {},
}


def _counted():
    return {
        name: default_registry().counter(name).value(type="memberId", **labels)
        for name, labels in COUNTED.items()
    }


def test_the_build_counts_what_it_made():
    before = _counted()
    _, red, _ = _table(0)
    after = _counted()
    assert {k: after[k] - before[k] for k in after} == {
        "photon_re_rows_total": red.num_passive_rows,
        "photon_re_capped_entities_total": 2,
        "photon_re_capacity_classes_total": len(red.buckets),
        "photon_re_dataset_builds_total": 1,
    }


# ---------------------------------------------------------------------------
# the scoring plan
# ---------------------------------------------------------------------------


def test_an_identity_datasets_score_plan_is_unchanged_all_blocks():
    """No passive rows, no second program: the plan the replicated
    coordinate asks for is the plan as before, every row from the blocks."""
    _, red, _ = _table(0, projector=ProjectorType.IDENTITY, cap=None)
    problem = _problem()
    plan = score_plan(red, problem)
    assert plan.kernel == "blocks" and plan.passive is None
    assert plan.rest is None and plan.block_rows == red.num_active_rows
    assert plan.gather_rows == 0
    before = score_plan(red, problem, passive_apart=False)
    assert (plan.block_rows, plan.gather_rows, plan.kernel) == (
        before.block_rows, before.gather_rows, before.kernel)
    assert [[b[:4] for b in g] for g in plan.groups] == [
        [b[:4] for b in g] for g in before.groups]


def test_passive_rows_are_scored_by_a_program_of_their_own(solved):
    """From per-member chunks of the widest bucket's capacity, each
    passive row in one slot, filed under ``chunks`` and not ``gather``."""
    red, problem = solved["red"], _problem()
    plan = score_plan(red, problem)
    rows, codes, ix, v = (np.asarray(a) for a in plan.passive)
    S = max(b.capacity for b in red.buckets)
    assert S == CAP
    C = codes.shape[0]
    assert rows.shape == (C, S) and ix.shape == v.shape == (C, S, ix.shape[2])
    held = rows[rows >= 0]
    passive = np.nonzero(_passive(solved))[0]
    assert sorted(held.tolist()) == passive.tolist()  # each exactly once
    assert plan.kernel == "blocks+chunks"
    assert plan.chunk_rows == red.num_passive_rows
    assert plan.gather_rows == (
        0 if plan.rest is None else plan.rest[0].shape[0])
    valid = int(np.count_nonzero(red.row_entity_codes >= 0))
    assert plan.block_rows + plan.chunk_rows + plan.gather_rows == valid
    counter = default_registry().counter("photon_re_score_rows_total")
    paths = ("blocks", "chunks", "gather")
    before = [counter.value(coordinate="passive", path=p) for p in paths]
    _count_scored_rows(
        "passive", plan.block_rows, plan.gather_rows, plan.chunk_rows)
    assert [
        counter.value(coordinate="passive", path=p) - b
        for p, b in zip(paths, before)
    ] == [plan.block_rows, red.num_passive_rows, plan.gather_rows]
    text = jax.jit(
        lambda b: score_random_effect(b, red, problem)
    ).lower(jnp.asarray(solved["bank"])).as_text()
    assert "re_score_passive" in text and "re_score" in text


# ---------------------------------------------------------------------------
# the fixed effect's reference, its gradient summed in float64
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("weighted", [False, True])
def test_the_float64_gradient_keeps_the_objective_and_sums_the_gradient_exactly(
        weighted):
    """The value is ``reference.SparseProblem``'s own; the gradient is
    the float64 sum of ``X^T (sigmoid(Xw) - y) * weight + l2 w``."""
    rng = np.random.default_rng(4)
    n, dim, k = 300, 25, 6
    ix = np.stack([rng.choice(dim, k, replace=False) for _ in range(n)])
    v = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    wt = rng.random(n).astype(np.float32) if weighted else None
    inner = reference.SparseProblem(
        ix.astype(np.int32), v, y, dim, l2=0.7, weights=wt)
    w = rng.normal(size=dim).astype(np.float32)
    value, grad = ri.Float64Gradient(inner).value_and_gradient(w)
    assert float(value) == float(inner.value_and_gradient(w)[0])
    z = (w.astype(np.float64)[ix] * v).sum(axis=1)
    r = (1.0 / (1.0 + np.exp(-z)) - y) * (1.0 if wt is None else wt)
    want = 0.7 * w.astype(np.float64)
    np.add.at(want, ix, v * r[:, None])
    np.testing.assert_allclose(np.asarray(grad), want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# the cell, at a tiny size, through its entry
# ---------------------------------------------------------------------------

CELL = "glmix-ads-indexmap-131k.cd"
# the row law, the cap, the entries a row and the optimizers are the
# configuration's own; the rows are fewer than ``members x 16`` so that
# the law's draws are cut back to fit and one-row members remain
TINY = {"members": 1024, "rows": 10240, "fixed_hashed_dim": 4096,
        "member_dim": 2048, "member_nnz": 8}


def _workload(run):
    """The cell's workload on ONE device, as the chip runs it: the test
    process has eight, on which the driver's ``--distributed auto``
    would lay a data mesh."""
    wl = dict(run.load_json(run.HERE, "workloads", CELL + ".json"))
    return {"driver_args": list(wl["driver_args"]) + ["--distributed", "off"]}


@pytest.fixture(scope="module")
def cell_run():
    from benchmark import faults, run
    from benchmark.entries import game_cd_indexmap as entry

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    kept = []
    # the run sets the cell's matmul precision for the whole process: put
    # back what the other tests of this process run at
    precision = jax.config.jax_default_matmul_precision
    try:
        out = run.run_cell(
            bench, CELL, 2_718_281_828, 0.2, False, config_override=TINY,
            workload_override=_workload(run),
            wrap_cell=lambda c: kept.append(c) or c, keep_outputs=kept,
        )
    finally:
        jax.config.update("jax_default_matmul_precision", precision)
    cell, outputs = kept

    def judged(o):
        return run.judge(cell.check(o), cell.wl["limits"])

    cases = {
        "control_bf16": judged(cell.reference_outputs("bf16")),
        "fault_half_batch": judged(cell.reference_outputs(
            "f32", faults.half_batch(outputs["rows"]))),
    }
    for name, plant in entry.FAULTS.items():
        cases["fault_" + name] = judged(plant(outputs))
    return {"out": out, "cell": cell, "cases": cases,
            "reference": judged(cell.reference_outputs("f32"))}


def test_the_tiny_cell_is_correct_and_covers_every_kind_of_member(cell_run):
    out, cell = cell_run["out"], cell_run["cell"]
    assert out["correct"], out["checks"]
    counts = cell.counts[cell.sample]
    assert (counts > cell.cap).any() and (counts < cell.cap).any()
    assert (counts == 1).any()
    readings = out["extra"]["readings"]
    assert readings["sampled_passive_rows"] > 0
    assert readings["cap_rule_breaks"] == 0
    assert all(c["ok"] for c in cell_run["reference"].values())


@pytest.mark.parametrize("case", [
    "control_bf16", "fault_half_batch", "fault_unchanged", "fault_altered",
    "fault_passive_full_row", "fault_cap_weight_dropped",
])
def test_each_control_and_fault_fails_a_judged_number(cell_run, case):
    checks = cell_run["cases"][case]
    assert not all(c["ok"] for c in checks.values()), case


def test_the_two_controls_fail_where_they_should(cell_run):
    """Passive rows scored with their full row fail the passive scores;
    the cap's weight dropped fails the bank."""
    cases = cell_run["cases"]
    assert not cases["fault_passive_full_row"]["passive_score_rms_gap"]["ok"]
    assert cases["fault_passive_full_row"]["active_score_rms_gap"]["ok"]
    assert not cases["fault_cap_weight_dropped"]["bank_rms_gap"]["ok"]


def test_a_warm_one_device_descent_lowers_nothing(tmp_path):
    """Once one step has run, two more steps of the cell's descent (the
    driver's coordinates on one device: INDEX_MAP, the cap, passive rows
    scored apart) lower no program."""
    import jax._src.test_util as jtu

    from benchmark import run
    from benchmark.entries import game_cd_indexmap as entry

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == CELL.rsplit(".", 1)[0])
    config = dict(run.load_json(run.ROOT, config_entry["file"]), **TINY)
    workload = dict(run.load_json(run.HERE, "workloads", CELL + ".json"),
                    **_workload(run))
    cell = entry.setup(run.SetupContext(
        config, workload, 3, str(tmp_path), False, run.Spans()))
    assert cell.step()["ok"]  # warm
    with jtu.count_jit_and_pmap_lowerings() as count:
        assert cell.step()["ok"]
        assert cell.step()["ok"]
    assert count() == 0, count()
