"""The ratings cell without the chip: the generator's counts and laws at
a tiny size, and the entry ``game_cd_mf`` on the CPU: a sound run, a
second seed on the same shapes, the reference in the program's place,
the lower-precision control and each fault, the cell's own two among
them; and the files of the metrics the cell adds."""

import os

import numpy as np
import pytest

from benchmark import data_ratings, faults, run
from benchmark.entries import game_cd_mf

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "movielens-20m-mf.cd"
# a size a test run can hold: the laws' three numbers a side at a scale
# where a movie may still have more ratings than the rank
TINY = {
    "users": 300, "items": 80, "rated_items": 76, "ratings": 7500,
    "min_ratings_per_user": 20, "max_ratings_per_user": 60,
    "min_ratings_per_item": 60, "max_ratings_per_item": 180,
}


def _config():
    cfg = dict(run.load_json(run.ROOT, "benchmark/configs/movielens-20m-mf.json"))
    cfg.update(TINY)
    return cfg


def _run(seed=11, **kw):
    return run.run_cell(BENCH, CELL, seed, 0.2, False, config_override=TINY, **kw)


def test_the_published_counts_give_a_law_on_each_side():
    """At the configuration's own size: the three numbers of each law to
    the unit (host arithmetic only; no row is made)."""
    cfg = run.load_json(run.ROOT, "benchmark/configs/movielens-20m-mf.json")
    assert (cfg["ratings"], cfg["users"], cfg["items"]) == (20_000_263, 138_493, 27_278)
    assert cfg["reduced"] == [] and cfg["rank"] == 64
    for side, entities in (("user", cfg["users"]), ("item", cfg["rated_items"])):
        counts = data_ratings.lognormal_counts(
            entities, cfg[f"min_ratings_per_{side}"], cfg[f"max_ratings_per_{side}"],
            cfg["ratings"])
        assert counts.sum() == cfg["ratings"] and len(counts) == entities
        assert counts[0] == cfg[f"max_ratings_per_{side}"]
        # none under the floor (the lightest user sits on it)
        assert counts[-1] == counts.min() >= cfg[f"min_ratings_per_{side}"]
        assert side == "item" or counts.min() == 20
        assert np.all(np.diff(counts) <= 0)
    # heavy tails: capacity classes 32..16,384 (users) and up to 131,072 (movies)
    assert int(np.ceil(np.log2(cfg["max_ratings_per_item"]))) == 17
    with pytest.raises(ValueError, match="sums to"):
        data_ratings.lognormal_counts(10, 1, 5, 1000)


def test_the_generator_keeps_its_counts_and_laws_on_two_seeds():
    cfg = _config()
    a, b = data_ratings.generate(cfg, 1), data_ratings.generate(cfg, 3_000_000_000)
    again = data_ratings.generate(cfg, 1)
    for d in (a, b):
        users, items = d.entity_of_row["user"], d.entity_of_row["item"]
        assert len(d.labels) == TINY["ratings"]  # the total, to the unit
        per_user = np.bincount(users, minlength=TINY["users"])
        assert per_user.min() == 20 and per_user.max() == 60
        assert np.array_equal(np.sort(per_user)[::-1], d.counts["user"])
        # no (user, movie) pair twice
        assert len(np.unique(users.astype(np.int64) * TINY["items"] + items)) == len(users)
        per_item = np.bincount(items, minlength=TINY["items"])
        assert np.count_nonzero(per_item) <= TINY["rated_items"]
        # the movies keep their law but for what a batch of 256 users
        # draws from one snapshot (here there are two batches in all)
        assert abs(per_item.max() - 180) <= 0.1 * 180
        assert np.abs(np.sort(per_item)[::-1][:76] - d.counts["item"]).sum() <= 0.05 * 7500
        # the rows are grouped by user, ids ascending
        assert np.all(np.diff(users) >= 0)
        assert d.fixed.indices.shape == (7500, 11) and d.fixed.dim == 21
        assert np.all(d.fixed.indices[:, -1] == 20) and np.all(d.fixed.values[:, -1] == 1.0)
        genres_a_row = d.fixed.values[:, :10].sum(axis=1)
        assert genres_a_row.min() >= 1 and 1.5 <= genres_a_row.mean() <= 2.6
        # the half-star grid
        assert set(np.unique(2 * d.labels).tolist()) <= set(range(1, 11))
        assert 3.0 <= d.labels.mean() <= 4.0

    def as_set(d):
        table = np.concatenate([
            d.entity_of_row["user"][:, None], d.entity_of_row["item"][:, None],
            d.labels[:, None], d.fixed.indices, d.fixed.values,
        ], axis=1).astype(np.float64)
        return table[np.lexsort(table.T[::-1])]

    # the same rows in another order, a user's rows among themselves
    assert np.array_equal(as_set(a), as_set(b))
    assert np.array_equal(a.entity_of_row["user"], b.entity_of_row["user"])
    assert not np.array_equal(a.entity_of_row["item"], b.entity_of_row["item"])
    assert np.array_equal(a.entity_of_row["item"], again.entity_of_row["item"])
    assert np.array_equal(a.labels, again.labels)


@pytest.fixture(scope="module")
def sound():
    kept = []
    out = _run(wrap_cell=lambda c: kept.append(c) or c, keep_outputs=kept)
    return out, kept[0], kept[1]


def _judged(cell, outputs):
    return run.judge(cell.check(outputs), cell.wl["limits"])


def test_a_sound_run_is_correct_over_four_coordinates(sound):
    out, cell, outputs = sound
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert sorted(out["metrics"]) == ["cd_iter_s", "setup_s"]
    assert cell.re_names == ["per-user", "per-item"]
    assert (cell.row_side, cell.col_side) == ("user", "item")
    assert set(out["checks"]) == {
        "fixed_first_gap", "fixed_value_gap", "fixed_grad_gap", "fixed_descent_gap",
        "user_bias_median_gap", "user_bias_rms_gap", "item_bias_median_gap",
        "item_bias_rms_gap", "mf_row_median_gap", "mf_row_rms_gap",
        "mf_col_median_gap", "mf_col_rms_gap", "objective_gap", "repeat_gap",
    }
    assert out["checks"]["repeat_gap"]["value"] == 0.0
    shapes = out["extra"]["schedule_shapes"]
    # a bias has one feature: every class has more ratings than that;
    # a factor has 64: only a movie with more than 64 ratings is primal
    assert set(shapes["per-user"]["block_kinds"]) == {"primal_id"}
    assert set(shapes["per-item"]["block_kinds"]) == {"primal_id"}
    for key in ("mf_row", "mf_col"):
        for (_, capacity), kind in zip(
            shapes[key]["blocks"], shapes[key]["block_kinds"]
        ):
            assert kind == ("primal_id" if capacity > 64 else "newton_id")
    assert set(shapes["mf_row"]["block_kinds"]) == {"newton_id"}
    assert "primal_id" in shapes["mf_col"]["block_kinds"]
    assert "sparse" not in str(shapes)
    assert outputs["row_latent"].shape == (300, 64)
    assert outputs["col_latent"].shape == (80, 64)
    assert outputs["banks"]["per-user"].shape == (300, 1)
    # a movie nobody rated keeps its starting factors' place in the model
    # and is never judged; the heaviest entity of each side always is
    for side in ("user", "item"):
        assert int(np.argmax(cell.counts[side])) in cell.sample[side]
        assert np.all(cell.counts[side][cell.sample[side]] > 0)
    whole, half = cell.work_per_unit(), cell.half_step_work()
    assert whole["flops"] > half["flops"] > 0 and whole["bytes"] > half["bytes"] > 0


def test_a_second_seed_runs_on_the_same_shapes(sound):
    out, _, _ = sound
    other = _run(seed=3_000_000_017)
    assert other["correct"], other["checks"]
    assert other["extra"]["array_shapes"] == out["extra"]["array_shapes"]
    assert other["extra"]["schedule_shapes"] == out["extra"]["schedule_shapes"]


def test_the_reference_in_the_programs_place_is_correct(sound):
    _, cell, _ = sound
    checks = _judged(cell, cell.reference_outputs("f32"))
    assert all(c["ok"] for c in checks.values()), checks


def test_the_lower_precision_control_is_not_correct(sound):
    _, cell, _ = sound
    checks = _judged(cell, cell.reference_outputs("bf16"))
    assert not all(c["ok"] for c in checks.values()), checks


@pytest.mark.parametrize("fault, caught_by", [
    ("unchanged", "user_bias_median_gap"),
    ("altered", None),
    # the cell's own two: only the column factors' numbers can tell them
    # (the objective is what the broken program would report of its model)
    ("col_skipped", "mf_col_median_gap"),
    ("col_stale", "mf_col_rms_gap"),
])
def test_a_planted_fault_is_not_correct(sound, fault, caught_by):
    _, cell, outputs = sound
    checks = _judged(cell, game_cd_mf.FAULTS[fault](outputs))
    assert not all(c["ok"] for c in checks.values()), checks
    if caught_by:
        assert not checks[caught_by]["ok"], checks
    if fault in ("col_skipped", "col_stale"):
        # nothing before the column half-step is touched
        assert checks["mf_row_rms_gap"]["ok"] and checks["item_bias_rms_gap"]["ok"]
        assert checks["fixed_value_gap"]["ok"] and checks["objective_gap"]["ok"], checks


def test_half_of_the_batch_left_out_under_the_timed_path_is_not_correct(monkeypatch):
    monkeypatch.setattr(
        game_cd_mf.Cell, "_row_weights", staticmethod(faults.half_batch))
    out = _run()
    assert not out["correct"], out["checks"]
    assert out["failed"] == 0  # the steps ran; what they returned is wrong


def test_every_new_metric_file_names_a_reader_and_the_cell():
    """(Nothing here rests on the ORDER of the metric entries.)"""
    new = {m["name"]: m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    assert set(new) == {
        "device_idle_pct.mf", "peak_hbm_gb.mf", "window_compiles.mf",
        "step_mfu_pct.mf", "mf_host_gap_s", "mf_fe_device_s", "mf_fe_eval_ms",
        "mf_user_bias_device_s", "mf_item_bias_device_s", "mf_row_half_device_s",
        "mf_col_half_device_s", "mf_score_device_s", "mf_re_score_device_s",
        "mf_primal_share_pct", "mf_padding_pct", "mf_half_step_roofline",
    }
    for name, m in new.items():
        spec = run.load_json(run.HERE, "metrics", name + ".json")
        assert os.path.exists(
            os.path.join(run.HERE, "readers", spec["reader"] + ".py")), name
        assert m["moves"] == "cd_iter_s" and m["workloads"] == [CELL]
    assert new["mf_half_step_roofline"]["unit"] == "%"
    cd_iter = next(e for e in BENCH["end_to_end"] if e["name"] == "cd_iter_s")
    assert CELL in cd_iter["workloads"]
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "movielens-20m-mf"
    config = next(c for c in BENCH["configs"] if c["name"] == "movielens-20m-mf")
    assert config["reduced"] == [] and len(config["source"]) <= 200


def test_the_new_reader_on_hand_made_inputs():
    import types

    from benchmark.readers import module_roofline

    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    trace = {"modules": {"/device:TPU:0": [
        ["jit_bank_fused_mf_row(1)", 0.0, 2e9], ["jit_bank_fused_scan_mf_col(2)", 3e9, 6e9],
        ["jit_bank_fused_per_user(3)", 9e9, 1e9],
    ]}, "spans": []}
    ctx = types.SimpleNamespace(
        traced_steps=2, peaks=peaks, _program_trace=trace,
        cell=types.SimpleNamespace(
            wl={"name": "none"},
            half_step_work=lambda: {"flops": 50.0, "bytes": 20.0}),
    )
    match = "^jit_bank_fused(_scan)?_mf_(row|col)\\("
    # least time 2 s (bytes bind) over 4 s a step of the two programs
    assert module_roofline.read(ctx, match, "half_step_work") == pytest.approx(50.0)
    # a program without such modules, or a cell that tells no such work
    assert module_roofline.read(ctx, "^jit_none\\(", "half_step_work") is None
    assert module_roofline.read(ctx, match, "no_such_work") is None
    ctx._program_trace = None
    assert module_roofline.read(ctx, match, "half_step_work") is None
