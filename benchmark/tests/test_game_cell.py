"""The full-GAME cell without the chip: the generator's shapes, and the
entry ``game_cd_multi`` at a tiny size on the CPU: a sound run, a second
seed on the same shapes, the lower-precision control, the reference in
the program's place and each fault, the cell's own two among them; and
the files of the metrics the cell adds."""

import os

import numpy as np
import pytest

from benchmark import data_game, faults, run
from benchmark.entries import game_cd_multi

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "game-ads-1b.cd"
# a size a test run can hold (3 : 2 and 16 / 24 rows as the configuration's);
# widths per row are the configuration's own
# (with a few dozen entities a side, ONE entity that stops an iteration apart
# is most of a bank's rms gap)
TINY = {"users": 384, "items": 256, "rows": 6144, "fixed_hashed_dim": 4096}


def _config():
    cfg = dict(run.load_json(run.ROOT, "benchmark/configs/game-ads-1b.json"))
    cfg.update(TINY)
    return cfg


def _run(seed=11, **kw):
    return run.run_cell(BENCH, CELL, seed, 0.2, False, config_override=TINY, **kw)


def test_every_user_has_16_rows_on_16_items_and_every_item_24_on_two_seeds():
    cfg = _config()
    a, b = data_game.generate(cfg, 1), data_game.generate(cfg, 3_000_000_000)
    again = data_game.generate(cfg, 1)
    for d in (a, b):
        users, items = d.entity_of_row["user"], d.entity_of_row["item"]
        assert np.bincount(users).tolist() == [16] * TINY["users"]
        assert np.bincount(items).tolist() == [24] * TINY["items"]
        assert all(
            len(set(items[users == u].tolist())) == 16 for u in range(TINY["users"])
        )
        assert d.fixed.indices.shape == (6144, 72) and d.fixed.dim == 4097
        assert d.sides["user"].indices.shape == d.sides["item"].indices.shape == (6144, 32)
        # no feature twice in a row
        assert all(len(set(r)) == 32 for r in d.sides["item"].indices[:64].tolist())

    def as_set(d):
        table = np.concatenate([
            d.fixed.indices, d.fixed.values, d.sides["user"].values,
            d.sides["item"].indices, d.entity_of_row["user"][:, None],
            d.entity_of_row["item"][:, None], d.labels[:, None],
        ], axis=1).astype(np.float64)
        return table[np.lexsort(table.T[::-1])]

    # the same rows in another order, a user's rows among themselves
    assert np.array_equal(as_set(a), as_set(b))
    assert np.array_equal(a.entity_of_row["user"], b.entity_of_row["user"])
    assert not np.array_equal(a.fixed.values, b.fixed.values)
    assert np.array_equal(a.fixed.values, again.fixed.values)
    assert np.array_equal(a.entity_of_row["item"], again.entity_of_row["item"])


def test_sizes_that_would_leave_the_items_uneven_are_refused():
    with pytest.raises(ValueError, match="uneven"):
        data_game.generate(dict(_config(), users=375, rows=6000, items=250), 1)


@pytest.fixture(scope="module")
def sound():
    kept = []
    out = _run(wrap_cell=lambda c: kept.append(c) or c, keep_outputs=kept)
    return out, kept[0], kept[1]


def _judged(cell, outputs):
    return run.judge(cell.check(outputs), cell.wl["limits"])


def test_a_sound_run_is_correct_over_three_coordinates(sound):
    out, cell, outputs = sound
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert sorted(out["metrics"]) == ["cd_iter_s", "setup_s"]
    assert cell.re_names == ["per-user", "per-item"]
    assert cell.side == {"per-user": "user", "per-item": "item"}
    assert set(out["checks"]) == {
        "fixed_first_gap", "fixed_value_gap", "fixed_grad_gap", "fixed_descent_gap",
        "user_bank_median_gap", "user_bank_rms_gap", "item_bank_median_gap",
        "item_bank_rms_gap", "objective_gap", "repeat_gap",
    }
    assert out["checks"]["repeat_gap"]["value"] == 0.0
    shapes = out["extra"]["schedule_shapes"]
    # one capacity class a side: 16, and 24 padded to 32; whole at this size
    assert shapes["per-user"]["buckets"] == [[384, 16, 32]]
    assert shapes["per-item"]["buckets"] == [[256, 32, 32]]
    assert shapes["per-user"]["block_kinds"] == shapes["per-item"]["block_kinds"] == [
        "newton"]
    assert shapes["per-user"]["sub_blocks"] == [1]
    assert outputs["banks"]["per-user"].shape == (384, 1000)
    assert outputs["banks"]["per-item"].shape == (256, 1000)
    # the needed work is told over all three coordinates
    whole = cell.work_per_unit()
    assert whole["flops"] > 0 and whole["bytes"] > 0
    assert out["extra"]["array_shapes"]["per-item.bank"] == [256, 1000]


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
    ("unchanged", "user_bank_median_gap"),
    ("altered", None),
    # the cell's own two: only the item bank's numbers can tell them (the
    # objective is what the broken program would report of its own model)
    ("last_left_at_zero", "item_bank_median_gap"),
    ("handoff_skipped", "item_bank_rms_gap"),
])
def test_a_planted_fault_is_not_correct(sound, fault, caught_by):
    _, cell, outputs = sound
    checks = _judged(cell, game_cd_multi.FAULTS[fault](outputs))
    assert not all(c["ok"] for c in checks.values()), checks
    if caught_by:
        assert not checks[caught_by]["ok"], checks
    if fault in ("last_left_at_zero", "handoff_skipped"):
        # nothing before the last coordinate is touched
        assert checks["user_bank_rms_gap"]["ok"] and checks["fixed_value_gap"]["ok"]
        assert checks["objective_gap"]["ok"], checks


def test_half_of_the_batch_left_out_under_the_timed_path_is_not_correct(monkeypatch):
    monkeypatch.setattr(
        game_cd_multi.Cell, "_row_weights", staticmethod(faults.half_batch))
    out = _run()
    assert not out["correct"], out["checks"]
    assert out["failed"] == 0  # the steps ran; what they returned is wrong


def test_every_new_metric_file_names_a_reader_and_the_new_cell():
    new = [m for m in BENCH["per_layer"] if m["workloads"] == [CELL]]
    assert [m["name"] for m in new] == [
        "device_idle_pct.game", "peak_hbm_gb.game", "window_compiles.game",
        "step_mfu_pct.game", "game_fe_device_s", "game_fe_eval_ms",
        "game_fe_margin_ms", "game_fe_gradient_ms", "game_user_bank_device_s",
        "game_item_bank_device_s", "game_re_score_device_s", "game_fe_score_device_s",
        "game_host_gap_s", "game_prefetch_wait_s", "game_newton_share_pct",
        "game_fe_dense_entries_pct",
    ]
    assert BENCH["per_layer"][-len(new):] == new  # appended, in order
    for m in new:
        spec = run.load_json(run.HERE, "metrics", m["name"] + ".json")
        assert os.path.exists(
            os.path.join(run.HERE, "readers", spec["reader"] + ".py")), m["name"]
        assert m["moves"] == "cd_iter_s"
    cd_iter = next(e for e in BENCH["end_to_end"] if e["name"] == "cd_iter_s")
    assert cd_iter["workloads"][-1] == CELL
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and BENCH["workloads"][-1] == entry


def test_the_two_new_readers_on_hand_made_inputs():
    import types

    from benchmark.readers import program_span_time, registry_label_share
    from photon_ml_tpu.obs.registry import default_registry, reset_default_registry

    ctx = types.SimpleNamespace(
        traced_steps=2, cell=types.SimpleNamespace(wl={"name": "none"}),
        _program_trace={"modules": {}, "spans": [
            ["photon.cd.prefetch_wait", 0.0, 1e9], ["photon.cd.update", 0.0, 5e9],
            ["photon.cd.prefetch_wait", 7e9, 2e9],
        ]},
    )
    assert program_span_time.read(ctx, "photon.cd.prefetch_wait") == pytest.approx(1.5)
    assert program_span_time.read(ctx, "photon.none") is None
    ctx._program_trace = None
    assert program_span_time.read(ctx, "photon.cd.prefetch_wait") is None
    reset_default_registry()
    # a program that does not count: nothing to read, and no error
    assert registry_label_share.read(None, "photon_bank_entities_total", {"kind": "newton"}) is None
    solved = default_registry().counter("photon_bank_entities_total")
    solved.inc(30, coordinate="per-user", kind="newton")
    solved.inc(10, coordinate="per-item", kind="newton")
    solved.inc(10, coordinate="per-item", kind="sparse")
    assert registry_label_share.read(
        None, "photon_bank_entities_total", {"kind": "newton"}) == pytest.approx(80.0)
    assert registry_label_share.read(
        None, "photon_bank_entities_total",
        {"kind": "newton", "coordinate": "per-item"}) == pytest.approx(20.0)
    reset_default_registry()
