"""The factored cell without the chip: the configuration's widths, the
generator's shapes at a tiny size on two seeds, and the entry
``game_cd_factored`` on the CPU: a sound run, a second seed on the same
shapes, the reference in the program's place, the lower-precision control
and each fault; and the files of the metrics the cell adds."""

import os

import numpy as np
import pytest

from benchmark import data_factored, faults, run, work_factored
from benchmark.entries import game_cd_factored

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "glmix-ads-factored-600k.cd"
CONFIG = "benchmark/configs/glmix-ads-factored-600k.json"
# a size a test run can hold: the rows a user, the entries a row and the
# latent width are the configuration's own, and there are many more rows
# than projection coefficients (16,384 to 320), as at the timed size: with
# 64 users the projection is barely determined and the score gaps read
# 10-100 times the timed size's
TINY = {"users": 1024, "fixed_hashed_dim": 4096, "user_dim": 40, "user_nnz": 8}


def _config(**extra):
    cfg = dict(run.load_json(run.ROOT, CONFIG))
    cfg.update(TINY, **extra)
    return cfg


def _run(seed=11, **kw):
    return run.run_cell(BENCH, CELL, seed, 0.2, False, config_override=TINY, **kw)


def test_the_configuration_keeps_glmix_ads_100m_s_widths():
    cfg = run.load_json(run.ROOT, CONFIG)
    base = run.load_json(run.ROOT, "benchmark/configs/glmix-ads-100m.json")
    for key in ("rows_per_user", "fixed_hashed_dim", "fixed_nnz", "user_dim",
                "user_nnz", "task", "precision"):
        assert cfg[key] == base[key], key
    assert cfg["reduced"] == ["users"] and cfg["users"] == 131072
    assert cfg["assumed"]["users_full"] == 600000
    assert cfg["latent_dim"] == cfg["planted"]["latent_dim"] == 8
    wl = run.load_json(run.HERE, "workloads", CELL + ".json")
    args = wl["driver_args"]
    assert args[args.index("--factored-random-effect-optimization-configurations") + 1] == (
        f"per-user:{cfg['latent_dim']},{cfg['inner_iterations']}")


@pytest.mark.parametrize("seed", [1, 3_000_000_000])
def test_the_generator_gives_the_configurations_shapes_on_every_seed(seed):
    cfg = _config()
    d = data_factored.generate(cfg, seed)
    n = TINY["users"] * cfg["rows_per_user"]
    assert d.fixed.indices.shape == (n, 72) and d.fixed.dim == 4097
    assert np.all(d.fixed.indices[:, 64] == 4096) and np.all(d.fixed.values[:, 64] == 1.0)
    assert d.user.indices.shape == d.user.values.shape == (n, 8) and d.user.dim == 40
    assert np.all(np.bincount(d.user_of_row) == cfg["rows_per_user"])
    # no user feature twice in a row
    assert all(len(set(r)) == 8 for r in d.user.indices)
    assert d.labels.shape == (n,) and set(np.unique(d.labels)) <= {0.0, 1.0}


def test_two_seeds_give_the_same_rows_in_another_order():
    cfg = _config()
    a, b = data_factored.generate(cfg, 1), data_factored.generate(cfg, 3_000_000_000)

    def as_set(d):
        table = np.concatenate([
            d.user_of_row[:, None], d.labels[:, None], d.user.indices, d.user.values,
            d.fixed.indices, d.fixed.values,
        ], axis=1).astype(np.float64)
        return table[np.lexsort(table.T[::-1])]

    assert np.array_equal(a.user_of_row, b.user_of_row)
    assert not np.array_equal(a.user.values, b.user.values)
    assert np.array_equal(as_set(a), as_set(b))


@pytest.fixture(scope="module")
def sound():
    kept = []
    out = _run(wrap_cell=lambda c: kept.append(c) or c, keep_outputs=kept)
    return out, kept[0], kept[1]


def _judged(cell, outputs):
    return run.judge(cell.check(outputs), cell.wl["limits"])


def test_a_sound_run_is_correct(sound):
    out, cell, outputs = sound
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert sorted(out["metrics"]) == ["cd_iter_s", "setup_s"]
    assert set(out["checks"]) == {
        "fixed_first_gap", "fixed_value_gap", "fixed_grad_gap", "fixed_descent_gap",
        "score_median_gap", "score_rms_gap", "objective_gap", "repeat_gap",
    }
    assert out["checks"]["repeat_gap"]["value"] == 0.0
    shapes = out["extra"]["schedule_shapes"]
    # 16 rows of 8 latent features: the primal kind, one block at this size
    assert shapes["block_kinds"] == ["primal_id"]
    assert out["extra"]["array_shapes"]["projection"] == [40, 8]
    assert outputs["bank"].shape == (1024, 8) and outputs["projection"].shape == (40, 8)
    for told in ("projection_gap", "bank_gap", "score_gap"):
        assert told in out["extra"]["readings"]
    # no user apart from the reference's last latent solve, none held
    assert out["extra"]["readings"]["latent_apart_users"] == 0
    assert out["extra"]["readings"]["latent_either_stop_users"] == 0
    whole = cell.work_per_unit()
    for part in (cell.projection_work(), cell.latent_bank_work()):
        assert whole["flops"] > part["flops"] > 0 and whole["bytes"] > part["bytes"] > 0


def test_a_second_seed_runs_on_the_same_shapes(sound):
    out, _, _ = sound
    other = _run(seed=3_000_000_017)
    assert other["correct"], other["checks"]
    assert other["extra"]["array_shapes"] == out["extra"]["array_shapes"]
    assert other["extra"]["schedule_shapes"] == out["extra"]["schedule_shapes"]


def test_the_reference_in_the_programs_place_agrees_with_itself(sound):
    _, cell, _ = sound
    checks = _judged(cell, cell.reference_outputs("f32"))
    assert all(c["ok"] for c in checks.values()), checks
    assert checks["score_rms_gap"]["value"] == 0.0


def test_the_lower_precision_control_fails_a_limit(sound):
    _, cell, _ = sound
    checks = _judged(cell, cell.reference_outputs("bf16"))
    assert not all(c["ok"] for c in checks.values()), checks
    assert not checks["score_rms_gap"]["ok"], checks


@pytest.mark.parametrize("fault, caught_by", [
    ("unchanged", "score_rms_gap"),
    # the cell's own: only the factored scores can tell it. (``altered``,
    # a tenth on the largest fixed coefficient, is not caught in this
    # cell: PERF.md section 2)
    ("projection_unfitted", "score_rms_gap"),
    # one user wrong: found apart, and not held to any stop of its path
    ("one_user_off", "score_rms_gap"),
])
def test_a_planted_fault_is_not_correct(sound, fault, caught_by):
    _, cell, outputs = sound
    readings = cell.check(game_cd_factored.FAULTS[fault](outputs))
    checks = run.judge(readings, cell.wl["limits"])
    assert not all(c["ok"] for c in checks.values()), checks
    if caught_by:
        assert not checks[caught_by]["ok"], checks
    if fault == "one_user_off":
        assert readings["latent_apart_users"] == 1
        assert readings["latent_either_stop_users"] == 0


def test_half_of_the_batch_left_out_under_the_timed_path_is_not_correct(monkeypatch):
    monkeypatch.setattr(
        game_cd_factored.Cell, "_row_weights", staticmethod(faults.half_batch))
    out = _run()
    assert not out["correct"], out["checks"]
    assert out["failed"] == 0


def test_the_needed_work_counts_entries_not_densified_rows():
    p = work_factored.projection_pass(rows=10, entries=4, latent=8)
    assert p == {"flops": 2.0 * 10 * 4 * 8, "bytes": 8.0 * 10 * 4}
    u = work_factored.latent_update(users=2, rows_per_user=5, entries=4, latent=8)
    assert u == {"flops": 2.0 * 10 * (4 * 8 + 64), "bytes": 8.0 * 10 * 4}


def test_every_new_metric_file_names_a_reader_and_the_cell():
    """Membership, not position: nothing here rests on the ORDER of the
    metric entries."""
    new = {m["name"]: m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]}
    assert set(new) == {
        "device_idle_pct.fre", "peak_hbm_gb.fre", "step_mfu_pct.fre",
        "window_compiles.fre", "fre_fe_device_s", "fre_latent_bank_device_s",
        "fre_projection_device_s", "fre_score_device_s", "fre_host_gap_s",
        "fre_projection_evals_per_iter", "fre_projection_roofline",
        "fre_latent_bank_roofline",
    }
    for name, m in new.items():
        spec = run.load_json(run.HERE, "metrics", name + ".json")
        assert os.path.exists(
            os.path.join(run.HERE, "readers", spec["reader"] + ".py")), name
        assert m["moves"] == "cd_iter_s"
    for name in ("fre_projection_roofline", "fre_latent_bank_roofline"):
        assert new[name]["unit"] == "%"
        spec = run.load_json(run.HERE, "metrics", name + ".json")
        assert hasattr(game_cd_factored.Cell, spec["args"]["work_of"])
    for m in BENCH["per_layer"]:
        if m["name"].startswith("setup_") and m["name"] != "setup_structure_build_s":
            assert CELL in m["workloads"], m["name"]
    cd_iter = next(e for e in BENCH["end_to_end"] if e["name"] == "cd_iter_s")
    assert CELL in cd_iter["workloads"]
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "glmix-ads-factored-600k"
    config = next(c for c in BENCH["configs"] if c["name"] == "glmix-ads-factored-600k")
    assert config["reduced"] == ["users"] and len(config["source"]) <= 200
