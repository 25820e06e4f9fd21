"""The harness without the chip: the files ``BENCHMARK.json`` names, the
yardstick's arithmetic, seed-independent shapes, and a whole run of each
cell at a tiny size on the CPU: sound, with the lower-precision control in
the program's place, and with each fault planted under the timed path."""

import importlib
import json
import os
import re

import numpy as np
import pytest

from benchmark import data, faults, run, trace_reduce, work

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# a size a test run can hold; widths per row are the configuration's own
TINY = {
    "criteo-logistic-1m.fit": {"rows": 4096, "hashed_dim": 2048},
    "glmix-ads-100m.cd": {"users": 256, "fixed_hashed_dim": 4096},
}
CELLS = sorted(TINY)


def _run(cell, seed=11, **kw):
    return run.run_cell(BENCH, cell, seed, 0.2, False, config_override=TINY[cell], **kw)


# ---- files and names -----------------------------------------------------


def test_every_named_file_exists_and_every_name_is_allowed():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
        "per_layer",
    }
    assert BENCH["paths"] == ["benchmark"] and 1 <= BENCH["run_seconds"] <= 51
    assert all(word.count("/") == 0 or word.startswith("benchmark/")
               for word in BENCH["command"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    sources = {"device_trace", "program_span", "program_counter", "host_clock"}
    assert all(m["source"] in sources for m in BENCH["end_to_end"] + BENCH["per_layer"])
    assert all(set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"} for m in BENCH["per_layer"])
    cells = {w["name"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        cfg = run.load_json(run.ROOT, c["file"])
        assert set(c["reduced"]) <= set(cfg) and cfg["reduced"] == c["reduced"]
        assert "shape_seed" in cfg and cfg["generator"] in data.GENERATORS
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] == f'{w["config"]}.{w["traffic"]}' and len(w["why"]) <= 200
        wl = run.load_json(run.HERE, "workloads", w["name"] + ".json")
        assert os.path.exists(os.path.join(run.HERE, "entries", wl["entry"] + ".py"))
        assert wl["limits"], "a cell compares at least one number"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        spec = run.load_json(run.HERE, "metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(run.HERE, "readers", spec["reader"] + ".py"))
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in BENCH["workloads"]:
        wl = run.load_json(run.HERE, "workloads", w["name"] + ".json")
        reported = [
            e["name"] for e in BENCH["end_to_end"]
            if w["name"] in e.get("workloads", cells) and e["name"] != "setup_s"
        ]
        assert reported and set(reported) == set(wl["end_to_end"])


# ---- the yardstick ---------------------------------------------------------


def test_needed_work_against_hand_counts():
    # 3 rows of 2 entries over 5 coefficients
    w = work.glm_value_and_gradient(entries=6, rows=3, dim=5)
    assert w["flops"] == 4 * 6
    assert w["bytes"] == 2 * 6 * 8 + 3 * 3 * 4 + 2 * 5 * 4
    s = work.sparse_score(entries=6, rows=3, dim=5)
    assert (s["flops"], s["bytes"]) == (12, 6 * 8 + 3 * 4 + 5 * 4)
    both = work.add(w, work.scale(s, 2))
    assert both["flops"] == 24 + 24
    peaks = work.peaks_for("TPU v5 lite")
    least = work.least_seconds({"flops": 197e12, "bytes": 819e9 * 3}, peaks)
    assert least == {"seconds": 3.0, "bound": "bytes"}
    with pytest.raises(KeyError):
        work.peaks_for("TPU v99")


def test_trace_reduction_on_hand_made_events():
    # a while [0, 100) holding a kernel [10, 40) and a fusion [50, 70);
    # idle [100, 150); a copy [150, 160)
    events = [
        ("while.1", 0.0, 100.0), ("kernel.1", 10.0, 30.0),
        ("fusion.2", 50.0, 20.0), ("copy.3", 150.0, 10.0),
    ]
    host = [("bench.step", 0.0, 200.0), ("bench.cd.re_update", 90.0, 70.0)]
    got = trace_reduce.reduce({"devices": {"/device:TPU:0": events}, "host": host})
    assert got["busy_s"] == pytest.approx(110e-9)
    assert got["ops"] == pytest.approx(
        {"while.1": 50e-9, "kernel.1": 30e-9, "fusion.2": 20e-9, "copy.3": 10e-9}
    )
    assert got["idle_gaps"] == [["bench.cd.re_update", pytest.approx(50e-9)]]
    assert trace_reduce.matching_seconds(got["ops"], "kernel|copy") == pytest.approx(40e-9)
    empty = trace_reduce.reduce({"devices": {}, "host": []})
    assert empty["busy_s"] == 0.0 and empty["planes"] == 0


def test_trace_reduction_on_a_recorded_trace():
    """A cut of the first traced chip run of ``glmix-ads-100m.cd`` (PR 26):
    the numbers below were read off it by hand."""
    path = os.path.join(HERE, "data", "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    got = trace_reduce.reduce(rec["trace"])
    assert got["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    for name, seconds in rec["expect"]["ops"].items():
        assert got["ops"][name] == pytest.approx(seconds, rel=1e-9)
    assert sum(got["ops"].values()) == pytest.approx(got["busy_s"], rel=1e-6)


# ---- shapes follow shape_seed, numbers follow --seed -------------------------


def _rows_of(d):
    return [d.rows] if hasattr(d, "rows") else [d.fixed, d.user]


def _as_set(rows, labels):
    """The rows whatever their order: each with its label, sorted."""
    table = np.concatenate(
        [rows.indices.astype(np.float64), rows.values, labels[:, None]], axis=1
    )
    return table[np.lexsort(table.T[::-1])]


@pytest.mark.parametrize("cell", CELLS)
def test_two_seeds_give_the_same_rows_and_shapes_in_another_order(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    cfg = dict(run.load_json(run.ROOT, f'benchmark/configs/{entry["config"]}.json'))
    cfg.update(TINY[cell])
    cfg["order_block"] = 512  # several blocks at this size
    a, b = data.generate(cfg, 1), data.generate(cfg, 3_000_000_000)
    again = data.generate(cfg, 1)
    for ra, rb, rc in zip(_rows_of(a), _rows_of(b), _rows_of(again)):
        assert ra.indices.shape == rb.indices.shape and ra.values.dtype == rb.values.dtype
        assert not np.array_equal(ra.values, rb.values)
        assert np.array_equal(ra.values, rc.values) and np.array_equal(ra.indices, rc.indices)
        assert np.array_equal(_as_set(ra, a.labels), _as_set(rb, b.labels))
    if hasattr(a, "rows"):
        # a row stays inside its block of order_block rows, so every tile of
        # (row block, feature block) holds as many entries on every seed
        def tiles(d):
            rb = np.repeat(np.arange(d.rows.indices.shape[0]) // 512, d.rows.indices.shape[1])
            return np.unique(
                np.stack([rb, d.rows.indices.reshape(-1) // 512]), axis=1, return_counts=True
            )
        (ta, ca), (tb, cb) = tiles(a), tiles(b)
        assert np.array_equal(ta, tb) and np.array_equal(ca, cb)
    else:
        assert np.array_equal(a.user_of_row, b.user_of_row)


# ---- whole runs at a tiny size ---------------------------------------------


@pytest.fixture(scope="module", params=CELLS)
def sound(request):
    """One sound run of a cell, with the cell and its outputs kept."""
    kept = []
    out = _run(request.param, wrap_cell=lambda c: kept.append(c) or c, keep_outputs=kept)
    return request.param, out, kept[0], kept[1]


def test_a_sound_run_is_correct_and_reports_the_contract_keys(sound):
    cell, out, _, _ = sound
    assert out["correct"], out["checks"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks" and out["failed"] == 0 and out["attempted"] >= 1
    assert "setup_s" in out["metrics"] and len(out["metrics"]) == 2
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_a_metric_taken_per_counted_work_divides_by_the_replayed_count(monkeypatch):
    """``per: counted``: the wall of all steps over what ONE replayed step
    counts, times the steps (no kernel launches to count on the CPU, so
    the count is planted)."""
    cell = "criteo-logistic-1m.fit"
    replayed = []

    def count(cell_, spec, trace_dir):
        replayed.append(spec)
        cell_.step()  # the replayed step is judged with the window's own
        return 19.0

    monkeypatch.setattr(run, "_replayed_count", count)
    out = _run(cell, seed=5)
    assert out["correct"], out["checks"]
    assert replayed == [run.load_json(run.HERE, "workloads", cell + ".json")["replay_count"]]
    wall, steps = out["extra"]["window_wall_s"], out["attempted"]
    assert out["metrics"]["fit_eval_ms"]["value"] == pytest.approx(1000 * wall / (19 * steps))
    assert out["metrics"]["fit_iter_ms"]["value"] == pytest.approx(
        1000 * wall / out["extra"]["units"])


def test_a_second_seed_runs_on_the_same_shapes(sound):
    cell, out, _, _ = sound
    other = _run(cell, seed=3_000_000_017)
    assert other["correct"], other["checks"]
    assert other["extra"]["array_shapes"] == out["extra"]["array_shapes"]
    assert other["extra"]["schedule_shapes"] == out["extra"]["schedule_shapes"]


def test_the_lower_precision_control_is_not_correct(sound):
    _, _, cell, _ = sound
    checks = run.judge(cell.check(cell.reference_outputs("bf16")), cell.wl["limits"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_the_reference_in_the_programs_place_is_correct(sound):
    _, _, cell, _ = sound
    checks = run.judge(cell.check(cell.reference_outputs("f32")), cell.wl["limits"])
    assert all(c["ok"] for c in checks.values()), checks


class _Faulty:
    """A cell whose step returns what ``fault`` makes of it."""

    def __init__(self, inner, fault):
        self.__dict__.update(_inner=inner, _fault=fault)

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def take_outputs(self):
        return self._fault(self._inner.take_outputs())


@pytest.mark.parametrize("fault", ["unchanged", "stalled", "altered", "half_batch"])
def test_a_run_with_the_timed_path_broken_is_not_correct(sound, fault, monkeypatch):
    cell, _, sound_cell, _ = sound
    entry = sound_cell.wl["entry"]
    module = importlib.import_module("benchmark.entries." + entry)
    if fault != "half_batch" and fault not in module.FAULTS:
        pytest.skip(f"{entry} has no such fault")
    if fault == "half_batch":
        monkeypatch.setattr(
            module.Cell, "_row_weights", staticmethod(faults.half_batch)
        )
        out = _run(cell)
    else:
        plant = module.FAULTS[fault]
        out = _run(cell, wrap_cell=lambda c: _Faulty(c, plant))
    assert not out["correct"], out["checks"]
    assert out["failed"] == 0  # the steps ran; what they returned is wrong
