"""The INDEX_MAP cell without the chip: the configuration's widths, its row
law against the counts the file states, and the files of the metrics the
cell adds. The cell's runs at a tiny size, sound, with the controls and
with each fault, are ``tests/test_indexmap_re.py``'s. Importing
``data_indexmap`` here adds its generator to ``data.GENERATORS`` for every
test of the process, as the other cells' files do for theirs."""

import os

import numpy as np

from benchmark import data_indexmap, run
from benchmark.entries import game_cd_indexmap

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "glmix-ads-indexmap-131k.cd"
CONFIG = "benchmark/configs/glmix-ads-indexmap-131k.json"
METRICS = {
    "device_idle_pct.idx", "peak_hbm_gb.idx", "window_compiles.idx",
    "step_mfu_pct.idx", "idx_fe_device_s", "idx_bank_device_s",
    "idx_active_score_device_s", "idx_passive_score_device_s",
    "idx_passive_rows_pct", "idx_capacity_classes", "idx_bank_padding_pct",
    "idx_bank_roofline", "setup_index_map_s",
}


def test_the_configuration_keeps_the_glmix_widths():
    cfg = run.load_json(run.ROOT, CONFIG)
    base = run.load_json(run.ROOT, "benchmark/configs/glmix-ads-100m.json")
    for key in ("fixed_hashed_dim", "fixed_nnz", "task", "precision"):
        assert cfg[key] == base[key], key
    assert (cfg["member_dim"], cfg["member_nnz"]) == (2 ** 18, 32)
    assert (cfg["members"], cfg["rows"], cfg["active_cap"]) == (
        131072, 2097152, 32)
    assert cfg["generator"] in data_indexmap.GENERATORS


def test_the_row_law_is_a_plain_power_law_of_mean_16_as_realized():
    cfg = run.load_json(run.ROOT, CONFIG)
    law = cfg["rows_law"]
    assert set(law) == {"min", "max", "exponent"}
    c = np.arange(law["min"], law["max"] + 1, dtype=np.float64)
    p = c ** -law["exponent"]
    assert abs(float(p @ c / p.sum()) - cfg["rows"] / cfg["members"]) < 0.01
    counts = data_indexmap.member_counts(cfg)
    cap = cfg["active_cap"]
    assert counts.sum() == cfg["rows"] and counts.min() >= law["min"]
    assert counts.max() <= law["max"]
    realized = cfg["realized"]
    assert int((counts > cap).sum()) == realized["over_cap_members"]
    assert int(np.minimum(counts, cap).sum()) == realized["active_rows"]
    assert int(np.maximum(counts - cap, 0).sum()) == realized["passive_rows"]


def test_every_new_metric_file_names_a_reader_and_the_cell():
    new = {m["name"]: m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]}
    assert set(new) == METRICS
    for name, m in new.items():
        spec = run.load_json(run.HERE, "metrics", name + ".json")
        assert os.path.exists(
            os.path.join(run.HERE, "readers", spec["reader"] + ".py")), name
        assert m["moves"] == (
            "setup_s" if name.startswith("setup_") else "cd_iter_s"), name
    assert new["idx_bank_roofline"]["unit"] == "%"
    spec = run.load_json(run.HERE, "metrics", "idx_bank_roofline.json")
    assert hasattr(game_cd_indexmap.Cell, spec["args"]["work_of"])
    cd_iter = next(e for e in BENCH["end_to_end"] if e["name"] == "cd_iter_s")
    assert CELL in cd_iter["workloads"]
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "glmix-ads-indexmap-131k"
    config = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert config["reduced"] == ["members"] and len(config["source"]) <= 200
