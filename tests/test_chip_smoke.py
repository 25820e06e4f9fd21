"""chip_smoke.py on the CPU: the legs at a tiny size (so the script is
debugged here before chip time is spent), the no-accelerator exit, and
the compile-cache placement rule the smoke and every driver rely on."""

import json
import os
import subprocess
import sys

import jax

import chip_smoke
from photon_ml_tpu.utils import backend

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = chip_smoke.SmokeSize(
    users=32, rows_per_user=64, fixed_dim=128, fixed_nnz=8,
    user_dim=16, user_nnz=4, val_rows_per_user=16, requests=64,
    parity_scores=8, parts=2, re_model_files=2, min_fixed_dim=128,
)


def test_legs_run_at_a_tiny_size_on_cpu(tmp_path):
    out = chip_smoke.run_legs(TINY, str(tmp_path / "work"), on_chip=False)
    a, b, c = out["leg_a_glm"], out["leg_b_game"], out["leg_c_serving"]
    assert a["fixed_dim"] >= TINY.min_fixed_dim
    assert a["batched_vs_single_max_abs_diff"] <= 5e-3
    # 8 virtual devices: --distributed auto meshes both training legs and
    # leg B shards its bank over all of them
    assert len(a["rows_per_device"]) == len(jax.devices())
    assert b["entity_shards"] == len(jax.devices())
    assert len(set(b["bank_rows_per_device"])) == 1
    assert b["cd_objective_history"][1] < b["cd_objective_history"][0]
    assert c["answered"] == TINY.requests
    assert c["recompiled_programs"] == 0
    assert c["saved_user_local_dim"] == TINY.user_dim
    assert c["scores_checked"] == TINY.parity_scores
    assert set(chip_smoke.native_builders().values()) <= {"built", "cached"}


def test_no_accelerator_exits_nonzero_naming_the_devices():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "CpuDevice" in proc.stderr, proc.stderr[-500:]
    assert proc.stdout.strip() == ""  # no result line


def test_last_stdout_line_is_exactly_the_result_object(capsys):
    chip_smoke.emit({"wall_s_total": 1.0}, jax.devices())
    report, result = map(json.loads, capsys.readouterr().out.splitlines())
    assert report["wall_s_total"] == 1.0
    assert result == {"ok": True, "device": report["device"]}
    device = result["device"]
    assert set(device) == {"platform", "kind", "count"}
    assert device["platform"] == jax.devices()[0].platform
    assert isinstance(device["kind"], str)
    assert device["count"] == len(jax.devices())


class TestCompilationCachePlacement:
    def _updates(self, monkeypatch):
        calls = {}
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: calls.__setitem__(k, v))
        return calls

    def test_env_directory_wins_and_code_sets_none(
        self, monkeypatch, tmp_path
    ):
        placed = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        calls = self._updates(monkeypatch)
        assert backend.enable_compilation_cache() == placed
        assert os.path.isdir(placed)
        assert "jax_compilation_cache_dir" not in calls

    def test_default_is_the_fixed_checkout_directory(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        calls = self._updates(monkeypatch)
        expected = os.path.join(REPO_ROOT, ".jax_cache")
        assert backend.enable_compilation_cache() == expected
        assert calls["jax_compilation_cache_dir"] == expected
        # a kernel's bytes, and so a program's key, must not follow the
        # caller's stack (tests/test_spd_solve.py reads the bytes)
        assert calls["jax_traceback_in_locations_limit"] == 1
