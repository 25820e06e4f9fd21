"""The four-chip cell without the chips: the entry ``game_cd_pod`` at a
tiny size on four virtual CPU devices: a sound run, the lower-precision
control, the reference in the program's place, and each fault.

The device count belongs to a process, and the other cells' tests in this
directory run on one device, so the runs are made by ONE child process
(this file as a script) and the tests read what it printed."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "glmix-ads-262m-pod4.cd"
TINY = {"users": 256, "fixed_hashed_dim": 4096}
CHIPS = 4


def _child() -> dict:
    """Every run and comparison, in the child: what each came to."""
    sys.path.insert(0, ROOT)
    import numpy as np

    from benchmark import faults, run
    from benchmark.entries import game_cd_pod

    bench = run.load_json(run.ROOT, "BENCHMARK.json")

    def judged(cell, outputs):
        checks = run.judge(cell.check(outputs), cell.wl["limits"])
        return {k: c["ok"] for k, c in checks.items()}

    kept = []
    out = run.run_cell(
        bench, CELL, 11, 0.2, False, config_override=TINY,
        wrap_cell=lambda c: kept.append(c) or c, keep_outputs=kept,
    )
    cell, outputs = kept
    whole = super(game_cd_pod.Cell, cell).work_per_unit()
    report = {
        "correct": out["correct"], "checks": out["checks"],
        "metrics": sorted(out["metrics"]), "device_count": out["device"]["count"],
        "schedule_shapes": out["extra"]["schedule_shapes"],
        "chips": cell.chips, "bank_shape": list(outputs["bank"].shape),
        "sample_by_owner": np.bincount(cell.sample % CHIPS).tolist(),
        "sample_distinct": len(set(cell.sample.tolist())),
        "work_share": [cell.work_per_unit()[k] / whole[k] for k in sorted(whole)],
        "control_bf16": judged(cell, cell.reference_outputs("bf16")),
        "reference_f32": judged(cell, cell.reference_outputs("f32")),
    }
    for name, plant in game_cd_pod.FAULTS.items():
        report["fault_" + name] = judged(cell, plant(outputs))
    # the fault every entry shares, under the timed path
    game_cd_pod.Cell._row_weights = staticmethod(faults.half_batch)
    half = run.run_cell(bench, CELL, 11, 0.2, False, config_override=TINY)
    report["half_batch"] = {"correct": half["correct"], "failed": half["failed"]}
    return report


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={CHIPS}",
    )
    env.pop("PHOTON_TILE_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=1200,
    )
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_a_sound_run_is_correct_on_the_dense_solver_of_each_shard(report):
    assert report["correct"], report["checks"]
    assert report["metrics"] == ["cd_iter_s", "setup_s"]
    assert report["device_count"] == report["chips"] == CHIPS
    shapes = report["schedule_shapes"]
    assert shapes["block_kinds"] == ["newton"] and shapes["blocks"] == [[256, 16, 32]]
    assert report["bank_shape"] == [256, 1000]


def test_the_bank_is_judged_on_as_many_users_of_every_owner(report):
    assert report["sample_by_owner"] == [64] * CHIPS
    assert report["sample_distinct"] == 256


def test_the_needed_work_is_told_per_chip(report):
    assert report["work_share"] == [1 / CHIPS] * 2


def test_the_lower_precision_control_is_not_correct(report):
    assert not all(report["control_bf16"].values()), report["control_bf16"]


def test_the_reference_in_the_programs_place_is_correct(report):
    assert all(report["reference_f32"].values()), report["reference_f32"]


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_outputs_with_a_fault_planted_are_not_correct(report, fault):
    assert not all(report["fault_" + fault].values()), report["fault_" + fault]


def test_a_run_with_half_of_the_batch_left_out_is_not_correct(report):
    # the steps ran; what they returned is wrong
    assert report["half_batch"] == {"correct": False, "failed": 0}


def _level_overshoot(users=12, rows=6, nnz=8, dim=40, l2=1.0):
    """Sparse rows of ``users`` users, the first of whom has offsets scaled
    (by bisection, in float64) until the full Newton step from zero lands
    level with zero: (indices, values, labels, offsets), that step."""
    import numpy as np

    rng = np.random.default_rng(7)
    ix = np.stack([
        np.stack([rng.permutation(dim)[:nnz] for _ in range(rows)]) for _ in range(users)
    ]).astype(np.int32)
    v = (2.0 * rng.standard_normal((users, rows, nnz))).astype(np.float32)
    y = (rng.random((users, rows)) < 0.5).astype(np.float32)
    off = rng.standard_normal((users, rows)).astype(np.float32)
    y[0], toward = 1.0, -np.abs(off[0]) - 0.5  # every row of user 0 is saturated the wrong way
    X = np.zeros((rows, dim))
    for r in range(rows):
        X[r, ix[0, r]] = v[0, r]

    def f(c, o):
        z = X @ c + o
        return float(np.sum(np.maximum(z, 0) + np.log1p(np.exp(-np.abs(z))) - z)) + 0.5 * l2 * c @ c

    def full_step(o):
        p = 1.0 / (1.0 + np.exp(-o))
        hessian = X.T @ (X * (p * (1 - p))[:, None]) + l2 * np.eye(dim)
        return -np.linalg.solve(hessian, X.T @ (p - 1.0))

    def rise(scale):
        o = (scale * toward).astype(np.float32).astype(np.float64)
        return f(full_step(o), o) - f(np.zeros(dim), o)

    low, high = 1.0, 2.0
    assert rise(low) < 0 < rise(high)
    for _ in range(60):
        mid = 0.5 * (low + high)
        low, high = (mid, high) if rise(mid) < 0 else (low, mid)
    off[0] = (low * toward).astype(np.float32)
    return (ix, v, y, off), full_step(off[0].astype(np.float64)).astype(np.float32)


def test_a_user_on_a_level_overshoot_is_held_to_either_stop_and_no_other_answer():
    import numpy as np

    sys.path.insert(0, ROOT)
    from benchmark import reference
    from benchmark.entries.game_cd_pod import APART_USERS, either_stop

    rows, level = _level_overshoot()
    dim, l2, max_iter, tol = 40, 1.0, 20, 1e-4

    def solve(users, max_iter, tol):
        return reference.solve_users(
            *(a[users] for a in rows), dim, l2, max_iter=max_iter, tol=tol)

    ref = solve(slice(None), max_iter, tol)
    optimum = solve(slice(None), max_iter, -1.0)[0]
    scale = float(np.max(np.abs(ref)))
    assert np.max(np.abs(level - optimum)) > 0.05 * scale  # the two stops are far apart
    held_in_all = 0
    for answer in (level, optimum):  # the configured solve made one of them
        bank = ref.copy()
        bank[0] = answer
        settled, apart, held = either_stop(ref, bank, rows, solve, l2, max_iter, tol)
        assert apart == held and np.max(np.abs(settled - bank)) <= 1e-3 * scale
        held_in_all += held
    assert held_in_all == 1
    # an answer between the two stops is on neither, and stays apart
    bank = ref.copy()
    bank[0] = 0.5 * (level + optimum)
    settled, apart, held = either_stop(ref, bank, rows, solve, l2, max_iter, tol)
    assert (apart, held) == (1, 0) and np.array_equal(settled, ref)
    # more users apart than a draw explains: nobody is looked at again
    bank = ref.copy()
    bank[: APART_USERS + 1] = 0.0
    settled, apart, held = either_stop(ref, bank, rows, solve, l2, max_iter, tol)
    assert (apart, held) == (APART_USERS + 1, 0) and np.array_equal(settled, ref)


if __name__ == "__main__":
    print(json.dumps(_child()), flush=True)
