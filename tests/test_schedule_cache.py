"""Persistent tile-schedule cache (ops/schedule_cache.py): disk-tier
hit/miss/corruption behavior, bit-identical reloads, the two bounded
in-memory LRU tiers in front of it, and the multi-host write-once /
read-many protocol."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import make_sparse_batch
from photon_ml_tpu.ops import schedule_cache as sc
from photon_ml_tpu.ops import tiled_sparse as ts
from photon_ml_tpu.ops.tiled_sparse import TileParams, tiled_batch_from_sparse

PARAMS = TileParams(s_hi=8, s_lo=8, chunk=32)  # window 64, tiny for tests


@pytest.fixture(autouse=True)
def _fresh_cache_state():
    """Process-global cache state must not leak between tests."""
    sc.reset_stats()
    ts._TILED_CACHE.clear()
    ts._SHARDED_CACHE.clear()
    yield
    sc.reset_stats()
    ts._TILED_CACHE.clear()
    ts._SHARDED_CACHE.clear()


def _coo(rng, n_entries=400, out_space=512, in_space=512):
    rows = rng.integers(0, out_space, size=n_entries).astype(np.int64)
    feats = rng.integers(0, in_space, size=n_entries).astype(np.int64)
    vals = rng.normal(size=n_entries).astype(np.float32)
    vals[vals == 0] = 1.0
    return rows, feats, vals


def _build(rows, feats, vals, *, feat_sorted=False, blocks=8):
    return ts._build_schedule_np(
        rows, feats, vals, params=PARAMS,
        sort_by_feature_block=feat_sorted, num_out_blocks=blocks,
    )


def random_problem(rng, n=100, d=150, k=6):
    rows, labels = [], []
    for _ in range(n):
        nnz = int(rng.integers(1, k + 1))
        ix = rng.choice(d, size=nnz, replace=False).tolist()
        vs = rng.normal(size=nnz).tolist()
        labels.append(float(rng.uniform() > 0.5))
        rows.append((ix, vs))
    return make_sparse_batch(rows, labels), d


def _assert_schedules_equal(a, b):
    for i, (x, y) in enumerate(zip(a, b)):
        xa, ya = np.asarray(x), np.asarray(y)
        assert xa.dtype == ya.dtype, f"array {i} dtype"
        assert xa.shape == ya.shape, f"array {i} shape"
        assert np.array_equal(xa, ya), f"array {i} content"


class TestDiskTier:
    def test_miss_then_hit_roundtrip(self, rng, tmp_path):
        rows, feats, vals = _coo(rng)
        with sc.cache_scope(str(tmp_path)):
            fresh = _build(rows, feats, vals)
            s1 = sc.stats()
            assert (s1.misses, s1.builds, s1.stores) == (1, 1, 1)
            reloaded = _build(rows, feats, vals)
            s2 = sc.stats()
        assert s2.hits == 1 and s2.builds == 1  # no second build
        _assert_schedules_equal(fresh, reloaded)

    def test_key_separates_passes_and_params(self, rng, tmp_path):
        rows, feats, vals = _coo(rng)
        digest = sc.content_digest(rows, feats, vals)
        k1 = sc.schedule_key(digest, PARAMS, False, 8)
        assert k1 == sc.schedule_key(digest, PARAMS, False, 8)
        assert k1 != sc.schedule_key(digest, PARAMS, True, 8)
        assert k1 != sc.schedule_key(digest, PARAMS, False, 9)
        import dataclasses

        other = dataclasses.replace(PARAMS, chunk=64)
        assert k1 != sc.schedule_key(digest, other, False, 8)
        # content participates: one flipped value changes the digest
        vals2 = vals.copy()
        vals2[0] += 1.0
        assert digest != sc.content_digest(rows, feats, vals2)

    def test_version_bump_falls_back_to_rebuild(
        self, rng, tmp_path, monkeypatch
    ):
        rows, feats, vals = _coo(rng)
        with sc.cache_scope(str(tmp_path)):
            _build(rows, feats, vals)
            monkeypatch.setattr(sc, "SCHEDULE_CACHE_VERSION", 999)
            _build(rows, feats, vals)
            s = sc.stats()
        # the bumped version neither hit the old artifact nor crashed:
        # it rebuilt and stored under the new version namespace
        assert s.hits == 0 and s.builds == 2 and s.stores == 2

    def test_corrupted_artifact_falls_back_to_rebuild(self, rng, tmp_path):
        rows, feats, vals = _coo(rng)
        digest = sc.content_digest(rows, feats, vals)
        key = sc.schedule_key(digest, PARAMS, False, 8)
        with sc.cache_scope(str(tmp_path)):
            fresh = _build(rows, feats, vals)
            # flip bytes inside the artifact (within the spot-checksum
            # window) — the damaged artifact must be rejected, not served
            path = os.path.join(
                sc._artifact_dir(str(tmp_path), key), "vals.npy"
            )
            with open(path, "r+b") as f:
                f.seek(200)
                f.write(b"\xff" * 32)
            rebuilt = _build(rows, feats, vals)
            s = sc.stats()
        assert s.corrupt >= 1 and s.builds == 2
        _assert_schedules_equal(fresh, rebuilt)

    def test_truncated_artifact_rejected(self, rng, tmp_path):
        rows, feats, vals = _coo(rng)
        digest = sc.content_digest(rows, feats, vals)
        key = sc.schedule_key(digest, PARAMS, False, 8)
        with sc.cache_scope(str(tmp_path)):
            _build(rows, feats, vals)
            path = os.path.join(
                sc._artifact_dir(str(tmp_path), key), "in_pos.npy"
            )
            size = os.path.getsize(path)
            with open(path, "r+b") as f:
                f.truncate(size // 2)
            assert sc.load_schedule(str(tmp_path), key) is None

    def test_bit_identical_tiled_batch_on_reload(self, rng, tmp_path):
        batch, d = random_problem(rng)
        tb_nocache = tiled_batch_from_sparse(batch, d, params=PARAMS)
        with sc.cache_scope(str(tmp_path)):
            tb_cold = tiled_batch_from_sparse(batch, d, params=PARAMS)
            tb_warm = tiled_batch_from_sparse(batch, d, params=PARAMS)
            s = sc.stats()
        assert s.hits == 2  # z + g pass both reloaded
        for tb in (tb_cold, tb_warm):
            _assert_schedules_equal(tb_nocache.z_sched, tb.z_sched)
            _assert_schedules_equal(tb_nocache.g_sched, tb.g_sched)
        assert tb_warm.meta == tb_nocache.meta

    def test_cache_off_by_default(self, rng):
        assert sc.resolve_cache_dir() is None  # hermetic under pytest
        rows, feats, vals = _coo(rng)
        _build(rows, feats, vals)
        s = sc.stats()
        assert (s.hits, s.misses, s.stores) == (0, 0, 0)
        assert s.builds == 1  # the build seam still counts

    def test_scope_overrides_configure(self, tmp_path):
        try:
            sc.configure(str(tmp_path / "configured"))
            assert sc.resolve_cache_dir() == str(tmp_path / "configured")
            with sc.cache_scope(str(tmp_path / "scoped")):
                assert sc.resolve_cache_dir() == str(tmp_path / "scoped")
            sc.configure("")  # explicit off beats the env var
            assert sc.resolve_cache_dir() is None
        finally:
            sc.configure(None)


class TestMemoryTiers:
    def test_lru_hit_refreshes_and_eviction_order(self):
        lru = sc.ScheduleLRU(2)
        lru.put("a", 1)
        lru.put("b", 2)
        assert lru.get("a") == 1  # refresh "a" -> "b" is now LRU
        lru.put("c", 3)
        assert lru.get("b") is None  # evicted
        assert lru.get("a") == 1 and lru.get("c") == 3
        assert len(lru) == 2

    def test_interleaved_tiled_and_sharded_build_once(self, rng):
        """Regression (round 5): interleaving ensure_tiled and
        ensure_tiled_sharded must not evict each other's schedules — each
        layout is built exactly once per process."""
        from photon_ml_tpu.ops.tiled_sparse import (
            ensure_tiled,
            ensure_tiled_sharded,
        )
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

        batch, d = random_problem(rng)
        mesh = make_mesh((2,), (DATA_AXIS,), devices=jax.devices()[:2])
        ensure_tiled(batch, d, params=PARAMS)
        ensure_tiled_sharded(batch, d, mesh, params=PARAMS)
        builds_after_first = sc.stats().builds
        assert builds_after_first > 0
        for _ in range(3):
            ensure_tiled(batch, d, params=PARAMS)
            ensure_tiled_sharded(batch, d, mesh, params=PARAMS)
        assert sc.stats().builds == builds_after_first

    @pytest.mark.parametrize("layout", ["tiled", "sharded"])
    def test_hit_path_pads_device_rows_on_the_device(self, rng, layout):
        """A cache hit re-pads labels, offsets and weights only; offsets
        that are already on the device (coordinate descent's residual)
        are padded there: the hit path traces under jit, where a pull to
        the host would raise."""
        from photon_ml_tpu.ops.tiled_sparse import (
            ensure_tiled,
            ensure_tiled_sharded,
        )
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

        batch, d = random_problem(rng, n=64)
        if layout == "tiled":
            ensure = lambda b: ensure_tiled(b, d, params=PARAMS)
        else:
            mesh = make_mesh((2,), (DATA_AXIS,), devices=jax.devices()[:2])
            ensure = lambda b: ensure_tiled_sharded(b, d, mesh, params=PARAMS)
        built = ensure(batch)
        builds = sc.stats().builds
        offsets = jnp.asarray(rng.normal(size=64), jnp.float32)
        padded = jax.jit(
            lambda off: ensure(batch._replace(offsets=off)).offsets
        )(offsets)
        assert sc.stats().builds == builds
        assert padded.shape == built.offsets.shape
        got = np.asarray(padded)
        # (the sharded layout pads each shard's rows to the window)
        assert np.isin(np.asarray(offsets), got).all()
        assert np.count_nonzero(got) == 64

    def test_sharded_pressure_does_not_evict_tiled(self, rng):
        """Several sharded conversions (> the sharded LRU bound) while a
        tiled conversion stays live: the tiled entry must survive."""
        from photon_ml_tpu.ops.tiled_sparse import (
            ensure_tiled,
            ensure_tiled_sharded,
        )
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

        mesh = make_mesh((2,), (DATA_AXIS,), devices=jax.devices()[:2])
        tiled_batch, d = random_problem(rng, n=60)
        ensure_tiled(tiled_batch, d, params=PARAMS)
        builds_tiled = sc.stats().builds
        others = [random_problem(rng, n=40 + 8 * i)[0] for i in range(3)]
        for b in others:
            ensure_tiled_sharded(b, d, mesh, params=PARAMS)
        ensure_tiled(tiled_batch, d, params=PARAMS)  # must still be cached
        # the re-ensure added no builds beyond the sharded conversions
        expected = builds_tiled + sum(
            1 for _ in others
        ) * 2 * 2  # 2 shards x (z+g) per sharded conversion
        assert sc.stats().builds == expected


_CHILD = r"""
import json, os, sys, time
import numpy as np

role, cache_dir = sys.argv[1], sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PHOTON_TILE_CACHE_WRITER"] = "1" if role == "writer" else "0"
os.environ["PHOTON_TILE_CACHE_WAIT_S"] = "60"
from photon_ml_tpu.ops import schedule_cache as sc
from photon_ml_tpu.ops import tiled_sparse as ts

rng = np.random.default_rng(7)
rows = rng.integers(0, 512, size=400).astype(np.int64)
feats = rng.integers(0, 512, size=400).astype(np.int64)
vals = rng.normal(size=400).astype(np.float32)
params = ts.TileParams(s_hi=8, s_lo=8, chunk=32)
if role == "writer":
    time.sleep(1.0)  # force the reader to actually wait
with sc.cache_scope(cache_dir):
    arrs = ts._build_schedule_np(
        rows, feats, vals, params=params,
        sort_by_feature_block=False, num_out_blocks=8,
    )
import hashlib
h = hashlib.blake2b(digest_size=16)
for a in arrs:
    h.update(np.ascontiguousarray(a).tobytes())
print(json.dumps({
    "role": role,
    "digest": h.hexdigest(),
    "builds": sc.stats().builds,
    "stores": sc.stats().stores,
}))
"""


class TestMultiHost:
    def test_two_process_write_once_read_many(self, tmp_path):
        """Host 0 builds and writes the artifact exactly once; the other
        process waits for it and reads — zero builds on the reader."""
        cache_dir = str(tmp_path / "cache")
        os.makedirs(cache_dir)

        def launch(role):
            return subprocess.Popen(
                [sys.executable, "-c", _CHILD, role, cache_dir],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            )

        reader = launch("reader")
        time.sleep(0.2)
        writer = launch("writer")
        out = {}
        for proc in (writer, reader):
            stdout, stderr = proc.communicate(timeout=120)
            assert proc.returncode == 0, stderr
            rec = json.loads(stdout.strip().splitlines()[-1])
            out[rec["role"]] = rec
        assert out["writer"]["builds"] == 1
        assert out["writer"]["stores"] == 1
        assert out["reader"]["builds"] == 0  # waited and read, never built
        assert out["reader"]["digest"] == out["writer"]["digest"]

    def test_reader_timeout_builds_locally_without_store(
        self, rng, tmp_path, monkeypatch
    ):
        monkeypatch.setenv(sc.ENV_WRITER, "0")
        monkeypatch.setenv(sc.ENV_WAIT_S, "0.2")
        rows, feats, vals = _coo(rng)
        with sc.cache_scope(str(tmp_path)):
            out = _build(rows, feats, vals)
        s = sc.stats()
        assert s.builds == 1 and s.stores == 0 and s.wait_s > 0
        assert len(out) == len(sc.SCHEDULE_ARRAY_NAMES)
