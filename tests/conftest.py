"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference tests distribution with partitioned local-mode Spark
(photon-test SparkTestUtils.scala:27-70 — `local[4]`, never a real cluster);
we do the same with XLA host devices: 8 virtual CPU devices so every
shard_map / pjit path executes real collectives without TPU hardware.

Must run before any jax import in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Hermetic tier-1: a developer's persistent tile-schedule cache must not
# leak into (or be polluted by) the test run — tests opt in explicitly
# via schedule_cache.cache_scope(tmp_path).
os.environ.pop("PHOTON_TILE_CACHE_DIR", None)
# Likewise the persistent XLA compile cache the drivers switch on at
# start-up (utils/backend.enable_compilation_cache): off for the test
# process, so no run depends on what an earlier one compiled.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration tests (subprocess boots)"
    )


def pytest_collection_modifyitems(items):
    # The whole-program smoke (three drivers end to end) runs after the
    # specific tests: when a run is cut at its time limit, what is lost
    # is the least specific check, and everything before it keeps its
    # place in the run.
    items.sort(key=lambda item: item.path.name == "test_chip_smoke.py")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def game_example_schema():
    """Shared GameExample Avro schema for GAME file-path tests (single
    definition of the test data contract; see photon_ml_tpu.io.schemas
    for the production schemas)."""
    from photon_ml_tpu.io import schemas

    return {
        "name": "GameExample", "type": "record",
        "fields": [
            {"name": "uid", "type": ["null", "string"], "default": None},
            {"name": "response", "type": "double"},
            {
                "name": "metadataMap",
                "type": ["null", {"type": "map", "values": "string"}],
                "default": None,
            },
            {
                "name": "features",
                "type": {"type": "array", "items": schemas.FEATURE_AVRO},
            },
            {
                "name": "userFeatures",
                "type": {"type": "array", "items": "FeatureAvro"},
            },
        ],
    }
