"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the standard JAX trick for
testing multi-chip sharding without TPUs) — equivalent in spirit to the
reference's planned docker-compose multi-worker smoketest
(`scripts/smoketest.sh:30-66`), but hermetic.  Must run before jax is
imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest


@pytest.fixture(scope="session")
def test_data_dir():
    """Directory of CSV/NDJSON/Parquet fixtures (mirrored from the
    reference's `test/data/`)."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "test", "data"
    )
