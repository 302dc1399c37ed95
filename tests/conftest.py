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


# Accepted assertions of the benchmark's own tests that no metric
# appended to `per_layer` can keep (the first two) or that count the
# batches pyarrow cut (the third), marked as expected failures here
# instead of edited (an accepted file under `tests/tpubench/` is a
# `benchmark` PR's to change; `tests/tpubench/conftest.py` marks a
# third the same way).  Strict: once the lines look by name, the marks
# have to go.  What else the first two tests assert is asserted again,
# by name, in `tests/tpubench/test_tpubench_launch_metrics.py`; what
# else the third does, in `tests/test_parquet_recut.py`; what else the
# two launch-metric tests do, in `tests/tpubench/test_tpubench_q18.py`;
# what else its own two do, in `tests/tpubench/test_tpubench_q4.py`;
# what else the Q4 cell's request and rehearsal tests do (the first for
# both its cases), in `tests/test_exists_semi.py`.  A parametrized test is named without its
# cases: every case is marked.
SUPERSEDED = {
    "test_tpubench_resident_hit_share.py::"
    "test_resident_hit_share_is_a_counter_of_the_h2d_layer_in_every_cell":
        "line 38 takes per_layer[-1] to be resident_hit_share, and a new "
        "entry goes to the end of the list: a benchmark PR makes it find "
        "the entry by name",
    "test_tpubench_mesh4.py::test_what_the_benchmark_holds_of_the_cell":
        "line 216 holds the cell to 19 per-layer metrics, and an entry "
        "with no workloads list is every cell's: a benchmark PR makes it "
        "hold the names it means",
    "test_tpubench_q12_join.py::"
    "test_rehearsal_past_2_20_key_slots_probes_on_the_device":
        "line 94 holds 11 probe launches over 1.2 M rows, which counted the "
        "two pieces pyarrow cut at the file's one row-group end; a scan "
        "hands on whole batches since PR 38 and there are 10: a benchmark "
        "PR corrects the digit",
    # PR 39 appends a cell, its configuration and four per-layer entries
    "test_tpubench_launch_metrics.py::"
    "test_the_five_are_appended_after_every_accepted_entry":
        "line 132 holds the five launch metrics to be the last entries of "
        "per_layer, and PR 39's four go to the end of the list: a benchmark "
        "PR makes it find them by name",
    "test_tpubench_launch_metrics.py::"
    "test_what_the_benchmark_holds_of_the_four_chip_cell":
        "line 212 takes workloads[-1] and configs[-1] to be the four-chip "
        "cell's, and PR 39's cell and configuration go to the end of the "
        "lists: a benchmark PR makes it find them by name",
    # the Q4 cell appends a cell, its configuration and two per-layer
    # entries
    "test_tpubench_q18.py::"
    "test_the_launch_five_then_this_cells_four_close_per_layer":
        "line 328 holds q18's four metrics to be the last entries of "
        "per_layer, and the Q4 cell's two go to the end of the list: a "
        "benchmark PR makes it find them by name",
    "test_tpubench_q18.py::test_the_four_chip_cell_by_name":
        "line 346 holds q18_sf10_highcard to be the last name of "
        "resident_hit_share.workloads, and q4_sf10_exists goes to the end of "
        "the list: a benchmark PR makes it find the cells by name",
    # the semi join marks a group of batches a launch
    "test_tpubench_q4.py::test_each_request_marks_and_looks_up_on_the_device":
        "line 235 holds one mark launch per batch; a group of batches is "
        "one launch now: a benchmark PR asserts "
        "`join.semi.mark_batches == lines`",
    "test_tpubench_q4.py::"
    "test_rehearsal_is_correct_and_keeps_its_data_on_the_device":
        "line 263 holds a request to a launch a build batch, 8 at 400,000 "
        "rows; a group of batches is one launch now, 6 a request: a "
        "benchmark PR corrects the count",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for test, why in SUPERSEDED.items():
            if item.nodeid.split("[")[0].endswith(test):
                item.add_marker(pytest.mark.xfail(reason=why, strict=True))
