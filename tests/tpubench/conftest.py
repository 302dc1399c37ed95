"""One accepted assertion that no cell added after `q12_sf10_join` can
keep, marked as an expected failure instead of edited (an accepted file of
the benchmark is a `benchmark` PR's to change).

`test_tpubench_q12_join.py:128` holds the LAST name of
`resident_hit_share.workloads` to be `q12_sf10_join`;
`test_tpubench_resident_hit_share.py:38` holds that list to be every cell,
in the order of `workloads`, and a new cell goes to the end of `workloads`.
From the first cell added after PR 28 the two cannot both hold.  What else
that test asserts is asserted again, for both cells, in
`test_tpubench_q3_join3.py::test_the_readers_register_for_their_cell_alone`.
Strict: once the line finds the cell by name, the mark has to go.

Besides: a harness run inside a test sets JAX's persistent-cache threshold
for its process (`tpubench/harness.py`); the fixture below puts it back, so
a test of another directory that lands on the same worker afterwards
(`tests/test_compile_cache.py` reads JAX's own default) finds JAX as it was.
"""

import pytest

SUPERSEDED = ("test_tpubench_q12_join.py::"
              "test_the_joins_five_metrics_register_for_this_cell_alone")
WHY = ("holds q12_sf10_join to be the last name of "
       "resident_hit_share.workloads, which test_tpubench_resident_hit_share.py "
       "holds to every cell in order: a benchmark PR makes line 128 look for "
       "the cell by name")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(SUPERSEDED):
            item.add_marker(pytest.mark.xfail(reason=WHY, strict=True))


@pytest.fixture(autouse=True)
def _jax_cache_threshold_as_found():
    import jax

    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    yield
    jax.config.update(name, before)
