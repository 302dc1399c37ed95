"""The peaks table and the bytes a query has to read."""

import pytest

from bench_helpers import REPO
from tpubench import peaks
from tpubench.spec import Spec

SPEC = Spec(REPO)


def test_v5e_peaks_with_their_source():
    p = peaks.peak("TPU v5 lite")
    assert (p["hbm_gbps"], p["hbm_gb"], p["bf16_tflops"]) == (819.0, 16.0, 197.0)
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_an_unknown_device_kind_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError):
        peaks.peak(kind)
    with pytest.raises(KeyError):
        peaks.roofline_share(1e9, 1.0, kind)


# (data set, template) -> (rows of its one table, bytes a row of the
# resident columns it must read)
CASES = {
    ("tpch_lineitem", "q1"): (60_000_000, 3 * 4 + 4 * 8),  # all seven
    ("tpch_lineitem", "q6"): (60_000_000, 4 + 3 * 8),  # shipdate, price, discount, quantity
    ("h2o_g1", "q1"): (10_000_000, 4 + 8),
    ("h2o_g1", "q2"): (10_000_000, 4 + 4 + 8),
    ("h2o_g1", "q3"): (10_000_000, 4 + 8 + 8),
    ("h2o_g1", "q5"): (10_000_000, 8 + 3 * 8),
}


@pytest.mark.parametrize("dataset,template", list(CASES))
def test_required_bytes_per_template(dataset, template):
    rows, per_row = CASES[(dataset, template)]
    sql = SPEC.query(dataset, template)
    tables = SPEC.dataset(dataset).TABLES
    (table, schema), = tables.items()
    assert peaks.required_bytes(sql, schema, rows) == rows * per_row
    # a query over a data set of one table scans that table, once
    assert peaks.query_scan(sql, tables, {table: rows}) == (rows, rows * per_row)


def test_a_column_is_referenced_by_name_not_by_prefix():
    schema = {"id1": "str", "id10": "i64", "v1": "f64"}
    assert peaks.named_in("SELECT id10, SUM(v1) FROM x GROUP BY id10",
                          schema) == ["id10", "v1"]


TWO = {"fact": {"f_key": "i64", "f_v": "f64", "f_note": "str"},
       "dim": {"d_key": "i64", "d_grp": "str"},
       "dim2": {"d_key": "i64"}}
ROWS = {"fact": 4_000, "dim": 1_000, "dim2": 10}


@pytest.mark.parametrize("sql,rows,nbytes", [
    # both tables, each at its own count and with its own columns
    ("SELECT d_grp, SUM(f_v) FROM fact JOIN dim ON fact.f_key = dim.d_key "
     "GROUP BY d_grp", 5_000, 4_000 * 16 + 1_000 * 12),
    # one table named: the other is not scanned, though it has the column
    ("SELECT COUNT(1) FROM dim WHERE d_key > 5", 1_000, 1_000 * 8),
    # a table is named as a whole word, not as a prefix of another
    ("SELECT COUNT(1) FROM dim2", 10, 0),
    ("SELECT 1", 0, 0),
])
def test_rows_and_bytes_are_summed_over_the_tables_a_query_names(
        sql, rows, nbytes):
    assert peaks.query_scan(sql, TWO, ROWS) == (rows, nbytes)


def test_roofline_share_is_least_time_over_time_taken():
    # 819 GB at 819 GB/s is one second: taking four is a quarter of the roofline
    assert peaks.roofline_share(819e9, 4.0, "TPU v5 lite") == pytest.approx(0.25)
