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


# (data set, query dir, template) -> the resident columns it must read
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
    assert peaks.required_bytes(sql, SPEC.dataset(dataset).SCHEMA, rows) == \
        rows * per_row


def test_a_column_is_referenced_by_name_not_by_prefix():
    schema = {"id1": "str", "id10": "i64", "v1": "f64"}
    assert peaks.referenced_columns("SELECT id10, SUM(v1) FROM x GROUP BY id10",
                                    schema) == ["id10", "v1"]


def test_roofline_share_is_least_time_over_time_taken():
    # 819 GB at 819 GB/s is one second: taking four is a quarter of the roofline
    assert peaks.roofline_share(819e9, 4.0, "TPU v5 lite") == pytest.approx(0.25)
