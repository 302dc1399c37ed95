#!/usr/bin/env python
"""The reference's own bench list, revived.

`/root/reference/Cargo.toml:50-68` comments out five criterion bench
targets (`read_csv`, `filter_primitive`, `sql_bench`, `dataframe_bench`,
`udf_udt`) and Travis runs `cargo bench` with nothing to execute
(`.travis.yml:30-33`).  These are their working equivalents over the
same fixture (`test/data/uk_cities.csv`, the reference's example
input), micro-scale so they run anywhere in seconds:

    python -m benchmarks.reference_benches

Prints one JSON object with p50 micro-timings per target.  The timed
benchmark is `python3 -m tpubench` (`tpubench/README.md`).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _p50(fn, runs=20, warmup=3):
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return round(float(np.median(times)) * 1e3, 3)


def main():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from datafusion_tpu import DataType, ExecutionContext, Field, Schema, lit

    data = os.path.join(repo, "test", "data", "uk_cities.csv")
    schema = Schema(
        [
            Field("city", DataType.UTF8, False),
            Field("lat", DataType.FLOAT64, False),
            Field("lng", DataType.FLOAT64, False),
        ]
    )

    def fresh_ctx():
        ctx = ExecutionContext()
        ctx.register_csv("cities", data, schema, has_header=False)
        return ctx

    results = {}

    # read_csv: scan + parse the fixture end to end
    ctx = fresh_ctx()
    results["read_csv_ms"] = _p50(
        lambda: ctx.sql_collect("SELECT city, lat, lng FROM cities")
    )

    # filter_primitive: Float64 comparison filter (the reference's
    # filter.rs could only gather Float64/Utf8)
    results["filter_primitive_ms"] = _p50(
        lambda: ctx.sql_collect("SELECT lat FROM cities WHERE lat > 52.0")
    )

    # sql_bench: the full csv_sql.rs statement, parse-to-rows
    results["sql_ms"] = _p50(
        lambda: ctx.sql_collect(
            "SELECT city, lat, lng, lat + lng FROM cities "
            "WHERE lat > 51.0 AND lat < 53"
        )
    )

    # dataframe_bench: the same query through the DataFrame API
    cities = ctx.table("cities")
    lat, lng = cities["lat"], cities["lng"]
    df = (
        cities.filter(lat.gt(lit(51.0)).and_(lat.lt(lit(53.0))))
        .select("city", lat, lng, lat + lng)
    )
    results["dataframe_ms"] = _p50(lambda: df.collect())

    # udf_udt: scalar UDF + struct-producing UDT (the console geo fns)
    from datafusion_tpu.cli import make_context

    geo = make_context()
    geo.register_csv("cities", data, schema, has_header=False)
    results["udf_udt_ms"] = _p50(
        lambda: geo.sql_collect(
            "SELECT ST_AsText(ST_Point(lat, lng)) FROM cities WHERE lat < 53"
        )
    )

    print(json.dumps(results))


if __name__ == "__main__":
    main()
