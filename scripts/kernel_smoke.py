#!/usr/bin/env python
"""Fused-pass / kernel smoke: the no-recompile-on-repeat guarantee
(same rows from a fresh operator tree) and Pallas interpret-mode parity.

Run by scripts/smoketest.sh on the CPU backend (hermetic); on a host
with an accelerator it exercises the same assertions against the real
device.  Exits nonzero on any violation; prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def build_ctx(device):
    from datafusion_tpu import DataType, ExecutionContext, Field, Schema
    from datafusion_tpu.exec.batch import make_host_batch
    from datafusion_tpu.exec.datasource import MemoryDataSource

    rng = np.random.default_rng(5)
    n = 200_000
    schema = Schema([
        Field("k", DataType.INT64, False),
        Field("v", DataType.FLOAT64, False),
        Field("w", DataType.INT64, False),
    ])
    k = rng.integers(0, 5000, n)  # high cardinality: sort-merge/hash path
    v = rng.normal(size=n)
    w = rng.integers(-1000, 1000, n)
    bs = 1 << 15
    batches = [
        make_host_batch(schema, [k[i:i + bs], v[i:i + bs], w[i:i + bs]],
                        [None] * 3)
        for i in range(0, n, bs)
    ]
    ctx = ExecutionContext(device=device, result_cache=False)
    ctx.register_datasource("t", MemoryDataSource(schema, batches))
    return ctx, n


QUERIES = [
    ("agg_high", "SELECT k, SUM(w), MIN(v), MAX(v), COUNT(1) FROM t "
                 "WHERE v > -2.0 GROUP BY k"),
    ("topk", "SELECT k, v, w FROM t ORDER BY v DESC, w LIMIT 50"),
    ("full_sort", "SELECT w, k FROM t WHERE k < 2500 ORDER BY w, k"),
    ("pipeline", "SELECT k, v * 2.0, w FROM t WHERE w > 0"),
]


def assert_parity(a, b, label):
    for name in a:
        ra, rb = a[name], b[name]
        assert len(ra) == len(rb), f"{label}/{name}: {len(ra)} vs {len(rb)} rows"
        # aggregates arrive in group-discovery order on both paths;
        # sorts in output order — compare sorted for safety
        for x, y in zip(sorted(map(str, ra)), sorted(map(str, rb))):
            assert x == y, f"{label}/{name}: {x!r} != {y!r}"


def main():
    device = os.environ.get("SMOKETEST_DEVICE") or None
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.utils.metrics import METRICS

    # no-recompile-on-repeat: a warm repeat of every query must add
    # ZERO kernel-cache misses and give the same rows
    ctx, _ = build_ctx(device)
    first = {name: collect(ctx.sql(sql)).to_rows() for name, sql in QUERIES}
    METRICS.reset()
    launches = {}
    repeat = {}
    for name, sql in QUERIES:
        before = METRICS.snapshot()["counts"].get("device.launches", 0)
        # fresh operator tree, same fingerprints
        repeat[name] = collect(ctx.sql(sql)).to_rows()
        launches[name] = (
            METRICS.snapshot()["counts"].get("device.launches", 0) - before
        )
    snap = METRICS.snapshot()["counts"]
    misses = snap.get("kernel_cache.misses", 0)
    assert misses == 0, f"warm repeat recompiled: {misses} kernel-cache misses"
    assert_parity(first, repeat, "first-vs-repeat")

    # Pallas interpret-mode parity (kernel code path, CPU interpreter)
    from datafusion_tpu.exec.pallas import hash_build

    rng = np.random.default_rng(9)
    pos = rng.integers(0, 600, 4000).astype(np.int32)
    live = rng.random(4000) > 0.1
    got = hash_build.build_slot_table(pos, live, 600, interpret=True)
    want = hash_build.build_slot_table_numpy(pos, live, 600)
    for g, w in zip(got, want):
        assert (np.asarray(g) == w).all(), "pallas hash_build parity"

    print(json.dumps({
        "name": "kernel_smoke",
        "queries": len(QUERIES),
        "repeat_parity": "exact",
        "warm_kernel_cache_misses": misses,
        "warm_launches": launches,
        "pallas_interpret_parity": "exact",
    }))


if __name__ == "__main__":
    main()
