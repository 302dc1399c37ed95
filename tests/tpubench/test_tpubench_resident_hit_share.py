"""`resident_hit_share` (`tpubench/metrics/`), the per-layer metric that
reads the engine's `h2d.resident_hits` / `h2d.resident_misses`: on
hand-made runs, on a run with no such counter (an older engine under this
benchmark), and its entry in `BENCHMARK.json`."""

import json
import os
import types

import pytest

from bench_helpers import REPO
from tpubench.spec import Spec


def _run(counts: dict):
    """All the reader touches of a `harness.Run`."""
    return types.SimpleNamespace(queries=2, counts=counts, timings={})


@pytest.mark.parametrize("counts, value", [
    ({"h2d.resident_hits": 1034, "h2d.resident_misses": 0}, 100.0),
    ({"h2d.resident_hits": 0, "h2d.resident_misses": 517}, 0.0),
    ({"h2d.resident_hits": 3, "h2d.resident_misses": 1}, 75.0),
    ({"h2d.resident_hits": 8}, 100.0),  # a counter nothing has bumped yet
    ({"h2d.resident_hits": 0, "h2d.resident_misses": 0}, None),  # no batch
    ({"h2d.bytes": 512, "device.h2d.transfers": 9}, None),  # an older engine
    ({}, None),
])
def test_resident_hit_share_reads_the_two_counters(counts, value):
    got = Spec(REPO).metric_reader("resident_hit_share")(_run(counts))
    assert got == (pytest.approx(value) if value is not None else None)


def test_resident_hit_share_is_a_counter_of_the_h2d_layer_in_every_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = bench["per_layer"][-1]
    assert m == {
        "name": "resident_hit_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "H2D", "moves": "rows_per_s",
        "workloads": [w["name"] for w in bench["workloads"]],
    }
    spec = Spec(REPO)
    for cell in m["workloads"]:
        assert m in spec.metrics_of(cell, "per_layer")
        assert any(e["name"] == "rows_per_s"
                   for e in spec.metrics_of(cell, "end_to_end"))


def test_the_engine_bumps_one_counter_a_batch():
    """The counters the reader names are the ones `device_inputs` bumps."""
    import numpy as np

    from datafusion_tpu.datatypes import DataType, Field, Schema
    from datafusion_tpu.exec.batch import device_inputs, make_host_batch
    from datafusion_tpu.utils.metrics import METRICS

    schema = Schema([Field("v", DataType.FLOAT64, False)])
    batch = make_host_batch(schema, [np.arange(64, dtype=np.float64)])
    before = dict(METRICS.counts)
    device_inputs(batch)
    device_inputs(batch)
    moved = {k: METRICS.counts[k] - before.get(k, 0)
             for k in ("h2d.resident_hits", "h2d.resident_misses")}
    assert moved == {"h2d.resident_hits": 1, "h2d.resident_misses": 1}
    run = types.SimpleNamespace(queries=1, counts=moved, timings={})
    assert Spec(REPO).metric_reader("resident_hit_share")(run) == 50.0
