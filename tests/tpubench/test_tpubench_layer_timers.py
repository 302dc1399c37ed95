"""The ten per-layer metrics that read the engine's stage timers
(`tpubench/metrics/`): each on a hand-made run, on a run with no query,
and on a program that has no such timer (an older engine under this
benchmark)."""

import json
import os
import types

import pytest

from bench_helpers import REPO
from tpubench.spec import Spec

TIMINGS = {
    "scan.parse": 6.9, "h2d.encode": 5.6, "h2d.dispatch": 1.5,
    "pipeline.wait": 8.6, "device.dispatch": 0.32, "query.other": 0.05,
    "d2h.wait": 2.3, "serve.finish": 1.7,
    "serve.path.queue_wait": 3.5, "serve.path.megabatch_window": 0.5,
    "serve.path.wall": 8.0,
}

# metric -> (the timers it reads, its value on TIMINGS over two queries)
CASES = {
    "scan_parse_ms_per_query": (["scan.parse"], 3450.0),
    "h2d_encode_ms_per_query": (["h2d.encode"], 2800.0),
    "h2d_dispatch_ms_per_query": (["h2d.dispatch"], 750.0),
    "stage_wait_ms_per_query": (["pipeline.wait"], 4300.0),
    "launch_dispatch_ms_per_query": (["device.dispatch"], 160.0),
    "query_other_ms_per_query": (["query.other"], 25.0),
    "d2h_wait_ms_per_query": (["d2h.wait"], 1150.0),
    "serve_queue_wait_ms_per_query": (["serve.path.queue_wait"], 1750.0),
    "serve_wait_share": (["serve.path.wall"], 50.0),  # per cent
    "serve_finish_ms_per_query": (["serve.finish"], 850.0),
}


def _run(queries: int, timings: dict):
    """All a reader touches of a `harness.Run`."""
    return types.SimpleNamespace(queries=queries, timings=timings, counts={},
                                 mix={"entry": "serve"})


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_layer_timer_metric_reads_its_timer(name):
    timers, value = CASES[name]
    read = Spec(REPO).metric_reader(name)
    assert read(_run(2, TIMINGS)) == pytest.approx(value)
    assert read(_run(0, TIMINGS)) is None  # no query in the window
    without = {k: v for k, v in TIMINGS.items() if k not in timers}
    assert read(_run(2, without)) is None  # the program has no such timer
    # a timer that exists and did not run in the window reads zero
    assert read(_run(2, {**TIMINGS, **dict.fromkeys(timers, 0.0)})) in (0.0, None)


def test_the_ten_are_program_spans_in_the_cells_the_issue_names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    only = {"scan_parse_ms_per_query": ["q1_sf10_cold"],
            **dict.fromkeys(["serve_queue_wait_ms_per_query", "serve_wait_share",
                             "serve_finish_ms_per_query"], ["q6_sf10_streams"])}
    for name in CASES:
        m = entries[name]
        assert (m["source"], m["better"], m["moves"]) == (
            "program_span", "lower", "rows_per_s")
        assert m["unit"] == ("%" if name == "serve_wait_share" else "ms")
        assert m.get("workloads") == only.get(name)
