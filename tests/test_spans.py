"""The tracing seam (`utils/metrics.py`): a stage timer accumulates as
before and gives its self time with no profile running, a served query's
spans and its `serve.path.*` account, and one name per device program.
(`tests/test_cli.py::TestProfilerTrace` holds the `ctx.sql` + `collect`
case under `utils.profiling.trace`.)"""

import os
import re
import threading
import time

import numpy as np
import pytest

from datafusion_tpu.datatypes import DataType, Field, Schema
from datafusion_tpu.exec.batch import make_host_batch
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.datasource import MemoryDataSource
from datafusion_tpu.utils import metrics as metrics_mod
from datafusion_tpu.utils.metrics import METRICS, Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Clock:
    """Stands in for the `time` module inside `utils/metrics.py`."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self) -> float:
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(metrics_mod, "time", c)
    return c


def test_timers_accumulate_and_nest_with_no_profile_running(clock):
    m = Metrics()
    with m.timer("outer") as outer:
        clock.now += 1
        with m.timer("child") as child:
            clock.now += 2
            with m.timer("grandchild"):
                clock.now += 4
        clock.now += 8
        with m.timer("child"):
            clock.now += 16
    assert dict(m.timings) == {"outer": 31.0, "child": 22.0, "grandchild": 4.0}
    assert (outer.wall_s, outer.self_s) == (31.0, 9.0)  # less both children
    assert (child.wall_s, child.self_s) == (6.0, 2.0)
    with pytest.raises(KeyError), m.timer("failing"):
        clock.now += 32
        raise KeyError("the block's own error passes through")
    assert m.timings["failing"] == 32.0


def test_timed_iter_times_the_producer_not_the_consumer(clock):
    m = Metrics()

    def produce():
        for i in range(2):
            clock.now += 1  # the producer's work
            yield i

    with m.timer("outer") as outer:
        for _ in m.timed_iter("scan", produce()):
            clock.now += 10  # the consumer's
    assert m.timings["scan"] == 2.0
    assert (outer.wall_s, outer.self_s) == (22.0, 20.0)


def test_self_time_leaves_out_other_threads(clock):
    m = Metrics()

    def stager():
        with m.timer("stage"):
            clock.now += 5

    with m.timer("query") as query:
        t = threading.Thread(target=stager)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    assert m.timings["stage"] == 5.0
    assert query.self_s == query.wall_s == 5.0


def _served_table():
    schema = Schema([Field("k", DataType.INT64, False),
                     Field("x", DataType.FLOAT64, False)])
    batches = [make_host_batch(schema, [np.arange(512) % 4, np.arange(512.0)])
               for _ in range(2)]
    ctx = ExecutionContext(device="cpu", result_cache=False)
    ctx.register_datasource("t", MemoryDataSource(schema, batches))
    return ctx


def _sql(lit: float) -> str:
    return f"SELECT k, SUM(x) FROM t WHERE x > {lit} GROUP BY k"


def _result_and_account(srv, sql: str, timer: str):
    """The ticket once its result is there and the worker has closed
    `timer` too: a ticket is fulfilled before its worker's account ends."""
    was = METRICS.timings.get(timer, 0.0)
    ticket = srv.submit(sql)
    ticket.result(timeout=60)
    give_up = time.monotonic() + 30
    while METRICS.timings.get(timer, 0.0) == was:
        assert time.monotonic() < give_up
        time.sleep(0.001)
    return ticket


def test_a_served_query_leaves_its_spans_with_its_qid(tmp_path):
    from datafusion_tpu.utils.profiling import trace
    from spans_helper import host_spans

    ctx = _served_table()
    with ctx.serve(window_s=0.002) as srv:
        srv.submit(_sql(1.0)).result(timeout=60)  # compiles outside the trace
        with trace(str(tmp_path)):
            ticket = _result_and_account(srv, _sql(2.0), "serve.finish")
            assert ticket.result().num_rows == 4
    spans = host_spans(str(tmp_path))
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (submit,), (group,), (finish,), (query,) = (
        by_name["dftpu.serve.submit"], by_name["dftpu.serve.group"],
        by_name["dftpu.serve.finish"], by_name["dftpu.query"])
    assert ticket.qid >= 1
    assert submit.stats["qid"] == finish.stats["qid"] == ticket.qid
    assert query.stats["qid"] == ticket.qid
    assert str(group.stats["qids"]).split() == [str(ticket.qid)]
    # client thread, then a worker; the query's span inside the finish's
    assert submit.end <= group.start and group.end <= finish.start
    assert finish.start <= query.start and query.end <= finish.end
    assert query.thread == finish.thread != submit.thread
    assert "dftpu.serve.window" in by_name
    assert {s.name for s in spans if s.thread == submit.thread
            and submit.start <= s.start and s.end <= submit.end} >= {
        "dftpu.parse", "dftpu.plan"}


def test_serve_path_is_the_tickets_wall_split_into_segments():
    ctx = _served_table()
    with ctx.serve(window_s=0.002) as srv:
        _result_and_account(srv, _sql(1.0), "serve.finish")
        before = dict(METRICS.timings)
        walls, client = [before["serve.path.wall"]], []
        for i in range(3):
            t0 = time.perf_counter()
            _result_and_account(srv, _sql(2.0 + i), "serve.finish")
            client.append(time.perf_counter() - t0)
            walls.append(METRICS.timings["serve.path.wall"])
    grew = {k: v - before.get(k, 0.0) for k, v in METRICS.timings.items()
            if k.startswith("serve.path.")}
    wall = grew.pop("serve.path.wall")
    assert set(grew) == {"serve.path." + s for s in (
        "admission", "megabatch_window", "queue_wait", "shared_launch_share",
        "demux_pull", "merge", "other")}
    assert sum(grew.values()) == pytest.approx(wall, rel=0.01)
    # one ticket's wall a query: what its client waited, loosely
    for seen, was, now in zip(client, walls, walls[1:]):
        assert seen / 2 <= now - was <= seen * 2


def test_no_lambda_is_jitted_in_the_engines_operators():
    """A jitted lambda's program is `jit__lambda` in a profile, whichever
    lambda it was: every jitted function under `exec/` has a name."""
    hits = []
    exec_dir = os.path.join(REPO, "datafusion_tpu", "exec")
    for d, _, files in os.walk(exec_dir):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    text = fh.read()
                hits += [f"{f}: {m.group(0)}" for m in
                         re.finditer(r"jit\(\s*lambda", text)]
    assert hits == []


def test_the_wire_decoder_is_named_in_the_device_trace():
    import jax.numpy as jnp

    from datafusion_tpu.exec.batch import _decode_jit

    decoder = _decode_jit((("f32",),))
    wires = ((jnp.zeros(8, jnp.float32),),)
    assert "jit_h2d_wire_decode" in decoder.lower(wires).as_text()[:200]
    assert decoder(wires)[0].dtype == jnp.float64
