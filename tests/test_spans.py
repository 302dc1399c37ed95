"""The tracing seam (`utils/metrics.py`): a stage timer accumulates as
before and gives its self time with no profile running, its thread's CPU
seconds beside its wall seconds while one runs, a served query's spans and its
`serve.path.*` account, the census of what a launch is handed
(`utils/retry.device_call`, only while a profile runs), the mesh's round
assembly as a span of its own, and one name per device program.
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
        self.cpu = 7.0  # the calling thread's CPU seconds
        self.cpu_reads = 0

    def perf_counter(self) -> float:
        return self.now

    def thread_time(self) -> float:
        self.cpu_reads += 1
        return self.cpu


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(metrics_mod, "time", c)
    return c


class _Profiling:
    """Stands in for `jax.profiler.TraceAnnotation` while a profile runs."""

    def __init__(self, name, **ids):
        pass

    @staticmethod
    def is_enabled() -> bool:
        return True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


@pytest.fixture
def profiling(monkeypatch):
    monkeypatch.setattr(metrics_mod, "_TRACE_ANNOTATION", _Profiling)


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
    # the thread's CPU clock is a system call: not read, no `.cpu` timer
    assert clock.cpu_reads == 0 and outer.cpu_s is None


def test_a_timer_records_its_threads_cpu_seconds_while_a_profile_runs(
        clock, profiling):
    """`<name>.cpu` accumulates like the wall, nested spans each keep
    their own, and the self time is the wall's as it was."""
    m = Metrics()
    with m.timer("outer") as outer:
        clock.now += 1
        clock.cpu += 0.5
        with m.timer("child") as child:
            clock.now += 2  # off the CPU: the clock of the thread stands
        with m.timer("child"):
            clock.now += 4
            clock.cpu += 4
    assert dict(m.timings) == {"outer": 7.0, "outer.cpu": 4.5,
                               "child": 6.0, "child.cpu": 4.0,
                               "span.overhead": 0.0}
    assert (outer.wall_s, outer.cpu_s, outer.self_s) == (7.0, 4.5, 1.0)
    assert (child.wall_s, child.cpu_s, child.self_s) == (2.0, 0.0, 2.0)
    with pytest.raises(KeyError), m.timer("failing"):
        clock.cpu += 3
        raise KeyError("the block's own error passes through")
    assert m.timings["failing.cpu"] == 3.0
    # it rides in `timings`: the snapshot and the harness's window delta
    from tpubench.harness import _delta

    before = m.snapshot()["timings_s"]
    assert before["child.cpu"] == 4.0
    with m.timer("child"):
        clock.now += 1
        clock.cpu += 0.25
    grew = _delta(m.snapshot()["timings_s"], before)
    assert (grew["child"], grew["child.cpu"]) == (1.0, 0.25)
    assert grew["outer.cpu"] == 0.0


def test_what_the_seam_costs_while_traced_is_no_timers_self_time(
        clock, monkeypatch):
    """The annotation and the clock reads around a traced interval are
    in its wall not at all and in no enclosing timer's self time: they
    sum in `span.overhead`, so a traced `query.other` stays the query's
    own dark time."""

    class Costly(_Profiling):
        def __init__(self, name, **ids):
            clock.now += 0.25  # made, and the span opened, before the wall

        def __exit__(self, *exc):
            clock.now += 0.5  # after the wall closed

    monkeypatch.setattr(metrics_mod, "_TRACE_ANNOTATION", Costly)
    m = Metrics()
    with m.timer("query") as query:
        clock.now += 1  # the query's own, dark
        for _ in range(2):
            with m.timer("child") as child:
                clock.now += 2
    assert (child.wall_s, child.self_s) == (2.0, 2.0)
    assert m.timings["child"] == 4.0
    assert query.wall_s == 1 + 2 * (0.25 + 2 + 0.5)
    assert query.self_s == 1.0
    assert m.timings["span.overhead"] == 3 * 0.75  # two children, the query


def _busy(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_cpu_seconds_tell_a_sleeping_thread_from_a_working_one(profiling):
    m = Metrics()
    with m.timer("asleep") as asleep:
        time.sleep(0.05)
    assert asleep.wall_s >= 0.05
    assert asleep.cpu_s < asleep.wall_s / 10
    assert m.timings["asleep.cpu"] == asleep.cpu_s
    # a busy loop is all CPU, on a machine that leaves it its core: the
    # suite's workers share theirs, so the best of a few tries
    shares = []
    for _ in range(5):
        with m.timer("busy") as busy:
            _busy(0.03)
        shares.append(busy.cpu_s / busy.wall_s)
        if shares[-1] > 0.9:
            break
    assert max(shares) > 0.9, shares
    assert m.timings["busy.cpu"] <= m.timings["busy"] * 1.01


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


# -- the census of what a launch is handed (`utils/retry.device_call`) ------

def _launch_args():
    """Seven leaves: two on the device, three host values of 32 + 4 + 8
    bytes, a string and a dtype that no call puts; None is no leaf."""
    import jax.numpy as jnp

    on_device = jnp.arange(4.0)
    return (((on_device, np.arange(4.0), None),),
            {"rows": np.int32(3), "scale": 2.0,
             "static": ("f64", np.dtype("int32")), "more": [on_device]})


def _add(pair, rows, scale, static, more):
    return pair[0] + pair[1] * scale + rows + more[0]


CENSUS = ("device.dispatch.leaves", "device.dispatch.host_leaves",
          "device.dispatch.host_bytes")


def _counted(before: dict) -> dict:
    return {k: METRICS.counts.get(k, 0) - before.get(k, 0)
            for k in CENSUS + ("device.launches", "device.launches.census")}


def test_the_census_counts_a_launchs_arguments_while_a_profile_runs(tmp_path):
    from datafusion_tpu.utils.profiling import trace
    from datafusion_tpu.utils.retry import device_call
    from spans_helper import host_spans

    args, kwargs = _launch_args()
    before = dict(METRICS.counts)
    with trace(str(tmp_path)):
        for _ in range(2):
            out = device_call(_add, *args, _tag="census", **kwargs)
        device_call(_add, *args, **kwargs)  # no tag
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0) * 4 + 3)
    assert _counted(before) == {
        "device.dispatch.leaves": 21, "device.dispatch.host_leaves": 9,
        "device.dispatch.host_bytes": 132, "device.launches": 3,
        "device.launches.census": 2}
    spans = [s for s in host_spans(str(tmp_path))
             if s.name == "dftpu.device.dispatch"]
    census = {"leaves": 7, "host": 3, "bytes": 44}
    assert [s.stats for s in spans] == [
        {"tag": "census", **census}, {"tag": "census", **census}, census]


def test_with_no_profile_running_a_launch_flattens_and_annotates_nothing(
        monkeypatch):
    import jax

    from datafusion_tpu.utils import retry

    def refuse(*a, **k):
        raise AssertionError("called with no profile running")

    args, kwargs = _launch_args()
    jitted = jax.jit(_add, static_argnames="static")
    jitted(*args, **kwargs)  # traced and compiled: flattens in Python
    monkeypatch.setattr(jax.tree_util, "tree_leaves", refuse)
    monkeypatch.setattr(retry, "_census", refuse)
    annotation = metrics_mod._TRACE_ANNOTATION
    assert annotation is not None and not annotation.is_enabled()
    monkeypatch.setattr(
        metrics_mod, "_TRACE_ANNOTATION",
        type("Off", (), {"is_enabled": staticmethod(annotation.is_enabled),
                         "__init__": refuse}))
    before, cpu = dict(METRICS.counts), METRICS.timings["device.dispatch.cpu"]
    retry.device_call(jitted, *args, _tag="census", **kwargs)
    assert METRICS.timings["device.dispatch.cpu"] == cpu
    assert _counted(before) == {
        "device.dispatch.leaves": 0, "device.dispatch.host_leaves": 0,
        "device.dispatch.host_bytes": 0, "device.launches": 1,
        "device.launches.census": 1}


def test_a_failed_launch_counts_no_arguments(tmp_path):
    from datafusion_tpu.utils.profiling import trace
    from datafusion_tpu.utils.retry import device_call

    def fails(x):
        raise ValueError("no transient: passes through")

    before = dict(METRICS.counts)
    with trace(str(tmp_path)), pytest.raises(ValueError):
        device_call(fails, np.arange(3))
    assert not any(_counted(before).values())
    assert METRICS.timings["device.dispatch"] > 0


# -- the mesh's round assembly is a span of its own -------------------------

def test_the_mesh_assembles_a_round_under_its_own_timer(tmp_path):
    """`mesh.assemble` closes once a round on the query's own thread, so
    the `query` span's self time (`query.other`) no longer holds it."""
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.parallel import PartitionedContext, make_mesh
    from datafusion_tpu.parallel.partition import PartitionedAggregateRelation
    from tpubench import data as tdata
    from tpubench.spec import Spec

    spec = Spec(REPO)
    lineitem = spec.dataset("tpch_lineitem")
    made = lineitem.generate(5, 6_000, threads=1)
    path = str(tmp_path / "lineitem.parquet")
    tdata.write_parquet(made["tables"]["lineitem"], path, 1_000)
    ctx = PartitionedContext(mesh=make_mesh(4), batch_size=512,
                             result_cache=False)
    ctx.register_resident_parquet("lineitem", path)
    sql = spec.query("tpch_lineitem", "q1").format(
        **lineitem.bind("q1", {"delta": 90}))
    collect(ctx.sql(sql))  # compiles

    spans, assemble = [], PartitionedAggregateRelation._assemble

    def slow_assemble(self, r, dtypes):
        time.sleep(0.02)  # what the consumer's assembly costs, magnified
        return assemble(self, r, dtypes)

    timer = METRICS.timer

    def keeping(name, **ids):
        span = timer(name, **ids)
        if name in ("mesh.assemble", "query"):
            spans.append(span)
        return span

    before = METRICS.snapshot()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(PartitionedAggregateRelation, "_assemble", slow_assemble)
        mp.setattr(METRICS, "timer", keeping)
        got = collect(ctx.sql(sql))
    assert made["oracle"].check("q1", {"delta": 90}, got) is None
    after = METRICS.snapshot()
    rounds = after["counts"]["mesh.rounds"] - before["counts"]["mesh.rounds"]
    assert rounds >= 2
    query, *rounds_spans = spans
    assert query.name == "query"
    assert [s.name for s in rounds_spans] == ["mesh.assemble"] * rounds
    grew = {k: after["timings_s"][k] - before["timings_s"].get(k, 0.0)
            for k in ("mesh.assemble", "query", "query.other")}
    assert grew["mesh.assemble"] >= 0.02 * rounds
    assert grew["mesh.assemble"] == pytest.approx(
        sum(s.wall_s for s in rounds_spans))
    # out of the query's residue
    assert grew["query.other"] == pytest.approx(query.self_s)
    assert query.self_s <= query.wall_s - grew["mesh.assemble"]


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
