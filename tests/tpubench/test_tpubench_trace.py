"""The reduction from a profiler trace to busy, idle, top operations and
labelled idle gaps: on hand-made events with known answers, and on two traces
recorded on a TPU v5e (tests/tpubench/data/, made by record_trace_fixture.py):
one under the benchmark's span names only, one with an engine span inside."""

import os

import numpy as np
import pytest

from tpubench import trace_reduce as tr

E = tr.Events.of
HERE = os.path.dirname(os.path.abspath(__file__))


def test_merge_is_the_union_of_intervals():
    s, e = tr.merge(np.array([5.0, 1.0, 1.5, 3.0, 3.0]),
                    np.array([6.0, 2.0, 2.5, 3.0, 3.2]))
    assert s.tolist() == [1.0, 3.0, 5.0] and e.tolist() == [2.5, 3.2, 6.0]
    assert tr.merge(np.zeros(0), np.zeros(0))[0].size == 0


def test_a_nested_interval_does_not_cut_its_parent_short():
    s, e = tr.merge(np.array([0.0, 1.0, 9.0]), np.array([10.0, 2.0, 12.0]))
    assert s.tolist() == [0.0] and e.tolist() == [12.0]


def test_busy_between_any_two_times():
    b = tr.Busy(np.array([1.0, 1.5, 4.0]), np.array([2.0, 2.5, 5.0]))
    assert b.between(0, 10) == pytest.approx(2.5)
    assert b.between(1.2, 4.5) == pytest.approx(1.8)
    assert b.between(2.5, 4.0) == 0
    assert b.between(np.array([0, 2.0]), np.array([1.0, 2.2])).tolist() == \
        pytest.approx([0.0, 0.2])


def test_an_op_counts_only_the_time_its_children_do_not_cover():
    """A `while` event spans the events of its body on the XLA Ops line."""
    start = np.array([0.0, 1.0, 1.5, 4.0, 6.0, 6.0])
    end = np.array([5.0, 3.0, 2.0, 4.5, 7.0, 6.5])
    assert tr.self_seconds(start, end).tolist() == \
        pytest.approx([2.5, 1.5, 0.5, 0.5, 0.5, 0.5])
    assert tr.self_seconds(start, end).sum() == pytest.approx(
        tr.Busy(start, end).between(0, 10))


@pytest.mark.parametrize("event,short", [
    ("%fusion.5 = f32[131072]{0:T(1024)S(1)} fusion(f32[256]{0:T(256)S(1)} "
     "%custom-call.6, s32[131072]{0:T(1024)S(1)} %c), kind=kCustom",
     "fusion.5 fusion f32[131072]"),
    ("%while.4 = (u32[]{:T(128)}, /*index=1*/f32[8]{0:T(128)S(1)}) "
     "while((u32[]{:T(128)}) %tuple.287), condition=%c, body=%b",
     "while.4 while (u32[], f32[8])"),
    ("tpubench.call.collect", "tpubench.call.collect"),
    ("jit__fused_group(12345)", "jit__fused_group(12345)"),
])
def test_hlo_event_names_are_cut_to_name_opcode_and_shape(event, short):
    assert tr.short_name(event) == short


SPANS = E([("tpubench.window", 0.0, 10.0), ("tpubench.request", 0.5, 6.0),
           ("tpubench.call.sql", 0.6, 0.9), ("tpubench.call.collect", 0.9, 5.5),
           ("tpubench.request", 9.0, 12.0)])


def test_segments_are_labelled_by_the_innermost_open_span():
    segs = [(n, round(a, 3), round(b, 3)) for n, a, b in
            tr.label_segments(SPANS, 0.0, 10.0)]
    assert segs == [
        ("no_span", 0.0, 0.5), ("tpubench.request", 0.5, 0.6),
        ("tpubench.call.sql", 0.6, 0.9), ("tpubench.call.collect", 0.9, 5.5),
        ("tpubench.request", 5.5, 6.0), ("no_span", 6.0, 9.0),
        ("tpubench.request", 9.0, 10.0)]
    assert sum(b - a for _, a, b in segs) == pytest.approx(10.0)


def test_an_engine_span_inside_a_benchmark_span_takes_the_label():
    """The engine's stage timers (`dftpu.*`) nest under the benchmark's
    calls on the same clock: the innermost open span labels the segment,
    whichever prefix it has, and every moment is still counted once."""
    spans = E([("tpubench.window", 0.0, 10.0), ("tpubench.request", 1.0, 9.0),
               ("tpubench.call.collect", 2.0, 8.0), ("dftpu.query", 2.5, 7.5),
               ("dftpu.pipeline.wait", 3.0, 5.0),
               ("dftpu.pipeline.stage", 3.5, 4.0),  # the stager's thread
               ("dftpu.device.dispatch", 6.0, 7.0)])
    segs = [(n, a, b) for n, a, b in tr.label_segments(spans, 0.0, 10.0)]
    assert segs == [
        ("no_span", 0.0, 1.0), ("tpubench.request", 1.0, 2.0),
        ("tpubench.call.collect", 2.0, 2.5), ("dftpu.query", 2.5, 3.0),
        ("dftpu.pipeline.wait", 3.0, 3.5), ("dftpu.pipeline.stage", 3.5, 4.0),
        ("dftpu.pipeline.wait", 4.0, 5.0), ("dftpu.query", 5.0, 6.0),
        ("dftpu.device.dispatch", 6.0, 7.0), ("dftpu.query", 7.0, 7.5),
        ("tpubench.call.collect", 7.5, 8.0), ("tpubench.request", 8.0, 9.0),
        ("no_span", 9.0, 10.0)]
    assert sum(b - a for _, a, b in segs) == pytest.approx(10.0)
    # idle seconds by label add up to the window's idle seconds
    ops = E([("fusion.1", 3.2, 3.7), ("fusion.1", 6.5, 8.5)])
    r = tr.reduce(tr.Trace({0: ops}, {}, spans, []))
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["dftpu.pipeline.wait"] == pytest.approx(0.5 + 1.0 - 0.3)
    assert gaps["dftpu.pipeline.stage"] == pytest.approx(0.5 - 0.2)
    assert gaps["dftpu.device.dispatch"] == pytest.approx(0.5)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["busy_s"] == pytest.approx(2.5)


def test_spans_of_several_threads_never_count_a_moment_twice():
    spans = E([("tpubench.window", 0, 4), ("tpubench.call.result", 0, 3),
               ("tpubench.call.result", 1, 4), ("tpubench.call.submit", 2, 2.5)])
    segs = tr.label_segments(spans, 0, 4)
    assert sum(b - a for _, a, b in segs) == pytest.approx(4.0)
    assert [n for n, _, _ in segs].count("tpubench.call.submit") == 1


def _trace():
    ops = E([("fusion.1", 1.0, 2.0), ("copy.2", 1.5, 2.5), ("fusion.1", 4.0, 5.0),
             ("fusion.9", 9.5, 11.0), ("fusion.9", -2.0, -1.0)])
    mods = E([("jit_agg(12)", 0.9, 2.6), ("jit_pull(7)", 3.9, 5.1)])
    return tr.Trace({0: ops}, {0: mods}, SPANS, [])


def test_reduce_gives_known_numbers():
    r = tr.reduce(_trace())
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(1.5 + 1.0 + 0.5)  # clipped at the window
    assert r["chips"] == 1
    # copy.2 starts inside the first fusion.1: the overlap counts once
    assert dict(map(tuple, r["device_ops"])) == pytest.approx({
        "jit_agg:fusion.1": 0.5, "jit_agg:copy.2": 1.0,
        "jit_pull:fusion.1": 1.0, "fusion.9": 0.5})
    assert sum(s for _, s in r["device_ops"]) == pytest.approx(r["busy_s"])
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps == pytest.approx({
        "no_span": 0.5 + 3.0, "tpubench.call.collect": 4.6 - 2.5,
        "tpubench.request": 0.1 + 0.5 + 0.5, "tpubench.call.sql": 0.3})
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert r["longest_gap_s"] == pytest.approx(4.5)  # 5.0 .. 9.5


def test_reduce_averages_over_the_chips_that_ran_something():
    t = _trace()
    t.ops[1] = E([("fusion.1", 2.0, 3.0)])
    t.ops[2] = E([("fusion.1", 20.0, 21.0)])  # nothing inside the window
    r = tr.reduce(t)
    assert r["chips"] == 2 and r["busy_s"] == pytest.approx((3.0 + 1.0) / 2)


def test_at_most_ten_entries_each():
    ops = E([(f"op.{i}", i, i + 0.5) for i in range(30)])
    spans = E([("tpubench.window", 0, 30)] +
              [(f"tpubench.query.q{i}", i, i + 1) for i in range(30)])
    r = tr.reduce(tr.Trace({0: ops}, {}, spans, []))
    assert len(r["device_ops"]) == 10 == len(r["idle_gaps"])


@pytest.mark.parametrize("trace", [
    tr.Trace({}, {}, SPANS, []),  # a CPU rehearsal: no device plane
    tr.Trace({0: E([("op", 1.0, 2.0)])}, {}, E([]), []),  # no window span
    tr.Trace({0: E([("op", 11.0, 12.0)])}, {}, SPANS, []),  # nothing ran in it
])
def test_nothing_to_reduce_gives_nothing(trace):
    assert tr.reduce(trace) is None


# -- the trace recorded on a TPU v5e: three requests of one jitted program of
# four bf16 matmul + tanh steps, a 4 ms host pause inside each request
# (`call.sql`) and a 2 ms pause after it (no span)

@pytest.fixture(scope="module")
def recorded():
    return tr.load(os.path.join(HERE, "data", "tiny_v5e.xplane.pb"))


def test_the_recorded_trace_has_the_planes_and_lines_the_reducer_reads(recorded):
    assert ("/device:TPU:0", "XLA Ops", 18) in recorded.lines
    assert ("/device:TPU:0", "XLA Modules", 3) in recorded.lines
    assert list(recorded.ops) == [0]
    assert recorded.modules[0].names == ["jit_step(14695355594955021225)"] * 3
    assert recorded.spans.names == ["tpubench.window"] + [
        "tpubench.request", "tpubench.call.sql", "tpubench.call.collect"] * 3
    assert sorted(set(recorded.ops[0].names)) == [
        "convolution_tanh_fusion fusion bf16[2048,2048]",
        "convolution_tanh_fusion.1 fusion bf16[2048,2048]",
        "convolution_tanh_fusion.2 fusion bf16[2048,2048]",
        "convolution_tanh_fusion.3 fusion bf16[2048,2048]",
        "copy-done copy-done bf16[2048,2048]",
        "copy-start copy-start (bf16[2048,2048], bf16[2048,2048], u32[])"]


def test_the_recorded_trace_reduces_to_known_numbers(recorded):
    r = tr.reduce(recorded)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.025534289, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.001086045, rel=1e-6)
    # 12 matmul steps of ~0.09 ms: 2 * 2048^3 flop each, near the bf16 peak
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.95747, rel=1e-4)
    ops = dict(map(tuple, r["device_ops"]))
    assert len(ops) == 6 and all(k.startswith("jit_step:") for k in ops)
    assert ops["jit_step:convolution_tanh_fusion fusion bf16[2048,2048]"] == \
        pytest.approx(0.000272955, rel=1e-5)
    assert sum(ops.values()) == pytest.approx(r["busy_s"], rel=1e-6)
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert list(gaps) == ["tpubench.call.sql", "no_span",
                          "tpubench.call.collect", "tpubench.request"]
    assert gaps["tpubench.call.sql"] == pytest.approx(0.012446045, rel=1e-6)  # 3 x ~4 ms
    assert gaps["no_span"] == pytest.approx(0.008143371, rel=1e-6)  # 3 x ~2 ms and the edges
    assert gaps["tpubench.call.collect"] == pytest.approx(0.003776329, rel=1e-6)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-9)
    assert r["longest_gap_s"] == pytest.approx(0.008340351, rel=1e-6)


# -- the same three requests recorded again (PR 27) with the second request's
# pause inside the engine's stage timer `pipeline.wait`: a `dftpu.` span nested
# in `tpubench.call.sql`, from the engine's own seam

@pytest.fixture(scope="module")
def recorded_engine():
    return tr.load(os.path.join(HERE, "data", "tiny_v5e_engine.xplane.pb"))


def test_the_engines_span_is_kept_beside_the_benchmarks(recorded_engine):
    assert recorded_engine.spans.names == ["tpubench.window"] + [
        "tpubench.request", "tpubench.call.sql", "tpubench.call.collect",
        "tpubench.request", "tpubench.call.sql", "dftpu.pipeline.wait",
        "tpubench.call.collect",
        "tpubench.request", "tpubench.call.sql", "tpubench.call.collect"]
    assert ("/device:TPU:0", "XLA Ops", 18) in recorded_engine.lines


def test_an_idle_gap_is_labelled_by_the_engine_span_that_covers_it(
        recorded_engine):
    r = tr.reduce(recorded_engine)
    assert r["window_s"] == pytest.approx(0.024975848, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.001085888, rel=1e-6)
    # busy and idle read as on the earlier recording: the same program, the
    # same pauses (0.95747 there)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.95652, rel=1e-4)
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert list(gaps) == ["no_span", "tpubench.call.sql", "dftpu.pipeline.wait",
                          "tpubench.call.collect", "tpubench.request"]
    # one of the three ~4 ms pauses moved from `call.sql` to the engine's span
    assert gaps["dftpu.pipeline.wait"] == pytest.approx(0.00398235, rel=1e-6)
    assert gaps["tpubench.call.sql"] == pytest.approx(0.00794408, rel=1e-6)
    assert gaps["tpubench.call.sql"] + gaps["dftpu.pipeline.wait"] == \
        pytest.approx(3 * 0.004, rel=0.02)
    # the labelled seconds still add up to the window's idle seconds
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-9)
    ops = dict(map(tuple, r["device_ops"]))
    assert sum(ops.values()) == pytest.approx(r["busy_s"], rel=1e-6)


def test_describe_lists_lines_and_top_ops(recorded):
    text = tr.describe(recorded)
    assert "/device:TPU:0 | XLA Ops | 18 events" in text
    assert "convolution_tanh_fusion fusion bf16[2048,2048]" in text
