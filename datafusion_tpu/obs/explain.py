"""EXPLAIN ANALYZE: run the query under a trace session, annotate the
physical operator tree with its measured runtime stats, and render the
merged span timeline (coordinator + worker).

The reference engine printed the logical plan and a wall clock and
nothing else; DataFusion later grew `EXPLAIN ANALYZE` as the standard
way to see per-operator rows and timings — this is that, for the TPU
rebuild, with the distributed path's worker-side fragment spans folded
into the same report.
"""

from __future__ import annotations

import time
from typing import Optional

from datafusion_tpu.obs import trace
from datafusion_tpu.obs.device import _fmt_bytes
from datafusion_tpu.obs.stats import collect_tree, iter_stats


def _fmt_s(s: float) -> str:
    return f"{s * 1e3:.3f}ms" if s < 1.0 else f"{s:.3f}s"


def _op_line(rel) -> str:
    st = rel.stats
    parts = [f"rows={st.rows_out}", f"batches={st.batches_out}",
             f"time={_fmt_s(st.time_s)}"]
    if st.execute_s:
        parts.append(f"device={_fmt_s(st.execute_s)}")
    if st.compile_s:
        parts.append(f"compile={_fmt_s(st.compile_s)}")
    if st.h2d_bytes:
        parts.append(f"h2d={_fmt_bytes(st.h2d_bytes)}")
    if st.d2h_bytes:
        parts.append(f"d2h={_fmt_bytes(st.d2h_bytes)}")
    if st.retries:
        parts.append(f"retries={st.retries}")
    for k, v in st.attrs.items():
        parts.append(f"{k}={v}")
    return f"{rel.op_label()}  [{', '.join(parts)}]"


def _render_spans(span_dicts: list[dict]) -> list[str]:
    """Indent spans under their parents (orphans — e.g. a prefetch
    thread's — sit at the root level) in start-time order."""
    by_id = {s["span_id"]: s for s in span_dicts}
    children: dict[Optional[str], list[dict]] = {}
    for s in span_dicts:
        parent = s.get("parent_id")
        if parent not in by_id:
            parent = None
        children.setdefault(parent, []).append(s)
    for kids in children.values():
        kids.sort(key=lambda s: s["start_ns"])
    lines: list[str] = []

    def walk(parent_id, depth):
        for s in children.get(parent_id, ()):
            dur = max(s["end_ns"] - s["start_ns"], 0) / 1e9
            attrs = s.get("attrs") or {}
            attr_txt = (
                "{" + ", ".join(f"{k}={v}" for k, v in attrs.items()) + "}"
                if attrs
                else ""
            )
            lines.append(
                "  " * depth
                + f"{s['name']}{attr_txt}  {_fmt_s(dur)}  [{s.get('proc', '?')}]"
            )
            walk(s["span_id"], depth + 1)

    walk(None, 0)
    return lines


class ExplainAnalyzeResult:
    """The materialized result of `EXPLAIN ANALYZE <stmt>`: the logical
    plan, the executed operator tree (stats attached), the query's rows
    (`.result`), and the merged span list (`.spans`).  `repr()` renders
    the annotated report; `chrome_trace()` exports the timeline."""

    def __init__(self, plan, root, result, spans: list[dict],
                 trace_id: str, wall_s: float, counters: Optional[dict] = None,
                 phases: Optional[dict] = None, hbm: Optional[dict] = None,
                 host_profile=None, cost: Optional[dict] = None):
        self.plan = plan
        self.root = root
        self.result = result
        self.spans = spans
        self.trace_id = trace_id
        self.wall_s = wall_s
        # per-query engine counter deltas (device launches, compile-
        # cache hits/misses, fused batch groups) — the fused-pass
        # observability satellite
        self.counters = counters or {}
        # cold-path phase breakdown (seconds per phase, obs/device.py)
        # and the query's HBM residency watermark from the device ledger
        self.phases = phases or {}
        self.hbm = hbm or {}
        # host-stack sampling profile of the run (obs/profiler.py):
        # per-phase top frames — WHERE in host code each phase's wall
        # went (None when DATAFUSION_TPU_PROFILE_EXPLAIN=0)
        self.host_profile = host_profile
        # cost-based planner decisions / runtime replans made DURING
        # this query ({"decisions": [...], "replans": [...]}) — the
        # feedback-driven planning subsystem's chosen-vs-default view
        self.cost = cost or {}

    def report(self) -> str:
        lines = [f"EXPLAIN ANALYZE  (trace {self.trace_id}, "
                 f"wall {_fmt_s(self.wall_s)}, rows {self.result.num_rows})"]
        if self.phases:
            from datafusion_tpu.obs.device import phase_bar

            lines.append(
                "Phases: " + phase_bar(self.phases, self.wall_s)
            )
        if self.hbm:
            lines.append(
                f"HBM: peak {_fmt_bytes(self.hbm.get('peak_bytes', 0))} "
                f"(live {_fmt_bytes(self.hbm.get('live_bytes', 0))}, "
                f"{self.hbm.get('buffers', 0)} buffer(s); device ledger)"
            )
        prof = self.host_profile
        if prof is not None and prof.samples:
            # per phase, the top host frames by sample count — the
            # attribution the phase bar can't give ("decode is 70% of
            # the wall" becomes "and it's all in _parse_chunk")
            lines.append(f"Host profile ({prof.summary()}):")
            for phase, d in prof.by_phase(3).items():
                frames = " · ".join(
                    f"{label} ×{count}" for label, count in d["top_frames"]
                )
                lines.append(
                    f"  {phase}: {d['samples']} sample(s) — {frames}"
                )
        for depth, rel in collect_tree(self.root):
            fused_chain = getattr(rel, "_fused_chain", None)
            marker = f"  <- fused pass [{fused_chain}]" if fused_chain else ""
            lines.append("  " * (depth + 1) + _op_line(rel) + marker)
        if self.counters:
            c = self.counters
            lines.append(
                "Fused passes: "
                f"launches_per_pass={c.get('device.launches', 0)}, "
                f"fused_groups={c.get('fused.groups', 0)} "
                f"({c.get('fused.group_batches', 0)} batches), "
                f"kernel_cache hit/miss="
                f"{c.get('kernel_cache.hits', 0)}/"
                f"{c.get('kernel_cache.misses', 0)}"
            )
            if c.get("coord.plan_rejected"):
                # fragments the coordinator refused to dispatch because
                # their plan failed static verification
                lines.append(
                    f"Plans rejected by verification: "
                    f"{c['coord.plan_rejected']}"
                )
        decisions = self.cost.get("decisions") or []
        replans = self.cost.get("replans") or []
        if decisions:
            # chosen-vs-default with the driving observation: the
            # statistics-fed planner shows its work, per decision
            lines.append(f"Cost decisions ({len(decisions)}):")
            for d in decisions:
                where = f" [{d['table']}]" if d.get("table") else ""
                lines.append(
                    f"  {d['decision']}{where}: chose {d['chosen']} "
                    f"(default {d['default']}) — {d['reason']}"
                )
        if replans:
            lines.append(f"Replans ({len(replans)}):")
            for r in replans:
                lines.append(
                    f"  {r['what']}: estimated {r['estimate']}, "
                    f"observed {r['actual']} — {r['action']}"
                )
        worker_spans = sum(
            1 for s in self.spans if str(s.get("proc", "")).startswith("worker")
        )
        lines.append(
            f"Spans ({len(self.spans)} total, {worker_spans} worker-side):"
        )
        lines += ["  " + ln for ln in _render_spans(self.spans)]
        return "\n".join(lines)

    def chrome_trace(self) -> dict:
        from datafusion_tpu.obs.export import chrome_trace

        return chrome_trace(self.spans)

    def write_chrome_trace(self, path: str) -> str:
        from datafusion_tpu.obs.export import write_chrome_trace

        return write_chrome_trace(path, self.spans)

    def otlp(self) -> dict:
        from datafusion_tpu.obs.otlp import spans_to_otlp

        return spans_to_otlp(self.spans)

    def write_otlp(self, path: str) -> str:
        from datafusion_tpu.obs.otlp import write_otlp

        return write_otlp(path, self.spans)

    def __repr__(self):
        return self.report()


class _RootTap:
    """Relation facade whose batches() run through the instrumentation
    seam — gives the ROOT operator its stats (interior operators are
    instrumented by their consumers)."""

    def __init__(self, rel):
        self.rel = rel
        # forward the result-cache capture hook so EXPLAIN ANALYZE runs
        # populate the cache exactly like plain runs (cache/result.py)
        fill = getattr(rel, "_result_cache_fill", None)
        if fill is not None:
            self._result_cache_fill = fill
        # forward the telemetry markers too: an analyzed query is still
        # a query — it feeds the same latency histogram / SLO funnel
        label = getattr(rel, "_telemetry_query", None)
        if label is not None:
            self._telemetry_query = label
            # the funnel's operator-report walk needs the real tree,
            # not this facade
            self._telemetry_root = rel
            # ...and the phase breakdown needs the pre-query stage-timer
            # snapshot the context stamped on the real relation
            pb = getattr(rel, "_phase_before", None)
            if pb is not None:
                self._phase_before = pb
            # explain_analyze exports the COMPLETE drained span set
            # after the run; the funnel's in-flight export would ship
            # an overlapping document missing only the root span
            self._telemetry_skip_otlp = True
        dumps = getattr(rel, "collect_flight_dumps", None)
        if dumps is not None:
            self.collect_flight_dumps = dumps

    @property
    def schema(self):
        return self.rel.schema

    def batches(self):
        return iter_stats(self.rel)


def explain_analyze(ctx, plan,
                    decision_mark: Optional[int] = None) -> ExplainAnalyzeResult:
    """Execute `plan` on `ctx` under a fresh trace session and package
    the annotated result.  The query runs to completion (EXPLAIN
    ANALYZE measures a real execution, not an estimate)."""
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.utils.metrics import METRICS

    _WATCHED = ("device.launches", "kernel_cache.hits",
                "kernel_cache.misses", "fused.groups",
                "fused.group_batches", "coord.plan_rejected")
    before = dict(METRICS.counts)
    # device data-plane instruments (obs/device.py): the phase
    # breakdown diffs the stage timers across the run, and a peak
    # WINDOW makes peak_bytes THIS query's high-water mark without
    # clobbering the process-wide watermark scrapes and fleet.hbm
    # aggregation report
    from datafusion_tpu.obs import device as _device
    from datafusion_tpu.obs.device import (
        LEDGER,
        phase_breakdown,
        phase_snapshot,
    )

    phase_before = phase_snapshot()
    LEDGER.begin_peak_window()
    # profile_sync: launches block on completion inside this run, so
    # the "execute" phase measures device wall instead of async
    # dispatch (which would fold real compute into "d2h").
    # profiler.profile: host-stack sampling for the run — per-phase top
    # frames in the report (the scoped sampler thread lives exactly as
    # long as this block; DATAFUSION_TPU_PROFILE_EXPLAIN=0 opts out)
    from datafusion_tpu.obs import profiler as _profiler
    from datafusion_tpu.obs.recorder import _env_flag

    profile_scope = _profiler.profile(
        name="explain_analyze",
        enabled=_env_flag("DATAFUSION_TPU_PROFILE_EXPLAIN", True),
    )
    # slice out the cost-based planner's decisions / replans made
    # during THIS query: the caller marks the store's decision serial
    # before planning (logical rewrites decide there); lowering and
    # runtime decisions land past the mark during execute/collect
    from datafusion_tpu import cost as _cost

    _cstore = _cost.store()
    _decision_mark = (_cstore.decision_serial if decision_mark is None
                      else decision_mark)
    _replan_mark = time.time()
    with trace.session() as tc, _device.profile_sync(), \
            profile_scope as prof_cap:
        t0 = time.perf_counter()
        with trace.span("query", plan=type(plan).__name__):
            rel = ctx.execute(plan)
            table = collect(_RootTap(rel))
        wall = time.perf_counter() - t0
    cost_view = {
        "decisions": [d for d in list(_cstore.decisions)
                      if d.get("seq", 0) > _decision_mark],
        "replans": [r for r in list(_cstore.replans)
                    if r.get("ts", 0.0) >= _replan_mark],
    }
    host_profile = None if prof_cap is None else prof_cap.report()
    phases = phase_breakdown(phase_before, wall)
    hbm = {"peak_bytes": LEDGER.window_peak_bytes(),
           "live_bytes": LEDGER.live_bytes(),
           "buffers": LEDGER.entries}
    counters = {
        k: METRICS.counts.get(k, 0) - before.get(k, 0) for k in _WATCHED
    }
    # exported as Prometheus gauges (obs/export.py renders
    # METRICS.gauges): last instrumented query's fused-pass facts
    METRICS.gauge("query.launches_per_pass", counters["device.launches"])
    METRICS.gauge(
        "query.kernel_cache_misses", counters["kernel_cache.misses"]
    )
    spans = trace.drain(tc.trace_id)
    spans.sort(key=lambda s: s["start_ns"])
    # env-gated OTLP push of the COMPLETE span set (the in-flight
    # export at the materialization boundary misses the root span)
    from datafusion_tpu.obs.otlp import export_spans

    export_spans(spans)
    return ExplainAnalyzeResult(
        plan, rel, table, spans, tc.trace_id, wall, counters,
        phases=phases, hbm=hbm, host_profile=host_profile,
        cost=cost_view,
    )
