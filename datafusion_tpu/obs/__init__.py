"""End-to-end observability: hierarchical spans, per-operator runtime
stats, EXPLAIN ANALYZE, and exporters.

The reference engine's only observability is a console wall clock
(`src/bin/console/main.rs:133`) and a `println!` of the plan; this
package explains *where a query's time went* — per operator, per
fragment, per worker:

- `obs.trace` — Dapper-style hierarchical spans (`span(name, **attrs)`)
  with a per-query `TraceContext` that rides fragment requests over the
  wire so worker-side spans parent under the coordinator's dispatch
  span.  Near-zero cost when disabled.
- `obs.stats` — per-operator runtime stats (rows/batches out, device
  execute vs XLA compile time, H2D/D2H bytes, transient retries)
  attached to physical operators (`Relation.stats`).
- `obs.explain` — `EXPLAIN ANALYZE <sql>`: runs the query under a trace
  session and renders the annotated operator tree + span tree.
- `obs.export` — Chrome-trace / Perfetto JSON (coordinator and worker
  timelines merged by trace_id) and a Prometheus-style text dump of the
  engine counters (`utils.metrics.METRICS` is the counter backend —
  nothing is double-counted).
- `obs.recorder` — the always-on query flight recorder: a lock-free
  bounded ring of trace-correlated lifecycle events on every node,
  dumped as JSON on demand, on slow/failed queries, and on crash.
- `obs.otlp` — OTLP/JSON span exporter (file or HTTP, stdlib-only):
  coordinator + worker spans stitch into one distributed trace any
  OpenTelemetry backend renders.
- `obs.aggregate` — per-node latency histograms merged into fleet-wide
  p50/p95/p99 views by the coordinator (worker snapshots piggyback on
  cluster heartbeats); renders as Prometheus gauges and the
  `datafusion-tpu top` view.
- `obs.slo` — SLO watchdog: declared latency/error objectives over
  sliding windows, burn-rate gauges, flight-recorder dump on breach.
- `obs.profiler` — host-side wall-clock sampling profiler (stdlib
  only): collapsed stacks / speedscope output with per-phase and
  per-trace attribution; scoped captures under EXPLAIN ANALYZE and the
  bench cold legs, continuous mode via `DATAFUSION_TPU_PROFILE_HZ`.
- `obs.httpd` — the unified debug HTTP plane (`/debug/metrics`,
  `/debug/flights`, `/debug/hbm`, `/debug/top`, `/debug/profile`,
  `/debug/bundle`) served on `DATAFUSION_TPU_DEBUG_PORT` by workers
  and coordinators; `datafusion-tpu debug-bundle` pulls every live
  member's bundle.

One seam joins these to the device's clock.  A `METRICS` stage timer
(`utils/metrics.py`: `timer`, `timed_iter`) is at once a stage timing
(`\\timing`, `/debug/metrics`, the benchmark's per-layer metrics), the
sampling profiler's stage, and a `dftpu.<name>` span in any running JAX
profile, on `/host:CPU` beside the device plane, with the query's `qid`.
`utils.profiling.trace(dir)` is how an operator takes such a profile; no
flag or environment variable turns the spans on.

Env knobs: `DATAFUSION_TPU_TRACE=1` enables span collection engine-wide;
`DATAFUSION_TPU_TRACE_FILE=path.json` additionally writes a Chrome trace
at process exit; `DATAFUSION_TPU_TRACE_BUF` bounds the in-memory span
buffer (default 100000; overflow counts in `obs.spans_dropped`).
Flight recorder: `DATAFUSION_TPU_FLIGHT[_BUF|_SLOW_S|_DIR|...]`
(obs/recorder.py).  OTLP: `DATAFUSION_TPU_OTLP_FILE` /
`DATAFUSION_TPU_OTLP_ENDPOINT`.  SLOs: `DATAFUSION_TPU_SLO_*`
(obs/slo.py).
"""

from datafusion_tpu.obs.trace import (  # noqa: F401 — public API surface
    TraceContext,
    adopt,
    current_span,
    current_trace,
    disable,
    drain,
    enable,
    enabled,
    ingest,
    session,
    span,
    spans,
)
