"""Per-stage timing and counters.

The reference's only observability is a wall-clock `Instant` in the
console (`src/bin/console/main.rs:133`) and a `println!` of the plan
(`context.rs:104`).  Here every query records parse/plan/optimize/
compile/execute stage timings plus engine counters (rows scanned,
bytes H2D, jit cache activity) — queryable via
`ExecutionContext.metrics()` and printed by the CLI's `\\timing` mode.

A stage timer (`Metrics.timer` / `timed_iter`) is the engine's one
tracing seam: the same interval accumulates `timings[name]`, publishes
the sampling profiler's stage, and is a `dftpu.<name>` span
(`jax.profiler.TraceAnnotation`) in whatever JAX profile is running —
on `/host:CPU`, nested by thread and time, on the clock the device
plane uses.  With no profile running no annotation is made (one
`is_enabled()` call an entry; PERF.md section 6 has the cost).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import defaultdict

# -- profiler publication tables (obs/profiler.py) --------------------
# While the sampling profiler has at least one active capture, these
# hold {thread_ident: current stage timer name} and {thread_ident:
# current trace_id}; the sampler thread reads them to attribute each
# stack sample to a phase and a query.  They live HERE (not in the
# profiler) so the publishers — `Metrics.timer`, the device-put seam,
# `obs/trace.adopt` — need no new imports and pay exactly one module-
# global read + None check when profiling is off.  All accesses are
# plain dict ops (lock-free per the DF005 contract: publication runs
# inside other subsystems' critical sections).  A table swapped out
# mid-scope means a stale restore writes into an orphaned dict — a
# benign race the profiler tolerates (the next timer entry republishes).
PROFILE_STAGES = None  # type: ignore[var-annotated]
PROFILE_TRACES = None  # type: ignore[var-annotated]

# -- per-client charge scopes (obs/attribution.py) --------------------
# {thread_ident: scope payload} — which client's work this thread is
# doing, published by the serving front door (attribution.client_scope
# / shared_scope) and read by the cost hooks on other subsystems' hot
# paths (utils/retry.device_call launch walls, the obs/device.py H2D
# seam).  Same contract as the profiler tables above: plain dict ops,
# lock-free (DF005), one global read + .get miss when serving is off.
# Always a dict (not None-gated): the readers are per-launch, not
# per-sample, and a dict miss is cheaper than a None dance at every
# publisher.
CLIENT_SCOPES: dict = {}


def set_profile_tables(stages, traces) -> None:
    """Install (or clear, with None/None) the publication tables —
    called by the profiler on first-capture start / last-capture end."""
    global PROFILE_STAGES, PROFILE_TRACES
    PROFILE_STAGES = stages
    PROFILE_TRACES = traces


def stage_enter(name: str):
    """Publish `name` as this thread's active stage for the sampling
    profiler.  Returns a restore token for `stage_exit` (None when no
    profiler is capturing — the disabled cost is one global read)."""
    tbl = PROFILE_STAGES
    if tbl is None:
        return None
    tid = threading.get_ident()
    prev = tbl.get(tid)
    tbl[tid] = name
    return (tbl, tid, prev)


def stage_exit(token) -> None:
    if token is None:
        return
    tbl, tid, prev = token
    if prev is None:
        tbl.pop(tid, None)
    else:
        tbl[tid] = prev


SPAN_PREFIX = "dftpu."

# the number one query's spans share (`qid=` on the spans that begin a
# thread's share of a query): taken at `Server.submit` for a ticket and
# at `collect_columns` for a query no server carries
QUERY_IDS = itertools.count(1)

# per-thread seconds spent inside stage timers that closed within the
# innermost open one: what a span's self time subtracts
_CHILDREN = threading.local()

_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, on first use


class _Span:
    """One stage-timer interval (see the module docstring).  After the
    block, `wall_s` is its duration and `self_s` that less the stage
    timers this thread ran inside it."""

    __slots__ = ("_metrics", "name", "_annotation", "_stage", "_t0",
                 "_outer", "wall_s", "self_s")

    def __init__(self, metrics: "Metrics", name: str, ids: dict):
        global _TRACE_ANNOTATION
        annotation = _TRACE_ANNOTATION
        if annotation is None:
            from jax.profiler import TraceAnnotation as annotation

            _TRACE_ANNOTATION = annotation
        self._metrics = metrics
        self.name = name
        # no profile running (one call into the profiler to ask): no
        # annotation object, a quarter of an entry's cost
        self._annotation = (annotation(SPAN_PREFIX + name, **ids)
                            if annotation.is_enabled() else None)

    def __enter__(self) -> "_Span":
        self._stage = stage_enter(self.name)
        self._outer = getattr(_CHILDREN, "s", 0.0)
        _CHILDREN.s = 0.0
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        wall = self.wall_s = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self.self_s = wall - _CHILDREN.s
        _CHILDREN.s = self._outer + wall
        self._metrics.timings[self.name] += wall
        stage_exit(self._stage)


class Metrics:
    def __init__(self):
        self.timings: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self.gauges: dict[str, float] = {}
        self._declared: set[str] = set()

    def reset(self):
        self.timings.clear()
        self.counts.clear()
        self.gauges.clear()
        for name in self._declared:  # declared names survive resets
            self.counts[name] += 0

    def timer(self, name: str, **ids) -> _Span:
        """`with METRICS.timer(name):` times the block into
        `timings[name]` and spans it as `dftpu.<name>`; `ids` (`qid=`)
        become the span's stats in a running profile."""
        return _Span(self, name, ids)

    def add(self, name: str, n: int = 1):
        self.counts[name] += n

    def declare(self, *names: str) -> None:
        """Materialize counters at zero so their names render in every
        snapshot/scrape from process start.  The contract for metric
        names downstream dashboards depend on BEFORE the code that
        increments them lands (the serving path's admission counters
        are declared this way).  Declared names survive `reset()`."""
        self._declared.update(names)
        for name in names:
            self.counts[name] += 0

    def observe(self, name: str, seconds: float):
        """Fold a duration that has no interval of its own on the
        calling thread into a stage timing (`compile.xla` from the
        jax.monitoring listener, `host.gc_pause`, `query.other`, the
        `serve.path.*` segments); no span.  This registry is the single
        counter backend; see obs/export.py's `prometheus_text` for the
        scrape format."""
        self.timings[name] += seconds

    def timed_iter(self, name: str, it):
        """Wrap a generator so time spent *producing* items (host parse,
        encode) accrues to `name`, while consumer time doesn't."""
        while True:
            with self.timer(name):
                try:
                    item = next(it)
                except StopIteration:
                    return
            yield item

    def gauge(self, name: str, value: float) -> None:
        """Set a point-in-time gauge (last-write-wins): per-query facts
        like `query.launches_per_pass` that counters can't express."""
        self.gauges[name] = value

    def snapshot(self) -> dict:
        return {
            "timings_s": dict(self.timings),
            "counts": dict(self.counts),
            "gauges": dict(self.gauges),
        }


# process-wide registry (a query engine, not a training loop: contention
# is nil and the reference used a global println anyway)
METRICS = Metrics()
