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

While a profile runs every interval also records the CPU seconds of
the thread that ran it (`time.thread_time()`), as
`timings[name + ".cpu"]`: wall less CPU is what the thread spent off the
CPU inside the block, waiting for the interpreter lock, asleep on a
queue or blocked in the runtime (a stager's `pipeline.wait.cpu` in a
scan that waits for its files reads about 1 % of the wall).  It rides in
`timings`, so every reader of the timers carries it with no edit.  Only
while a profile runs, because the clock is a system call: 0.3 us in the
sandbox, ~11 us on the TPU host, where two reads a span were 37 ms of a
347 ms warm Q1 (PERF.md section 6, PR 37); its resolution there is a
10 ms tick, so a sum over a window means something and one span's value
does not.  What the seam itself costs while a profile runs (annotation
and clock reads: 32-66 us a span there) accumulates as
`timings["span.overhead"]` and is in no timer's self time.
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
    timers this thread ran inside it; `cpu_s` is its thread's CPU
    seconds and `stats` what the `_stats` callable gave for the span,
    both None where no profile runs (the clock is then not read, the
    callable not called).  While one runs, an enclosing timer's self
    time also leaves out what the seam itself cost around this
    interval, summed in `timings["span.overhead"]`: a traced
    `query.other` stays the query's own dark time, not the tracing's."""

    __slots__ = ("_metrics", "name", "_annotation", "_stage", "_t0", "_c0",
                 "_g0", "_outer", "wall_s", "cpu_s", "self_s", "stats")

    def __init__(self, metrics: "Metrics", name: str, ids: dict,
                 stats=None):
        global _TRACE_ANNOTATION
        annotation = _TRACE_ANNOTATION
        if annotation is None:
            from jax.profiler import TraceAnnotation as annotation

            _TRACE_ANNOTATION = annotation
        self._metrics = metrics
        self.name = name
        # no profile running (one call into the profiler to ask): no
        # annotation object, a quarter of an entry's cost; the object
        # opens the span as it is made, so `stats` runs before it
        self.stats = self._annotation = self.cpu_s = None
        if annotation.is_enabled():
            # from here to the end of `__exit__` the interval and the
            # seam's own work around it (the annotation, two reads of
            # the CPU clock) are no enclosing timer's self time
            self._g0 = time.perf_counter()
            if stats is not None:
                self.stats = stats()
                ids = {**ids, **self.stats}
            self._annotation = annotation(SPAN_PREFIX + name, **ids)

    def __enter__(self) -> "_Span":
        self._stage = stage_enter(self.name)
        self._outer = getattr(_CHILDREN, "s", 0.0)
        _CHILDREN.s = 0.0
        if self._annotation is not None:
            self._annotation.__enter__()
            self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        wall = self.wall_s = time.perf_counter() - self._t0
        timings = self._metrics.timings
        gross = wall
        if self._annotation is not None:
            cpu = self.cpu_s = time.thread_time() - self._c0
            timings[self.name + ".cpu"] += cpu
            self._annotation.__exit__(*exc)
            gross = time.perf_counter() - self._g0
            timings["span.overhead"] += gross - wall
        self.self_s = wall - _CHILDREN.s
        _CHILDREN.s = self._outer + gross
        timings[self.name] += wall
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

    def timer(self, name: str, _stats=None, **ids) -> _Span:
        """`with METRICS.timer(name):` times the block into
        `timings[name]` and spans it as `dftpu.<name>`; `ids` (`qid=`)
        become the span's stats in a running profile, and so does the
        dict `_stats()` returns: called only while a profile runs and
        before the span opens, for stats that cost something to make
        (`utils/retry.device_call`'s census of a launch's arguments)."""
        return _Span(self, name, ids, _stats)

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
