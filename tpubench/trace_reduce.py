"""From a JAX profiler trace (`.xplane.pb`) to device busy and idle time,
the device operations that took most of it, and the idle gaps labelled by
what the host was doing: the innermost open span of the benchmark
(`tpubench.*`) or of the engine's stage timers (`dftpu.*`, the seam in
`datafusion_tpu/utils/metrics.py`).

What the trace holds, as read on a TPU v5e with jax 0.9 (PERF.md, PR 22):
one plane per chip named `/device:TPU:<n>` whose line `XLA Ops` has one
event per executed HLO operation and whose line `XLA Modules` has one per
executed program; a plane `/host:CPU` with one line per host thread, on
which `jax.profiler.TraceAnnotation` spans appear under their names.
Event times are nanoseconds from the start of the profile, on one clock
for host and device.

The window that is reduced is the benchmark's own `tpubench.window` span,
so busy and idle refer to exactly the requests that were measured.  All
arithmetic is on numpy arrays of (start, end) and is tested on a recorded
trace (`tests/tpubench/data/`).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("tpubench.", "dftpu.")  # the benchmark's, the engine's
WINDOW_SPAN = "tpubench.window"
TOP_N = 10


@dataclass
class Events:
    """Events of one kind: names and [start, end) in seconds."""
    names: list = field(default_factory=list)
    start: np.ndarray = field(default_factory=lambda: np.zeros(0))
    end: np.ndarray = field(default_factory=lambda: np.zeros(0))

    @classmethod
    def of(cls, rows) -> "Events":
        rows = sorted(rows, key=lambda r: r[1])
        return cls([r[0] for r in rows],
                   np.array([r[1] for r in rows], float),
                   np.array([r[2] for r in rows], float))


@dataclass
class Trace:
    ops: dict  # device index -> Events of the XLA Ops line
    modules: dict  # device index -> Events of the XLA Modules line
    spans: Events  # the host spans of `SPAN_PREFIXES`, all threads
    lines: list  # (plane, line, number of events), for a look by hand


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    ops, modules, span_rows, lines = {}, {}, [], []
    for plane in ProfileData.from_file(path).planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            rows = [(short_name(e.name), e.start_ns / 1e9,
                     (e.start_ns + e.duration_ns) / 1e9) for e in line.events]
            lines.append((plane.name, line.name, len(rows)))
            if dev and line.name == OPS_LINE:
                ops[int(dev.group(1))] = Events.of(rows)
            elif dev and line.name == MODULES_LINE:
                modules[int(dev.group(1))] = Events.of(rows)
            elif not dev:
                span_rows += [r for r in rows if r[0].startswith(SPAN_PREFIXES)]
    return Trace(ops, modules, Events.of(span_rows), lines)


_HLO = re.compile(r"^%?([\w.\-]+) = (.*?)\s([\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*.*?\*/")


def short_name(event_name: str) -> str:
    """The TPU's `XLA Ops` events are named by the whole HLO instruction
    (`%fusion.5 = f32[131072]{0:T(1024)S(1)} fusion(f32[256]{...} ...`):
    keep the instruction's name, its opcode and its result shape without
    layouts (`fusion.5 fusion f32[131072]`).  Other names pass unchanged."""
    m = _HLO.match(event_name)
    if not m:
        return event_name
    shape = _LAYOUT.sub("", m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape[:48]}"


def merge(start: np.ndarray, end: np.ndarray) -> tuple:
    """The union of intervals as disjoint sorted (start, end) arrays."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    first = np.concatenate([[True], s[1:] > e[:-1]])
    last = np.concatenate([first[1:], [True]])
    return s[first], e[last]


def self_seconds(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Each event's duration less the part its children cover.  The `XLA
    Ops` line nests: a `while` event spans the events of its body, so plain
    durations would count the body twice.  `start` must be sorted."""
    own = end - start
    stack: list = []
    for i in np.lexsort((-end, start)):  # a parent before its children
        while stack and end[stack[-1]] <= start[i]:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(end[i], end[stack[-1]]) - start[i]
        stack.append(i)
    return np.maximum(own, 0.0)


class Busy:
    """Disjoint busy intervals with their running total, so that the busy
    time inside any [a, b) is two look-ups."""

    def __init__(self, start: np.ndarray, end: np.ndarray):
        self.s, self.e = merge(start, end)
        self.cum = np.concatenate([[0.0], np.cumsum(self.e - self.s)])

    def _upto(self, t):
        """Busy seconds before time t."""
        t = np.asarray(t, float)
        i = np.searchsorted(self.s, t, side="right")  # intervals begun
        done = self.cum[i]
        over = np.where(i > 0, np.maximum(self.e[np.maximum(i, 1) - 1] - t, 0), 0)
        return done - over

    def between(self, a, b):
        return self._upto(b) - self._upto(a)


def label_segments(spans: Events, lo: float, hi: float) -> list:
    """Cut [lo, hi) into segments labelled by the host span that was open,
    the benchmark's or the engine's: of those open at once (nested, or on several threads) the one
    opened last, which for nested spans is the innermost; "no_span" where
    none was.  Returns (label, start, end) rows."""
    inner = [i for i, n in enumerate(spans.names) if n != WINDOW_SPAN]
    marks = sorted(
        [(max(spans.start[i], lo), 0, i) for i in inner
         if spans.end[i] > lo and spans.start[i] < hi]
        + [(min(spans.end[i], hi), 1, i) for i in inner
           if spans.end[i] > lo and spans.start[i] < hi])
    out, open_, at = [], [], lo
    for t, closes, i in marks:
        if t > at:
            label = spans.names[max(open_)] if open_ else "no_span"
            out.append((label, at, t))
            at = t
        if closes:
            open_.remove(i)
        else:
            open_.append(i)  # indices are in start order: max = latest
    if hi > at:
        out.append(("no_span", at, hi))
    return out


def reduce(trace: Trace, top_n: int = TOP_N) -> "dict | None":
    """{"window_s", "busy_s" (averaged over the chips that ran anything),
    "device_ops": [[name, seconds], ...], "idle_gaps": [[label, seconds],
    ...], "longest_gap_s"}; None where the trace has no window span or no
    device plane (a CPU rehearsal)."""
    win = [i for i, n in enumerate(trace.spans.names) if n == WINDOW_SPAN]
    if not win or not trace.ops:
        return None
    lo, hi = trace.spans.start[win[0]], trace.spans.end[win[0]]
    busy_per_chip, op_time, idle, longest = [], {}, {}, 0.0
    segments = label_segments(trace.spans, lo, hi)
    for dev, ev in trace.ops.items():
        s, e = np.clip(ev.start, lo, hi), np.clip(ev.end, lo, hi)
        busy = Busy(s, e)
        total = float(busy.between(lo, hi))
        if total <= 0:
            continue
        busy_per_chip.append(total)
        for name, secs in _op_seconds(ev, self_seconds(s, e),
                                      trace.modules.get(dev)):
            op_time[name] = op_time.get(name, 0.0) + secs
        for label, a, b in segments:
            idle[label] = idle.get(label, 0.0) + float(
                (b - a) - busy.between(a, b))
        edges_s = np.concatenate([busy.s[busy.e > lo], [hi]])
        edges_e = np.concatenate([[lo], busy.e[busy.e > lo]])
        longest = max(longest, float(np.max(edges_s - edges_e)))
    if not busy_per_chip:
        return None
    n = len(busy_per_chip)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top_n]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top_n]
    return {
        "window_s": float(hi - lo),
        "busy_s": float(np.mean(busy_per_chip)),
        "chips": n,
        "device_ops": [[k, v / n] for k, v in top],
        "idle_gaps": [[k, v / n] for k, v in gaps],
        "longest_gap_s": longest,
    }


def _program(module_event: str) -> str:
    """`jit__fused_group(1234567)` -> `jit__fused_group`."""
    return re.sub(r"\(\d+\)$", "", module_event)


def _op_seconds(ops: Events, seconds: np.ndarray, modules) -> list:
    """(name, total seconds) per operation, the name prefixed with the
    program (`XLA Modules` event) it ran inside, where one encloses it."""
    names, op_id = np.unique(np.asarray(ops.names, dtype=object).astype(str),
                             return_inverse=True)
    progs, prog_id = [""], np.zeros(len(op_id), np.int64)
    if modules is not None and len(modules.start):
        uniq, mod_id = np.unique([_program(n) for n in modules.names],
                                 return_inverse=True)
        progs += [p + ":" for p in uniq]
        j = np.searchsorted(modules.start, ops.start, side="right") - 1
        inside = (j >= 0) & (ops.start < modules.end[np.maximum(j, 0)])
        prog_id = np.where(inside, mod_id[np.maximum(j, 0)] + 1, 0)
    total = np.bincount(prog_id * len(names) + op_id, weights=seconds,
                        minlength=len(progs) * len(names))
    return [(progs[k // len(names)] + names[k % len(names)], float(total[k]))
            for k in np.flatnonzero(total)]


def describe(trace: Trace, top_n: int = 15) -> str:
    """What a trace holds, for a look by hand."""
    out = [f"{p} | {ln} | {n} events" for p, ln, n in trace.lines]
    for dev, ev in trace.ops.items():
        ops = _op_seconds(ev, self_seconds(ev.start, ev.end),
                          trace.modules.get(dev))
        out.append(f"device {dev}: top ops by own seconds, whole trace")
        out += [f"  {v:.6f}  {k}"
                for k, v in sorted(ops, key=lambda kv: -kv[1])[:top_n]]
    return "\n".join(out)
