"""One run of one cell: set up, warm up, measure, check, print one line.

    python3 -m tpubench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The process is the only one that touches JAX, so the only holder of the
chip.  Without a TPU (or with fewer chips than the cell asks for) it exits
with code 2 and prints no result.  `--rehearse-rows N` is the sandbox
rehearsal: it needs an explicit `JAX_PLATFORMS=cpu`, cuts the data set's
first table to N rows (the others by the data set's own rule), and prints
counts only, never a time under a device metric's name.

Set-up (everything before the first timed request) is: the data from the
seed on a thread beside JAX's start-up, its Parquet files, the resident
tables the engine's reader makes of them, the programs of this cell's
traffic (compiled, or loaded from the persistent cache at its fixed path
inside the checkout), and warm-up until a pass brings no new program.  With `--trace 1` the window is the
mix's `trace_seconds` under the JAX profiler and the metrics are the cell's
per-layer ones; with `--trace 0` it is `--seconds` and the end-to-end ones.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field

from tpubench import check, traffic
from tpubench import data as tdata
from tpubench.spec import Spec, device_guard

NO_DEVICE_EXIT = 2
WARM_PASSES = 3
TOGETHER_TRIES = 3


@dataclass
class Run:
    """What a metric reader (`metrics/<name>.py: read(run)`) is given."""
    mix: dict
    window: traffic.Window
    done: list  # outcomes that completed and were right
    counts: dict  # the program's counters, after minus before the window
    timings: dict  # the program's stage timers, likewise, in seconds
    spans: object  # entries.Spans of the window
    setup: dict  # setup_s, compile_s, persistent_cache_hits
    compiles_in_window: int
    trace: "dict | None"  # trace_reduce.reduce() of the traced window
    device: dict
    # peaks.query_scan summed over the queries of `done`: the rows of the
    # tables each names, and the least bytes it has to read of them
    rows_scanned: int
    bytes_needed: int
    detail: dict = field(default_factory=dict)

    @property
    def measured_s(self) -> float:
        return self.window.t_close - self.window.t_open

    @property
    def queries(self) -> int:
        return sum(len(o.request.queries) for o in self.done)

    def latencies_ms(self) -> list:
        return [(o.end - o.start) * 1e3 for o in self.done]


class CompileWatch:
    """Backend compilations and persistent-cache hits, as JAX reports
    them (`chip_smoke.py` listens the same way)."""

    def __init__(self, jax):
        self.compiles: list = []  # (perf_counter at the end, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), secs))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def seconds(self) -> float:
        return sum(s for _, s in self.compiles)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.compiles if t0 <= t <= t1)


def _snapshot() -> dict:
    from datafusion_tpu.utils.metrics import METRICS

    snap = METRICS.snapshot()
    return {"counts": dict(snap["counts"]), "timings": dict(snap["timings_s"])}


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def _parse(argv):
    ap = argparse.ArgumentParser(prog="python3 -m tpubench",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-rows", type=int, default=0,
                    help="sandbox rehearsal on JAX_PLATFORMS=cpu at this many "
                         "rows; prints counts only")
    return ap.parse_args(argv)


class CellRun:
    """The state of one run, from the arguments to the last line."""

    def __init__(self, args, spec: Spec, t0: float):
        self.args, self.spec, self.t0 = args, spec, t0
        self.cell = spec.cell(args.workload)
        self.config = spec.config(self.cell["config"])
        self.mix = spec.traffic(self.cell["traffic"])
        self.rehearsal = args.rehearse_rows > 0
        self.rows = args.rehearse_rows or self.config["rows"]
        self.dataset = spec.dataset(self.config["dataset"])
        self.out_dir = os.path.join(spec.root, "chiprun_out", "tpubench")
        os.makedirs(self.out_dir, exist_ok=True)
        self.log = open(os.path.join(self.out_dir, self.cell["name"] + ".log"), "a")

    def say(self, line: str) -> None:
        line = f"[{time.perf_counter() - self.t0:7.2f}] {line}"
        print(line, flush=True)
        self.log.write(line + "\n")
        self.log.flush()

    # -- set-up ----------------------------------------------------------
    def start(self) -> bool:
        """The data on a thread (numpy only: pyarrow does not survive the
        death of a thread that used it, `io/io_thread.py`) while JAX
        reaches the chip; False where the chips are not there."""
        made: dict = {}

        def make():
            try:
                made.update(tdata.prepare(
                    self.dataset, self.config["dataset"], self.args.seed,
                    self.rows, self.spec.root,
                    threads=min(8, os.cpu_count() or 1)))
            except BaseException as e:  # noqa: BLE001 — re-raised below
                made["error"] = e

        maker = threading.Thread(target=make, name="make-data")
        maker.start()
        import jax

        # keep every program, however quick to compile: PR 21 measured 25 s
        # of recompiling sub-second programs per process at JAX's thresholds
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        self.watch = CompileWatch(jax)
        self.devices = jax.devices()
        self.device = {"platform": self.devices[0].platform,
                       "kind": self.devices[0].device_kind,
                       "count": len(self.devices)}
        self.say(f"device {self.device}  jax {jax.__version__}")
        maker.join()
        if not self.rehearsal and (self.device["platform"] != "tpu"
                                   or len(self.devices) < self.cell["chips"]):
            print(f"tpubench: cell {self.cell['name']} needs "
                  f"{self.cell['chips']} TPU chip(s); JAX reports {self.device}",
                  file=sys.stderr)
            return False
        if "error" in made:
            raise made["error"]

        import datafusion_tpu  # noqa: F401 — x64, and the cache's fixed path
        from tpubench import entries

        self.say(f"compile cache {jax.config.jax_compilation_cache_dir}")
        self.oracle = made["oracle"]
        self.spans = entries.Spans()
        engine_device = "cpu" if self.rehearsal else self.config["engine"]["device"]
        paths, self.table_rows = tdata.parquet_files(
            made, self.config["row_group_rows"])
        self.say(f"files ready (found again: {made['cached']})")
        self.entry = self.spec.entry(self.mix["entry"])(
            engine_device, self.config["engine"], paths, self.spans)
        self.say(f"entry ready (rows {self.table_rows})")
        self.maker = traffic.RequestMaker(self.mix, self._sql)
        return True

    def _sql(self, template: str, params: dict) -> str:
        text = self.spec.query(self.config["queries"], template)
        return text.format(**self.dataset.bind(template, params))

    def _programs(self) -> int:
        return (len(self.watch.compiles) + self.watch.cache_hits
                + _snapshot()["counts"].get("kernel_cache.misses", 0))

    def warm_up(self) -> dict:
        """Every literal that compiles a program of its own (`grid`), alone
        and in each of the group sizes the entry point may run as one
        program (`together`); then the mix itself until a pass brings no
        new program."""
        warm = self.mix.get("warmup", {})
        for n in ([1] if warm.get("grid") else []) + warm.get("together", []):
            seen, sends = self._programs(), 0
            for group in self.maker.grid(warm["grid"], n):
                for _ in range(TOGETHER_TRIES if n > 1 else 1):
                    before = self._programs()
                    self.entry.send_together(group)
                    sends += 1
                    if self._programs() > before:
                        break
            self.say(f"warm-up grid {warm['grid']}, {n} together: {sends} "
                     f"sends, {self._programs() - seen} new programs")
        for i in range(WARM_PASSES):
            seen = self._programs()
            w = traffic.run_closed(
                self.maker, self.entry.send, self.args.seed + 1000003 + i,
                self.mix["loop"].get("clients", 1), seconds=1e9,
                max_each=warm.get("requests_each", 1))
            for o in w.outcomes:
                if o.error is not None:
                    raise o.error
            self.say(f"warm-up pass {i}: {len(w.outcomes)} requests, "
                     f"{self._programs() - seen} new programs")
            if self._programs() == seen:
                break
        self.spans.rows.clear()
        return {"compile_s": self.watch.seconds(),
                "persistent_cache_hits": self.watch.cache_hits}

    # -- the window ------------------------------------------------------
    def measure(self, setup: dict) -> Run:
        import jax

        from tpubench import peaks, trace_reduce

        args, seconds = self.args, self.args.seconds
        trace_dir = os.path.join(tdata.data_dir(self.spec.root), "trace",
                                 self.cell["name"])
        if args.trace:
            seconds = min(seconds, self.mix.get("trace_seconds", 3))
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the host spans are the benchmark's own
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        before = _snapshot()
        setup["setup_s"] = time.perf_counter() - self.t0
        self.say(f"set-up done in {setup['setup_s']:.2f} s "
                 f"(compile {setup['compile_s']:.2f} s, "
                 f"{setup['persistent_cache_hits']} cache hits); "
                 f"window {seconds} s")
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                window = traffic.run(self.maker, self.entry.send, args.seed,
                                     seconds)
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        after = _snapshot()
        counts = _delta(after["counts"], before["counts"])
        timings = _delta(after["timings"], before["timings"])
        self.say(f"window closed: {len(window.outcomes)} requests in "
                 f"{window.t_close - window.t_open:.3f} s")

        reduced = self._reduce_trace(trace_dir) if args.trace else None
        done, errors, worst_gap = self._check(window)
        scans = [peaks.query_scan(q.sql, self.dataset.TABLES, self.table_rows)
                 for o in done for q in o.request.queries]
        peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in self.devices[: self.cell["chips"]])
        device = {**self.device, "memory_peak_bytes": peak_bytes}
        if reduced:
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        compiles = (self.watch.between(window.t_open, window.t_close)
                    + counts.get("kernel_cache.misses", 0))
        # what `correct` compares, each number beside its limit: the answers
        # against the oracle (how many were wrong, and the widest relative gap
        # of a float value), and the configuration's guard that the device
        # did the work (`guarantees.device`)
        must_launch, must_be_zero = device_guard(self.config)
        compared = {
            "wrong_answers": {
                "value": sum("wrong answer" in e for e in errors), "at_most": 0},
            "worst_rel_gap": {"value": worst_gap, "at_most": check.RTOL},
            must_launch: {"value": counts.get(must_launch, 0), "at_least": 1},
            **{c: {"value": counts.get(c, 0), "at_most": 0}
               for c in must_be_zero},
        }
        detail = {
            "requests_done": len(done), "errors": errors[:5],
            "compared": compared,
            "compiles_in_window": compiles,
            "setup": setup,
            "counts": {k: v for k, v in counts.items() if v},
            "timings": {k: round(v, 6) for k, v in timings.items() if v},
            "trace": reduced,
        }
        return Run(mix=self.mix, window=window, done=done, counts=counts, timings=timings,
                   spans=self.spans, setup=setup, compiles_in_window=compiles,
                   trace=reduced, device=device,
                   rows_scanned=sum(r for r, _ in scans),
                   bytes_needed=sum(b for _, b in scans), detail=detail)

    def _reduce_trace(self, trace_dir: str) -> "dict | None":
        from tpubench import trace_reduce

        xplane = trace_reduce.find_xplane(trace_dir)
        loaded = trace_reduce.load(xplane)
        with open(os.path.join(self.out_dir,
                               self.cell["name"] + ".trace.txt"), "w") as f:
            f.write(trace_reduce.describe(loaded) + "\n")  # for a look by hand
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = trace_reduce.reduce(loaded)
        if reduced is None and not self.rehearsal:
            raise RuntimeError("the trace holds no device operation inside "
                               "the measured window")
        return reduced

    def _check(self, window: traffic.Window) -> tuple:
        """Every result of the window against the oracle, after it closed:
        (the outcomes that were right, what was wrong with the others, the
        widest relative gap of any float value compared)."""
        done, errors, worst = [], [], check.Worst()
        outcomes = sorted(window.outcomes, key=lambda o: o.request.rid)
        for o in outcomes:
            if o.error is not None:
                errors.append(f"rid {o.request.rid}: {o.error!r}")
                continue
            bad = [d for q, r in zip(o.request.queries, o.results)
                   if (d := self.oracle.check(q.template, q.params, r, worst))]
            if bad:
                errors.append(f"rid {o.request.rid}: wrong answer: {bad[0]}")
            else:
                done.append(o)
            o.results = None  # checked: let it go
        self.say("first requests " + json.dumps(
            [q.sql for o in outcomes[:8] for q in o.request.queries]))
        return done, errors, worst.gap

    # -- the last line ---------------------------------------------------
    def report(self, run: Run) -> dict:
        kind = "per_layer" if self.args.trace else "end_to_end"
        metrics = {}
        for m in self.spec.metrics_of(self.cell["name"], kind):
            if self.rehearsal and m["source"] != "program_counter":
                continue  # a CPU run gives counts, never a device's times
            value = self.spec.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        compared = run.detail["compared"]
        line = {
            "correct": all(
                c["value"] <= c["at_most"] if "at_most" in c
                else c["value"] >= c["at_least"] for c in compared.values()),
            "attempted": len(run.window.outcomes),
            "failed": len(run.window.outcomes) - len(run.done),
            "metrics": metrics,
            "device": run.device,
        }
        if run.trace:
            line["breakdown"] = {"device_ops": run.trace["device_ops"],
                                 "idle_gaps": run.trace["idle_gaps"]}
        line["compared"] = compared  # last in the line, and on stderr below
        self.say("detail " + json.dumps(run.detail, default=str))
        return line


def main(argv=None, t0: "float | None" = None, root: "str | None" = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = _parse(argv)
    if args.rehearse_rows and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        print("tpubench: --rehearse-rows needs an explicit JAX_PLATFORMS=cpu",
              file=sys.stderr)
        return NO_DEVICE_EXIT
    if importlib.util.find_spec("datafusion_tpu") is None:
        print("tpubench: the engine (datafusion_tpu) is not in this checkout",
              file=sys.stderr)
        return NO_DEVICE_EXIT
    cell = CellRun(args, Spec(root) if root else Spec(), t0)
    cell.say(f"cell {cell.cell['name']} seed {args.seed} seconds {args.seconds} "
             f"trace {args.trace}"
             + (f" REHEARSAL rows {cell.rows}" if cell.rehearsal else ""))
    try:
        if not cell.start():
            return NO_DEVICE_EXIT
        try:
            line = cell.report(cell.measure(cell.warm_up()))
        finally:
            cell.entry.close()
    finally:
        cell.log.close()
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():
        limit = next(k for k in c if k != "value")
        print(f"tpubench compared: {name} {c['value']} {limit} {c[limit]}",
              file=sys.stderr, flush=True)
    return 0
