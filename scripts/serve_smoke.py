"""Serving front-door smoke: closed-loop concurrent load against the
async admission + pinned-table + megabatch path (ROADMAP item 2).

The harness runs the workload the serving arc was built for — many
clients, one hot table — and gates on the acceptance criteria:

1. >= 8 closed-loop clients against a 2-worker serving executor, zero
   failed queries, every answer matching its serialized twin.
2. Megabatch fusion observable: ``serve.megabatch_launches`` > 0 and
   launches-per-query < 1 on the batched phase.
3. Warm pinned-table H2D silence: zero ``device.h2d.transfers`` (and
   zero ``h2d.bytes``) across the warm phase.
4. Throughput: queries/s >= 3x serialized back-to-back execution of
   the same workload.  Both legs run under the same per-launch latency
   floor (``benchmarks/serve_load.launch_floor_plan`` — a stand-in
   for a device launch on CPU-only hosts, default 10 ms;
   DFTPU_SERVE_SMOKE_FLOOR_MS=0 strips it on hosts with a device).
5. p99 within DFTPU_SERVE_SMOKE_P99_S (default 1.0 s) on the timed
   phase.
6. Admission-counter conservation: admitted + shed == submitted, and
   queue-depth sheds are real decisions (exercised with a depth-1
   server).
7. Per-client metering conservation (obs/attribution.py): every
   closed-loop client's device-seconds delta is recorded, their sum is
   within 10% of the measured launch wall over the timed phase, and a
   live ``/debug/tenants`` scrape serves the per-client breakdown.
8. Tail attribution: an induced-queueing phase (one executor, no
   megabatching, a launch floor) breaches a tight p99 SLO whose
   artifact carries the tail explainer ranking ``queue_wait`` as the
   dominant p99 segment.

The load generator, rung warm-up, floor injection, and timed-phase
quantile machinery are shared with the ``concurrency`` bench config
(`benchmarks/serve_load.py`) so the gate and the bench cannot drift.

Run directly:  python scripts/serve_smoke.py
"""

from __future__ import annotations

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

CLIENTS = int(os.environ.get("DFTPU_SERVE_SMOKE_CLIENTS", "8"))
PER_CLIENT = int(os.environ.get("DFTPU_SERVE_SMOKE_QUERIES", "8"))
WORKERS = int(os.environ.get("DFTPU_SERVE_SMOKE_WORKERS", "2"))
ROWS = int(os.environ.get("DFTPU_SERVE_SMOKE_ROWS", "8192"))
FLOOR_MS = float(os.environ.get("DFTPU_SERVE_SMOKE_FLOOR_MS", "10"))
P99_BOUND_S = float(os.environ.get("DFTPU_SERVE_SMOKE_P99_S", "1.0"))
MIN_SPEEDUP = float(os.environ.get("DFTPU_SERVE_SMOKE_SPEEDUP", "3.0"))


def main() -> int:
    import numpy as np

    from benchmarks import data as bdata
    from benchmarks import serve_load
    from datafusion_tpu.errors import QueryShedError
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.obs import attribution
    from datafusion_tpu.obs.aggregate import HISTOGRAMS
    from datafusion_tpu.obs.device import LEDGER
    from datafusion_tpu.testing import faults
    from datafusion_tpu.utils.metrics import METRICS

    def q(lit: float) -> str:
        return (f"SELECT k, SUM(v1), AVG(v2), COUNT(1) FROM t "
                f"WHERE v2 < {lit:.6f} GROUP BY k")

    n_queries = CLIENTS * PER_CLIENT
    lits = [0.1 + 0.8 * i / n_queries for i in range(n_queries)]
    floor = serve_load.launch_floor_plan(FLOOR_MS)

    # -- serialized baseline leg --------------------------------------
    ctx = ExecutionContext(result_cache=False)
    ctx.register_datasource(
        "t", bdata.groupby_batches(ROWS, 64, 1 << 15)[1]
    )
    collect(ctx.sql(q(0.95)))  # compile outside the timing
    if FLOOR_MS > 0:
        faults.install(floor)
    try:
        t0 = time.perf_counter()
        serial_out = [collect(ctx.sql(q(lit))) for lit in lits]
        serial_s = time.perf_counter() - t0
    finally:
        faults.clear()
    qps_serial = n_queries / serial_s
    print(f"serialized: {n_queries} queries in {serial_s:.2f}s "
          f"({qps_serial:.1f} q/s, launch floor {FLOOR_MS} ms)",
          flush=True)

    # -- served leg ---------------------------------------------------
    sctx = ExecutionContext(result_cache=False)
    sctx.register_datasource(
        "t", bdata.groupby_batches(ROWS, 64, 1 << 15)[1]
    )
    srv = sctx.serve(workers=WORKERS, window_s=0.01,
                     megabatch_max=CLIENTS)
    results: dict = {}
    errors: list = []
    try:
        srv.submit(q(0.95)).result(timeout=300)  # pins the table
        assert LEDGER.pins_snapshot(), "table was not pinned"
        # warm every megabatch rung a fragmented window can produce,
        # then one closed-loop round — the timed phase is compile-free
        serve_load.warm_rungs(srv, q, CLIENTS)
        serve_load.closed_loop(srv, q, CLIENTS, PER_CLIENT,
                               lambda i: 0.95 + 4e-4 * i, {}, errors)
        assert not errors, f"warm-up failures: {errors[:3]}"

        # -- timed warm phase, gates armed ----------------------------
        h_before = (HISTOGRAMS["serve.latency"].snapshot()
                    if "serve.latency" in HISTOGRAMS else None)
        before = dict(METRICS.counts)
        meter_before = {
            cid: dict(costs)
            for cid, costs in attribution.METER.snapshot().items()
        }
        dispatch_before = METRICS.timings.get("device.dispatch", 0.0)
        if FLOOR_MS > 0:
            faults.install(floor)
        try:
            served_s = serve_load.closed_loop(
                srv, q, CLIENTS, PER_CLIENT, lambda i: lits[i],
                results, errors,
            )
        finally:
            faults.clear()
    finally:
        srv.stop()

    # gate 1: zero failures, exact answers, exactly-once per client
    assert not errors, f"{len(errors)} served queries failed: {errors[:3]}"
    assert len(results) == n_queries, (len(results), n_queries)
    for i, lit in enumerate(lits):
        got = sorted(results[divmod(i, PER_CLIENT)].to_rows())
        want = sorted(serial_out[i].to_rows())
        assert len(got) == len(want), f"lit={lit}"
        for g, w in zip(got, want):
            for gv, wv in zip(g, w):
                np.testing.assert_allclose(gv, wv, rtol=1e-9,
                                           err_msg=f"lit={lit}")
    qps_served = n_queries / served_s
    delta = {k: v - before.get(k, 0) for k, v in METRICS.counts.items()}
    print(f"served: {n_queries} queries in {served_s:.2f}s "
          f"({qps_served:.1f} q/s) — zero failures, answers match",
          flush=True)

    # gate 2: megabatch fusion observable, launches amortized
    mega = delta.get("serve.megabatch_launches", 0)
    launches = delta.get("device.launches", 0)
    assert mega > 0, "no megabatched launches on the batched phase"
    assert launches < n_queries, (
        f"{launches} launches for {n_queries} queries — not amortized"
    )
    print(f"megabatching: {mega} fused launches, "
          f"{launches / n_queries:.3f} launches/query", flush=True)

    # gate 3: warm pinned table moved zero bytes H2D
    h2d_events = delta.get("device.h2d.transfers", 0)
    h2d_bytes = delta.get("h2d.bytes", 0)
    assert h2d_events == 0 and h2d_bytes == 0, (
        f"warm phase moved H2D: {h2d_events} transfers, "
        f"{h2d_bytes} bytes"
    )
    print("pinned table: 0 H2D transfers / 0 bytes across the warm "
          "phase", flush=True)

    # gate 4: throughput
    speedup = qps_served / qps_serial
    assert speedup >= MIN_SPEEDUP, (
        f"served {qps_served:.1f} q/s is only {speedup:.2f}x the "
        f"serialized {qps_serial:.1f} q/s (need >= {MIN_SPEEDUP}x)"
    )
    print(f"throughput: {speedup:.2f}x serialized "
          f"(gate >= {MIN_SPEEDUP}x)", flush=True)

    # gate 5: timed-phase p99
    p50, p99 = serve_load.phase_quantiles(
        HISTOGRAMS.get("serve.latency"), h_before
    )
    assert p99 is not None and p99 <= P99_BOUND_S, (
        f"timed-phase p99 {p99}s exceeds {P99_BOUND_S}s"
    )
    print(f"latency: timed-phase p50 {p50}s p99 {p99}s "
          f"(bound {P99_BOUND_S}s)", flush=True)

    # gate 6: admission conservation + a real queue-depth shed
    assert srv.admitted + srv.shed == srv.submitted, (
        srv.admitted, srv.shed, srv.submitted
    )
    tiny = sctx.serve(workers=1, window_s=0.005, queue_depth=1)
    shed = 0
    tickets = []
    try:
        for i in range(8):
            try:
                tickets.append(tiny.submit(q(0.91 + i * 1e-3)))
            except QueryShedError as e:
                assert e.reason == "queue"
                shed += 1
        for t in tickets:
            t.result(timeout=300)
    finally:
        tiny.stop()
    assert shed >= 1, "depth-1 queue never shed under a burst"
    assert tiny.admitted + tiny.shed == tiny.submitted
    print(f"admission: conservation holds "
          f"(admitted {srv.admitted} + shed {srv.shed} == submitted "
          f"{srv.submitted}); depth-1 server shed {shed}/8", flush=True)

    # gate 7: per-client metering sums to the fleet's measured launch
    # wall (within 10%) over the timed phase, and /debug/tenants
    # serves the per-client breakdown live
    import json
    import urllib.request

    meter_after = attribution.METER.snapshot()

    def _delta(cid: str, key: str) -> float:
        return (meter_after.get(cid, {}).get(key, 0.0)
                - meter_before.get(cid, {}).get(key, 0.0))

    client_ids = [f"c{ci}" for ci in range(CLIENTS)]
    for cid in client_ids:
        assert _delta(cid, "queries") == PER_CLIENT, (
            cid, _delta(cid, "queries"))
    dev_sum = sum(_delta(cid, "device_seconds") for cid in client_ids)
    launch_wall = (METRICS.timings.get("device.dispatch", 0.0)
                   - dispatch_before)
    assert launch_wall > 0, "timed phase dispatched no launches?"
    ratio = dev_sum / launch_wall
    assert 0.9 <= ratio <= 1.1, (
        f"per-client device-seconds {dev_sum:.4f}s vs measured launch "
        f"wall {launch_wall:.4f}s — conservation off ({ratio:.3f})"
    )
    from datafusion_tpu.obs.httpd import start_debug_server

    dbg = start_debug_server(-1)
    assert dbg is not None, "ephemeral debug plane failed to bind"
    try:
        with urllib.request.urlopen(
            f"{dbg.url}/debug/tenants", timeout=10
        ) as resp:
            doc = json.loads(resp.read())
    finally:
        dbg.close()
    for cid in client_ids:
        assert cid in doc["clients"], f"{cid} missing from /debug/tenants"
        assert doc["clients"][cid]["device_seconds"] > 0
    assert doc["conservation"]["launch_wall_s"] > 0
    print(f"metering: {len(client_ids)} clients, per-client "
          f"device-seconds sum {dev_sum:.4f}s vs launch wall "
          f"{launch_wall:.4f}s ({ratio * 100:.1f}%), /debug/tenants "
          f"serves all clients", flush=True)

    # gate 8: induced queueing names queue_wait as the dominant tail
    # segment, and the SLO breach artifact carries the tail explainer
    import glob
    import tempfile

    from datafusion_tpu.obs import recorder
    from datafusion_tpu.obs import slo as slo_mod

    breach_dir = tempfile.mkdtemp(prefix="serve_smoke_breach_")
    recorder.configure(directory=breach_dir, dump_interval_s=0)
    attribution.EXPLAINER.clear()
    prev_wd = slo_mod.WATCHDOG
    wd = slo_mod.SloWatchdog(min_samples=4)
    wd.add(slo_mod.Objective("serve_tail", "p99", 0.002))
    slo_mod.WATCHDOG = wd
    errors_q: list = []
    # one executor, no megabatching, a launch floor: every query
    # occupies the worker for >= the floor, so a closed-loop burst
    # queues N-deep behind it — queue_wait IS the latency
    qsrv = sctx.serve(workers=1, window_s=0.002, megabatch_max=1)
    try:
        faults.install(serve_load.launch_floor_plan(max(FLOOR_MS, 25.0)))
        try:
            serve_load.closed_loop(
                qsrv, q, CLIENTS, 2, lambda i: 0.3 + 1e-4 * i,
                {}, errors_q, client_prefix="qc",
            )
        finally:
            faults.clear()
    finally:
        qsrv.stop()
        slo_mod.WATCHDOG = prev_wd
        recorder.configure(dump_interval_s=30.0)
    assert not errors_q, f"queueing phase failures: {errors_q[:3]}"
    rows = wd.evaluate()
    assert rows and rows[0]["breached"], f"no SLO breach induced: {rows}"
    tail = attribution.EXPLAINER.explain()
    assert tail["top"] == "queue_wait", (
        f"tail explainer top segment {tail['top']!r}, want queue_wait: "
        f"{tail['segments'][:3]}"
    )
    artifacts = sorted(glob.glob(f"{breach_dir}/flight-*.json"))
    assert artifacts, "breach produced no flight artifact"
    with open(artifacts[-1]) as f:
        breach_doc = json.load(f)
    assert breach_doc["reason"] == "slo_breach"
    assert breach_doc["tail"]["top"] == "queue_wait", (
        breach_doc["tail"]["segments"][:3]
    )
    top_row = breach_doc["tail"]["segments"][0]
    print(f"tail explainer: induced queueing breached "
          f"{rows[0]['name']} (burn {rows[0]['burn_rate']:.1f}); "
          f"artifact ranks queue_wait first "
          f"(p99 {top_row['p99_s'] * 1e3:.1f} ms, "
          f"{top_row['share_of_wall'] * 100:.0f}% of wall)", flush=True)

    print("SERVE SMOKE PASSED", flush=True)
    return 0


if __name__ == "__main__":
    from datafusion_tpu.obs.httpd import run_with_ci_bundle

    sys.exit(run_with_ci_bundle(main, "serve_smoke"))
