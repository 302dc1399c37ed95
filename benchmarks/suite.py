"""The five BASELINE.md benchmark configs.

Protocol (BASELINE.md "Measurement protocol"): the engine's own
single-thread CPU path is the baseline (the reference functionally
cannot run configs 2-5 — aggregates/sort are `unimplemented!()`,
`context.rs:161`); warm runs report p50 after warm-up (device-resident
steady state, excludes XLA compile); cold runs rebuild the operator
tree and re-scan the file each time, so they include parse, dictionary
encode, H2D, kernel, and D2H — with a per-phase breakdown from the
engine's METRICS counters.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from benchmarks import data as bdata


def log(*a):
    print(*a, file=sys.stderr, flush=True)


WARMUP = int(os.environ.get("BENCH_WARMUP", 3))
WARM_RUNS = int(os.environ.get("BENCH_RUNS", 10))
COLD_RUNS = int(os.environ.get("BENCH_COLD_RUNS", 3))

Q1 = (
    "SELECT l_returnflag, l_linestatus, "
    "SUM(l_quantity), SUM(l_extendedprice), "
    "SUM(l_extendedprice * (1 - l_discount)), "
    "SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)), "
    "AVG(l_quantity), AVG(l_extendedprice), AVG(l_discount), COUNT(1) "
    "FROM lineitem "
    "WHERE l_shipdate <= '1998-09-02' "
    "GROUP BY l_returnflag, l_linestatus"
)


def _p50(times: list[float]) -> float:
    return float(np.median(times))


def _timed(fn, runs: int, warmup: int = WARMUP) -> tuple[float, object]:
    out = None
    for _ in range(warmup):
        out = fn()
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return _p50(times), out


def _assert_tables_match(got, want, label: str, rtol=1e-9):
    got_rows = sorted(got.to_rows())
    want_rows = sorted(want.to_rows())
    assert len(got_rows) == len(want_rows), (
        f"{label}: row count differs: {len(got_rows)} vs {len(want_rows)}"
    )
    for g, w in zip(got_rows, want_rows):
        for gv, wv in zip(g, w):
            if isinstance(gv, float) or isinstance(wv, float):
                np.testing.assert_allclose(gv, wv, rtol=rtol, err_msg=label)
            else:
                assert gv == wv, f"{label}: {gv!r} != {wv!r} in {g} vs {w}"


# Published per-chip peak HBM bandwidth in GB/s, keyed by the
# `device_kind` JAX reports.  Source: Google Cloud documentation,
# "TPU v5e" (16 GB of HBM at 819 GB/s).  A kind that is not listed is
# an error, not a default: add it here with its source.
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}  # what JAX calls a v5e chip


def hbm_peak_gbps(device_kind: str) -> float:
    if device_kind not in HBM_PEAK_GBPS:
        raise KeyError(
            f"no published HBM peak for device_kind {device_kind!r}; "
            f"known: {sorted(HBM_PEAK_GBPS)}"
        )
    return HBM_PEAK_GBPS[device_kind]


def _device_peak_gbps() -> float:
    import jax

    return hbm_peak_gbps(jax.devices()[0].device_kind)


# A run pinned to the CPU is a logic check: its device-side times are
# None (never the CPU time under the device's name), and these pass
# None through to every derived number.
def _rate(rows, seconds):
    return None if seconds is None else round(rows / seconds, 1)


def _ms(seconds, digits=2):
    return None if seconds is None else round(seconds * 1e3, digits)


def _ratio(base_s, dev_s):
    return None if dev_s is None else round(base_s / dev_s, 3)


def _pass_metrics(fn, bytes_per_pass: float, runs: int = 3) -> dict:
    """Measured launches_per_pass (the `device.launches` counter the
    engine increments per executable dispatch — not a formula) and an
    achieved-HBM estimate for one warm query, so BENCH rounds can check
    both monotonically.  `hbm_peak_bytes` is MEASURED residency from
    the device ledger (obs/device.py): the high-water mark of
    actually-live device buffers across the timed passes, replacing the
    guessed-peak formula as the item-4 `hbm_util` gate's numerator
    source of truth."""
    from datafusion_tpu.utils.metrics import METRICS

    from datafusion_tpu.obs import recorder
    from datafusion_tpu.obs.device import LEDGER

    fn()  # ensure warm before counting
    before = METRICS.snapshot()["counts"].get("device.launches", 0)
    flight_before = recorder.emitted()
    LEDGER.begin_peak_window()
    t0 = time.perf_counter()
    for _ in range(runs):
        fn()
    wall = (time.perf_counter() - t0) / runs
    after = METRICS.snapshot()["counts"].get("device.launches", 0)
    launches = max(0, after - before) / runs
    hbm = bytes_per_pass / max(wall, 1e-9) / 1e9
    return {
        "launches_per_pass": round(launches, 1),
        "hbm_gbps_achieved": round(hbm, 2),
        "hbm_util_pct": round(100 * hbm / _device_peak_gbps(), 2),
        "hbm_peak_bytes": LEDGER.window_peak_bytes(),
        # flight-recorder cost accounting: events emitted per warm pass
        # (each emit is ~1µs lock-free work — the ≤2% overhead budget
        # holds as long as this stays in the tens per millisecond-scale
        # query; see tests/test_telemetry.py::test_emit_overhead)
        "flight_events_per_pass": round(
            (recorder.emitted() - flight_before) / runs, 1
        ),
    }


def _phase_before() -> dict:
    """Stage-timer snapshot for the cold-path phase breakdown
    (obs/device.py): capture before the timed cold runs, feed to
    `_cold_phase_ms` after."""
    from datafusion_tpu.obs.device import phase_snapshot

    return phase_snapshot()


def _cold_profile(prof_cap) -> dict:
    """Per-phase top host frames from a cold leg's sampling-profiler
    capture (obs/profiler.py): `{phase: {"samples": n, "top_frames":
    [[label, count], ...]}}` — the BENCH-round record of WHERE the
    cold wall's host CPU went, beside `cold_phase_ms`'s how-much."""
    if prof_cap is None:
        return {}
    return prof_cap.report().by_phase(3)


def _cold_phase_ms(before: dict, total_wall_s: float, nruns: int) -> dict:
    """Per-run cold-phase milliseconds (decode/h2d/compile/execute/d2h/
    other) from the stage-timer deltas across `nruns` runs — the
    measured decomposition ROADMAP item 3's "cold >= 2x CPU" target is
    tuned against, recorded per BENCH config as `cold_phase_ms`.
    `total_wall_s` must be the MEASURED wall of the same runs the
    deltas cover (incl. any warmup run — its compile-heavy wall is far
    above p50, so approximating it as one p50 would overflow the
    accounted phases and zero "other")."""
    from datafusion_tpu.obs.device import phase_breakdown

    phases = phase_breakdown(before, total_wall_s)
    return {k: round(v * 1e3 / nruns, 2) for k, v in phases.items()}


def _warm_query(device, src, table, sql, rows, runs=WARM_RUNS, warmup=None):
    """Steady-state p50 of re-running one operator tree (device-resident
    inputs after warm-up).  The CPU baseline gets fewer runs (it is the
    yardstick, not the metric — and the single-core path is slow)."""
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.materialize import collect

    if device == "cpu":
        runs = min(3, runs)
        warmup = 1 if warmup is None else warmup
    ctx = ExecutionContext(device=device)
    ctx.register_datasource(table, src)
    rel = ctx.sql(sql)
    p50, out = _timed(lambda: collect(rel), runs, warmup if warmup is not None else WARMUP)
    log(f"    {device or 'default'} warm: p50 {p50*1e3:.1f} ms, {rows/p50/1e6:.2f} M rows/s")
    return p50, out


# -- config 1: CSV scan + projection + filter (examples/csv_sql.rs) --
def config1_csv_filter(device_kind: str):
    from datafusion_tpu.datatypes import DataType, Field, Schema
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.utils.metrics import METRICS

    rows = int(os.environ.get("BENCH_CSV_ROWS", 2_000_000))
    path = bdata.cities_csv(rows)
    schema = Schema(
        [
            Field("city", DataType.UTF8, False),
            Field("lat", DataType.FLOAT64, False),
            Field("lng", DataType.FLOAT64, False),
        ]
    )
    sql = "SELECT city, lat, lng, lat + lng FROM cities WHERE lat > 51.0 AND lat < 53.0"

    def cold(device):
        # 512k-row batches: fewer, larger dispatches amortize the
        # per-batch overhead
        ctx = ExecutionContext(device=device, batch_size=1 << 19)
        ctx.register_csv("cities", path, schema, has_header=True)
        return collect(ctx.sql(sql))

    log("  config 1: CSV scan+filter (cold, scan-inclusive)")
    cpu_p50, cpu_out = _timed(lambda: cold("cpu"), COLD_RUNS, warmup=1)
    log(f"    cpu cold: p50 {cpu_p50*1e3:.1f} ms, {rows/cpu_p50/1e6:.2f} M rows/s")
    if device_kind == "cpu":
        dev_p50, dev_out = None, cpu_out
        cold_phase_ms, hbm_peak, cold_profile = {}, 0, {}
    else:
        from datafusion_tpu.obs import profiler as _profiler
        from datafusion_tpu.obs.device import LEDGER, profile_sync

        METRICS.reset()
        pb = _phase_before()
        LEDGER.begin_peak_window()
        t0 = time.perf_counter()
        # profile_sync: launches block so the "execute" phase measures
        # device wall, not async dispatch (obs/device.py); the host
        # profiler samples the same runs for per-phase top frames
        with profile_sync(), _profiler.profile(name="bench.cold1") as pc:
            dev_p50, dev_out = _timed(lambda: cold(device_kind), COLD_RUNS, warmup=1)
        # warmup=1: the warm-up run's stage timers are in the deltas,
        # so the wall fed to the breakdown is the measured total
        cold_phase_ms = _cold_phase_ms(
            pb, time.perf_counter() - t0, COLD_RUNS + 1
        )
        cold_profile = _cold_profile(pc)
        hbm_peak = LEDGER.window_peak_bytes()
        snap = METRICS.snapshot()
        parse = snap["timings_s"].get("scan.parse", 0.0) / (COLD_RUNS + 1)
        log(
            f"    {device_kind} cold: p50 {dev_p50*1e3:.1f} ms, "
            f"{rows/dev_p50/1e6:.2f} M rows/s (parse {parse*1e3:.0f} ms/run)"
            f"  phases={cold_phase_ms}"
        )
        _assert_tables_match(dev_out, cpu_out, "config1")
    return {
        "name": "csv_scan_filter",
        "rows": rows,
        "value": _rate(rows, dev_p50),
        "unit": "rows/s",
        "p50_ms": _ms(dev_p50),
        "vs_baseline": _ratio(cpu_p50, dev_p50),
        "cold_phase_ms": cold_phase_ms,
        "cold_profile": cold_profile,
        "hbm_peak_bytes": hbm_peak,
        "out_rows": dev_out.num_rows,
    }


# -- config 2: GROUP BY hash-aggregate, low and high cardinality --
def config2_groupby(device_kind: str):
    rows = int(os.environ.get("BENCH_GROUPBY_ROWS", 4_000_000))
    out = {"name": "groupby_aggregate", "rows": rows, "unit": "rows/s"}
    sql = (
        "SELECT k, SUM(v1), AVG(v2), MIN(v3), MAX(v3), COUNT(1) "
        "FROM t GROUP BY k"
    )
    for label, groups in (("small_16", 16), ("high_100k", 100_000)):
        log(f"  config 2: GROUP BY {groups} groups (warm)")
        _, src = bdata.groupby_batches(rows, groups, 1 << 19)
        cpu_p50, cpu_out = _warm_query("cpu", src, "t", sql, rows)
        if device_kind == "cpu":
            dev_p50 = None
        else:
            dev_p50, dev_out = _warm_query(device_kind, src, "t", sql, rows)
            _assert_tables_match(dev_out, cpu_out, f"config2/{label}", rtol=1e-6)
        out[label] = {
            "groups": groups,
            "value": _rate(rows, dev_p50),
            "p50_ms": _ms(dev_p50),
            "vs_baseline": _ratio(cpu_p50, dev_p50),
        }
        if device_kind != "cpu":
            # fused-pass acceptance metrics: measured launch count and
            # achieved HBM for the warm aggregate pass (3 f64 value
            # columns + int64 key read once, plus ids + mask)
            from datafusion_tpu.exec.context import ExecutionContext
            from datafusion_tpu.exec.materialize import collect as _collect

            mctx = ExecutionContext(device=device_kind)
            mctx.register_datasource("t", src)
            mrel = mctx.sql(sql)
            out[label].update(_pass_metrics(
                lambda: _collect(mrel), rows * (3 * 8 + 8 + 4 + 1)
            ))
    out["value"] = out["high_100k"]["value"]
    out["vs_baseline"] = out["high_100k"]["vs_baseline"]
    return out


# -- config 3: TPC-H Q1 over Parquet lineitem (the headline) --
def config3_tpch_q1(device_kind: str, sf=None):
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.datasource import MemoryDataSource
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.utils.metrics import METRICS

    if sf is None:
        sf = float(os.environ.get("BENCH_SF", 1))
    sf = int(sf) if sf == int(sf) else sf
    log(f"  config 3: TPC-H Q1, Parquet lineitem SF-{sf}")
    path = bdata.lineitem_parquet(sf)
    rows = int(bdata.LINEITEM_ROWS_PER_SF * sf)

    def cold(device):
        # 512k-row batches: fewer, larger dispatches amortize per-batch
        # link overhead (same setting for the CPU baseline)
        ctx = ExecutionContext(device=device, batch_size=1 << 19)
        ctx.register_parquet("lineitem", path)
        return collect(ctx.sql(Q1))

    # cold: full scan -> encode -> H2D -> kernel each run
    cold("cpu")  # compile CPU kernels outside the timed region
    cpu_cold_p50, cpu_out = _timed(lambda: cold("cpu"), COLD_RUNS, warmup=0)
    log(f"    cpu cold: p50 {cpu_cold_p50*1e3:.0f} ms, {rows/cpu_cold_p50/1e6:.2f} M rows/s")
    if device_kind != "cpu":
        from datafusion_tpu.obs.device import LEDGER, profile_sync
        from datafusion_tpu.obs.device import enabled as device_ledger_enabled

        from datafusion_tpu.obs import profiler as _profiler

        cold(device_kind)  # compile device kernels
        METRICS.reset()
        pb = _phase_before()
        LEDGER.begin_peak_window()
        t0 = time.perf_counter()
        with profile_sync(), _profiler.profile(name="bench.cold3") as pc:
            dev_cold_p50, dev_out = _timed(lambda: cold(device_kind), COLD_RUNS, warmup=0)
        cold_phase_ms = _cold_phase_ms(
            pb, time.perf_counter() - t0, COLD_RUNS
        )
        cold_profile = _cold_profile(pc)
        hbm_peak = LEDGER.window_peak_bytes()
        snap = METRICS.snapshot()
        nruns = COLD_RUNS
        parse_encode = (
            snap["timings_s"].get("scan.parse", 0.0)
            + snap["timings_s"].get("h2d.encode", 0.0)
        )
        breakdown = {
            "parse_encode_s": round(parse_encode / nruns, 3),
            "h2d_mb": round(snap["counts"].get("h2d.bytes", 0) / nruns / 1e6, 1),
        }
        if device_ledger_enabled():
            # the h2d.dispatch timer accrues at the ledger seam; with
            # the ledger off it reads 0 and device_and_d2h_s would
            # silently absorb transfer time — omit both rather than
            # misattribute
            h2d = snap["timings_s"].get("h2d.dispatch", 0.0)
            breakdown["h2d_dispatch_s"] = round(h2d / nruns, 3)
            breakdown["device_and_d2h_s"] = round(
                max(dev_cold_p50 - (parse_encode + h2d) / nruns, 0.0), 3
            )
        log(f"    {device_kind} cold: p50 {dev_cold_p50*1e3:.0f} ms, "
            f"{rows/dev_cold_p50/1e6:.2f} M rows/s  breakdown={breakdown}  "
            f"phases={cold_phase_ms}")
        _assert_tables_match(dev_out, cpu_out, "config3 cold")
    else:
        dev_cold_p50 = None
        breakdown = {}
        cold_phase_ms, hbm_peak, cold_profile = {}, 0, {}

    # warm: the same rows resident in memory (and after warm-up, on
    # device) — steady-state re-query throughput
    q1_batch = int(os.environ.get("BENCH_Q1_BATCH", str(1 << 19)))
    ctx = ExecutionContext(device="cpu", batch_size=q1_batch)
    ctx.register_parquet("lineitem", path)
    scan_src = ctx.datasources["lineitem"]
    batches = list(scan_src.batches())
    mem_src = MemoryDataSource(scan_src.schema, batches)
    cpu_warm_p50, cpu_warm_out = _warm_query("cpu", mem_src, "lineitem", Q1, rows)
    utilization = {}
    if device_kind != "cpu":
        dev_warm_p50, dev_warm_out = _warm_query(device_kind, mem_src, "lineitem", Q1, rows)
        _assert_tables_match(dev_warm_out, cpu_warm_out, "config3 warm")
        utilization = _q1_device_utilization(
            device_kind, mem_src, rows, batch_size=q1_batch
        )
        log(f"    utilization: {utilization}")
    else:
        dev_warm_p50 = None

    return {
        "name": "tpch_q1_parquet" if sf == 1 else f"tpch_q1_parquet_sf{sf}",
        "sf": sf,
        "rows": rows,
        "unit": "rows/s",
        "value": _rate(rows, dev_warm_p50),
        "warm_p50_ms": _ms(dev_warm_p50),
        "vs_baseline": _ratio(cpu_warm_p50, dev_warm_p50),
        "cold_value": _rate(rows, dev_cold_p50),
        "cold_p50_ms": _ms(dev_cold_p50),
        "cold_vs_baseline": _ratio(cpu_cold_p50, dev_cold_p50),
        "cold_breakdown": breakdown,
        "cold_phase_ms": cold_phase_ms,
        "cold_profile": cold_profile,
        "hbm_peak_bytes": hbm_peak,
        "utilization": utilization,
    }


def _q1_device_utilization(device_kind: str, mem_src, rows: int,
                           batch_size: "int | None" = None) -> dict:
    """Device-side throughput and bandwidth utilization for the warm Q1
    kernel, separated from the host's per-synchronization cost.

    Launches pipeline; a host<->device synchronization does not, so the
    measured warm p50 includes one sync per query.  This measures (a)
    that cost itself (a trivial launch+block), and (b) N accumulate
    passes dispatched back-to-back with ONE final block — the
    device-only rate with the sync amortized — then converts
    bytes-touched into achieved HBM bandwidth against the chip's
    published peak (`HBM_PEAK_GBPS`).
    """
    import time as _t

    import jax
    import jax.numpy as jnp

    from datafusion_tpu.exec.context import ExecutionContext

    if batch_size is None:
        # derive from the source's ACTUAL batch geometry rather than a
        # literal: the launch correction multiplies launches/pass, and
        # launches/pass follows the batch count — a utilization context
        # batched differently from the measured config would correct
        # with the wrong launch count (this feeds BASELINE.md claims)
        sizes = [b.num_rows for b in mem_src.batches()]
        batch_size = max(sizes) if sizes else 1 << 19
    ctx = ExecutionContext(device=device_kind, batch_size=batch_size)
    ctx.register_datasource("lineitem", mem_src)
    rel = ctx.sql(Q1)
    for _ in range(2):
        jax.block_until_ready(rel.accumulate())

    tiny = jnp.ones((8,))
    trivial = jax.jit(lambda x: x + 1)
    jax.block_until_ready(trivial(tiny))
    floors = []
    for _ in range(5):
        t0 = _t.perf_counter()
        jax.block_until_ready(trivial(tiny))
        floors.append(_t.perf_counter() - t0)
    sync_floor = float(np.median(floors))

    # per-launch overhead: N trivial launches chained + one block, with
    # the single-launch sync cost subtracted
    n_triv = 20
    t0 = _t.perf_counter()
    y = tiny
    for _ in range(n_triv):
        y = trivial(y)
    jax.block_until_ready(y)
    launch_floor = max(
        (_t.perf_counter() - t0 - sync_floor) / n_triv, 0.0
    )

    from datafusion_tpu.utils.metrics import METRICS

    n_passes = 5
    launches_before = METRICS.snapshot()["counts"].get("device.launches", 0)
    t0 = _t.perf_counter()
    states = [rel.accumulate() for _ in range(n_passes)]
    jax.block_until_ready(states)
    total = _t.perf_counter() - t0
    launches_after = METRICS.snapshot()["counts"].get("device.launches", 0)
    device_time = max(total - sync_floor, 1e-9)
    dev_rows_s = n_passes * rows / device_time

    # traffic lower bound: every input column read once per pass —
    # 4 f64 value columns (quantity, extendedprice, discount, tax; the
    # derived slots compute on-device from these), 2 narrow key-code
    # columns, dense int32 ids, 1-byte mask
    bytes_per_pass = rows * (4 * 8 + 2 * 4 + 4 + 1)
    hbm_gbps = n_passes * bytes_per_pass / device_time / 1e9
    peak_gbps = _device_peak_gbps()
    # launch-corrected compute: the per-pass time minus the measured
    # per-launch overhead x launches/pass
    # measured launches, not a formula: the engine counts every
    # executable dispatch (`device.launches` in utils/retry.device_call)
    # — under fused passes a warm Q1 pass is 1-2 launches regardless of
    # batch count, and BASELINE.md claims must reflect what ran
    launches_per_pass = max(
        1, round((launches_after - launches_before) / n_passes)
    )
    compute_per_pass = max(
        device_time / n_passes - launches_per_pass * launch_floor, 1e-9
    )
    hbm_corrected = bytes_per_pass / compute_per_pass / 1e9
    return {
        "sync_floor_ms": round(sync_floor * 1e3, 1),
        "launch_floor_ms": round(launch_floor * 1e3, 2),
        "launches_per_pass": launches_per_pass,
        "device_rows_per_s": round(dev_rows_s, 1),
        "device_time_per_pass_ms": round(device_time / n_passes * 1e3, 2),
        "hbm_gbps_achieved": round(hbm_gbps, 1),
        "hbm_gbps_launch_corrected": round(hbm_corrected, 1),
        "hbm_peak_gbps": peak_gbps,
        "hbm_util_pct": round(100 * hbm_gbps / peak_gbps, 2),
        "hbm_util_pct_launch_corrected": round(
            100 * hbm_corrected / peak_gbps, 2
        ),
    }


# -- config 4: ORDER BY + LIMIT TopK on device --
def config4_sort_topk(device_kind: str):
    rows = int(os.environ.get("BENCH_SORT_ROWS", 4_000_000))
    log("  config 4: single-key TopK via lax.top_k (warm)")
    _, src = bdata.sort_batches(rows, 1 << 19)
    sql = "SELECT s, b, x FROM t ORDER BY s DESC LIMIT 100"
    cpu_p50, cpu_out = _warm_query("cpu", src, "t", sql, rows)
    if device_kind == "cpu":
        dev_p50 = None
    else:
        dev_p50, dev_out = _warm_query(device_kind, src, "t", sql, rows)
        _assert_tables_match(dev_out, cpu_out, "config4 topk", rtol=1e-12)

    # float64 / int64 keys — the default SQL numeric types — ride the
    # wide full-width-score top_k path
    singles = {}
    for label, ssql in (
        ("single_f64", "SELECT a, b, x FROM t ORDER BY a DESC LIMIT 100"),
        ("single_i64", "SELECT b, a, x FROM t ORDER BY b LIMIT 100"),
    ):
        log(f"  config 4 {label}: wide-path TopK (warm)")
        scpu_p50, scpu_out = _warm_query("cpu", src, "t", ssql, rows)
        if device_kind == "cpu":
            sdev_p50 = None
        else:
            sdev_p50, sdev_out = _warm_query(device_kind, src, "t", ssql, rows)
            _assert_tables_match(sdev_out, scpu_out, f"config4 {label}", rtol=1e-12)
        singles[label] = {
            "value": _rate(rows, sdev_p50),
            "p50_ms": _ms(sdev_p50),
            "vs_baseline": _ratio(scpu_p50, sdev_p50),
        }

    log("  config 4m: multi-key TopK (sort kernel, warm)")
    msql = "SELECT a, b, x FROM t ORDER BY a DESC, b LIMIT 100"
    mcpu_p50, mcpu_out = _warm_query("cpu", src, "t", msql, rows)
    if device_kind == "cpu":
        mdev_p50 = None
    else:
        mdev_p50, mdev_out = _warm_query(device_kind, src, "t", msql, rows)
        _assert_tables_match(mdev_out, mcpu_out, "config4 multikey", rtol=1e-12)

    full_rows = int(os.environ.get("BENCH_FULLSORT_ROWS", 1_000_000))
    log("  config 4b: full ORDER BY (warm)")
    _, fsrc = bdata.sort_batches(full_rows, 1 << 19)
    fsql = "SELECT a, b, x FROM t ORDER BY a, b"
    fcpu_p50, fcpu_out = _warm_query("cpu", fsrc, "t", fsql, full_rows, runs=5)
    full_metrics = {}
    if device_kind == "cpu":
        fdev_p50 = None
    else:
        fdev_p50, fdev_out = _warm_query(device_kind, fsrc, "t", fsql, full_rows, runs=5)
        _assert_tables_match(fdev_out, fcpu_out, "config4 fullsort")
        # fused-pass acceptance metrics for the warm full sort (2 key
        # operands read + the permutation's byte planes written)
        from datafusion_tpu.exec.context import ExecutionContext
        from datafusion_tpu.exec.materialize import collect as _collect

        fctx = ExecutionContext(device=device_kind)
        fctx.register_datasource("t", fsrc)
        frel = fctx.sql(fsql)
        full_metrics = _pass_metrics(
            lambda: _collect(frel), full_rows * (2 * 8 + 3)
        )
    return {
        "name": "sort_topk",
        "rows": rows,
        "unit": "rows/s",
        "value": _rate(rows, dev_p50),
        "p50_ms": _ms(dev_p50),
        "vs_baseline": _ratio(cpu_p50, dev_p50),
        **singles,
        "multi_key": {
            "value": _rate(rows, mdev_p50),
            "p50_ms": _ms(mdev_p50),
            "vs_baseline": _ratio(mcpu_p50, mdev_p50),
        },
        "full_sort": {
            "rows": full_rows,
            "value": _rate(full_rows, fdev_p50),
            "p50_ms": _ms(fdev_p50),
            "vs_baseline": _ratio(fcpu_p50, fdev_p50),
            **full_metrics,
        },
    }


# -- cache config: warm-repeat phase (result cache hit rate + speedup) --
def config_cache(device_kind: str):
    """Cold-vs-warm repeat of one query through the full SQL front end:
    the cold leg executes (and fills the result cache), the warm legs
    re-submit the identical SQL and must be served from the coordinator
    result cache (parse+plan+fingerprint+replay, no device work).
    Reports the hit rate and the warm/cold speedup."""
    from datafusion_tpu import cache as qcache
    from datafusion_tpu.cache.result import CachedResultRelation
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.materialize import collect

    rows = int(os.environ.get("BENCH_CACHE_ROWS", 2_000_000))
    groups = 10_000
    sql = (
        "SELECT k, SUM(v1), AVG(v2), MIN(v3), MAX(v3), COUNT(1) "
        "FROM t GROUP BY k"
    )
    log("  config cache: warm-repeat result cache")
    _, src = bdata.groupby_batches(rows, groups, 1 << 19)
    device = None if device_kind == "cpu" else device_kind
    with qcache.configured(enabled=True):
        ctx = ExecutionContext(device="cpu" if device is None else device)
        ctx.register_datasource("t", src)

        def run():
            return collect(ctx.sql(sql))

        run()  # compile + warm device state outside the cold timing
        ctx.result_cache.clear()
        t0 = time.perf_counter()
        cold_out = run()
        cold_s = time.perf_counter() - t0
        rel = ctx.sql(sql)
        assert isinstance(rel, CachedResultRelation), (
            "warm repeat was not served from the result cache"
        )
        warm_runs = max(WARM_RUNS, 5)
        times = []
        for _ in range(warm_runs):
            t0 = time.perf_counter()
            warm_out = collect(ctx.sql(sql))
            times.append(time.perf_counter() - t0)
        warm_s = _p50(times)
        _assert_tables_match(warm_out, cold_out, "config cache", rtol=1e-9)
        stats = ctx.result_cache.stats()
        # the per-context run history must have recorded every warm
        # repeat as a cache hit under the query's fingerprint (closes
        # the open BASELINE.md note from the observability/cache PRs)
        runs = ctx.stats_history(ctx.last_fingerprint)
        warm_hits = [r for r in runs if r.get("cache_hit")]
        assert len(warm_hits) >= warm_runs, (
            f"stats_history recorded {len(warm_hits)} warm hits for "
            f"{warm_runs} warm runs: {runs!r}"
        )
    hit_rate = stats["hits"] / max(stats["hits"] + stats["misses"], 1)
    log(
        f"    cold {cold_s * 1e3:.1f} ms -> warm p50 {warm_s * 1e3:.2f} ms "
        f"({cold_s / warm_s:.0f}x), hit rate {hit_rate:.2f}, "
        f"{stats['bytes']} cached bytes, "
        f"{len(warm_hits)}/{len(runs)} history runs cache-hit"
    )
    return {
        "name": "result_cache_warm_repeat",
        "rows": rows,
        "unit": "rows/s",
        "value": round(rows / warm_s, 1),
        "warm_p50_ms": round(warm_s * 1e3, 3),
        "cold_ms": round(cold_s * 1e3, 2),
        "warm_speedup": round(cold_s / warm_s, 1),
        "hit_rate": round(hit_rate, 4),
        "cached_bytes": stats["bytes"],
        "history_warm_hits": len(warm_hits),
        "vs_baseline": round(cold_s / warm_s, 3),
    }


def config_ingest(device_kind: str):
    """Streaming ingestion vs full rescan: the TPC-H Q1 materialized
    view maintained incrementally (datafusion_tpu/ingest) against
    recomputing it from scratch after every delta.

    Closed loop: `deltas` appends of `delta_rows` lineitem rows each.
    Per delta the timed legs are (a) the append — WAL-free, so the
    number is pure maintenance: delta encode + ONE fused monoid fold
    into the view's device accumulators — and (b) a full rescan of
    the defining query over the grown table.  At EVERY cut the view
    must be bit-identical to the rescan (untimed), each delta must
    cost exactly one counted maintenance launch, and the headline
    gate is maintenance >= 5x cheaper than the rescan.  `value` is
    the sustained ingest rate (rows/s through append+maintain);
    freshness is the p50 append latency — the view is synchronously
    fresh when append returns."""
    from datafusion_tpu.exec.context import ExecutionContext

    sf = float(os.environ.get("BENCH_INGEST_SF", 0.1))
    sf = int(sf) if sf == int(sf) else sf
    deltas = int(os.environ.get("BENCH_INGEST_DELTAS", 15))
    delta_rows = int(os.environ.get("BENCH_INGEST_DELTA_ROWS", 2000))
    log(f"  config ingest: Q1 view maintenance over lineitem SF-{sf}, "
        f"{deltas} deltas x {delta_rows} rows")
    path = bdata.lineitem_parquet(sf)
    base_rows = int(bdata.LINEITEM_ROWS_PER_SF * sf)
    device = None if device_kind == "cpu" else device_kind
    ctx = ExecutionContext(device="cpu" if device is None else device,
                           batch_size=1 << 19, result_cache=False)
    ctx.register_parquet("lineitem", path)
    ing = ctx.ingest()
    view = ing.create_view("q1", Q1)
    assert view.incremental, (
        f"Q1 view fell back to full recompute: {view.fallback_reason}")

    rng = np.random.default_rng(17)
    flags, statuses = ["A", "N", "R"], ["F", "O"]

    def make_delta():
        return {
            "l_returnflag": [flags[i] for i in
                             rng.integers(0, 3, delta_rows)],
            "l_linestatus": [statuses[i] for i in
                             rng.integers(0, 2, delta_rows)],
            "l_quantity": rng.uniform(1, 50, delta_rows).round(2),
            "l_extendedprice": rng.uniform(900, 105000,
                                           delta_rows).round(2),
            "l_discount": rng.uniform(0, 0.1, delta_rows).round(2),
            "l_tax": rng.uniform(0, 0.08, delta_rows).round(2),
            "l_shipdate": ["1995-06-15"] * delta_rows,
        }

    # warm both legs' compiles outside the timed loop (the warmup
    # delta stays in the stream — it is real data, just untimed)
    ing.append("lineitem", make_delta())
    ctx.sql_collect(Q1)
    launches0 = view.maintain_launches
    append_times, rescan_times = [], []
    for i in range(deltas):
        cols = make_delta()
        t0 = time.perf_counter()
        ing.append("lineitem", cols)
        append_times.append(time.perf_counter() - t0)
        got = sorted(ing.read_view("q1").to_rows())
        t0 = time.perf_counter()
        want = ctx.sql_collect(Q1)
        rescan_times.append(time.perf_counter() - t0)
        assert got == sorted(want.to_rows()), (
            f"view diverged from batch rescan at delta {i}")
    assert view.maintain_launches - launches0 == deltas, (
        f"{view.maintain_launches - launches0} maintenance launches "
        f"for {deltas} deltas — must be exactly one fused launch each")
    assert view.full_recomputes == 0
    append_p50, rescan_p50 = _p50(append_times), _p50(rescan_times)
    speedup = rescan_p50 / append_p50
    assert speedup >= 5.0, (
        f"incremental maintenance only {speedup:.1f}x cheaper than a "
        f"full rescan (append p50 {append_p50 * 1e3:.2f} ms vs rescan "
        f"p50 {rescan_p50 * 1e3:.1f} ms)")
    total_rows = base_rows + (deltas + 1) * delta_rows
    log(f"    append+maintain p50 {append_p50 * 1e3:.2f} ms "
        f"({delta_rows / append_p50:,.0f} rows/s) vs full rescan p50 "
        f"{rescan_p50 * 1e3:.1f} ms over {total_rows:,} rows — "
        f"{speedup:.0f}x cheaper per delta, "
        f"{deltas} deltas = {deltas} fused launches")
    return {
        "name": "ingest_q1_view",
        "rows": total_rows,
        "unit": "rows/s",
        "value": round(delta_rows / append_p50, 1),
        "delta_rows": delta_rows,
        "deltas": deltas,
        "append_p50_ms": round(append_p50 * 1e3, 3),
        "freshness_p50_ms": round(append_p50 * 1e3, 3),
        "rescan_p50_ms": round(rescan_p50 * 1e3, 2),
        "speedup_vs_rescan": round(speedup, 1),
        "maintain_launches": deltas,
        "vs_baseline": round(speedup, 3),
    }


def config_concurrency(device_kind: str):
    """Throughput under concurrency: the serving front door vs
    serialized back-to-back execution of the SAME workload — the first
    config where queries/s, not single-query latency, is the number.

    Closed-loop: `clients` threads each submit `per_client` distinct-
    literal variants of one aggregate shape (one compiled core,
    result-cache-proof literals).  The serving leg pins the table in
    device memory, shares group-id encoders across queries, and fuses
    compatible concurrent plans into megabatched launches; reported
    p50/p99 come from the `serve.latency` fleet histogram (timed
    round only).

    On the CPU backend a per-launch latency floor is injected
    (`BENCH_SERVE_LAUNCH_FLOOR_MS`, default 10; =0 disables) — see
    `benchmarks/serve_load.launch_floor_plan`, the harness shared with
    `scripts/serve_smoke.py` so the two cannot drift.  BOTH legs run
    under the same floor; real accelerators run uninjected."""
    from benchmarks import serve_load
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.obs.aggregate import HISTOGRAMS
    from datafusion_tpu.testing import faults
    from datafusion_tpu.utils.metrics import METRICS

    rows = int(os.environ.get("BENCH_SERVE_ROWS", 32768))
    groups = int(os.environ.get("BENCH_SERVE_GROUPS", 64))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 8))
    per_client = int(os.environ.get("BENCH_SERVE_QUERIES", 8))
    floor_ms = float(os.environ.get(
        "BENCH_SERVE_LAUNCH_FLOOR_MS",
        "10" if device_kind == "cpu" else "0",
    ))
    log(f"  config concurrency: {clients} clients x {per_client} "
        f"queries over {rows} rows, launch floor {floor_ms} ms")
    _, src = bdata.groupby_batches(rows, groups, 1 << 15)
    device = None if device_kind == "cpu" else device_kind

    def q(lit: float) -> str:
        return (f"SELECT k, SUM(v1), AVG(v2), COUNT(1) FROM t "
                f"WHERE v2 < {lit:.6f} GROUP BY k")

    lits = [0.1 + 0.8 * i / (clients * per_client)
            for i in range(clients * per_client)]

    # serialized baseline: the same workload back-to-back on one thread
    ctx = ExecutionContext(
        device="cpu" if device is None else device, result_cache=False
    )
    ctx.register_datasource("t", src)
    collect(ctx.sql(q(0.95)))  # compile outside the timing
    if floor_ms > 0:
        faults.install(serve_load.launch_floor_plan(floor_ms))
    try:
        t0 = time.perf_counter()
        serial_out = [collect(ctx.sql(q(lit))) for lit in lits]
        serial_s = time.perf_counter() - t0
    finally:
        faults.clear()
    qps_serial = len(lits) / serial_s

    # served: closed-loop clients against the front door on a FRESH
    # context (no shared device caches with the baseline leg).
    # Megabatch cap = client count: a full closed-loop round flushes
    # the window the moment every client's query is queued (the window
    # is the MAX wait, size triggers early dispatch).
    sctx = ExecutionContext(
        device="cpu" if device is None else device, result_cache=False
    )
    sctx.register_datasource("t", bdata.groupby_batches(
        rows, groups, 1 << 15)[1])
    srv = sctx.serve(workers=2, window_s=0.01, megabatch_max=clients)
    results: dict = {}
    errors: list = []
    try:
        srv.submit(q(0.95)).result(timeout=300)  # pin + compile
        # untimed warm-up: every megabatch rung + one closed-loop
        # round, so the timed round is deterministically compile-free
        # (warm steady state is the measurement, as in every config)
        serve_load.warm_rungs(srv, q, clients)
        serve_load.closed_loop(srv, q, clients, per_client,
                               lambda i: 0.95 + 0.0005 * i, {}, errors)
        assert not errors, f"warm-up failures: {errors[:3]}"
        # timed-phase baselines (AFTER warm-up, like the smoke's, so
        # the reported fusion count and launches/query cover the same
        # phase)
        warm_launches0 = METRICS.counts.get("device.launches", 0)
        mega0 = METRICS.counts.get("serve.megabatch_launches", 0)
        h_before = (HISTOGRAMS["serve.latency"].snapshot()
                    if "serve.latency" in HISTOGRAMS else None)
        # tail attribution: the timed round's per-segment critical-path
        # decomposition (obs/attribution.py) is part of the bench
        # record — a concurrency regression should name the segment
        # that grew (queue wait vs window vs launch share vs demux),
        # not just the headline q/s
        from datafusion_tpu.obs import attribution

        attribution.EXPLAINER.clear()
        meter0 = {cid: dict(c) for cid, c in
                  attribution.METER.snapshot().items()}
        dispatch0 = METRICS.timings.get("device.dispatch", 0.0)
        if floor_ms > 0:
            faults.install(serve_load.launch_floor_plan(floor_ms))
        try:
            served_s = serve_load.closed_loop(
                srv, q, clients, per_client, lambda i: lits[i],
                results, errors,
            )
        finally:
            faults.clear()
    finally:
        srv.stop()
    assert not errors, f"{len(errors)} served queries failed: {errors[:3]}"
    qps_served = len(lits) / served_s
    # correctness: every served answer matches its serialized twin
    for i, lit in enumerate(lits):
        _assert_tables_match(
            results[divmod(i, per_client)], serial_out[i],
            f"concurrency lit={lit}",
        )
    mega = METRICS.counts.get("serve.megabatch_launches", 0) - mega0
    launches_per_query = (
        METRICS.counts.get("device.launches", 0) - warm_launches0
    ) / len(lits)
    p50, p99 = serve_load.phase_quantiles(
        HISTOGRAMS.get("serve.latency"), h_before
    )
    # the timed round's tail decomposition + metering conservation:
    # per-segment p50/p99 contributions and the apportioned
    # device-seconds against the measured launch wall
    tail = attribution.EXPLAINER.explain()
    meter1 = attribution.METER.snapshot()
    dev_sum = sum(
        c.get("device_seconds", 0.0)
        - meter0.get(cid, {}).get("device_seconds", 0.0)
        for cid, c in meter1.items()
    )
    launch_wall = METRICS.timings.get("device.dispatch", 0.0) - dispatch0
    log(
        f"    serialized {qps_serial:.1f} q/s -> served "
        f"{qps_served:.1f} q/s ({qps_served / qps_serial:.2f}x), "
        f"{mega} megabatch launches, "
        f"{launches_per_query:.2f} launches/query, "
        f"p50 {p50} p99 {p99}, tail top {tail['top']}"
    )
    return {
        "name": "concurrency",
        "unit": "queries/s",
        "value": round(qps_served, 2),
        "qps_serialized": round(qps_serial, 2),
        "vs_baseline": round(qps_served / qps_serial, 3),
        "clients": clients,
        "queries": len(lits),
        "megabatch_launches": mega,
        "launches_per_query": round(launches_per_query, 3),
        "launch_floor_ms": floor_ms,
        "p50_s": p50,
        "p99_s": p99,
        "critical_path": {
            "top": tail["top"],
            "segments": {
                r["segment"]: {"p50_s": r["p50_s"], "p99_s": r["p99_s"],
                               "share_of_wall": r["share_of_wall"]}
                for r in tail["segments"]
            },
        },
        "metering": {
            "clients": sum(1 for cid in meter1 if cid.startswith("c")),
            "device_seconds_sum": round(dev_sum, 6),
            "launch_wall_s": round(launch_wall, 6),
        },
    }


# -- worker-on-the-chip smoke (part of the bench protocol) --
def config_worker_smoke(device_kind: str):
    """Coordinator -> TPU-worker parity smoke on the attached chip
    (scripts/tpu_worker_smoke.py).  This process already holds the
    chip — and a chip belongs to one process — so the worker serves
    from a thread here, over the same socket protocol a remote worker
    speaks, instead of from a child that could never reach the device.
    A failure is recorded under "error" and makes `bench.py` exit
    non-zero after the other configs have printed.  On an explicit CPU
    run it reports skipped."""
    import importlib.util
    import threading
    import traceback

    out = {"name": "tpu_worker_smoke", "value": 0, "unit": "s",
           "vs_baseline": 0.0}
    if device_kind == "cpu":
        out["skipped"] = "no accelerator attached"
        return out
    log("  worker smoke: coordinator -> worker-on-TPU fragment parity")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_tpu_worker_smoke",
        os.path.join(repo, "scripts", "tpu_worker_smoke.py"),
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    from datafusion_tpu.parallel.worker import serve

    server = serve("127.0.0.1:0", device=device_kind)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        result = smoke.run_parity(
            server.server_address[:2], f"in-process thread, device={device_kind}"
        )
    except Exception as e:  # noqa: BLE001 — the leg's failure is its result
        log(traceback.format_exc())
        out["error"] = f"{type(e).__name__}: {e}"[:500]
        return out
    finally:
        server.shutdown()
        server.server_close()
    out.update(result)
    out["value"] = result["query_s"]
    out["vs_baseline"] = 1.0  # parity leg: pass/fail, not a speed ratio
    log(f"    pass: {result['rows']} rows, query {result['query_s']}s")
    return out


# -- config 5: partitioned aggregate over an 8-device mesh --
def config5_mesh(_device_kind: str):
    """Runs in a subprocess pinned to a CPU-simulated 8-device mesh
    (the same trick the tests use), so it never contends for the chip
    this process holds; `bench.py` labels its output `platform: cpu`.
    `chip_smoke.py` drives the mesh path on real chips."""
    import json
    import subprocess

    log("  config 5: partitioned mesh aggregate (8 virtual CPU devices)")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.mesh_bench"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=1200,
    )
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh bench failed:\n{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


# -- config joins: TPC-H Q3/Q5/Q10/Q12 shapes over the join subsystem --
def config_joins(device_kind: str):
    """Multi-table TPC-H shapes through the hash-join operator, gated
    on bit-level parity against a pandas-merge oracle, plus a warm
    pinned-probe leg: once the (dense-int, unique-key) orders build is
    resident, repeat passes must launch ZERO build kernels and stay
    under a launches-per-pass ceiling derived from the probe batch
    count — the 'warm probes move no build work' serving contract."""
    import pandas as pd

    from datafusion_tpu import cache as qcache
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.datasource import CsvDataSource
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.utils.metrics import METRICS

    sf = float(os.environ.get("BENCH_JOIN_SF", 0.01))
    batch_rows = 1 << 14
    tables = bdata.tpch_join_csvs(sf)
    device = None if device_kind == "cpu" else device_kind
    frames = {}
    with qcache.configured(enabled=False):
        ctx = ExecutionContext(
            device="cpu" if device is None else device, batch_size=batch_rows
        )
        for name, (path, schema) in tables.items():
            ctx.register_datasource(
                name, CsvDataSource(path, schema, True, batch_rows))
            frames[name] = pd.read_csv(path)

        def rows_of(sql):
            def key(row):
                return tuple(
                    (v is None, 0 if v is None else v) for v in row)
            return sorted(collect(ctx.sql(sql)).to_rows(), key=key)

        def check(label, got, want_df):
            want = sorted(
                tuple(None if pd.isna(v) else v for v in t)
                for t in want_df.itertuples(index=False)
            )
            assert len(got) == len(want), (
                f"{label}: {len(got)} rows vs oracle {len(want)}")
            for g, w in zip(got, want):
                for gv, wv in zip(g, w):
                    if isinstance(gv, float) or isinstance(wv, float):
                        np.testing.assert_allclose(
                            gv, wv, rtol=1e-9, err_msg=label)
                    else:
                        assert gv == wv, f"{label}: {g} vs {w}"

        li, od, cu, na = (frames["lineitem"], frames["orders"],
                          frames["customer"], frames["nation"])
        li = li.assign(rev=li.l_extendedprice * (1 - li.l_discount))
        results = {}

        q3 = ("SELECT o_orderkey, o_shippriority, "
              "SUM(l_extendedprice * (1 - l_discount)) FROM lineitem "
              "JOIN orders ON lineitem.l_orderkey = orders.o_orderkey "
              "JOIN customer ON orders.o_custkey = customer.c_custkey "
              "WHERE c_mktsegment = 1 "
              "GROUP BY o_orderkey, o_shippriority")
        t, got = _timed(lambda: rows_of(q3), runs=3, warmup=1)
        o3 = (li.merge(od, left_on="l_orderkey", right_on="o_orderkey")
              .merge(cu, left_on="o_custkey", right_on="c_custkey"))
        o3 = (o3[o3.c_mktsegment == 1]
              .groupby(["o_orderkey", "o_shippriority"], as_index=False)
              .agg(rev=("rev", "sum")))
        check("q3", got, o3[["o_orderkey", "o_shippriority", "rev"]])
        results["q3_s"] = round(t, 4)
        log(f"    Q3 shape: {len(got)} groups, p50 {t * 1e3:.1f} ms")

        q5 = ("SELECT n_name, SUM(l_extendedprice * (1 - l_discount)) "
              "FROM lineitem "
              "JOIN orders ON lineitem.l_orderkey = orders.o_orderkey "
              "JOIN customer ON orders.o_custkey = customer.c_custkey "
              "JOIN nation ON customer.c_nationkey = nation.n_nationkey "
              "GROUP BY n_name")
        t, got = _timed(lambda: rows_of(q5), runs=3, warmup=1)
        o5 = (li.merge(od, left_on="l_orderkey", right_on="o_orderkey")
              .merge(cu, left_on="o_custkey", right_on="c_custkey")
              .merge(na, left_on="c_nationkey", right_on="n_nationkey")
              .groupby("n_name", as_index=False).agg(rev=("rev", "sum")))
        check("q5", got, o5[["n_name", "rev"]])
        results["q5_s"] = round(t, 4)
        log(f"    Q5 shape: {len(got)} nations, p50 {t * 1e3:.1f} ms")

        q10 = ("SELECT c_custkey, n_name, "
               "SUM(l_extendedprice * (1 - l_discount)) FROM lineitem "
               "JOIN orders ON lineitem.l_orderkey = orders.o_orderkey "
               "JOIN customer ON orders.o_custkey = customer.c_custkey "
               "JOIN nation ON customer.c_nationkey = nation.n_nationkey "
               "WHERE o_orderdate <= '1995-06-30' "
               "GROUP BY c_custkey, n_name")
        t, got = _timed(lambda: rows_of(q10), runs=3, warmup=1)
        o10 = (li.merge(od, left_on="l_orderkey", right_on="o_orderkey")
               .merge(cu, left_on="o_custkey", right_on="c_custkey")
               .merge(na, left_on="c_nationkey", right_on="n_nationkey"))
        o10 = (o10[o10.o_orderdate <= "1995-06-30"]
               .groupby(["c_custkey", "n_name"], as_index=False)
               .agg(rev=("rev", "sum")))
        check("q10", got, o10[["c_custkey", "n_name", "rev"]])
        results["q10_s"] = round(t, 4)
        log(f"    Q10 shape: {len(got)} groups, p50 {t * 1e3:.1f} ms")

        q12 = ("SELECT l_shipmode, COUNT(1) FROM lineitem "
               "JOIN orders ON lineitem.l_orderkey = orders.o_orderkey "
               "WHERE l_quantity > 25 GROUP BY l_shipmode")
        t, got = _timed(lambda: rows_of(q12), runs=3, warmup=1)
        o12 = (li.merge(od, left_on="l_orderkey", right_on="o_orderkey"))
        o12 = (o12[o12.l_quantity > 25]
               .groupby("l_shipmode", as_index=False)
               .agg(n=("l_orderkey", "count")))
        check("q12", [(a, int(b)) for a, b in got],
              o12[["l_shipmode", "n"]])
        results["q12_s"] = round(t, 4)
        log(f"    Q12 shape: {len(got)} shipmodes, p50 {t * 1e3:.1f} ms")

        # warm pinned-probe gate on Q12 (orders build: unique dense int
        # key -> device path, pinned after the timed passes above)
        n_line = len(li)
        n_batches = -(-n_line // batch_rows)
        before = METRICS.snapshot()["counts"]
        pm = _pass_metrics(lambda: rows_of(q12), bytes_per_pass=0.0)
        after = METRICS.snapshot()["counts"]
        build_launches = (after.get("device.launches.join.build", 0)
                          - before.get("device.launches.join.build", 0))
        assert build_launches == 0, (
            f"warm Q12 passes launched {build_launches} build kernels")
        reuse = (after.get("join.build.reuse", 0)
                 - before.get("join.build.reuse", 0))
        assert reuse >= 3, f"pinned build reused {reuse} times in 4 passes"
        # ceiling: scan decode + filter + fused probe + aggregate per
        # probe batch, plus a fixed epilogue allowance
        ceiling = 8 * n_batches + 32
        assert pm["launches_per_pass"] <= ceiling, (
            f"warm Q12 launches_per_pass {pm['launches_per_pass']} "
            f"exceeds ceiling {ceiling} ({n_batches} probe batches)")
        log(f"    warm Q12: {pm['launches_per_pass']} launches/pass "
            f"(ceiling {ceiling}), 0 build launches, reuse={reuse}")

    total_rows = sum(len(f) for f in frames.values())
    wall = results["q3_s"] + results["q5_s"] + results["q10_s"] + results["q12_s"]
    return {
        "name": "tpch_joins",
        "rows": total_rows,
        "unit": "rows/s",
        "value": round(total_rows * 4 / max(wall, 1e-9), 1),
        "launches_per_pass_warm_q12": pm["launches_per_pass"],
        "probe_batches": n_batches,
        "vs_baseline": 1.0,  # parity leg: pass/fail, not a speed ratio
        **results,
    }


def config_adaptive(device_kind: str):
    """Feedback-driven planning (datafusion_tpu/cost): the same
    workload cold (empty cost store) vs trained (statistics persisted
    by the cold leg, loaded by a fresh process).

    Each leg is a SUBPROCESS so it pays its own compiles — the whole
    point is that the trained leg's pre-sized aggregate compiles ONE
    sort-merge kernel where the cold leg climbs the capacity regrow
    ladder, and its join builds the smaller side.  Gates: at least two
    decision classes flip, results bit-exact across legs, and the
    mis-defaulted aggregate shape speeds up >= 1.2x."""
    import importlib.util
    import json as _json
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    smoke_path = os.path.join(repo, "scripts", "adaptive_smoke.py")
    spec = importlib.util.spec_from_file_location("_adaptive", smoke_path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    tmpdir = tempfile.mkdtemp(prefix="df-tpu-bench-adaptive-")
    smoke._write_tables(tmpdir)

    def leg(label, cost="1"):
        env = dict(os.environ)
        env["DATAFUSION_TPU_COST_DIR"] = tmpdir
        env["DATAFUSION_TPU_COST"] = cost
        env.setdefault("DATAFUSION_TPU_FUSE_GROUP", "8")
        out = subprocess.run(
            [sys.executable, smoke_path, "--leg", tmpdir],
            cwd=repo, env=env, capture_output=True, text=True,
            timeout=600,
        )
        assert out.returncode == 0, (
            f"adaptive {label} leg failed:\n{out.stderr[-4000:]}")
        r = _json.loads(out.stdout.strip().splitlines()[-1])
        log(f"    {label}: agg {r['agg_wall_s'] * 1e3:.0f} ms, "
            f"decisions {r['decisions'] or '[]'}")
        return r

    log("  config adaptive: cold vs trained planning")
    cold = leg("cold")
    trained = leg("trained")
    changed = sorted(set(trained["decisions"]) - set(cold["decisions"]))
    assert len(changed) >= 2, (
        f"expected >=2 decision classes to flip, got {changed}")
    assert trained["agg_rows"] == cold["agg_rows"], (
        "trained aggregate rows diverged from cold")
    assert trained["join_rows"] == cold["join_rows"], (
        "trained join rows diverged from cold")
    speedup = cold["agg_wall_s"] / max(trained["agg_wall_s"], 1e-9)
    assert speedup >= 1.2, (
        f"trained aggregate speedup {speedup:.2f}x below the 1.2x gate "
        f"(cold {cold['agg_wall_s']:.3f}s, "
        f"trained {trained['agg_wall_s']:.3f}s)")
    log(f"    trained speedup on the mis-defaulted aggregate: "
        f"{speedup:.2f}x, decisions flipped: {changed}")
    return {
        "name": "adaptive_planning",
        "rows": smoke.ROWS,
        "unit": "speedup",
        "value": round(speedup, 3),
        "cold_agg_ms": round(cold["agg_wall_s"] * 1e3, 1),
        "trained_agg_ms": round(trained["agg_wall_s"] * 1e3, 1),
        "decisions_changed": changed,
        "vs_baseline": round(speedup, 3),
    }
