"""Device data-plane observability (datafusion_tpu/obs/device.py):
HBM residency-ledger semantics under churn (release on buffer death,
no double-count on re-adopt, owner re-tagging), the leak detector's
two-sweep confirmation, the cold-path phase breakdown, per-table scan
histograms at the datasource boundary, lint rule DF006, and the
EXPLAIN ANALYZE phase-bar/HBM rendering."""

from __future__ import annotations

import gc
import os

import numpy as np
import pytest

from datafusion_tpu.datatypes import DataType, Field, Schema
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.obs import aggregate, device, recorder
from datafusion_tpu.obs.device import (
    PHASE_ORDER,
    DeviceLedger,
    phase_bar,
    phase_breakdown,
    phase_ms,
    phase_snapshot,
)
from datafusion_tpu.utils.metrics import METRICS

SCHEMA = Schema(
    [
        Field("k", DataType.INT64, False),
        Field("v", DataType.FLOAT64, False),
    ]
)


def _write_csv(path, rows=256, seed=11):
    rng = np.random.default_rng(seed)
    with open(path, "w", encoding="utf-8") as f:
        f.write("k,v\n")
        for _ in range(rows):
            f.write(f"{int(rng.integers(0, 8))},{rng.uniform(-5, 5):.6f}\n")
    return str(path)


@pytest.fixture()
def ledger():
    """A fresh, isolated DeviceLedger (the process-global LEDGER keeps
    serving the engine untouched)."""
    led = DeviceLedger()
    yield led
    led.clear()


class TestLedgerChurn:
    def test_put_tracks_then_release_on_death(self, ledger):
        arr = np.arange(4096, dtype=np.float64)
        out = ledger.put(arr, None, owner="scan.t")
        assert ledger.live_bytes() == out.nbytes
        assert ledger.peak_bytes() == out.nbytes
        assert ledger.owners() == {
            "scan.t": {"bytes": out.nbytes, "buffers": 1}
        }
        # cache eviction / batch teardown = the handle dies: the
        # weakref finalizer must release the entry (and the peak
        # watermark must survive as the high-water record)
        nbytes = out.nbytes
        del out
        gc.collect()
        assert ledger.live_bytes() == 0
        assert ledger.entries == 0
        assert ledger.peak_bytes() == nbytes

    def test_readopt_does_not_double_count(self, ledger):
        # failover fragment replay / warm re-collect adopts buffers the
        # engine already tracks: attribution refreshes, bytes do not
        # double-count
        import jax.numpy as jnp

        x = jnp.arange(1024)
        ledger.adopt(x, owner="fragment.q1")
        ledger.adopt(x, owner="fragment.q1.replay")
        assert ledger.entries == 1
        assert ledger.live_bytes() == x.nbytes
        assert list(ledger.owners()) == ["fragment.q1.replay"]

    def test_retag_moves_buffers_to_a_cache_owner(self, ledger):
        # buffers a cache takes over stop being transient: retag moves
        # them to the cache's owner and out of the leak sweep's
        # candidate set
        import jax.numpy as jnp

        cols = (jnp.arange(512), jnp.arange(512, dtype=jnp.float32))
        ledger.adopt(cols, owner="scan", cached=False)
        assert ledger.owners()["scan"]["buffers"] == 2
        ledger.retag(cols, "pin.lineitem", cached=True)
        owners = ledger.owners()
        assert "scan" not in owners
        assert owners["pin.lineitem"]["buffers"] == 2
        # cached entries never become leak candidates
        assert ledger.sweep(None, grace_s=0.0) == 0
        assert ledger.sweep(None, grace_s=0.0) == 0

    def test_peak_window_preserves_process_peak(self, ledger):
        # EXPLAIN ANALYZE / bench cold legs measure per-run peaks via a
        # WINDOW: the process-wide watermark (what scrapes and
        # fleet.hbm.peak_bytes report) must survive untouched
        big = ledger.put(np.zeros(1 << 16, np.uint8), None, owner="x")
        high = ledger.peak_bytes()
        assert high >= big.nbytes
        del big
        gc.collect()
        ledger.begin_peak_window()
        small = ledger.put(np.zeros(1 << 10, np.uint8), None, owner="y")
        assert small is not None
        assert ledger.window_peak_bytes() < high
        assert ledger.window_peak_bytes() >= 1 << 10
        assert ledger.peak_bytes() == high  # process peak intact

    def test_readopt_clears_leak_candidate(self, ledger):
        # a buffer marked as a leak candidate by one sweep that is then
        # re-adopted (fragment replay) was just proven in use: the
        # refresh must clear candidacy, not let a later sweep report it
        import jax.numpy as jnp

        x = jnp.arange(512)
        ledger.adopt(x, owner="fragment.q1", cached=False)
        assert ledger.sweep(None, grace_s=0.0) == 0  # marks candidate
        ledger.adopt(x, owner="fragment.q1.replay", cached=False)
        assert ledger.sweep(None, grace_s=0.0) == 0  # re-marks, no report
        assert ledger.leaks_reported == 0

    def test_transfer_profiles_without_residency(self, ledger):
        arr = np.arange(2048, dtype=np.int32)
        before = METRICS.counts.get("h2d.dispatch", 0)  # timing key
        out = ledger.transfer(arr, None)
        assert out is not None
        assert ledger.entries == 0  # transient: profiled, not resident

    def test_transfer_profile_false_is_silent(self, ledger):
        # the mesh stacker's fan-out arm: dispatch without blocking or
        # recording — no flight event, no timer accrual; the caller
        # times the batch under ONE h2d.dispatch stage timer and
        # records ONE note_h2d with its wall
        recorder.clear()
        before_t = METRICS.timings.get("h2d.dispatch", 0.0)
        out = ledger.transfer(np.arange(1024), None, profile=False)
        assert out is not None
        assert METRICS.timings.get("h2d.dispatch", 0.0) == before_t
        assert not [
            e for e in recorder.events() if e["kind"] == "device.h2d"
        ]
        with METRICS.timer("h2d.dispatch") as span:
            pass
        ledger.note_h2d(out.nbytes, span.wall_s)
        assert METRICS.timings.get("h2d.dispatch", 0.0) == pytest.approx(
            before_t + span.wall_s)
        events = [
            e for e in recorder.events() if e["kind"] == "device.h2d"
        ]
        assert len(events) == 1 and events[0]["attrs"]["bytes"] == out.nbytes

    def test_leak_detector_two_sweep_confirmation(self, ledger):
        import jax.numpy as jnp

        leaked = ledger.adopt(jnp.arange(256), owner="anon", cached=False)
        recorder.clear()
        before = ledger.leaks_reported
        # sweep 1 marks the candidate, never reports
        assert ledger.sweep(None, grace_s=0.0) == 0
        # sweep 2 past the grace reports it, exactly once
        assert ledger.sweep(None, grace_s=0.0) == 1
        assert ledger.sweep(None, grace_s=0.0) == 0
        assert ledger.leaks_reported == before + 1
        leaks = [e for e in recorder.events() if e["kind"] == "device.leak"]
        assert len(leaks) == 1
        assert leaks[0]["attrs"]["bytes"] == leaked.nbytes

    def test_sweep_scopes_to_completing_trace(self, ledger):
        import jax.numpy as jnp

        e = ledger.adopt(jnp.arange(64), owner="anon", cached=False)
        assert e is not None
        tok = next(iter(ledger._entries))
        ledger._entries[tok].trace_id = "trace-a"
        # a different query completing must not candidate trace-a's
        # buffers
        assert ledger.sweep("trace-b", grace_s=0.0) == 0
        assert ledger.sweep("trace-b", grace_s=0.0) == 0
        # its own completion does
        assert ledger.sweep("trace-a", grace_s=0.0) == 0
        assert ledger.sweep("trace-a", grace_s=0.0) == 1

    def test_untraced_sweep_skips_traced_queries_buffers(self, ledger):
        # an UNTRACED query completing (trace_id None) must not
        # candidate a concurrent traced query's in-flight buffers —
        # only trace-less ones are in scope
        import jax.numpy as jnp

        traced = ledger.adopt(jnp.arange(64), owner="anon", cached=False)
        assert traced is not None
        tok = next(iter(ledger._entries))
        ledger._entries[tok].trace_id = "trace-running"
        assert ledger.sweep(None, grace_s=0.0) == 0
        assert ledger.sweep(None, grace_s=0.0) == 0  # still no report
        assert ledger.leaks_reported == 0

    def test_put_events_claim_gbps_only_under_profile_sync(self, ledger):
        # async production put: dispatch-only wall, no GB/s claim;
        # profiled put (EXPLAIN ANALYZE / bench cold legs): blocked on
        # completion, true achieved GB/s vs the link baseline
        recorder.clear()
        out1 = ledger.put(np.arange(512), None, owner="x")
        assert out1 is not None
        with device.profile_sync():
            out2 = ledger.put(np.arange(512, dtype=np.int64), None,
                              owner="x")
            assert out2 is not None
        ev = [e for e in recorder.events() if e["kind"] == "device.h2d"]
        assert len(ev) == 2
        assert ev[0]["attrs"].get("dispatch_only") is True
        assert "gbps" not in ev[0]["attrs"]
        assert "gbps" in ev[1]["attrs"]
        assert "dispatch_only" not in ev[1]["attrs"]

    def test_put_of_device_array_is_residency_not_h2d(self, ledger):
        # device-resident input = reshard/placement (mesh state
        # distribution), not a host->device transfer: tracked, but no
        # device.h2d event and no h2d.dispatch accrual
        import jax.numpy as jnp

        dev = jnp.arange(1024)
        recorder.clear()
        before = METRICS.timings.get("h2d.dispatch", 0.0)
        out = ledger.put(dev, None, owner="mesh.state")
        assert out is not None
        assert ledger.entries == 1
        assert METRICS.timings.get("h2d.dispatch", 0.0) == before
        assert not [
            e for e in recorder.events() if e["kind"] == "device.h2d"
        ]

    def test_report_text_renders(self, ledger):
        held = ledger.put(
            np.arange(1000, dtype=np.float64), None, owner="scan.t"
        )
        assert held is not None  # the live handle keeps the entry live
        text = ledger.report_text()
        assert "live" in text and "peak" in text
        assert "scan.t" in text


class TestQueryIntegration:
    def test_query_tracks_and_gc_frees(self, tmp_path):
        from datafusion_tpu.obs.device import LEDGER

        path = _write_csv(tmp_path / "t.csv")
        LEDGER.clear()
        ctx = ExecutionContext()
        ctx.register_csv("t", path, SCHEMA, has_header=True)
        out = collect(ctx.sql("SELECT k, SUM(v) FROM t GROUP BY k"))
        assert out.num_rows == 8
        assert LEDGER.peak_bytes() > 0
        # engine teardown releases every tracked buffer
        del ctx, out
        gc.collect()
        assert LEDGER.live_bytes() == 0

    def test_launch_tags_decompose_launches(self, tmp_path):
        path = _write_csv(tmp_path / "t.csv")
        ctx = ExecutionContext()
        ctx.register_csv("t", path, SCHEMA, has_header=True)
        before = {
            k: v for k, v in METRICS.counts.items()
            if k.startswith("device.launches.")
        }
        collect(ctx.sql("SELECT k, SUM(v) FROM t GROUP BY k"))
        tagged = {
            k: v - before.get(k, 0)
            for k, v in METRICS.counts.items()
            if k.startswith("device.launches.") and v > before.get(k, 0)
        }
        assert any(k.startswith("device.launches.agg") for k in tagged), (
            tagged
        )

    def test_explain_analyze_renders_phases_and_hbm(self, tmp_path):
        path = _write_csv(tmp_path / "t.csv")
        ctx = ExecutionContext()
        ctx.register_csv("t", path, SCHEMA, has_header=True)
        res = ctx.sql_collect(
            "EXPLAIN ANALYZE SELECT k, SUM(v) FROM t GROUP BY k"
        )
        assert set(res.phases) == set(PHASE_ORDER)
        assert res.hbm["peak_bytes"] > 0
        report = res.report()
        assert "Phases: " in report
        assert "HBM: peak " in report

    def test_metrics_text_exposes_hbm_and_scan_histograms(self, tmp_path):
        from datafusion_tpu.obs.device import LEDGER

        path = _write_csv(tmp_path / "t.csv")
        aggregate.reset_histograms()
        ctx = ExecutionContext()
        ctx.register_csv("t", path, SCHEMA, has_header=True)
        collect(ctx.sql("SELECT k, SUM(v) FROM t GROUP BY k"))
        LEDGER.live_bytes()  # refresh the gauges
        text = ctx.metrics_text()
        assert 'name="device.hbm.live_bytes"' in text
        assert 'name="device.hbm.peak_bytes"' in text
        assert 'name="scan.t.latency.count"' in text
        assert 'name="scan.t.bytes.p50"' in text

    def test_flight_event_carries_phases(self, tmp_path):
        # a completed root query's flight event records the phase
        # breakdown (the slow-query artifact copies the same dict)
        path = _write_csv(tmp_path / "t.csv")
        recorder.clear()
        ctx = ExecutionContext()
        ctx.register_csv("t", path, SCHEMA, has_header=True)
        collect(ctx.sql("SELECT k, SUM(v) FROM t GROUP BY k"))
        done = [e for e in recorder.events() if e["kind"] == "query.done"]
        assert done, [e["kind"] for e in recorder.events()]
        phases = done[-1]["attrs"].get("phases")
        assert phases is not None and set(phases) == set(PHASE_ORDER)


class TestScanHistograms:
    def test_observe_scan_geometry(self):
        aggregate.reset_histograms()
        aggregate.observe_scan("lineitem", 0.25, 1 << 20)
        lat = aggregate.HISTOGRAMS["scan.lineitem.latency"]
        by = aggregate.HISTOGRAMS["scan.lineitem.bytes"]
        assert lat.count == 1 and by.count == 1
        assert by.base == 1.0 and by.nbuckets == 48
        # a byte-geometry quantile answers in bytes, not seconds
        q = by.quantile(0.5)
        assert q is not None and q >= 1 << 20

    def test_bytes_histograms_merge_fleet_wide(self):
        aggregate.reset_histograms()
        aggregate.observe_scan("t", 0.01, 4096)
        snap = aggregate.node_snapshot()
        agg = aggregate.FleetAggregator(include_local=False)
        agg.ingest("w1", snap)
        agg.ingest("w2", dict(snap, ts=snap["ts"]))
        fleet = agg.fleet()
        merged = fleet["histograms"]["scan.t.bytes"]
        # geometry survives the snapshot round trip: same base/buckets
        assert merged.base == 1.0
        assert merged.count == 2
        gauges = agg.gauges()
        assert gauges["fleet.scan.t.bytes.count"] == 2
        assert "fleet.scan.t.latency.p50_s" in gauges

    def test_fleet_hbm_sums_across_nodes(self):
        snap = {
            "ts": __import__("time").time(),
            "histograms": {},
            "counts": {},
            "gauges": {"device.hbm.live_bytes": 100,
                       "device.hbm.peak_bytes": 250},
        }
        agg = aggregate.FleetAggregator(include_local=False)
        agg.ingest("w1", snap)
        agg.ingest("w2", dict(snap))
        gauges = agg.gauges()
        assert gauges["fleet.hbm.live_bytes"] == 200
        assert gauges["fleet.hbm.peak_bytes"] == 500


class TestPhaseBreakdown:
    def test_profile_sync_scopes_and_launch_works_inside(self):
        # profile-sync is the opt-in "block launches for phase-accurate
        # execute timing" mode used by EXPLAIN ANALYZE; it must nest,
        # scope, and leave device_call functional
        import jax.numpy as jnp

        from datafusion_tpu.utils.retry import device_call

        assert not device.profile_sync_active()
        with device.profile_sync():
            assert device.profile_sync_active()
            with device.profile_sync():  # nests
                assert device.profile_sync_active()
                out = device_call(lambda: jnp.arange(8) * 2, _tag="test")
                assert int(out[3]) == 6
            assert device.profile_sync_active()
        assert not device.profile_sync_active()

    def test_breakdown_math(self):
        before = phase_snapshot()
        METRICS.observe("scan.parse", 0.10)
        METRICS.observe("h2d.dispatch", 0.05)
        METRICS.observe("compile.xla", 0.02)
        METRICS.observe("device.dispatch", 0.08)
        METRICS.observe("d2h.wait", 0.03)
        phases = phase_breakdown(before, wall_s=0.40)
        assert phases["decode"] == pytest.approx(0.10)
        assert phases["h2d"] == pytest.approx(0.05)
        assert phases["compile"] == pytest.approx(0.02)
        # compile splits OUT of the dispatch wall
        assert phases["execute"] == pytest.approx(0.06)
        assert phases["d2h"] == pytest.approx(0.03)
        # other = wall - accounted (host merge, planning, assembly)
        assert phases["other"] == pytest.approx(0.40 - 0.26)
        ms = phase_ms(phases)
        assert ms["decode"] == pytest.approx(100.0)

    def test_bar_renders_proportional(self):
        phases = {"decode": 0.5, "h2d": 0.25, "execute": 0.25,
                  "compile": 0.0, "d2h": 0.0, "other": 0.0}
        bar = phase_bar(phases, wall_s=1.0)
        assert "decode" in bar and "50%" in bar
        assert "h2d" in bar and "25%" in bar
        # zero phases stay out of the line
        assert "compile" not in bar

    def test_bar_empty(self):
        assert phase_bar({}, 1.0) == "(no phases recorded)"

class TestHbmPressureSlo:
    def test_hbm_frac_burn_and_breach(self, monkeypatch):
        from datafusion_tpu.obs import slo
        from datafusion_tpu.obs.device import LEDGER

        monkeypatch.setenv("DATAFUSION_TPU_HBM_BYTES", str(1 << 20))
        wd = slo.SloWatchdog(capture_on_breach=False)
        wd.add(slo.Objective("pressure", "hbm_frac", 0.5))
        LEDGER.clear()
        held = LEDGER.put(np.zeros(1 << 17, np.uint8), None, owner="x")
        row = wd.evaluate()[0]
        assert row["kind"] == "hbm_frac"
        # 128KiB live of a 1MiB device, 50% allowed -> burn 0.25
        assert row["burn_rate"] == pytest.approx(0.25, rel=0.05)
        assert not row["breached"]
        held2 = LEDGER.put(np.zeros(1 << 19, np.uint8), None, owner="x")
        row = wd.evaluate()[0]
        assert row["breached"] and row["burn_rate"] >= 1.0
        assert held is not None and held2 is not None
        LEDGER.clear()

    def test_capacity_sums_local_devices(self, monkeypatch):
        # ledger live bytes span ALL local devices (the mesh shards
        # across them), so capacity must too — dividing by one chip
        # would over-report pressure N-fold on an N-device host
        import jax

        from datafusion_tpu.obs import device as obs_device

        monkeypatch.delenv("DATAFUSION_TPU_HBM_BYTES", raising=False)

        class _Dev:
            def __init__(self, limit):
                self._limit = limit

            def memory_stats(self):
                return {"bytes_limit": self._limit}

        monkeypatch.setattr(jax, "devices", lambda: [_Dev(1 << 30)] * 4)
        assert obs_device.hbm_capacity_bytes() == 4 * (1 << 30)

        class _Opaque:
            def memory_stats(self):
                return None

        # one device hiding its stats -> unknown total, stay dormant
        monkeypatch.setattr(
            jax, "devices", lambda: [_Dev(1 << 30), _Opaque()]
        )
        assert obs_device.hbm_capacity_bytes() is None

    def test_unknown_capacity_stays_dormant(self, monkeypatch):
        from datafusion_tpu.obs import device as obs_device
        from datafusion_tpu.obs import slo

        monkeypatch.delenv("DATAFUSION_TPU_HBM_BYTES", raising=False)
        monkeypatch.setattr(obs_device, "hbm_capacity_bytes", lambda: None)
        wd = slo.SloWatchdog(capture_on_breach=False)
        wd.add(slo.Objective("pressure", "hbm_frac", 0.5))
        row = wd.evaluate()[0]
        assert row["burn_rate"] == 0.0 and not row["breached"]
        assert row["samples"] == 0

    def test_env_declaration(self):
        from datafusion_tpu.obs import slo

        objs = slo.objectives_from_env(
            {"DATAFUSION_TPU_SLO_PRESSURE_HBM_FRAC": "0.8"}
        )
        assert [(o.name, o.kind, o.threshold) for o in objs] == [
            ("pressure", "hbm_frac", 0.8)
        ]


class TestLintDF006:
    def test_raw_device_put_is_a_finding(self):
        from datafusion_tpu.analysis.lint import lint_source

        src = "import jax\n\ndef f(a):\n    return jax.device_put(a)\n"
        findings = lint_source(src, "datafusion_tpu/exec/foo.py")
        assert any(f.rule == "DF006" for f in findings), findings

    def test_alias_reference_is_a_finding(self):
        from datafusion_tpu.analysis.lint import lint_source

        src = "import jax\nput = jax.device_put\n"
        findings = lint_source(src, "datafusion_tpu/exec/foo.py")
        assert any(f.rule == "DF006" for f in findings), findings

    def test_device_module_and_suppression_exempt(self):
        from datafusion_tpu.analysis.lint import lint_source

        src = "import jax\n\ndef f(a):\n    return jax.device_put(a)\n"
        assert not [
            f for f in lint_source(src, "datafusion_tpu/obs/device.py")
            if f.rule == "DF006"
        ]
        suppressed = (
            "import jax\n\ndef f(a):\n"
            "    return jax.device_put(a)  # df-lint: ok(DF006) — probe\n"
        )
        assert not [
            f for f in lint_source(suppressed, "datafusion_tpu/exec/foo.py")
            if f.rule == "DF006"
        ]

    def test_repo_is_df006_clean(self):
        from datafusion_tpu.analysis.lint import RawDevicePut, lint_paths

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        findings = lint_paths(
            [os.path.join(repo, "datafusion_tpu")], rules=[RawDevicePut()]
        )
        assert findings == [], findings
