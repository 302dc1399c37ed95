"""Chaos suite: deterministic fault injection through every recovery
path (SURVEY §5.3).

The fault plans are seeded and hit-counted (`testing/faults.py`), so
each scenario replays exactly: worker processes killed mid-fragment,
connection resets on response recv, corrupted frames, transient device
errors inside workers — in every case a distributed aggregate must
return results identical to the fault-free run, and the recovery
bookkeeping (failover order, probation re-admission, duplicate-response
dedup, deadlines) is asserted directly.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from datafusion_tpu.datatypes import DataType, Field, Schema
from datafusion_tpu.errors import (
    DeviceTransientError,
    ExecutionError,
    QueryDeadlineError,
    TransientError,
    classify_transient,
)
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.parallel.coordinator import (
    DistributedContext,
    HeartbeatMonitor,
    WorkerHandle,
)
from datafusion_tpu.testing import faults
from datafusion_tpu.utils import retry
from datafusion_tpu.utils.deadline import Deadline, deadline_scope

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEMA = Schema(
    [
        Field("region", DataType.UTF8, False),
        Field("city", DataType.UTF8, True),
        Field("v", DataType.INT64, False),
        Field("x", DataType.FLOAT64, True),
    ]
)

GROUP_SQL = (
    "SELECT region, SUM(v), COUNT(1), MIN(v), MAX(v), "
    "MIN(city), MAX(city) FROM t GROUP BY region"
)


def _write_partitions(tmp_path, n_parts=3, rows_per=300):
    rng = np.random.default_rng(23)
    regions = ["north", "south", "east", "west"]
    cities = [f"city{i}" for i in range(30)]
    paths = []
    for p in range(n_parts):
        path = tmp_path / f"part{p}.csv"
        with open(path, "w", encoding="utf-8") as f:
            f.write("region,city,v,x\n")
            for _ in range(rows_per):
                r = regions[rng.integers(0, len(regions))]
                c = cities[rng.integers(0, len(cities))] if rng.random() > 0.05 else ""
                f.write(f"{r},{c},{int(rng.integers(-1000, 1000))},"
                        f"{rng.uniform(-5, 5):.6f}\n")
        paths.append(str(path))
    return paths


def _spawn_worker(fault_plan=None, bind="127.0.0.1:0", extra_env=None):
    """One worker OS process; `fault_plan` rides the environment, so
    the injection config path itself is under test."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    if fault_plan is not None:
        env["DATAFUSION_TPU_FAULTS"] = json.dumps(fault_plan)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "datafusion_tpu.worker",
         "--bind", bind, "--device", "cpu"],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    line = proc.stdout.readline()
    assert "listening on" in line, line
    host, port = line.strip().rsplit(" ", 1)[1].rsplit(":", 1)
    return proc, (host, int(port))


@pytest.fixture(scope="module")
def healthy_workers():
    procs, addrs = [], []
    try:
        for _ in range(2):
            proc, addr = _spawn_worker()
            procs.append(proc)
            addrs.append(addr)
        yield procs, addrs
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)


def _register(ctx, paths):
    from datafusion_tpu.exec.datasource import CsvDataSource
    from datafusion_tpu.parallel.partition import PartitionedDataSource

    ctx.register_datasource(
        "t",
        PartitionedDataSource([CsvDataSource(p, SCHEMA, True, 131072) for p in paths]),
    )
    return ctx


def _rows(ctx, sql=GROUP_SQL):
    def key(row):
        return tuple((v is None, 0 if v is None else v) for v in row)

    return sorted(collect(ctx.sql(sql)).to_rows(), key=key)


def _local_want(paths, sql=GROUP_SQL):
    return _rows(_register(ExecutionContext(device="cpu"), paths), sql)


class TestFaultPlanMechanics:
    def test_after_and_count(self):
        with faults.scoped({"rules": [
            {"site": "s", "op": "raise", "exc": "ValueError",
             "after": 2, "count": 2},
        ]}) as plan:
            faults.check("s")  # hit 1: before `after`
            with pytest.raises(ValueError):
                faults.check("s")  # hit 2: fires
            with pytest.raises(ValueError):
                faults.check("s")  # hit 3: fires (count 2)
            faults.check("s")  # count exhausted
            snap = plan.snapshot()[0]
            assert (snap["hits"], snap["fired"]) == (4, 2)
        assert faults.active() is None

    def test_delay_range_draws_are_seeded(self):
        spec = {"site": "s", "op": "delay", "seconds": [0.0, 0.01],
                "count": 3}
        r1 = faults._Rule(spec, seed=9, index=0)
        r2 = faults._Rule(spec, seed=9, index=0)
        draws1 = [r1.delay_s("s", k) for k in range(1, 4)]
        draws2 = [r2.delay_s("s", k) for k in range(1, 4)]
        assert draws1 == draws2  # pure function of the plan
        assert len(set(draws1)) == 3  # per-firing ordinals differ
        assert all(0.0 <= d <= 0.01 for d in draws1)
        r3 = faults._Rule(spec, seed=10, index=0)
        assert r3.delay_s("s", 1) != draws1[0]  # seed moves the schedule
        # scalar form unchanged; malformed ranges rejected at install
        r4 = faults._Rule({"site": "s", "op": "delay", "seconds": 0.25},
                          0, 0)
        assert r4.delay_s("s", 1) == 0.25
        with pytest.raises(ValueError):
            faults._Rule({"site": "s", "op": "delay",
                          "seconds": [1.0, 0.5]}, 0, 0)

    def test_delay_range_fires_end_to_end(self):
        with faults.scoped({"seed": 3, "rules": [
            {"site": "s", "op": "delay", "seconds": [0.0, 0.001],
             "count": 0},
        ]}) as plan:
            faults.check("s")
            faults.check("s")
            assert plan.snapshot()[0]["fired"] == 2

    def test_site_glob_and_where(self):
        with faults.scoped({"rules": [
            {"site": "wire.*", "op": "raise", "exc": "ValueError",
             "where": {"shard": 1}, "count": 0},
        ]}):
            faults.check("device.call", shard=1)  # site mismatch
            faults.check("wire.send", shard=0)  # where mismatch
            with pytest.raises(ValueError):
                faults.check("wire.send", shard=1)

    def test_role_scoping(self):
        with faults.scoped({"rules": [
            {"site": "s", "op": "raise", "exc": "ValueError",
             "role": "worker", "count": 0},
        ]}):
            faults.check("s")  # this process is role "main"
            faults.set_role("worker")
            try:
                with pytest.raises(ValueError):
                    faults.check("s")
            finally:
                faults.set_role("main")

    def test_delay_and_seeded_probability(self):
        t0 = time.perf_counter()
        with faults.scoped({"seed": 5, "rules": [
            {"site": "s", "op": "delay", "seconds": 0.02, "count": 1},
        ]}):
            faults.check("s")
        assert time.perf_counter() - t0 >= 0.02

        def fired_sequence():
            with faults.scoped({"seed": 11, "rules": [
                {"site": "s", "op": "raise", "exc": "ValueError",
                 "p": 0.5, "count": 0},
            ]}):
                out = []
                for _ in range(20):
                    try:
                        faults.check("s")
                        out.append(0)
                    except ValueError:
                        out.append(1)
                return out

        seq = fired_sequence()
        assert seq == fired_sequence()  # same seed, same draws
        assert 0 < sum(seq) < 20

    def test_p_rule_virtual_hit_clock_is_per_site(self):
        """Each (rule, site) pair has its own hit clock: hits at one
        site never shift another site's draws (ROADMAP follow-on — the
        old shared-RNG stream reshuffled under interleaving)."""
        spec = {"seed": 9, "rules": [
            {"site": "*", "op": "raise", "exc": "ValueError",
             "p": 0.5, "count": 0},
        ]}

        def pattern(site, n, warmup_other=0):
            with faults.scoped(spec):
                for _ in range(warmup_other):
                    try:
                        faults.check("other.site")
                    except ValueError:
                        pass
                out = []
                for _ in range(n):
                    try:
                        faults.check(site)
                        out.append(0)
                    except ValueError:
                        out.append(1)
                return out

        base = pattern("a.site", 30)
        # interleaved traffic on another site leaves a.site's draws
        # untouched — the property that makes chaos soaks replayable
        assert pattern("a.site", 30, warmup_other=17) == base
        assert 0 < sum(base) < 30

    def test_p_rule_deterministic_under_thread_interleaving(self):
        """The SET of firing (site, hit-index) pairs is a pure function
        of the plan, so the per-site fire counts match no matter how
        many threads deliver the hits."""
        import threading

        spec = {"seed": 21, "rules": [
            {"site": "s", "op": "raise", "exc": "ValueError",
             "p": 0.3, "count": 0},
        ]}

        def run(n_threads, hits_total):
            fired = []
            lock = threading.Lock()

            def hammer(n):
                for _ in range(n):
                    try:
                        faults.check("s")
                    except ValueError:
                        with lock:
                            fired.append(1)

            with faults.scoped(spec):
                threads = [
                    threading.Thread(target=hammer,
                                     args=(hits_total // n_threads,))
                    for _ in range(n_threads)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=30)
            return len(fired)

        sequential = run(1, 120)
        assert 0 < sequential < 120
        for n_threads in (4, 8):
            assert run(n_threads, 120) == sequential

    def test_corrupt_is_deterministic_and_offsettable(self):
        data = bytes(range(64))
        spec = {"seed": 3, "rules": [
            {"site": "s", "op": "corrupt", "count": 0},
        ]}
        with faults.scoped(spec):
            a = bytes(faults.corrupt("s", data))
        with faults.scoped(spec):
            b = bytes(faults.corrupt("s", data))
        assert a == b != data
        with faults.scoped({"rules": [
            {"site": "s", "op": "corrupt", "offset": 0, "count": 1},
        ]}):
            c = bytes(faults.corrupt("s", data))
        assert c[0] == data[0] ^ 0x5A

    def test_install_from_file(self, tmp_path):
        p = tmp_path / "plan.json"
        p.write_text(json.dumps({"rules": [
            {"site": "s", "op": "raise", "exc": "ValueError"},
        ]}))
        try:
            faults.install(f"@{p}")
            with pytest.raises(ValueError):
                faults.check("s")
        finally:
            faults.clear()

    def test_unknown_exception_rejected_at_install(self):
        with pytest.raises(ValueError, match="unknown fault exception"):
            faults.install({"rules": [{"site": "s", "exc": "NoSuchError"}]})
        faults.clear()


class TestTypedRetry:
    def test_classification_is_typed(self):
        # the error types jax raises are matched by NAME (no jax import
        # needed to classify) and by leading status token — not by
        # scanning free text in the retry loop
        XlaRuntimeError = type("XlaRuntimeError", (Exception,), {})
        assert isinstance(
            classify_transient(XlaRuntimeError("UNAVAILABLE: socket closed")),
            DeviceTransientError,
        )
        assert isinstance(
            classify_transient(XlaRuntimeError("DEADLINE_EXCEEDED: rpc")),
            DeviceTransientError,
        )
        assert classify_transient(XlaRuntimeError("INVALID_ARGUMENT: shape")) is None
        # only the LEADING status token decides: free text that merely
        # mentions a retryable word is a permanent failure
        assert classify_transient(
            XlaRuntimeError("INTERNAL: transport unavailable, read body")
        ) is None
        assert classify_transient(ValueError("UNAVAILABLE: nope")) is None
        assert isinstance(classify_transient(ConnectionResetError()), TransientError)
        # already-typed errors pass through unchanged
        e = DeviceTransientError("injected")
        assert classify_transient(e) is e

    def test_backoff_capped_exponential_full_jitter(self):
        retry.seed_backoff(1234)
        seq = [retry.backoff_s(a, base=0.25, cap=5.0) for a in range(1, 12)]
        retry.seed_backoff(1234)
        assert seq == [retry.backoff_s(a, base=0.25, cap=5.0) for a in range(1, 12)]
        for a, d in enumerate(seq, start=1):
            assert 0.0 <= d <= min(5.0, 0.25 * 2 ** (a - 1))
        # jitter: the ladder must not be the deterministic ceiling
        assert len({round(d, 6) for d in seq}) > 3

    def test_device_call_retries_typed_transients(self, monkeypatch):
        monkeypatch.setattr(retry, "_BASE_S", 0.001)
        calls = []
        with faults.scoped({"rules": [
            {"site": "device.call", "op": "raise",
             "exc": "DeviceTransientError", "count": 2},
        ]}):
            out = retry.device_call(lambda: calls.append(1) or "ok")
        assert out == "ok" and len(calls) == 1

    def test_device_call_permanent_error_raises_immediately(self):
        calls = []
        with faults.scoped({"rules": [
            {"site": "device.call", "op": "raise",
             "exc": "ExecutionError", "count": 0},
        ]}) as plan:
            with pytest.raises(ExecutionError):
                retry.device_call(lambda: calls.append(1))
            assert plan.snapshot()[0]["fired"] == 1  # no second attempt
        assert not calls

    def test_device_call_exhausts_attempts(self, monkeypatch):
        monkeypatch.setattr(retry, "_BASE_S", 0.001)
        monkeypatch.setattr(retry, "_ATTEMPTS", 3)
        with faults.scoped({"rules": [
            {"site": "device.call", "op": "raise",
             "exc": "DeviceTransientError", "count": 0},
        ]}) as plan:
            with pytest.raises(DeviceTransientError):
                retry.device_call(lambda: "never")
            assert plan.snapshot()[0]["fired"] == 3

    def test_deadline_bounds_retry_sleeps(self, monkeypatch):
        # backoff wants seconds; the deadline has milliseconds — the
        # call must fail fast with the typed deadline error, not sleep
        monkeypatch.setattr(retry, "_BASE_S", 30.0)
        monkeypatch.setattr(retry, "_CAP_S", 30.0)
        retry.seed_backoff(0)
        t0 = time.perf_counter()
        with faults.scoped({"rules": [
            {"site": "device.call", "op": "raise",
             "exc": "DeviceTransientError", "count": 0},
        ]}):
            with deadline_scope(Deadline.after(0.01)):
                with pytest.raises(QueryDeadlineError):
                    retry.device_call(lambda: "never")
        assert time.perf_counter() - t0 < 5.0


class _ScriptedHandle(WorkerHandle):
    """WorkerHandle whose request() runs a script instead of a socket."""

    def __init__(self, name, script, log):
        super().__init__(name, 0)
        self._script = script  # callable(msg) -> response dict (or raises)
        self._log = log
        self.probe_ok = False

    def request(self, msg, timeout=-1):
        self._log.append((self.host, msg.get("type")))
        return self._script(msg)

    def probe(self):
        self._log.append((self.host, "probe"))
        return self.probe_ok


class TestCoordinatorBookkeeping:
    def test_failover_reassigns_in_rotation_order(self):
        from datafusion_tpu.parallel.coordinator import _dispatch
        from datafusion_tpu.parallel.physical import PlanFragment

        log = []

        def dies(msg):
            raise ConnectionResetError("boom")

        a = _ScriptedHandle("a", dies, log)
        b = _ScriptedHandle("b", lambda m: {"type": "partial_state"}, log)
        frag = PlanFragment(0, 1, {}, {}, "q")
        out = _dispatch([a, b], [frag], "execute_fragment")
        assert [h for h, _ in log] == ["a", "b"]  # a fails, b takes over
        assert out[0][0] is frag and not a.alive and b.alive

    def test_no_workers_left_error_message(self):
        from datafusion_tpu.parallel.coordinator import _dispatch
        from datafusion_tpu.parallel.physical import PlanFragment

        log = []

        def dies(msg):
            raise ConnectionRefusedError("nope")

        handles = [_ScriptedHandle(n, dies, log) for n in ("a", "b")]
        with pytest.raises(ExecutionError, match="all 2 workers are down"):
            _dispatch(handles, [PlanFragment(0, 1, {}, {}, "q")], "execute_fragment")
        # the last-gasp probe rounds ran before giving up
        assert [h for h, k in log if k == "probe"]

    def test_dispatch_readmits_recovered_worker(self):
        from datafusion_tpu.parallel.coordinator import _dispatch
        from datafusion_tpu.parallel.physical import PlanFragment

        log = []
        state = {"calls": 0}

        def flaky(msg):
            state["calls"] += 1
            if state["calls"] == 1:
                raise ConnectionResetError("restarting")
            return {"type": "partial_state"}

        a = _ScriptedHandle("a", flaky, log)
        a.probe_ok = True  # "restarted" by the time dispatch re-probes
        out = _dispatch([a], [PlanFragment(0, 1, {}, {}, "q")], "execute_fragment")
        assert out[0][1]["type"] == "partial_state"
        assert a.alive  # re-admitted, not dead forever

    def test_worker_error_not_masked_by_lapsed_deadline(self):
        # a genuine worker error arriving just as the deadline lapses
        # must keep its message — only request TIMEOUTS convert
        from datafusion_tpu.parallel.coordinator import _dispatch
        from datafusion_tpu.parallel.physical import PlanFragment

        def slow_error(msg):
            time.sleep(0.08)
            raise ExecutionError("worker says: unknown aggregate")

        a = _ScriptedHandle("a", slow_error, [])
        with pytest.raises(ExecutionError, match="unknown aggregate"):
            _dispatch([a], [PlanFragment(0, 1, {}, {}, "q")],
                      "execute_fragment", Deadline.after(0.03))

    def test_dispatch_deadline_expires(self):
        from datafusion_tpu.parallel.coordinator import _dispatch
        from datafusion_tpu.parallel.physical import PlanFragment

        a = _ScriptedHandle("a", lambda m: {"type": "partial_state"}, [])
        with pytest.raises(QueryDeadlineError):
            _dispatch([a], [PlanFragment(0, 1, {}, {}, "q")],
                      "execute_fragment", Deadline.after(-1.0))

    def test_heartbeat_probation_and_failure_detection(self):
        log = []
        a = _ScriptedHandle("a", lambda m: None, log)
        mon = HeartbeatMonitor([a], interval=0.01, probation_pings=2,
                               fail_threshold=2)
        # up worker missing two consecutive probes goes down
        a.probe_ok = False
        mon.poll_once()
        assert a.alive  # one miss is not dead (slow != dead)
        mon.poll_once()
        assert not a.alive
        # recovery: two consecutive healthy probes = one probation cycle
        a.probe_ok = True
        mon.poll_once()
        assert not a.alive  # probation
        mon.poll_once()
        assert a.alive  # re-admitted

    def test_heartbeat_streaks_reset_on_external_state_flip(self):
        # dispatch failover flips alive between monitor cycles: stale
        # probe streaks must not shortcut probation / fail thresholds
        a = _ScriptedHandle("a", lambda m: None, [])
        mon = HeartbeatMonitor([a], interval=0.01, probation_pings=2,
                               fail_threshold=2)
        a.probe_ok = True
        for _ in range(5):
            mon.poll_once()  # long healthy streak
        a.mark_down()  # dispatch-side failover, not the monitor
        mon.poll_once()
        assert not a.alive  # stale ok-streak must not readmit instantly
        mon.poll_once()
        assert a.alive  # two FRESH consecutive probes readmit
        # symmetric: accumulate misses while down, then a dispatch-side
        # last-gasp re-admission — the stale bad-streak must not demote
        # the worker on its first missed probe
        a.probe_ok = False
        for _ in range(3):
            mon.poll_once()
        assert not a.alive
        a.readmit()
        mon.poll_once()
        assert a.alive  # one fresh miss < fail_threshold
        mon.poll_once()
        assert not a.alive  # two fresh consecutive misses demote


class TestDistributedChaos:
    """Real worker OS processes + seeded fault plans: distributed
    results must be identical to the fault-free local run."""

    def test_worker_killed_mid_fragment(self, tmp_path, healthy_workers):
        _, addrs = healthy_workers
        paths = _write_partitions(tmp_path)
        crashy, crashy_addr = _spawn_worker(fault_plan={"rules": [
            {"site": "worker.fragment", "op": "kill", "after": 1},
        ]})
        try:
            dctx = _register(DistributedContext([crashy_addr, *addrs]), paths)
            assert _rows(dctx) == _local_want(paths)
            assert crashy.wait(timeout=10) == 17  # died by injected fault
            crashy_handle = dctx.workers[0]
            assert not crashy_handle.alive  # marked down by failover
        finally:
            if crashy.poll() is None:
                crashy.terminate()
                crashy.wait(timeout=10)

    def test_connection_reset_on_recv(self, tmp_path, healthy_workers):
        # the response is lost AFTER the worker already executed the
        # fragment: failover replays it elsewhere, and the merge must
        # still fold each fragment exactly once
        _, addrs = healthy_workers
        paths = _write_partitions(tmp_path)
        dctx = _register(DistributedContext(addrs), paths)
        with faults.scoped({"rules": [
            {"site": "wire.recv", "op": "raise",
             "exc": "ConnectionResetError", "after": 1, "count": 1},
        ]}) as plan:
            got = _rows(dctx)
            assert plan.snapshot()[0]["fired"] == 1
        assert got == _local_want(paths)

    def test_corrupted_frame_fails_over(self, tmp_path, healthy_workers):
        _, addrs = healthy_workers
        paths = _write_partitions(tmp_path)
        dctx = _register(DistributedContext(addrs), paths)
        with faults.scoped({"rules": [
            {"site": "wire.recv.payload", "op": "corrupt",
             "offset": 0, "after": 1, "count": 1},
        ]}) as plan:
            got = _rows(dctx)
            assert plan.snapshot()[0]["fired"] == 1
        assert got == _local_want(paths)

    def test_transient_device_errors_inside_worker(self, tmp_path,
                                                   healthy_workers):
        _, addrs = healthy_workers
        paths = _write_partitions(tmp_path)
        flaky, flaky_addr = _spawn_worker(
            fault_plan={"rules": [
                # two consecutive transient device failures, then clean
                {"site": "device.call", "op": "raise",
                 "exc": "DeviceTransientError", "count": 2},
            ]},
            extra_env={"DATAFUSION_TPU_RETRY_BASE_S": "0.001"},
        )
        try:
            dctx = _register(DistributedContext([flaky_addr, *addrs]), paths)
            assert _rows(dctx) == _local_want(paths)
            assert flaky.poll() is None  # retried internally, still up
        finally:
            flaky.terminate()
            flaky.wait(timeout=10)

    def test_duplicate_response_not_double_merged(self, tmp_path,
                                                  healthy_workers,
                                                  monkeypatch):
        # simulate a replayed fragment whose first (merely slow)
        # response ALSO arrives: the merge must drop the duplicate, or
        # SUM/COUNT double and dictionary codes remap twice
        from datafusion_tpu.parallel import coordinator as coord_mod

        _, addrs = healthy_workers
        paths = _write_partitions(tmp_path)
        real = coord_mod._dispatch

        def duplicating(workers, fragments, request_type, deadline=None,
                        **kw):
            out = real(workers, fragments, request_type, deadline, **kw)
            return out + [out[0]]

        monkeypatch.setattr(coord_mod, "_dispatch", duplicating)
        dctx = _register(DistributedContext(addrs), paths)
        assert _rows(dctx) == _local_want(paths)
        from datafusion_tpu.utils.metrics import METRICS

        assert METRICS.snapshot()["counts"].get(
            "coord.duplicate_responses_dropped"
        )

    def test_killed_worker_readmitted_after_restart(self, tmp_path,
                                                    healthy_workers):
        _, addrs = healthy_workers
        paths = _write_partitions(tmp_path)
        with socket.socket() as s:  # reserve a fixed port for the restart
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        victim, victim_addr = _spawn_worker(bind=f"127.0.0.1:{port}")
        # result_cache=False: this test re-runs the SAME query to assert
        # failover/readmission mechanics — a coordinator result-cache
        # hit would answer without dispatching anything
        dctx = _register(
            DistributedContext([victim_addr, *addrs], result_cache=False),
            paths,
        )
        want = _local_want(paths)
        try:
            assert _rows(dctx) == want
            victim.kill()
            victim.wait(timeout=10)
            assert _rows(dctx) == want  # survivors cover the fragments
            handle = dctx.workers[0]
            assert not handle.alive
            # restart on the same endpoint; one probation cycle of the
            # heartbeat loop re-admits it
            victim, _ = _spawn_worker(bind=f"127.0.0.1:{port}")
            mon = HeartbeatMonitor(dctx.workers, interval=0.05,
                                   probation_pings=1)
            mon.poll_once()
            assert handle.alive
            assert _rows(dctx) == want
            # the background thread form works too
            handle.mark_down()
            mon.start()
            try:
                deadline = time.monotonic() + 30
                while not handle.alive and time.monotonic() < deadline:
                    time.sleep(0.02)
                assert handle.alive
            finally:
                mon.stop()
        finally:
            if victim.poll() is None:
                victim.terminate()
                victim.wait(timeout=10)

    def test_query_deadline_enforced(self, tmp_path, healthy_workers):
        _, addrs = healthy_workers
        paths = _write_partitions(tmp_path, n_parts=2, rows_per=50)
        dctx = _register(
            DistributedContext(addrs, query_deadline_s=1e-6), paths
        )
        with pytest.raises(QueryDeadlineError):
            _rows(dctx)
        # a sane budget flows through and succeeds
        dctx2 = _register(
            DistributedContext(addrs, query_deadline_s=120.0), paths
        )
        assert _rows(dctx2) == _local_want(paths)


class TestWireHardening:
    def test_unparseable_frame_raises_protocol_error(self):
        from datafusion_tpu.parallel.wire import ProtocolError, recv_msg

        a, b = socket.socketpair()
        try:
            garbage = b"\x02not json at all"
            a.sendall(len(garbage).to_bytes(8, "big") + garbage)
            with pytest.raises(ProtocolError):
                recv_msg(b)
        finally:
            a.close()
            b.close()

    def test_protocol_error_is_connection_error(self):
        from datafusion_tpu.parallel.wire import ProtocolError

        assert issubclass(ProtocolError, ConnectionError)


class TestLinkRateCacheKey:
    def test_keyed_by_device_identity(self):
        from datafusion_tpu.exec.batch import _link_cache_key

        class Dev:
            def __init__(self, id):
                self.id = id

            def __repr__(self):
                return f"Dev({self.id})"

        assert _link_cache_key(None, "tpu") == "tpu"
        k0 = _link_cache_key(Dev(0), "tpu")
        k1 = _link_cache_key(Dev(1), "tpu")
        assert k0 != k1  # same platform, different chips: separate rates
        assert k0 == _link_cache_key(Dev(0), "tpu")
