"""Feedback-driven planning (datafusion_tpu/cost): the cost/statistics
store, the advisor's decision functions, and the adaptive loop end to
end.

The contracts under test:
- store mechanics: EWMA/last/max views per field, lock-free observe,
  decision/replan logs, bounded persistence;
- persistence survives a process restart (reset + reload from the same
  ``DATAFUSION_TPU_COST_DIR``), and a corrupt store file degrades to an
  empty store that never blocks planning;
- table keys retire on the RIGHT version bumps: a rewritten backing
  file and an ingest append each read/write fresh entries, while a
  byte-identical re-registration keeps learned statistics;
- trained-store planning flips real decisions (aggregate capacity
  pre-size, join build side) with bit-exact results;
- an induced cardinality misestimate triggers a replan that still
  returns the exact answer (and shows up in counters, flight events,
  and EXPLAIN ANALYZE);
- ``DATAFUSION_TPU_COST=0`` restores static planning: same results,
  zero decisions.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from datafusion_tpu import cost
from datafusion_tpu.cost import advisor
from datafusion_tpu.cost.store import CostStore
from datafusion_tpu.datatypes import DataType, Field, Schema
from datafusion_tpu.exec.batch import StringDictionary, make_host_batch
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.datasource import MemoryDataSource
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.utils.metrics import METRICS


@pytest.fixture(autouse=True)
def _fresh_store():
    """Each test owns the process store and its env knobs."""
    saved = {
        k: os.environ.pop(k, None)
        for k in ("DATAFUSION_TPU_COST", "DATAFUSION_TPU_COST_DIR",
                  "DATAFUSION_TPU_COST_REPLAN_RATIO")
    }
    cost.reset_store()
    yield
    cost.reset_store()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


SCHEMA = Schema(
    [Field("k", DataType.UTF8, False), Field("v", DataType.FLOAT64, False)]
)


def _mem_source(groups: int = 4, rows: int = 200):
    d = StringDictionary()
    keys = [f"g{i % groups}" for i in range(rows)]
    codes = np.array([d.add(s) for s in keys], dtype=np.int32)
    vals = np.arange(rows, dtype=np.float64)
    batch = make_host_batch(SCHEMA, [codes, vals], [None, None], [d, None])
    return MemoryDataSource(SCHEMA, [batch])


def _ctx(tables=None) -> ExecutionContext:
    # result cache off: these tests assert on per-execution planning
    # behavior, which a cached result would short-circuit
    ctx = ExecutionContext(device="cpu", result_cache=False)
    for name, ds in (tables or {"t": _mem_source()}).items():
        ctx.register_datasource(name, ds)
    return ctx


SQL = "SELECT k, SUM(v) FROM t GROUP BY k"


def _rows(ctx, sql=SQL):
    return sorted(collect(ctx.sql(sql)).to_rows())


# -- store mechanics ------------------------------------------------------


class TestCostStore:
    def test_observe_keeps_ewma_last_and_max(self):
        st = CostStore()
        st.observe("t", "scan", rows=100)
        st.observe("t", "scan", rows=10)
        rec = st.lookup("t", "scan")
        assert rec["n"] == 2
        assert rec["rows_last"] == 10
        assert rec["rows_max"] == 100
        # EWMA sits between the samples, pulled toward the newer one
        assert 10 < rec["rows"] < 100

    def test_value_defaults_on_miss(self):
        st = CostStore()
        assert st.value("t", "scan", "rows") is None
        assert st.value("t", "scan", "rows", 7) == 7
        st.observe("t", "scan", rows=3)
        assert st.value("t", "scan", "rows_last", 7) == 3
        assert st.value("t", "scan", "nope", 7) == 7

    def test_decisions_carry_monotone_serials(self):
        st = CostStore()
        a = st.note_decision("x", 1, 2, "because")
        b = st.note_decision("y", 3, 4, "because", table="t")
        assert b["seq"] == a["seq"] + 1
        assert b["table"] == "t"
        assert [d["decision"] for d in st.decisions] == ["x", "y"]

    def test_snapshot_groups_by_table(self):
        st = CostStore()
        st.observe("t1", "scan", rows=5)
        st.observe("t1", "agg:g=k", groups=2)
        st.observe("t2", "scan", rows=9)
        snap = st.snapshot()
        assert set(snap["tables"]) == {"t1", "t2"}
        assert set(snap["tables"]["t1"]) == {"scan", "agg:g=k"}
        assert snap["entries"] == 3


# -- persistence ----------------------------------------------------------


class TestPersistence:
    def test_store_survives_restart(self, tmp_path):
        os.environ["DATAFUSION_TPU_COST_DIR"] = str(tmp_path)
        cost.reset_store()
        st = cost.store()
        st.observe("t@s1", "scan", rows=123)
        st.flush(force=True)
        # "restart": drop the process store, reload from disk
        cost.reset_store()
        st2 = cost.store()
        assert st2 is not st
        assert st2.value("t@s1", "scan", "rows_last") == 123

    def test_flush_is_throttled_until_forced(self, tmp_path):
        path = str(tmp_path / "cost_store.json")
        st = CostStore(path)
        st.observe("t", "scan", rows=1)
        assert st.flush(force=True)
        st.observe("t", "scan", rows=2)
        assert not st.flush()  # inside the save interval
        assert st.flush(force=True)

    def test_corrupt_store_degrades_to_empty(self, tmp_path):
        path = tmp_path / "cost_store.json"
        path.write_text('{"version": 1, "entries": {"t\\tscan"')
        before = METRICS.counts.get("cost.store.corrupt", 0)
        st = CostStore(str(path))
        assert len(st) == 0
        assert METRICS.counts.get("cost.store.corrupt", 0) == before + 1
        # ...and planning on top of the empty store still answers
        os.environ["DATAFUSION_TPU_COST_DIR"] = str(tmp_path)
        cost.reset_store()
        ctx = _ctx()
        assert _rows(ctx)

    def test_wrong_schema_version_dropped(self, tmp_path):
        path = tmp_path / "cost_store.json"
        path.write_text(json.dumps(
            {"version": 999, "entries": {"t\tscan": {"n": 1}}}))
        st = CostStore(str(path))
        assert len(st) == 0

    def test_flush_prunes_to_entry_budget(self, tmp_path):
        from datafusion_tpu.cost.store import _MAX_ENTRIES

        path = str(tmp_path / "cost_store.json")
        st = CostStore(path)
        for i in range(_MAX_ENTRIES + 10):
            st.observe(f"t{i}", "scan", rows=i)
        assert st.flush(force=True)
        with open(path, encoding="utf-8") as f:
            payload = json.load(f)
        assert len(payload["entries"]) == _MAX_ENTRIES


# -- table keys: version bumps retire the right entries -------------------


class TestTableKeys:
    def test_rewritten_file_reads_fresh_entries(self, tmp_path):
        p = tmp_path / "t.csv"
        p.write_text("k,v\na,1\nb,2\n")
        ctx = ExecutionContext(device="cpu", result_cache=False)
        ctx.register_csv("t", str(p), SCHEMA)
        key1 = ctx.cost_table_key("t")
        assert "@s" in key1  # file-identity key, stable across restarts
        # same file re-registered (a restart): identical key — the
        # learned statistics survive
        ctx2 = ExecutionContext(device="cpu", result_cache=False)
        ctx2.register_csv("t", str(p), SCHEMA)
        assert ctx2.cost_table_key("t") == key1
        # rewritten file: new key, stale cardinalities unreachable
        p.write_text("k,v\na,1\nb,2\nc,3\nd,4\n")
        ctx3 = ExecutionContext(device="cpu", result_cache=False)
        ctx3.register_csv("t", str(p), SCHEMA)
        assert ctx3.cost_table_key("t") != key1

    def test_ingest_append_bumps_key(self):
        from datafusion_tpu.ingest import AppendableSource

        src = AppendableSource.wrap(_mem_source(), "t")
        ctx = ExecutionContext(device="cpu", result_cache=False)
        ctx.register_datasource("t", src)
        key1 = ctx.cost_table_key("t")
        assert "@d0" in key1
        src.append_batch(src.build_batch({"k": ["z"], "v": [9.0]}))
        key2 = ctx.cost_table_key("t")
        assert key2 != key1 and "@d1" in key2

    def test_reregistration_bumps_in_memory_key(self):
        ctx = _ctx()
        key1 = ctx.cost_table_key("t")
        ctx.register_datasource("t", _mem_source(groups=8))
        assert ctx.cost_table_key("t") != key1


# -- the adaptive loop end to end -----------------------------------------


class TestAdaptivePlanning:
    def test_scan_and_groups_observed(self):
        ctx = _ctx()
        _rows(ctx)
        st = cost.store()
        tkey = ctx.cost_table_key("t")
        assert st.value(tkey, "scan", "rows_last") == 200
        assert st.value(tkey, "agg:g=k", "groups_last") == 4

    def test_trained_store_presizes_aggregate(self):
        ctx = _ctx()
        r1 = _rows(ctx)  # cold: observes 4 groups
        r2 = _rows(ctx)  # trained: pre-sizes from the learned count
        assert r1 == r2
        ds = [d for d in cost.store().decisions
              if d["decision"] == "agg.capacity"]
        assert ds and "~4 groups" in ds[-1]["reason"]

    def test_join_build_side_swaps_bit_exact(self):
        sm = Schema([Field("id", DataType.FLOAT64, False),
                     Field("name", DataType.UTF8, False)])
        bg = Schema([Field("fk", DataType.FLOAT64, False),
                     Field("x", DataType.FLOAT64, False)])
        d = StringDictionary()
        codes = np.array([d.add(f"n{i}") for i in range(5)], dtype=np.int32)
        small = MemoryDataSource(sm, [make_host_batch(
            sm, [np.arange(5, dtype=np.float64), codes],
            [None, None], [None, d])])
        fk = np.asarray(np.arange(500) % 5, dtype=np.float64)
        big = MemoryDataSource(bg, [make_host_batch(
            bg, [fk, np.arange(500, dtype=np.float64)],
            [None, None], [None, None])])
        sql = ("SELECT name, SUM(x) FROM small JOIN big ON id = fk "
               "GROUP BY name")
        ctx = _ctx({"small": small, "big": big})
        cold = _rows(ctx, sql)  # observes both scans + the build side
        trained = _rows(ctx, sql)  # build side swaps to the small table
        assert cold == trained
        ds = [d0 for d0 in cost.store().decisions
              if d0["decision"] == "join.build_side"]
        assert ds and ds[-1]["chosen"] == "left"

    def test_misestimate_triggers_replan_with_exact_answer(self):
        ctx = _ctx()
        want = _rows(ctx)
        # poison the store: claim this (table, GROUP BY shape) has
        # thousands of groups — the pre-sized plan must abort cheaply
        # and re-derive capacity from actuals
        st = cost.store()
        st.observe(ctx.cost_table_key("t"), "agg:g=k", groups=4000)
        before = METRICS.counts.get("plan.replans", 0)
        assert _rows(ctx) == want
        assert METRICS.counts.get("plan.replans", 0) == before + 1
        rp = list(st.replans)[-1]
        assert rp["what"] == "aggregate.capacity"
        assert rp["estimate"] == 4000 and rp["actual"] <= 8
        # the replan corrected the learned cardinality for next time
        assert st.value(
            ctx.cost_table_key("t"), "agg:g=k", "groups_last") == 4

    def test_replan_ratio_env_knob(self):
        os.environ["DATAFUSION_TPU_COST_REPLAN_RATIO"] = "1000000"
        ctx = _ctx()
        want = _rows(ctx)
        st = cost.store()
        st.observe(ctx.cost_table_key("t"), "agg:g=k", groups=4000)
        before = METRICS.counts.get("plan.replans", 0)
        assert _rows(ctx) == want  # tolerant ratio: no replan fires
        assert METRICS.counts.get("plan.replans", 0) == before

    def test_cost_off_restores_static_planning(self):
        ctx = _ctx()
        want = _rows(ctx)
        os.environ["DATAFUSION_TPU_COST"] = "0"
        assert _rows(ctx) == want
        assert _rows(ctx) == want
        assert not list(cost.store().decisions)
        # observation still flows when decisions are off (the serving
        # path's row weights read the same store)
        assert cost.store().value(
            ctx.cost_table_key("t"), "scan", "rows_last") == 200

    def test_explain_analyze_renders_decisions(self):
        ctx = _ctx()
        _rows(ctx)
        res = ctx.sql("EXPLAIN ANALYZE " + SQL)
        rep = res.report()
        assert "Cost decisions" in rep
        assert "agg.capacity" in rep and "default" in rep
        assert res.cost["decisions"]

    def test_explain_analyze_renders_replans(self):
        ctx = _ctx()
        _rows(ctx)
        cost.store().observe(ctx.cost_table_key("t"), "agg:g=k",
                             groups=4000)
        res = ctx.sql("EXPLAIN ANALYZE " + SQL)
        assert "Replans (" in res.report()
        assert res.cost["replans"]


# -- advisor decision functions (unit) ------------------------------------


class TestAdvisor:
    def test_agg_shape_is_order_insensitive(self):
        assert advisor.agg_shape(["b", "a"]) == advisor.agg_shape(["a", "b"])

    def test_serve_window_shrinks_for_sparse_arrivals(self):
        st = CostStore()
        st.observe(cost.SERVE_KEY, "arrivals", interval_s=1.0)
        chosen = advisor.serve_window_s(st, 0.002)
        assert chosen < 0.002

    def test_serve_window_widens_for_dense_arrivals(self):
        st = CostStore()
        st.observe(cost.SERVE_KEY, "arrivals", interval_s=0.0001)
        chosen = advisor.serve_window_s(st, 0.002)
        assert chosen > 0.002

    def test_scan_chunk_needs_link_rate(self):
        st = CostStore()
        st.observe("t", "scan", rows=1000, nbytes=8000)
        # no measured link rate -> keep the configured chunking
        assert advisor.scan_chunk_rows(st, "t", "cpu", 1000) is None


# -- guardrails -----------------------------------------------------------


class TestGuardrails:
    def test_schema_preservation_veto(self):
        from datafusion_tpu.analysis.verify import (
            PlanVerificationError,
            assert_schema_preserved,
        )

        a = Schema([Field("x", DataType.FLOAT64, False)])
        b = Schema([Field("x", DataType.FLOAT64, False)])
        assert_schema_preserved(a, b, "cost rewrite")  # equal: fine
        c = Schema([Field("y", DataType.FLOAT64, False)])
        with pytest.raises(PlanVerificationError):
            assert_schema_preserved(a, c, "cost rewrite")

    def test_df005_covers_cost_observe_path(self):
        from datafusion_tpu.analysis import lint

        src = (
            "import threading\n"
            "class CostStore:\n"
            "    def observe(self, k, s, **f):\n"
            "        with self._lock:\n"
            "            pass\n"
        )
        found = lint.lint_source(src, "datafusion_tpu/cost/store.py")
        assert any(f.rule == "DF005" for f in found)
        # the real store passes its own lint
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        real = os.path.join(repo, "datafusion_tpu", "cost", "store.py")
        assert lint.lint_paths([real]) == []

    def test_debug_cost_snapshot_shape(self):
        ctx = _ctx()
        _rows(ctx)
        snap = cost.store().snapshot()
        assert {"path", "entries", "tables", "decisions", "replans"} \
            <= set(snap)
        # JSON-serializable end to end (the /debug/cost contract)
        json.dumps(snap)

    def test_console_cost_command(self):
        import io

        from datafusion_tpu.cli import Console

        ctx = _ctx()
        _rows(ctx)
        _rows(ctx)
        out = io.StringIO()
        con = Console(ctx, out=out)
        assert con.handle_command("\\cost")
        text = out.getvalue()
        assert "Cost store:" in text and "agg:g=k" in text
