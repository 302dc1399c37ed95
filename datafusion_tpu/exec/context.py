"""ExecutionContext: the API hub (reference `src/execution/context.rs`).

`ctx.sql(text)` parses, plans, optimizes (projection push-down is
*enabled* here — the reference keeps it commented out, `context.rs:88`),
and maps the plan onto device operators.  The plan->operator boundary
(`execute()`, reference `context.rs:103-163`) is where fusion happens:

    Projection(Selection(TableScan))  -> one fused scan+filter+project
                                         XLA kernel (PipelineRelation)
    Aggregate(Selection(TableScan))   -> one fused filter+aggregate
                                         kernel (AggregateRelation)
    Limit(Sort(...))                  -> device sort with early slice

Everything the reference left `unimplemented!()` — Aggregate, Sort,
Limit, EmptyRelation, CREATE EXTERNAL TABLE execution (`context.rs:47-75`),
scalar UDF lookup (`context.rs:222-224`) — is implemented.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Callable, Iterator, Optional, Union

import numpy as np

from datafusion_tpu.datatypes import DataType, Field, Schema
from datafusion_tpu.errors import ExecutionError, NotSupportedError, PlanError
from datafusion_tpu.exec.aggregate import AggregateRelation
from datafusion_tpu.exec.batch import RecordBatch
from datafusion_tpu.exec.datasource import (
    CsvDataSource,
    DataSource,
    NdJsonDataSource,
    ParquetDataSource,
)
from datafusion_tpu.exec import fused
from datafusion_tpu.exec.materialize import ResultTable, collect
from datafusion_tpu.exec.relation import DataSourceRelation, PipelineRelation, Relation
from datafusion_tpu.exec.sort import LimitRelation, SortRelation
from datafusion_tpu.plan.expr import FunctionMeta, FunctionType
from datafusion_tpu.plan.logical import (
    Aggregate,
    EmptyRelation,
    Join,
    Limit,
    LogicalPlan,
    Projection,
    Selection,
    Sort,
    TableScan,
)
from datafusion_tpu.obs import recorder
from datafusion_tpu.sql import ast
from datafusion_tpu.sql.optimizer import push_down_projection
from datafusion_tpu.sql.parser import parse_sql
from datafusion_tpu.sql.planner import SqlToRel, convert_data_type
from datafusion_tpu.utils.metrics import METRICS

# admission/backpressure counter contract for the serving path
# (datafusion_tpu/serve.py): `queries_admitted` counts here (every
# root query that enters execute); the serving front door increments
# `queries_queued` on every admitted enqueue and `queries_shed` on
# every refusal (queue depth, deadline infeasibility, HBM headroom),
# so admitted + shed == submitted.  Declared so all three names render
# in every scrape from process start, served or not.
METRICS.declare("queries_admitted", "queries_queued", "queries_shed")


class _EmptyRelationExec(Relation):
    """One conceptual row, zero columns (for table-less SELECTs)."""

    _CAP = 8

    @property
    def schema(self) -> Schema:
        return Schema([])

    def batches(self) -> Iterator[RecordBatch]:
        yield RecordBatch(
            Schema([]), [], [], [], num_rows=1, mask=np.ones(self._CAP, dtype=bool)
        )


class DdlResult:
    """Outcome of a DDL statement (CREATE EXTERNAL TABLE)."""

    def __init__(self, message: str):
        self.message = message

    def __repr__(self):
        return self.message


class ExplainResult:
    def __init__(self, plan: LogicalPlan):
        self.plan = plan

    def __repr__(self):
        return repr(self.plan)


class _ContextSchemaProvider:
    """Adapter exposing the context's catalog to the planner (reference
    `ExecutionContextSchemaProvider`, `context.rs:211-225` — whose
    get_function_meta was `unimplemented!()`; here UDFs actually work)."""

    def __init__(self, ctx: "ExecutionContext"):
        self.ctx = ctx

    def get_table_meta(self, name: str) -> Optional[Schema]:
        ds = self.ctx.datasources.get(name)
        return ds.schema if ds is not None else None

    def get_function_meta(self, name: str) -> Optional[FunctionMeta]:
        return self.ctx.functions.get(name.lower())


_SOURCE_SERIALS = itertools.count(1)


def _source_serial(ds) -> int:
    """A number this process gives an in-memory datasource once, for
    `query_fingerprint`; a wrapper that serves the same data (serve's
    `PinnedSource.inner`) has its source's."""
    while getattr(ds, "inner", None) is not None:
        ds = ds.inner
    serial = ds.__dict__.get("_fingerprint_serial")
    if serial is None:
        serial = ds.__dict__.setdefault(
            "_fingerprint_serial", next(_SOURCE_SERIALS))
    return serial


class ExecutionContext:
    """Register datasources, run SQL, pull columnar results.

    `device`: None (JAX default — the TPU when one is attached),
    "cpu", or "tpu".  Selection happens at this plan->operator boundary,
    mirroring the north-star `with_device("tpu")` design.
    """

    def __init__(self, device: Optional[str] = None, batch_size: int = 131072,
                 result_cache=None):
        self.datasources: dict[str, DataSource] = {}
        self.functions: dict[str, FunctionMeta] = {}
        self.batch_size = batch_size
        self.device = None
        # catalog versioning: every (re-)registration of a table name
        # bumps a context-wide serial, and the result-cache fingerprint
        # folds the versions of every table a plan scans in — so
        # re-registering a table instantly invalidates dependent entries
        self._catalog_versions: dict[str, int] = {}
        self._catalog_serial = 0
        self._functions_version = 0
        # result cache: None = off, False = explicitly off (workers'
        # internal per-fragment contexts), a CacheStore, or the env
        # default (datafusion_tpu.cache knobs)
        if result_cache is None:
            from datafusion_tpu import cache as _cache

            result_cache = _cache.make_store("result")
        elif result_cache is False:
            result_cache = None
        self._result_cache = result_cache
        self._stats_history: dict[str, list[dict]] = {}
        self._history_cap = 32  # runs kept per fingerprint
        self._history_fingerprints = 128  # distinct fingerprints kept
        self.last_fingerprint: Optional[str] = None
        # per-thread root/recursion guard: concurrent queries on one
        # context must not see each other's in-execute state (a subtree
        # expansion mistaken for a root would mis-wire the cache seam)
        self._execute_tls = threading.local()
        # root queries on this context feed the fleet telemetry funnel
        # (latency histogram, SLO watchdog, slow/failed-query capture);
        # workers' per-fragment contexts flip this off
        self._telemetry = True
        if device is not None:
            import jax

            device = device.lower()
            matches = [d for d in jax.devices() if device in d.platform.lower()]
            if not matches:
                try:
                    matches = list(jax.devices(device))
                except RuntimeError:
                    matches = []
            if not matches:
                raise ExecutionError(f"no {device!r} device available")
            self.device = matches[0]
        self._optimize = True
        # builtin math functions are ordinary catalog entries (the
        # reference's UDF lookup was unimplemented!(), context.rs:222-224)
        from datafusion_tpu.exec.expression import BUILTIN_FUNCTIONS

        for fname, fn in BUILTIN_FUNCTIONS.items():
            self.register_udf(fname, [DataType.FLOAT64], DataType.FLOAT64, fn)

    # -- catalog --
    def register_datasource(self, name: str, ds: DataSource) -> None:
        """reference `context.rs:99`.  Re-registering a name bumps its
        catalog version: cached results that scanned the old table stop
        matching (fingerprint) AND are dropped eagerly (tag)."""
        self._catalog_serial += 1
        self._catalog_versions[name] = self._catalog_serial
        if self._result_cache is not None:
            self._result_cache.invalidate_tag(name)
        self.datasources[name] = ds

    def catalog_version(self, name: str) -> int:
        """Monotonic version of a registered table (0 = never seen)."""
        return self._catalog_versions.get(name, 0)

    def register_csv(
        self, name: str, path: str, schema: Schema, has_header: bool = True
    ) -> None:
        self.register_datasource(
            name, CsvDataSource(path, schema, has_header, self.batch_size)
        )

    def register_parquet(self, name: str, path: str, schema: Optional[Schema] = None):
        self.register_datasource(name, ParquetDataSource(path, schema, self.batch_size))

    def register_ndjson(self, name: str, path: str, schema: Schema) -> None:
        self.register_datasource(name, NdJsonDataSource(path, schema, self.batch_size))

    def register_udf(
        self,
        name: str,
        arg_types: list[DataType],
        return_type: DataType,
        jax_fn: Optional[Callable] = None,
        host_fn: Optional[Callable] = None,
    ) -> None:
        """Register a scalar UDF.

        `jax_fn` must be jax-traceable — it fuses into the pipeline
        kernel like any builtin.  `host_fn` (numpy in/out) is for
        functions with no tensor form (string/struct producers, e.g.
        the console's ST_* geo functions); those evaluate post-kernel
        at the materialization boundary."""
        if jax_fn is None and host_fn is None:
            raise ExecutionError(f"UDF {name!r} needs a jax_fn or a host_fn")
        meta = FunctionMeta(
            name.lower(),
            [Field(f"arg{i}", t, True) for i, t in enumerate(arg_types)],
            return_type,
            FunctionType.Scalar,
            jax_fn,
            host_fn,
        )
        # a (re-)registered UDF changes what identical SQL text computes
        self._functions_version += 1
        self.functions[name.lower()] = meta

    def _jax_functions(self) -> dict[str, Callable]:
        return {name: fm.jax_fn for name, fm in self.functions.items() if fm.jax_fn}

    def table(self, name: str):
        """A DataFrame over a registered datasource (the programmatic
        twin of `FROM name`)."""
        from datafusion_tpu.dataframe import DataFrame

        ds = self.datasources.get(name)
        if ds is None:
            raise ExecutionError(f"No datasource registered as {name!r}")
        return DataFrame(self, TableScan("default", name, ds.schema))

    # -- entry points --
    def sql(self, sql_text: str) -> Union[Relation, DdlResult, ExplainResult]:
        """Parse, plan, optimize, build the operator tree (lazy — no data
        is read until batches are pulled).  Reference `context.rs:43-97`."""
        with METRICS.timer("parse"):
            stmt = parse_sql(sql_text)
        if isinstance(stmt, ast.SqlCreateExternalTable):
            return self._execute_ddl(stmt)
        if isinstance(stmt, ast.SqlCreateMaterializedView):
            view = self.ingest().create_view(stmt.name, stmt.query_sql)
            return DdlResult(
                f"Registered materialized view {stmt.name} "
                f"({'incremental' if view.incremental else 'recompute'})")
        if isinstance(stmt, ast.SqlExplain):
            # mark the cost store's decision serial BEFORE planning so
            # EXPLAIN ANALYZE can attribute the rewrite decisions made
            # while optimizing THIS statement (join order / build side)
            from datafusion_tpu import cost as _cost

            decision_mark = _cost.store().decision_serial
            plan = self._plan(stmt.stmt)
            if stmt.analyze:
                # EXPLAIN ANALYZE executes the query under a trace
                # session and annotates the operator tree with measured
                # stats (obs/explain.py)
                from datafusion_tpu.obs.explain import explain_analyze

                return explain_analyze(
                    self, plan, decision_mark=decision_mark)
            if stmt.verify:
                # EXPLAIN VERIFY type-checks the plan WITHOUT executing
                # and renders the inferred schema per operator
                # (analysis/verify.py)
                from datafusion_tpu.analysis import verify as _averify

                with METRICS.timer("verify"):
                    report = _averify.verify_plan(
                        plan, functions=self.functions
                    )
                return _averify.ExplainVerifyResult(plan, report)
            return ExplainResult(plan)
        plan = self._plan(stmt)
        return self.execute(plan)

    def sql_collect(self, sql_text: str) -> Union[ResultTable, DdlResult, ExplainResult]:
        out = self.sql(sql_text)
        if isinstance(out, Relation):
            with METRICS.timer("collect"):
                return collect(out)
        return out

    def _plan(self, stmt: ast.SqlNode) -> LogicalPlan:
        planner = SqlToRel(_ContextSchemaProvider(self))
        with METRICS.timer("plan"):
            plan = planner.sql_to_rel(stmt)
        if self._optimize:
            with METRICS.timer("optimize"):
                plan = push_down_projection(plan)
                plan = self._cost_rewrite(plan)
        recorder.record("query.plan", plan=type(plan).__name__)
        return plan

    def _cost_rewrite(self, plan: LogicalPlan) -> LogicalPlan:
        """Cost-driven logical rewrites (join build side / order —
        datafusion_tpu/cost/optimizer.py).  Advisory by contract: any
        failure — including the verifier vetoing a schema-changing
        rewrite — discards the rewrite and keeps the static plan."""
        from datafusion_tpu import cost as _cost

        if not _cost.enabled():
            return plan
        try:
            from datafusion_tpu.cost.optimizer import apply_cost_rewrites

            return apply_cost_rewrites(self, plan)
        except Exception:  # noqa: BLE001 — cost rewrites must never fail a query
            METRICS.add("cost.rewrite_errors")
            return plan

    def _execute_ddl(self, stmt: ast.SqlCreateExternalTable) -> DdlResult:
        # the intent the reference commented out (context.rs:47-75)
        if stmt.columns:
            schema = Schema(
                [
                    Field(c.name, convert_data_type(c.data_type), c.allow_null)
                    for c in stmt.columns
                ]
            )
        elif stmt.file_type == ast.FileType.Parquet:
            schema = None  # inferred from file metadata
        else:
            raise PlanError(
                f"CREATE EXTERNAL TABLE ... STORED AS {stmt.file_type.value} "
                "requires an explicit column list"
            )
        if stmt.file_type == ast.FileType.CSV:
            self.register_csv(stmt.name, stmt.location, schema, stmt.header_row)
        elif stmt.file_type == ast.FileType.NdJson:
            self.register_ndjson(stmt.name, stmt.location, schema)
        else:
            self.register_parquet(stmt.name, stmt.location, schema)
        return DdlResult(f"Registered table {stmt.name}")

    # -- result caching (datafusion_tpu/cache) --
    def query_fingerprint(self, plan: LogicalPlan) -> str:
        """Canonical identity of `plan`'s result under this context's
        catalog state: plan wire JSON + per-table catalog versions +
        backing-file versions (mtime, size — an externally rewritten
        file must not serve stale cached rows) + the execution
        environment facts that change answers (device, batch size, UDF
        registry version)."""
        from datafusion_tpu.cache import (
            plan_fingerprint,
            scan_tables,
            source_version,
        )

        versions: dict[str, object] = {}
        for t in scan_tables(plan):
            entry: list = [self.catalog_version(t)]
            ds = self.datasources.get(t)
            if ds is not None:
                # streaming (appendable) tables version by append count:
                # every delta must stop dependent cached results from
                # matching even if a registration bump were ever missed
                dv = getattr(ds, "data_version", None)
                if dv is not None:
                    entry.append(["data", int(dv)])
                try:
                    entry.append(source_version(ds.to_meta()))
                except PlanError:
                    # non-serializable (in-memory) sources have no file
                    # identity: the object stands for its data.  Two
                    # contexts of one process that register different
                    # in-memory tables under one name at one catalog
                    # version must not share a fingerprint, since what
                    # it keys (a pinned join build, a cached result)
                    # outlives the context in process-wide stores
                    entry.append(["mem", _source_serial(ds)])
            versions[t] = entry
        return plan_fingerprint(plan, versions, extra={
            "device": str(self.device) if self.device is not None else "",
            "batch_size": self.batch_size,
            "functions_v": self._functions_version,
        })

    @property
    def result_cache(self):
        """The context's result CacheStore (None when caching is off)."""
        return self._result_cache

    def _record_history(self, fingerprint: str, summary: dict,
                        root: Optional[Relation] = None) -> None:
        entry = {"fingerprint": fingerprint, "ts": time.time(), **summary}
        if root is not None:
            from datafusion_tpu.obs import trace as obs_trace

            if obs_trace.enabled():
                from datafusion_tpu.obs.stats import collect_tree

                entry["operators"] = [
                    {"op": rel.op_label(), "depth": depth,
                     **rel.stats.snapshot()}
                    for depth, rel in collect_tree(root)
                ]
        # query completion is the cost store's persistence seam: cold
        # path, no locks held, throttled internally (cost/store.flush)
        from datafusion_tpu import cost as _cost

        _cost.flush()
        hist = self._stats_history.setdefault(fingerprint, [])
        hist.append(entry)
        del hist[: -self._history_cap]
        # bound the number of distinct fingerprints too (a long-lived
        # coordinator seeing parameterized SQL mints one per literal):
        # drop the oldest-inserted fingerprints beyond the cap
        while len(self._stats_history) > self._history_fingerprints:
            # tolerant pop: two threads recording concurrently may race
            # to evict the same oldest key
            try:
                self._stats_history.pop(next(iter(self._stats_history)), None)
            except (StopIteration, RuntimeError):
                break

    def stats_history(self, fingerprint: Optional[str] = None):
        """Per-query run history keyed by plan fingerprint: each entry
        records rows, wall seconds, whether it was a cache hit, and —
        on instrumented runs (EXPLAIN ANALYZE / tracing) — per-operator
        stats.  Warm-vs-cold runs of the same query compare directly.
        With a fingerprint returns that query's runs (oldest first);
        without, the whole mapping."""
        if fingerprint is not None:
            return list(self._stats_history.get(fingerprint, ()))
        return {k: list(v) for k, v in self._stats_history.items()}

    # -- plan -> operators (reference context.rs:103-163) --
    def execute(self, plan: LogicalPlan) -> Relation:
        """The cache seam: a root-level plan whose fingerprint is cached
        replays materialized batches (`CachedResultRelation`); a miss
        executes normally with a capture hook attached, filled by
        `collect_columns` at the materialization boundary.  Recursive
        calls (operator subtrees) pass straight through to
        `_execute_plan`, which subclasses override.

        Root-level plans are statically verified first (analysis/
        verify.py, `DATAFUSION_TPU_VERIFY`, default on): an unknown
        column or mistyped expression raises `PlanVerificationError`
        with a source-anchored diagnostic *here*, before any operator
        is built or any batch touches a device."""
        tls = self._execute_tls
        if getattr(tls, "in_execute", False):
            return self._execute_plan(plan)
        tls.in_execute = True
        try:
            # admission boundary: every root query counts here (the
            # serving path's queue/shed counters join this registry).
            # Workers' per-fragment contexts don't count — a fragment
            # is one shard of an already-admitted query, and the fleet
            # aggregator sums this counter across nodes
            if self._telemetry:
                METRICS.add("queries_admitted")
                recorder.record("query.admit", plan=type(plan).__name__)
            if self._result_cache is None:
                self._verify(plan)
                return self._tag_root(self._execute_plan(plan), plan)
            from datafusion_tpu.cache import scan_tables
            from datafusion_tpu.cache.result import (
                CachedResultRelation,
                attach_result_capture,
            )

            fp = self.last_fingerprint = self.query_fingerprint(plan)
            entry = self._result_cache.get(fp)
            if entry is not None:
                # no verify on the warm path: an identical fingerprint
                # means this exact plan already verified on the miss
                # that populated the entry — a repeat walk finds nothing
                recorder.record("cache.hit", level="result",
                                fingerprint=fp[:16])
                return self._tag_root(CachedResultRelation(
                    plan.schema, entry, fp,
                    on_complete=lambda s: self._record_history(fp, s),
                    batch_size=self.batch_size,
                ), plan)
            recorder.record("cache.miss", level="result",
                            fingerprint=fp[:16])
            self._verify(plan)
            rel = self._execute_plan(plan)
            attach_result_capture(
                rel, self._result_cache, fp, tags=scan_tables(plan),
                on_complete=lambda s: self._record_history(fp, s, root=rel),
            )
            return self._tag_root(rel, plan)
        finally:
            tls.in_execute = False

    def _tag_root(self, rel: Relation, plan: LogicalPlan) -> Relation:
        """Mark a root relation for the per-query telemetry funnel
        (`obs/aggregate.query_completed` fires at its materialization
        boundary).  Workers' per-fragment contexts disable this —
        their work records as fragment latency on the serve path, not
        as fleet query latency."""
        if self._telemetry:
            rel._telemetry_query = type(plan).__name__
            # stage-timer snapshot: the funnel diffs against this at
            # completion to decompose the query into phases
            # (decode/H2D/compile/execute/D2H — obs/device.py)
            from datafusion_tpu.obs.device import phase_snapshot

            rel._phase_before = phase_snapshot()
        return rel

    def _verify(self, plan: LogicalPlan) -> None:
        """Static pre-execution verification of a root-level plan
        (DATAFUSION_TPU_VERIFY=0 skips — byte-identical behavior)."""
        from datafusion_tpu.analysis import verify as _averify

        if not _averify.verify_enabled():
            return
        recorder.record("query.verify", plan=type(plan).__name__)
        with METRICS.timer("verify"):
            _averify.check_plan(plan, functions=self.functions)

    # -- feedback-driven planning seams (datafusion_tpu/cost) ----------
    def cost_table_key(self, name: str) -> str:
        """Stable cost-store identity of table `name`'s current data
        (datafusion_tpu/cost.table_key; falls back to the bare name)."""
        from datafusion_tpu import cost as _cost

        try:
            return _cost.table_key(self, name)
        except Exception:  # noqa: BLE001 — keying must never fail a query
            return name

    def _cost_scan_source(self, name: str, ds):
        """Learned scan chunk sizing: rebuild the datasource with a
        batch size matched to the measured device link and the table's
        observed bytes/row (cost/advisor.scan_chunk_rows).  Identity on
        host-speed links, reusable in-memory sources, cold stores, or
        with the subsystem disabled."""
        from datafusion_tpu import cost as _cost

        if not _cost.enabled() or getattr(ds, "reusable_batches", False):
            return ds
        cur = getattr(ds, "batch_size", None)
        if not cur:
            return ds
        try:
            from datafusion_tpu.cost import advisor

            rows = advisor.scan_chunk_rows(
                _cost.store(), self.cost_table_key(name), self.device, cur
            )
        except Exception:  # noqa: BLE001 — sizing is advisory
            return ds
        if rows is None or rows == cur:
            return ds
        import copy

        sized = copy.copy(ds)
        sized.batch_size = rows
        return sized

    def _cost_annotate_aggregate(self, rel: AggregateRelation,
                                 plan: LogicalPlan) -> AggregateRelation:
        """Wire an AggregateRelation into the cost loop: where its
        actual group cardinality should be recorded, and — when the
        store already knows this (table, GROUP BY shape) — the
        estimated group count that pre-sizes the accumulator."""
        from datafusion_tpu import cost as _cost
        from datafusion_tpu.plan.expr import Column as _Col

        if not rel.key_cols:
            return rel
        try:
            from datafusion_tpu.cache import scan_tables

            tables = scan_tables(plan)
        except Exception:  # noqa: BLE001 — annotation is advisory
            return rel
        if len(tables) != 1:
            return rel
        sch = rel.child.schema
        names = [
            sch.field(e.index).name
            if isinstance(e, _Col) and e.index < len(sch) else repr(e)
            for e in rel._group_expr
        ]
        from datafusion_tpu.cost import advisor

        tkey = self.cost_table_key(tables[0])
        shape = advisor.agg_shape(names)
        rel._cost_obs = (tkey, shape)  # observation flows even when off
        if not _cost.enabled():
            return rel
        store = _cost.store()
        est = advisor.agg_group_estimate(store, tkey, names)
        if est:
            from datafusion_tpu.exec.aggregate import group_capacity

            rel._cost_hint = int(est)
            rel._cost_decisions = [store.note_decision(
                "agg.capacity", group_capacity(int(est)),
                "grow-on-demand from 8",
                f"observed ~{int(est)} groups for {shape}",
                table=tables[0],
            )]
        return rel

    def _execute_plan(self, plan: LogicalPlan) -> Relation:
        fns = self._jax_functions()
        rel = self._execute_fused(plan, fns)
        if rel is not None:
            return rel
        if isinstance(plan, TableScan):
            ds = self.datasources.get(plan.table_name)
            if ds is None:
                raise ExecutionError(f"No datasource registered as {plan.table_name!r}")
            if plan.projection is not None:
                ds = ds.with_projection(plan.projection)
            ds = self._cost_scan_source(plan.table_name, ds)
            # the table name rides the relation so the datasource
            # boundary can feed the per-table scan histograms
            # (`scan.<table>.latency` / `scan.<table>.bytes`) and the
            # cost store's per-table row statistics
            rel = DataSourceRelation(ds, table_name=plan.table_name)
            rel._cost_key = self.cost_table_key(plan.table_name)
            return rel
        if isinstance(plan, EmptyRelation):
            return _EmptyRelationExec()
        if isinstance(plan, Selection):
            return PipelineRelation(
                self.execute(plan.input), plan.expr, None, plan.schema,
                functions=fns, device=self.device,
            )
        if isinstance(plan, Projection):
            # fuse Projection(Selection(x)) into one kernel
            if isinstance(plan.input, Selection):
                child = self.execute(plan.input.input)
                return PipelineRelation(
                    child, plan.input.expr, plan.expr, plan.schema,
                    functions=fns, device=self.device,
                    function_metas=self.functions,
                )
            return PipelineRelation(
                self.execute(plan.input), None, plan.expr, plan.schema,
                functions=fns, device=self.device,
                function_metas=self.functions,
            )
        if isinstance(plan, Aggregate):
            # fuse Aggregate(Selection(x)) into one kernel
            if isinstance(plan.input, Selection):
                child = self.execute(plan.input.input)
                pred = plan.input.expr
            else:
                child = self.execute(plan.input)
                pred = None
            return self._cost_annotate_aggregate(AggregateRelation(
                child, plan.group_expr, plan.aggr_expr, plan.schema,
                predicate=pred, functions=fns, device=self.device,
            ), plan)
        if isinstance(plan, Sort):
            return SortRelation(
                self.execute(plan.input), plan.expr, plan.schema, device=self.device
            )
        if isinstance(plan, Limit):
            if isinstance(plan.input, Sort):
                # device sort slices the permutation directly
                return SortRelation(
                    self.execute(plan.input.input),
                    plan.input.expr,
                    plan.schema,
                    limit=plan.limit,
                    device=self.device,
                )
            return LimitRelation(self.execute(plan.input), plan.limit, plan.schema)
        if isinstance(plan, Join):
            from datafusion_tpu.join.relation import HashJoinRelation

            # build-side identity: the right subtree's result under the
            # current catalog/data versions PLUS the key columns the
            # hash index is built over (the same dimension subtree
            # joined on different keys needs different builds) — the
            # ledger pin key that lets warm queries reuse a resident
            # build, invalidated by any catalog/data version bump
            try:
                keys = ",".join(str(r) for _, r in plan.on)
                build_key = (
                    f"join:{self.query_fingerprint(plan.right)}:k={keys}"
                )
            except PlanError:
                build_key = None
            rel = HashJoinRelation(
                self.execute(plan.left), self.execute(plan.right),
                plan.on, plan.join_type, plan.schema,
                device=self.device, build_key=build_key,
            )
            # build-side observation target: a single-table build side
            # feeds the row statistics the build-side/order rewrites
            # (cost/optimizer.py) decide from
            try:
                from datafusion_tpu.cache import scan_tables as _scan_tables

                rtabs = _scan_tables(plan.right)
                if len(rtabs) == 1:
                    rel._cost_obs = (
                        self.cost_table_key(rtabs[0]), "join-build"
                    )
            except Exception:  # noqa: BLE001 — annotation is advisory
                pass
            return rel
        raise ExecutionError(f"Cannot execute plan node {type(plan).__name__}")

    def _execute_fused(self, plan: LogicalPlan, fns) -> Optional[Relation]:
        """Fused-pass plan-chain collapse (exec/fused.py): lower whole
        filter->project->aggregate chains — and [Limit](Sort(...)) over
        filter/column-projection chains — into ONE physical operator.
        Returns None whenever a chain doesn't qualify (the caller falls
        through to the default per-operator lowering, which already
        fuses the two-node shapes)."""
        from datafusion_tpu.exec.hostfn import contains_host_fn

        if isinstance(plan, Aggregate):
            hit = fused.rewrite_aggregate(plan)
            if hit is None:
                return None
            base, group_expr, aggr_expr, pred = hit
            checked = ([] if pred is None else [pred]) + [
                a.args[0] for a in aggr_expr if a.args
            ]
            if any(contains_host_fn(e, self.functions) for e in checked):
                return None
            try:
                rel = AggregateRelation(
                    self.execute(base), group_expr, aggr_expr, plan.schema,
                    predicate=pred, functions=fns, device=self.device,
                )
            except (NotSupportedError, PlanError):
                return None  # inlined shape the kernel can't take
            rel._fused_chain = "filter+project+aggregate"
            return self._cost_annotate_aggregate(rel, plan)

        if isinstance(plan, (Selection, Projection)):
            flat = fused.flatten_chain(plan)
            if flat is None:
                return None
            base, pred, proj, n = flat
            # single nodes and Projection(Selection(x)) lower to the
            # exact same fused PipelineRelation below — only DEEPER
            # chains (stacked selections/projections from subqueries or
            # DataFrame pipelines) need the collapse
            if n <= 1 or (
                n == 2
                and isinstance(plan, Projection)
                and isinstance(plan.input, Selection)
            ):
                return None
            if pred is not None and contains_host_fn(pred, self.functions):
                return None
            rel = PipelineRelation(
                self.execute(base), pred, proj, plan.schema,
                functions=fns, device=self.device,
                function_metas=self.functions,
            )
            rel._fused_chain = f"{n}-node chain"
            return rel

        limit = None
        sort = plan
        if isinstance(plan, Limit) and isinstance(plan.input, Sort):
            limit, sort = plan.limit, plan.input
        if isinstance(sort, Sort):
            hit = fused.rewrite_sort(sort, limit)
            if hit is None:
                return None
            base, keys, pred, out_cols = hit
            rel = SortRelation(
                self.execute(base), keys, plan.schema, limit=limit,
                device=self.device, predicate=pred, output_cols=out_cols,
            )
            rel._fused_chain = "filter+project+sort"
            return rel
        return None

    def execute_physical(self, physical_plan):
        """Execute a PhysicalPlan statement wrapper — the unit of work
        the reference defined but never consumed (`physicalplan.rs:18-34`).

        Interactive -> Relation (lazy); Write -> materialize to the
        target file, returns row count; Show -> first `count` rows as a
        ResultTable.
        """
        kind = physical_plan.kind
        if kind == "interactive":
            return self.execute(physical_plan.plan)
        if kind == "write":
            if (physical_plan.file_format or "csv").lower() != "csv":
                raise NotSupportedError(
                    f"write format {physical_plan.file_format!r} not supported"
                )
            table = collect(self.execute(physical_plan.plan))
            table.to_csv(physical_plan.filename)
            return table.num_rows
        if kind == "show":
            table = collect(self.execute(physical_plan.plan))
            return ResultTable(
                table.schema,
                [c[: physical_plan.count] for c in table.columns],
                [None if v is None else v[: physical_plan.count] for v in table.validity],
            )
        raise ExecutionError(f"unknown physical plan kind {kind!r}")

    def ingest(self, wal_dir: Optional[str] = None):
        """This context's streaming-ingest state (datafusion_tpu/ingest
        — appendable tables, materialized views, the durable ingest
        log), created on first use.  `wal_dir` (or
        ``DATAFUSION_TPU_INGEST_WAL_DIR``) enables durability; pass it
        on the FIRST call — later calls return the existing instance."""
        ing = getattr(self, "_ingest", None)
        if ing is None:
            import os as _os

            from datafusion_tpu import ingest as _ingest_mod

            if wal_dir is None:
                wal_dir = _os.environ.get(
                    "DATAFUSION_TPU_INGEST_WAL_DIR") or None
            ing = self._ingest = _ingest_mod.IngestContext(
                self, wal_dir=wal_dir)
        return ing

    def serve(self, **kwargs):
        """A started serving front door over this context
        (datafusion_tpu/serve.Server): bounded admission, HBM-pinned
        resident tables, cross-query plan megabatching.  Keyword
        arguments override the ``DATAFUSION_TPU_SERVE_*`` env knobs."""
        from datafusion_tpu import serve as _serve

        return _serve.Server(self, **kwargs).start()

    def metrics(self) -> dict:
        return METRICS.snapshot()

    def metrics_text(self) -> str:
        """Engine counters/timings in Prometheus text exposition format
        (obs/export.py; `METRICS` is the single counter backend), plus
        this process's histogram quantiles (query latency, per-table
        `scan.<t>.latency`/`scan.<t>.bytes`) and circuit-breaker state
        gauges (utils/breaker.py; empty when breakers are off)."""
        from datafusion_tpu.obs import attribution
        from datafusion_tpu.obs.aggregate import histogram_gauges
        from datafusion_tpu.obs.export import prometheus_text
        from datafusion_tpu.utils import breaker as breaker_mod

        # accrue pin byte-seconds and fold tenant.<id>.* metering
        # gauges into the registry so the scrape carries them
        attribution.refresh_tenant_gauges()
        gauges = histogram_gauges()
        gauges.update(breaker_mod.gauges())
        return prometheus_text(METRICS, extra_gauges=gauges)
