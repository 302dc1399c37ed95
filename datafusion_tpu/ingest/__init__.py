"""Streaming ingestion + incrementally maintained materialized views.

The reference engine answers a query by re-scanning a file registered
once; every repeat answer is a full rescan or a cache hit, never a
*fresher* one.  This package turns the engine from answer-my-query
into serve-my-dashboard:

- **Append path** — `IngestContext.append(table, columns)` turns a
  registered table into an :class:`AppendableSource` (host-resident,
  append-only) and grows it by delta batches.  Every acked append is
  durably on the ingest log FIRST (`utils/wal.py` segments — the same
  append-before-ack contract the cluster control plane has: a disk
  fault raises :class:`IngestUnavailableError` and nothing is applied).
  Each append re-registers the table, so the catalog version bumps and
  every dependent result-cache fingerprint stops matching immediately.

- **Incremental views** — `CREATE MATERIALIZED VIEW name AS SELECT…`
  registers a continuous query.  For monoid aggregate shapes
  (SUM/COUNT/MIN/MAX numeric, AVG as SUM÷COUNT) the view keeps its
  aggregate *device state* resident and folds each delta through the
  existing partial→final machinery: maintenance is ONE tagged fused
  launch per delta (``view.maintain``) instead of a rescan.  Shapes
  the fold cannot take (no aggregate over the table, string MIN/MAX —
  whose device ranks are invalidated whenever the dictionary grows)
  re-lower to a full recompute with a counted reason
  (``view.fallback.<reason>``).

- **Subscriptions + freshness** — subscribers park on a view revision
  (`wait_for`) and wake when the aggregate advances; with a cluster
  attached each advance also lands in the control-plane KV
  (``views/<name>`` via a ``view`` event) so remote watchers ride the
  resumption-token watch path across failover.  Freshness lag is a
  gauge per view (``view.<name>.lag_s``) and an SLO kind
  (``DATAFUSION_TPU_SLO_<NAME>_FRESHNESS_S``, obs/slo.py).

Exactness: delta batches are encoded against the table's canonical
per-column string dictionaries and fold in arrival order, so the
incremental group ids, accumulator contents, and finalized rows are
bit-identical to a batch rescan of the same batches at every cut —
the same invariant the fused/unfused kernel parity tests pin down.

Locking: one internal mutex serializes appends, folds, and reads, and
is — like `utils/wal.py`'s — deliberately held across the WAL write
(revision assignment and log order must agree, or the log's revision
dedup could silently drop an acked append).  `lockcheck.note_blocking`
announces the boundary; callers must not hold engine locks into here.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Callable, Iterator, Optional, Sequence

import numpy as np

from datafusion_tpu.analysis import lockcheck
from datafusion_tpu.datatypes import DataType, Schema
from datafusion_tpu.errors import (
    DataFusionError,
    IngestError,
    IngestUnavailableError,
)
from datafusion_tpu.exec.batch import (
    RecordBatch,
    StringDictionary,
    make_host_batch,
)
from datafusion_tpu.exec.datasource import DataSource
from datafusion_tpu.obs import recorder
from datafusion_tpu.parallel.wire import BinWriter, dec_array, enc_array
from datafusion_tpu.utils.metrics import METRICS

__all__ = [
    "AppendableSource",
    "IngestContext",
    "MaterializedView",
    "freshness_lags",
    "max_freshness_lag",
]

# live views, for the freshness SLO kind and the debug endpoint — a
# weak registry so a dropped IngestContext takes its views with it
_LIVE_VIEWS: "weakref.WeakValueDictionary[str, MaterializedView]" = (
    weakref.WeakValueDictionary()
)
# live ingest contexts (for /debug/ingest): weak for the same reason
_LIVE_CONTEXTS: "weakref.WeakSet[IngestContext]" = weakref.WeakSet()


def debug_snapshot() -> dict:
    """The ``/debug/ingest`` document: every live IngestContext's
    status plus the process-wide freshness lags (read-only)."""
    return {
        "contexts": [c.status() for c in list(_LIVE_CONTEXTS)],
        "freshness_lags_s": freshness_lags(),
    }


def freshness_lags() -> dict:
    """Per-view freshness lag in seconds (0.0 = fully caught up)."""
    out = {}
    for name, view in list(_LIVE_VIEWS.items()):
        out[name] = view.lag()
    return out


def max_freshness_lag() -> Optional[float]:
    """Worst freshness lag across live views; None when no views exist
    (the SLO stays dormant rather than reading a vacuous 0)."""
    lags = freshness_lags()
    if not lags:
        return None
    return max(lags.values())


# -- appendable source ------------------------------------------------


class AppendableSource(DataSource):
    """Host-resident append-only table: a materialized base plus delta
    batches, all encoding Utf8 columns against ONE canonical
    per-column :class:`StringDictionary`.

    The dictionary discipline is the whole point: group-key codes and
    predicate compare-tables are dictionary-relative, so every batch
    of a table must share its column dictionaries or incremental view
    state diverges from a batch rescan.  Wrapping a file source
    materializes it once (streaming tables ARE the serving working
    set); appends extend the canonical dictionaries in place.

    `data_version` bumps per append and folds into query fingerprints
    (`ExecutionContext.query_fingerprint`) beside the catalog version.
    `to_meta` inherits the base's `PlanError` raise on purpose: an
    in-memory growing table has no file identity, so distributed
    coordinators fall back to local execution instead of shipping it.
    """

    reusable_batches = True

    def __init__(self, schema: Schema, batches: Sequence[RecordBatch],
                 name: Optional[str] = None):
        self._schema = schema
        self._batches: list[RecordBatch] = list(batches)
        self.name = name
        self.base_batches = len(self._batches)
        self.base_version: list = []  # file identity of the base scan
        self.data_version = 0
        self.total_rows = sum(b.num_rows for b in self._batches)
        self.append_rows = 0
        self.append_bytes = 0
        # canonical per-column dictionaries: batches of one scan share
        # per-column global dict objects, so the newest batch's dict is
        # the whole table's (it has every prior batch's entries)
        self._dicts: list[Optional[StringDictionary]] = []
        for i, f in enumerate(schema.fields):
            if f.data_type != DataType.UTF8:
                self._dicts.append(None)
                continue
            d = None
            for b in reversed(self._batches):
                if b.dicts[i] is not None:
                    d = b.dicts[i]
                    break
            self._dicts.append(d if d is not None else StringDictionary())
        # projected-batch cache: (cols, id(batch)) -> projected batch.
        # Identity-stable projections are what let per-batch device
        # copies and group-id caches amortize across queries; bounded
        # by (#distinct projections × #batches), and the parent holds
        # every batch alive so ids never recycle.
        self._proj_cache: dict = {}

    @classmethod
    def wrap(cls, source: DataSource, name: Optional[str] = None
             ) -> "AppendableSource":
        """An appendable twin of `source`, materialized once.  Already-
        appendable sources pass through.  The base's file identity
        (`cache.fingerprint.source_version`) is kept so crash recovery
        can detect a base file rewritten underneath the delta log —
        replaying acked deltas over a silently different base would
        diverge without a trace."""
        if isinstance(source, cls):
            return source
        out = cls(source.schema, list(source.batches()), name=name)
        from datafusion_tpu.cache.fingerprint import source_version
        from datafusion_tpu.errors import PlanError

        try:
            out.base_version = source_version(source.to_meta())
        except PlanError:
            out.base_version = []
        return out

    @property
    def schema(self) -> Schema:
        return self._schema

    def batches(self) -> Iterator[RecordBatch]:
        # iterate a snapshot: a concurrent append must not extend a
        # scan that already started (the query sees a consistent cut)
        return iter(list(self._batches))

    def with_projection(self, projection: Sequence[int]) -> "DataSource":
        return _AppendableProjection(self, tuple(projection))

    def meta(self) -> dict:
        """In-memory identity block (debug endpoints, ingest-log
        bookkeeping) — NOT `to_meta`, which keeps raising `PlanError`
        so this source is never shipped to workers."""
        return {"Appendable": {
            "name": self.name or "", "data_version": self.data_version,
            "rows": self.total_rows, "base_batches": self.base_batches,
        }}

    def _projected(self, batch: RecordBatch, cols: tuple,
                   out_schema: Schema) -> RecordBatch:
        key = (cols, id(batch))
        hit = self._proj_cache.get(key)
        if hit is not None:
            return hit
        out = RecordBatch(
            out_schema,
            [batch.data[i] for i in cols],
            [batch.validity[i] for i in cols],
            [batch.dicts[i] for i in cols],
            num_rows=batch.num_rows,
            mask=batch.mask,
        )
        self._proj_cache[key] = out
        return out

    # -- building delta batches --

    def build_batch(self, columns: dict) -> RecordBatch:
        """Validate and assemble one delta batch from per-column values
        (``{name: list|ndarray}``; None entries are nulls).  Utf8
        columns encode against — and extend — the canonical
        dictionaries.  Raises :class:`IngestError` on schema mismatch;
        nothing is applied until :meth:`append_batch`."""
        fields = self._schema.fields
        names = {f.name for f in fields}
        unknown = [c for c in columns if c not in names]
        if unknown:
            raise IngestError(
                f"append to {self.name or '?'}: unknown column(s) "
                f"{sorted(unknown)}")
        missing = [f.name for f in fields if f.name not in columns]
        if missing:
            raise IngestError(
                f"append to {self.name or '?'}: missing column(s) "
                f"{missing}")
        lengths = {len(columns[f.name]) for f in fields}
        if len(lengths) > 1:
            raise IngestError(
                f"append to {self.name or '?'}: ragged columns "
                f"(lengths {sorted(lengths)})")
        n = lengths.pop() if lengths else 0
        data: list[np.ndarray] = []
        validity: list[Optional[np.ndarray]] = []
        for i, f in enumerate(fields):
            vals = columns[f.name]
            if f.data_type == DataType.UTF8:
                seq = list(vals)
                codes = (self._dicts[i].encode(seq) if seq
                         else np.zeros(0, np.int32))
                isnull = np.fromiter((s is None for s in seq), dtype=bool,
                                     count=len(seq))
                data.append(codes)
                validity.append(~isnull if isnull.any() else None)
                continue
            arr, val = _numeric_column(vals, f, self.name)
            data.append(arr)
            validity.append(val)
        # zero-row deltas (n == 0) still form a real empty batch, so
        # the WAL record, catalog bump, and view revisions all advance
        return make_host_batch(self._schema, data, validity,
                               dicts=list(self._dicts))

    def append_batch(self, batch: RecordBatch) -> None:
        """Apply one built delta batch (after the ingest log accepted
        it): the table grows, `data_version` bumps."""
        self._batches.append(batch)
        self.data_version += 1
        self.append_rows += batch.num_rows
        self.total_rows += batch.num_rows
        self.append_bytes += sum(
            np.asarray(a).dtype.itemsize * batch.num_rows
            for a in batch.data)

    def delta_batches(self) -> list[RecordBatch]:
        """The appended (non-base) batches, oldest first."""
        return list(self._batches[self.base_batches:])


class _AppendableProjection(DataSource):
    """Column-subset view over an :class:`AppendableSource` that stays
    live: each scan re-reads the parent's current batch list, and the
    projected batch objects are identity-cached on the parent so
    device copies amortize across queries and appends."""

    reusable_batches = True

    def __init__(self, parent: AppendableSource, projection: tuple):
        self._parent = parent
        self._projection = projection
        self._schema = parent.schema.select(list(projection))

    @property
    def schema(self) -> Schema:
        return self._schema

    def batches(self) -> Iterator[RecordBatch]:
        for b in list(self._parent._batches):
            yield self._parent._projected(b, self._projection, self._schema)

    def with_projection(self, projection: Sequence[int]) -> "DataSource":
        cols = tuple(self._projection[i] for i in projection)
        return _AppendableProjection(self._parent, cols)


def _numeric_column(vals, field, table) -> tuple:
    """(array, validity) for one non-Utf8 append column; None entries
    become nulls (validity carries them, padding value 0)."""
    dtype = field.data_type.np_dtype
    if isinstance(vals, np.ndarray) and vals.dtype != object:
        return np.ascontiguousarray(vals).astype(dtype, copy=False), None
    seq = list(vals)
    isnull = np.fromiter((v is None for v in seq), dtype=bool,
                         count=len(seq))
    if not isnull.any():
        try:
            return np.asarray(seq).astype(dtype), None
        except (TypeError, ValueError) as e:
            raise IngestError(
                f"append to {table or '?'}: column {field.name!r} "
                f"not coercible to {field.data_type}: {e}") from None
    filled = [0 if v is None else v for v in seq]
    try:
        arr = np.asarray(filled).astype(dtype)
    except (TypeError, ValueError) as e:
        raise IngestError(
            f"append to {table or '?'}: column {field.name!r} "
            f"not coercible to {field.data_type}: {e}") from None
    return arr, ~isnull


# -- wire blocks (WAL records + snapshots) ----------------------------


def _block_from_batch(schema: Schema, batch: RecordBatch,
                      bw: Optional[BinWriter]) -> list:
    """Column blocks for one delta batch: numeric columns ride as RAW
    CRC'd wire segments (`enc_array` + BinWriter — the serving wire's
    own format), Utf8 columns as raw string lists (codes are
    dictionary-relative, so only the strings are replay-stable)."""
    n = batch.num_rows
    cols = []
    for i, f in enumerate(schema.fields):
        doc: dict = {"name": f.name}
        v = batch.validity[i]
        if f.data_type == DataType.UTF8:
            codes = np.asarray(batch.data[i][:n])
            strings = list(batch.dicts[i].decode(codes)) if n else []
            if v is not None:
                vn = np.asarray(v[:n])
                strings = [None if not vn[j] else strings[j]
                           for j in range(n)]
            doc["s"] = strings
        else:
            doc["a"] = enc_array(
                np.ascontiguousarray(np.asarray(batch.data[i][:n])), bw)
            if v is not None:
                doc["v"] = enc_array(
                    np.asarray(v[:n]).astype(np.uint8), bw)
        cols.append(doc)
    return cols


def _columns_from_block(schema: Schema, cols: list) -> dict:
    """Invert `_block_from_batch` into the `append()` columns mapping."""
    out: dict = {}
    by_name = {c.get("name"): c for c in cols}
    for f in schema.fields:
        doc = by_name.get(f.name)
        if doc is None:
            raise IngestError(f"ingest-log block missing column {f.name!r}")
        if "s" in doc:
            out[f.name] = doc["s"]
            continue
        arr = dec_array(doc["a"])
        if doc.get("v") is not None:
            val = dec_array(doc["v"]).astype(bool)
            lst = arr.tolist()
            out[f.name] = [lst[j] if val[j] else None
                           for j in range(len(lst))]
        else:
            out[f.name] = arr
    return out


# -- materialized views -----------------------------------------------


class MaterializedView:
    """One registered continuous query over an appendable table.

    Incremental shape (`incremental=True`): the defining plan lowers to
    an operator tree whose aggregate sits directly over the table scan
    and carries no string MIN/MAX slots.  The view owns the aggregate's
    device accumulator state; `fold(deltas)` stages each delta exactly
    as the scan loop would (canonical dictionaries → stable group ids →
    aux tables → device inputs) and advances the state with ONE tagged
    launch.  `read()` injects the state into the relation and collects
    through the unchanged finalize path — bit-identical to a batch
    rescan at every cut.

    Non-incremental shapes keep `fallback_reason` and recompute in full
    per delta (counted, still exact, still fresh).
    """

    def __init__(self, name: str, sql: str, ctx, table: str,
                 root, agg, proj: Optional[tuple],
                 fallback_reason: Optional[str] = None):
        self.name = name
        self.sql = sql
        self.ctx = ctx
        self.table = table
        self.revision = 0
        self._root = root  # operator tree for injected reads
        self._agg = agg  # the AggregateRelation owning the device state
        self._proj = proj  # scan projection (columns of the table)
        self.incremental = agg is not None and fallback_reason is None
        self.fallback_reason = fallback_reason
        self._state = None
        self._capacity = 0
        self._result = None  # fallback views: last full recompute
        self._pending_since: Optional[float] = None
        self.maintain_launches = 0
        self.full_recomputes = 0
        self.last_advance_ts = time.time()

    # -- freshness --

    def lag(self) -> float:
        """Seconds of un-folded ingest this view is behind (0.0 when
        caught up).  Nonzero only while an acked append has not yet
        advanced the revision — exactly the window the freshness SLO
        exists to bound."""
        since = self._pending_since
        return 0.0 if since is None else max(0.0, time.monotonic() - since)

    def mark_pending(self) -> None:
        if self._pending_since is None:
            self._pending_since = time.monotonic()

    # -- maintenance --

    def fold(self, source: AppendableSource,
             deltas: Sequence[RecordBatch]) -> None:
        """Advance the view over `deltas` (appended batches, oldest
        first).  Incremental: one fused tagged launch; fallback: one
        counted full recompute.  Empty deltas advance the revision
        without a launch.  Called under the ingest lock."""
        try:
            if not self.incremental:
                self._recompute_full()
            else:
                live = [b for b in deltas if b.num_rows > 0]
                if live:
                    self._fold_incremental(source, live)
        finally:
            self.revision += 1
            self._pending_since = None
            self.last_advance_ts = time.time()
            METRICS.gauge(f"view.{self.name}.revision", self.revision)
            METRICS.gauge(f"view.{self.name}.lag_s", 0.0)

    def _fold_incremental(self, source: AppendableSource,
                          deltas: Sequence[RecordBatch]) -> None:
        from datafusion_tpu.exec.expression import compute_aux_values
        from datafusion_tpu.exec.relation import device_scope
        from datafusion_tpu.utils.retry import device_call

        agg = self._agg
        core = agg.core
        chunk = []
        for full in deltas:
            # the batch exactly as the view's scan would yield it: the
            # identity-cached projection, so device copies and group-id
            # slots are SHARED with any query scanning the same table
            batch = (full if self._proj is None else
                     source._projected(full, self._proj,
                                       agg.child.schema))
            for idx in agg.key_cols:
                if batch.dicts[idx] is not None:
                    agg._key_dicts[idx] = batch.dicts[idx]
            ids = agg._group_ids(batch)
            aux = compute_aux_values(core.aux_specs, batch, agg._aux_cache)
            str_aux = agg._compute_str_aux(batch, core.slots)
            with device_scope(agg.device):
                data, validity, mask = agg._device_inputs(batch, core)
            chunk.append((data, validity, tuple(aux),
                          np.int32(batch.num_rows), mask, ids, str_aux))
        # capacity picked AFTER the whole delta's keys are encoded
        needed = agg._pick_capacity(self._capacity)
        if self._state is None:
            self._capacity = needed
            self._state = core._init_state(needed)
        elif needed > self._capacity:
            self._state = core._grow_state(self._state, needed)
            self._capacity = needed
        with METRICS.timer("view.maintain"), device_scope(agg.device):
            if len(chunk) == 1:
                c = chunk[0]
                self._state = device_call(
                    core.jit, c[0], c[1], c[2], c[3], c[4], c[5],
                    self._state, c[6], agg._params, _tag="view.maintain",
                )
            else:
                self._state = device_call(
                    core.fused_jit, tuple(chunk), self._state,
                    agg._params, _tag="view.maintain",
                )
        self.maintain_launches += 1
        METRICS.add("view.maintain_launches")
        recorder.record("view.maintain", view=self.name,
                        batches=len(chunk), launches=1)

    def _recompute_full(self) -> None:
        """Fallback maintenance: re-collect the defining query in full
        (exact, counted — the incremental path's foil in the bench)."""
        from datafusion_tpu.exec.materialize import collect

        with METRICS.timer("view.recompute"):
            self._result = collect(self.ctx.execute(self._plan()))
        self.full_recomputes += 1
        METRICS.add("view.full_recomputes")
        recorder.record("view.recompute", view=self.name,
                        reason=self.fallback_reason or "")

    def _plan(self):
        from datafusion_tpu.sql.parser import parse_sql

        return self.ctx._plan(parse_sql(self.sql))

    # -- reads --

    def read(self):
        """The view's current contents as a ResultTable.  Incremental:
        inject the resident state and collect through the unchanged
        finalize path (the state tuples are immutable device arrays,
        so reads repeat).  Fallback: the last full recompute."""
        from datafusion_tpu.exec.materialize import collect

        if not self.incremental:
            if self._result is None:
                self._recompute_full()
            return self._result
        if self._state is not None:
            self._agg._injected_state = self._state
        try:
            return collect(self._root)
        finally:
            # a collect that never reached accumulate() (upstream
            # raise) must not leave the injection armed for a later,
            # unrelated read
            self._agg.__dict__.pop("_injected_state", None)

    def status(self) -> dict:
        return {
            "name": self.name, "table": self.table, "sql": self.sql,
            "incremental": self.incremental,
            "fallback_reason": self.fallback_reason,
            "revision": self.revision, "lag_s": round(self.lag(), 6),
            "maintain_launches": self.maintain_launches,
            "full_recomputes": self.full_recomputes,
            "groups": (self._agg.encoder.num_groups
                       if self._agg is not None else None),
        }


# -- the ingest context ----------------------------------------------


class IngestContext:
    """Per-ExecutionContext streaming state: appendable tables,
    materialized views, the durable ingest log, and subscriber wakeups.

    With `wal_dir` set, every append and view definition is a log
    record (append-before-ack); `recover()` — called after the base
    tables are registered — replays acked appends and re-plans views,
    re-converging them exactly.  Without a log the subsystem runs
    in-memory (byte-identical semantics, no durability), matching the
    cluster control plane's convention.
    """

    def __init__(self, ctx, wal_dir: Optional[str] = None):
        self.ctx = ctx
        # ONE mutex serializes append→log→apply→notify and view reads;
        # deliberately held across the WAL write (module docstring: log
        # order must agree with revision order or the WAL's dedup could
        # drop an acked append).  Announced to lockcheck like wal.py's.
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._tables: dict[str, AppendableSource] = {}
        self._views: dict[str, MaterializedView] = {}
        # post-apply hooks: (table, batch) -> None, called OUTSIDE the
        # lock (the serving layer grows pins and broadcasts here)
        self.on_applied: list[Callable] = []
        # optional cluster handle carrying .view_advance(name, rev) and
        # .invalidate(table) — the serving layer attaches it
        self.cluster = None
        self._wal = None
        self._rev = 0
        self.recovery: dict = {}
        if wal_dir:
            from datafusion_tpu.utils.wal import WriteAheadLog

            self._wal = WriteAheadLog(wal_dir)
        METRICS.declare("ingest.appends", "ingest.rows", "ingest.bytes",
                        "view.maintain_launches", "view.full_recomputes")
        _LIVE_CONTEXTS.add(self)

    # -- tables --

    def attach(self, table: str) -> AppendableSource:
        """Make `table` appendable (idempotent): the registered source
        is wrapped into an :class:`AppendableSource` (materializing it)
        and re-registered, bumping the catalog version once."""
        lockcheck.note_blocking("ingest.attach")
        with self._lock:
            return self._attach_locked(table)

    def _attach_locked(self, table: str) -> AppendableSource:
        src = self._tables.get(table)
        if src is not None:
            return src
        ds = self.ctx.datasources.get(table)
        if ds is None:
            raise IngestError(f"no datasource registered as {table!r}")
        src = self._wrap_source(table, ds)
        self._tables[table] = src
        return src

    def _wrap_source(self, table: str, ds) -> AppendableSource:
        """Wrap + re-register, bumping the catalog version once.  A
        serving-layer resident wrapper (serve.PinnedSource) exposes
        ``splice_appendable``: the appendable splices in UNDER it —
        the wrapper stays registered, so the HBM pin (and the device
        copies it holds) survives attachment, and appends grow the
        pinned resident copy in place instead of re-materializing a
        divergent one."""
        splice = getattr(ds, "splice_appendable", None)
        if splice is not None:
            src = splice(AppendableSource)
            self.ctx.register_datasource(table, ds)
            return src
        src = AppendableSource.wrap(ds, name=table)
        self.ctx.register_datasource(table, src)
        return src

    # -- the append path --

    def append(self, table: str, columns: dict,
               client: Optional[str] = None) -> dict:
        """Append one delta of rows to `table` — durable-then-applied.

        Returns ``{"table", "rows", "rev", "views": {name: revision}}``.
        A WAL disk fault raises :class:`IngestUnavailableError` with
        NOTHING applied (the `wal_unavailable` contract: retry when the
        log recovers; the log's revision dedup absorbs replays).
        Schema mismatches raise :class:`IngestError` before the log is
        touched."""
        t0 = time.perf_counter()
        lockcheck.note_blocking("ingest.append")
        with self._lock:
            src = self._attach_locked(table)
            batch = src.build_batch(columns)
            affected = [v for v in self._views.values()
                        if v.table == table]
            for v in affected:
                v.mark_pending()
            rev = self._rev + 1
            if self._wal is not None:
                bw = BinWriter()
                rec = {
                    "kind": "append", "rev": rev, "table": table,
                    "client": client or "", "rows": batch.num_rows,
                    "cols": _block_from_batch(src.schema, batch, bw),
                }
                try:
                    self._wal.append([(rec, bw)])
                except OSError as e:
                    METRICS.add("ingest.wal_write_failures")
                    for v in affected:
                        v._pending_since = None
                    # burn the revision: the disk state after a failed
                    # write/fsync is UNKNOWN — the record may well be
                    # durable despite the error.  Reusing `rev` for the
                    # next append would collide with that torn record
                    # and recovery's rev dedup could then drop the
                    # ACKED record in its favor.  A burned rev at worst
                    # replays a never-acked append (durability is a
                    # superset of the ack stream), never loses one.
                    self._rev = rev
                    raise IngestUnavailableError(
                        f"append to {table!r} could not be logged "
                        f"durably ({e}); not acknowledged — retry when "
                        f"the log recovers") from e
            self._rev = rev
            views = self._apply_locked(src, table, batch, affected)
            self._cond.notify_all()
        self._post_apply(table, batch, views)
        if self._wal is not None and self._wal.should_snapshot():
            self.maybe_snapshot()
        METRICS.add("ingest.appends")
        METRICS.add("ingest.rows", batch.num_rows)
        METRICS.add("ingest.bytes", sum(
            np.asarray(a).dtype.itemsize * batch.num_rows
            for a in batch.data))
        METRICS.observe("ingest.append.latency", time.perf_counter() - t0)
        recorder.record("ingest.append", table=table, rows=batch.num_rows,
                        rev=rev, client=client or "")
        return {"table": table, "rows": batch.num_rows, "rev": rev,
                "views": views}

    def _apply_locked(self, src: AppendableSource, table: str,
                      batch: RecordBatch, affected) -> dict:
        src.append_batch(batch)
        # catalog bump: dependent cached results stop matching (PR 3
        # fingerprints fold catalog + data versions) and drop eagerly.
        # When a serving wrapper fronts the appendable, the WRAPPER
        # re-registers — replacing it with the bare source would tear
        # the HBM pin out of the catalog slot.
        registered = self.ctx.datasources.get(table)
        if registered is not None and \
                getattr(registered, "inner", None) is src:
            self.ctx.register_datasource(table, registered)
        else:
            self.ctx.register_datasource(table, src)
        views = {}
        for v in affected:
            v.fold(src, [batch])
            views[v.name] = v.revision
        return views

    def _post_apply(self, table: str, batch: RecordBatch,
                    views: dict) -> None:
        """Outside-lock fan-out: serving hooks (pin growth) and the
        cluster broadcast (stale-result invalidation + view advances
        for remote watchers).  Best-effort by design — the append is
        already durable and applied."""
        for hook in list(self.on_applied):
            try:
                hook(table, batch)
            except Exception:  # noqa: BLE001 — a hook must not unwind an applied append
                METRICS.add("ingest.hook_failures")
        cl = self.cluster
        if cl is None:
            return
        try:
            cl.invalidate(table)
            for name, rev in views.items():
                cl.view_advance(name, rev)
        except (DataFusionError, OSError):
            METRICS.add("ingest.cluster_notify_failures")

    # -- views --

    def create_view(self, name: str, query_sql: str) -> MaterializedView:
        """Register `name` as a continuous query (the executable side
        of ``CREATE MATERIALIZED VIEW``): logged durably, built from
        the table's current contents, maintained per delta."""
        lockcheck.note_blocking("ingest.create_view")
        with self._lock:
            if name in self._views:
                raise IngestError(f"materialized view {name!r} exists")
            view = self._build_view(name, query_sql)
            rev = self._rev + 1
            if self._wal is not None:
                rec = {"kind": "view", "rev": rev, "name": name,
                       "sql": query_sql}
                try:
                    self._wal.append([(rec, None)])
                except OSError as e:
                    METRICS.add("ingest.wal_write_failures")
                    raise IngestUnavailableError(
                        f"view {name!r} could not be logged durably "
                        f"({e}); not registered — retry when the log "
                        f"recovers") from e
            self._rev = rev
            self._register_view_locked(view)
        recorder.record("view.create", view=name, table=view.table,
                        incremental=view.incremental,
                        reason=view.fallback_reason or "")
        return view

    def _register_view_locked(self, view: MaterializedView) -> None:
        src = self._tables.get(view.table)
        if src is None:
            src = self._attach_locked(view.table)
        # initial build from the table's current contents — for the
        # incremental shape this is the same fold the deltas take (one
        # fused launch over the existing batches)
        if view.incremental:
            existing = list(src._batches)
            view.fold(src, existing)
        else:
            view.fold(src, [])
        self._views[view.name] = view
        _LIVE_VIEWS[view.name] = view
        self._cond.notify_all()

    def _build_view(self, name: str, query_sql: str) -> MaterializedView:
        """Plan the defining SELECT and decide incremental eligibility:
        the lowered tree must carry an AggregateRelation directly over
        the table's scan, with no string MIN/MAX slots (their device
        ranks are invalidated whenever the dictionary grows).  Every
        refusal is a counted reason — the fallback still serves exact,
        fresh answers, just at rescan cost."""
        from datafusion_tpu.cache import scan_tables
        from datafusion_tpu.exec.aggregate import AggregateRelation
        from datafusion_tpu.exec.relation import DataSourceRelation
        from datafusion_tpu.sql.parser import parse_sql

        stmt = parse_sql(query_sql)
        plan = self.ctx._plan(stmt)
        tables = scan_tables(plan)
        if len(tables) != 1:
            raise IngestError(
                f"materialized view {name!r}: exactly one base table "
                f"required (got {tables})")
        table = tables[0]
        self._attach_locked(table)

        def fallback(reason: str) -> MaterializedView:
            METRICS.add(f"view.fallback.{reason}")
            recorder.record("view.fallback", view=name, reason=reason)
            return MaterializedView(name, query_sql, self.ctx, table,
                                    None, None, None,
                                    fallback_reason=reason)

        # build the injection tree OUTSIDE the cache seam: a cached
        # replay relation has no aggregate to inject into
        tls = self.ctx._execute_tls
        prev = getattr(tls, "in_execute", False)
        tls.in_execute = True
        try:
            root = self.ctx._execute_plan(plan)
        finally:
            tls.in_execute = prev
        agg = None
        node = root
        while node is not None:
            if isinstance(node, AggregateRelation):
                agg = node
                break
            node = getattr(node, "child", None)
        if agg is None:
            return fallback("plan_shape")
        scan = agg.child
        if not isinstance(scan, DataSourceRelation):
            return fallback("scan_shape")
        src = self._tables[table]
        ds = scan.datasource
        if ds is src:
            proj = None
        elif (isinstance(ds, _AppendableProjection)
              and ds._parent is src):
            proj = ds._projection
        elif getattr(ds, "inner", None) is src:
            # serving wrapper (serve.PinnedSource) fronting the
            # appendable — same batches, same dictionaries
            proj = None
        elif getattr(getattr(ds, "parent", None), "inner", None) is src:
            # projected serving wrapper (serve._PinnedProjection);
            # `cols` are parent-absolute indices, same convention as
            # _AppendableProjection
            proj = tuple(ds.cols)
        else:
            return fallback("scan_shape")
        if any(sl.is_string for sl in agg.core.slots):
            return fallback("string_minmax")
        return MaterializedView(name, query_sql, self.ctx, table,
                                root, agg, proj)

    def view(self, name: str) -> MaterializedView:
        v = self._views.get(name)
        if v is None:
            raise IngestError(f"no materialized view {name!r}")
        return v

    def views(self) -> dict:
        return dict(self._views)

    def read_view(self, name: str):
        """The view's current ResultTable (serialized against folds)."""
        lockcheck.note_blocking("ingest.read")
        with self._lock:
            return self.view(name).read()

    # -- subscriptions --

    def wait_for(self, name: str, after_revision: int,
                 timeout: Optional[float] = None) -> Optional[int]:
        """Park until `name` advances past `after_revision`; returns
        the new revision, or None on timeout.  The local twin of the
        cluster watch (remote subscribers ride ``views/<name>`` KV
        events with resumption-token proof)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        lockcheck.note_blocking("ingest.wait")
        with self._cond:
            while True:
                v = self.view(name)
                if v.revision > after_revision:
                    return v.revision
                if deadline is None:
                    self._cond.wait()
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._cond.wait(remaining):
                    if self.view(name).revision > after_revision:
                        return self.view(name).revision
                    return None

    # -- durability --

    def recover(self) -> dict:
        """Replay the ingest log (call once, after base tables are
        registered): snapshot deltas, then every acked append in log
        order, then re-plan views — each re-converges to the exact
        batch answer.  Appends for unregistered tables are dropped with
        a count (the base table's DDL is the caller's job, exactly as
        the cluster leaves membership config to its operator)."""
        if self._wal is None:
            return {}
        snap, events, _deadlines = self._wal.recover()
        applied = dropped = 0
        # recovered view revisions must continue the pre-crash sequence
        # (no duplicated or skipped revisions for parked subscribers):
        # each view resumes at its snapshot revision (or 1, the creation
        # fold, for log-created views) plus the acked appends replayed
        # for its table after that point
        counts: dict = {}  # table -> event appends applied
        view_docs: list = []  # (name, sql, base_rev, counts at creation)
        with self._lock:
            if snap:
                for table, doc in (snap.get("tables") or {}).items():
                    base = doc.get("base")
                    if base and self.ctx.datasources.get(table) is not None:
                        src = self._attach_locked(table)
                        if src.base_version and src.base_version != base:
                            # the base file changed underneath the
                            # delta log: replay proceeds (the deltas
                            # are still exact over the NEW base) but
                            # the drift is never silent
                            METRICS.add("ingest.base_drift")
                            recorder.record("ingest.base_drift",
                                            table=table)
                    for block in doc.get("blocks", ()):
                        if self._replay_append_locked(table, block):
                            applied += 1
                        else:
                            dropped += 1
                for doc in snap.get("views") or ():
                    view_docs.append((doc.get("name"), doc.get("sql"),
                                      int(doc.get("revision") or 1), {}))
            for ev in events:
                kind = ev.get("kind")
                if kind == "append":
                    table = ev.get("table", "")
                    if self._replay_append_locked(
                            table, ev.get("cols") or []):
                        applied += 1
                        counts[table] = counts.get(table, 0) + 1
                    else:
                        dropped += 1
                elif kind == "view":
                    view_docs.append((ev.get("name"), ev.get("sql"), 1,
                                      dict(counts)))
            self._rev = max(self._rev, self._wal.last_rev)
            for name, sql, base_rev, at in view_docs:
                if not name or not sql or name in self._views:
                    continue
                try:
                    view = self._build_view(name, sql)
                    self._register_view_locked(view)
                except DataFusionError:
                    METRICS.add("ingest.recovery_view_failures")
                    continue
                view.revision = base_rev + (
                    counts.get(view.table, 0) - at.get(view.table, 0))
                METRICS.gauge(f"view.{name}.revision", view.revision)
        if dropped:
            METRICS.add("ingest.recovery_dropped", dropped)
        self.recovery = {
            **self._wal.recovery,
            "appends_replayed": applied,
            "appends_dropped": dropped,
            "views_recovered": len(self._views),
        }
        recorder.record("ingest.recovered", **{
            k: v for k, v in self.recovery.items()
            if isinstance(v, (int, float, str))})
        return self.recovery

    def _replay_append_locked(self, table: str, cols: list) -> bool:
        if self.ctx.datasources.get(table) is None:
            return False
        src = self._attach_locked(table)
        try:
            batch = src.build_batch(_columns_from_block(src.schema, cols))
        except IngestError:
            return False
        affected = [v for v in self._views.values() if v.table == table]
        self._apply_locked(src, table, batch, affected)
        return True

    def maybe_snapshot(self) -> None:
        """Compact the ingest log: one snapshot carrying every table's
        delta blocks + view definitions, after which covered segments
        reap.  Best-effort (a failed snapshot leaves the log intact)."""
        if self._wal is None:
            return
        lockcheck.note_blocking("ingest.snapshot")
        with self._lock:
            bw = BinWriter()
            tables = {}
            for name, src in self._tables.items():
                blocks = [_block_from_batch(src.schema, b, bw)
                          for b in src.delta_batches()]
                if blocks:
                    tables[name] = {"blocks": blocks,
                                    "base": src.base_version}
            snap = {
                "rev": self._rev,
                "tables": tables,
                "views": [{"name": v.name, "sql": v.sql,
                           "revision": v.revision}
                          for v in self._views.values()],
            }
        try:
            self._wal.write_snapshot(snap, bw)
        except OSError:
            METRICS.add("ingest.snapshot_failures")

    # -- introspection --

    def status(self) -> dict:
        with self._lock:
            return {
                "rev": self._rev,
                "wal": (self._wal.manifest()
                        if self._wal is not None else None),
                "recovery": dict(self.recovery),
                "tables": {n: s.meta()["Appendable"]
                           for n, s in self._tables.items()},
                "views": {n: v.status() for n, v in self._views.items()},
            }

    def close(self) -> None:
        if self._wal is not None:
            self._wal.close()
