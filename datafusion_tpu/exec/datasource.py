"""DataSource protocol + concrete sources.

Mirrors the reference `DataSource` trait and `CsvDataSource`
(`src/execution/datasource.rs:26-50`), plus the Parquet/NDJSON sources
it declares but never implements (`dfparser.rs:33-34`).  A DataSource
is re-iterable (each `batches()` call restarts the scan) and
projection-aware — `with_projection` returns a source that parses only
the needed columns, which is what the push-down optimizer targets.

`DataSourceMeta` mirrors `datasource.rs:70-85`: the serializable
description of a source that distributed mode ships to workers.
"""

from __future__ import annotations

import weakref
from typing import Iterator, Optional, Sequence

from datafusion_tpu.datatypes import Schema
from datafusion_tpu.errors import PlanError
from datafusion_tpu.exec.batch import RecordBatch
from datafusion_tpu.io.readers import (
    DEFAULT_BATCH_SIZE,
    CsvReader,
    NdJsonReader,
    ParquetReader,
    infer_parquet_schema,
)


class DataSource:
    """Base: schema + re-iterable batches (reference `datasource.rs:26-29`)."""

    # True when re-scans hand out the SAME RecordBatch objects, so
    # device copies cached on them amortize across queries (in-memory
    # tables).  File scans parse fresh batches per query: shipping a
    # reusable table to the accelerator pays once; shipping a stream
    # pays every query.
    reusable_batches = False

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    def batches(self) -> Iterator[RecordBatch]:
        raise NotImplementedError

    def with_projection(self, projection: Sequence[int]) -> "DataSource":
        raise NotImplementedError

    def to_meta(self) -> dict:
        raise PlanError(f"{type(self).__name__} is not serializable")


class CsvDataSource(DataSource):
    def __init__(
        self,
        path: str,
        schema: Schema,
        has_header: bool = True,
        batch_size: int = DEFAULT_BATCH_SIZE,
        projection: Optional[Sequence[int]] = None,
        reader: Optional[str] = None,
    ):
        self.path = path
        self.table_schema = schema
        self.has_header = has_header
        self.batch_size = batch_size
        self.projection = list(projection) if projection is not None else None
        # two parsers, both full-fidelity and parity-tested in CI:
        # the native C++ one (the host hot loop — reference
        # `datasource.rs:31-50` is native too) selected per-source via
        # `reader="native"` or process-wide via
        # DATAFUSION_TPU_CSV_READER=native, and the pyarrow SIMD parser
        # with auto_dict_encode (measured ~2x the native reader), the
        # default
        import os

        from datafusion_tpu.native import native_available

        self.reader_choice = reader
        choice = reader or os.environ.get("DATAFUSION_TPU_CSV_READER", "auto")
        if choice == "native" and native_available():
            from datafusion_tpu.native.csv import NativeCsvReader

            self._reader = NativeCsvReader(
                path, schema, has_header, batch_size, self.projection
            )
        else:
            self._reader = CsvReader(
                path, schema, has_header, batch_size, self.projection
            )

    @property
    def schema(self) -> Schema:
        return self._reader.out_schema

    def batches(self) -> Iterator[RecordBatch]:
        return self._reader.batches()

    def with_projection(self, projection: Sequence[int]) -> "CsvDataSource":
        return CsvDataSource(
            self.path, self.table_schema, self.has_header, self.batch_size,
            projection, reader=self.reader_choice,
        )

    def to_meta(self) -> dict:
        # wire format mirrors DataSourceMeta::CsvFile (datasource.rs:72-77)
        return {
            "CsvFile": {
                "filename": self.path,
                "schema": self.table_schema.to_json(),
                "has_header": self.has_header,
                "projection": self.projection,
            }
        }


class NdJsonDataSource(DataSource):
    def __init__(
        self,
        path: str,
        schema: Schema,
        batch_size: int = DEFAULT_BATCH_SIZE,
        projection: Optional[Sequence[int]] = None,
    ):
        self.path = path
        self.table_schema = schema
        self.batch_size = batch_size
        self.projection = list(projection) if projection is not None else None
        self._reader = NdJsonReader(path, schema, batch_size, self.projection)

    @property
    def schema(self) -> Schema:
        return self._reader.out_schema

    def batches(self) -> Iterator[RecordBatch]:
        return self._reader.batches()

    def with_projection(self, projection: Sequence[int]) -> "NdJsonDataSource":
        return NdJsonDataSource(self.path, self.table_schema, self.batch_size, projection)

    def to_meta(self) -> dict:
        # same wire shape as the CSV/Parquet variants (datasource.rs:70-85);
        # the reference declares NDJSON in DDL but never got this far
        return {
            "NdJsonFile": {
                "filename": self.path,
                "schema": self.table_schema.to_json(),
                "projection": self.projection,
            }
        }


class ParquetDataSource(DataSource):
    def __init__(
        self,
        path: str,
        schema: Optional[Schema] = None,
        batch_size: int = DEFAULT_BATCH_SIZE,
        projection: Optional[Sequence[int]] = None,
        row_groups: Optional[Sequence[int]] = None,
    ):
        self.path = path
        self.table_schema = schema if schema is not None else infer_parquet_schema(path)
        self.batch_size = batch_size
        self.projection = list(projection) if projection is not None else None
        self.row_groups = None if row_groups is None else list(row_groups)
        self._reader = ParquetReader(
            path, self.table_schema, batch_size, self.projection,
            self.row_groups,
        )

    @property
    def schema(self) -> Schema:
        return self._reader.out_schema

    def batches(self, whole: bool = True) -> Iterator[RecordBatch]:
        # `whole=False`: the file's own cut (`ParquetReader.batches`)
        return self._reader.batches(whole)

    def with_projection(self, projection: Sequence[int]) -> "ParquetDataSource":
        return ParquetDataSource(
            self.path, self.table_schema, self.batch_size, projection,
            self.row_groups,
        )

    def to_meta(self) -> dict:
        # mirrors DataSourceMeta::ParquetFile (datasource.rs:79-84); a
        # source held to some of the file's row groups says which, so
        # the worker that rebuilds it scans no more than it did
        return {
            "ParquetFile": {
                "filename": self.path,
                "schema": self.table_schema.to_json(),
                "projection": self.projection,
                **({} if self.row_groups is None
                   else {"row_groups": self.row_groups}),
            }
        }


class SharedScanState:
    """What the relations over one reusable source keep in common,
    owned by the source (a `MemoryDataSource`, a `serve.PinnedSource`)
    for as long as it lives, keyed by what each piece depends on:

    - per GROUP BY column set (table column ids, whatever projection
      the query scans through): ONE append-only group-key encoder and
      the lock that serialises its mutation across relations running
      at once.  Ids depend on the key columns and on nothing a query's
      literals or aggregates say, so every core over the same keys —
      each date literal compiles its own — replays the one id array a
      batch caches per key set (every global aggregate shares the zero
      ids).  Bounded by the GROUP BY sets ever asked of the table.
    - per core: the aux / rank-table caches (their keys are the core's
      spec and slot positions), held WEAKLY: the kernel LRU
      (`exec/kernels.py`) still decides how long a core lives.

    Both doors reach it: `AggregateRelation.accumulate` adopts it for
    `ctx.sql`, the serving megabatch for `Server.submit`."""

    def __init__(self):
        from datafusion_tpu.analysis import lockcheck

        self._lock = lockcheck.make_lock("exec.shared_scan_state")
        self._by_keys: dict = {}
        self._by_core = weakref.WeakKeyDictionary()

    def for_core(self, core, cols=None) -> dict:
        """`cols` maps the core's column positions to the table's (a
        projected scan); None where they are the same."""
        from datafusion_tpu.analysis import lockcheck
        from datafusion_tpu.exec.aggregate import GroupKeyEncoder

        keys = tuple(
            core.key_cols if cols is None
            else (cols[k] for k in core.key_cols)
        )
        with self._lock:
            ids = self._by_keys.get(keys)
            if ids is None:
                ids = self._by_keys[keys] = {
                    "encoder": GroupKeyEncoder(len(keys)),
                    "lock": lockcheck.make_lock("exec.shared_ids"),
                }
            caches = self._by_core.get(core)
            if caches is None:
                caches = self._by_core[core] = {"aux": {}, "str_aux": {}}
        return {**ids, **caches}

    def clear(self) -> None:
        with self._lock:
            self._by_keys.clear()
            self._by_core.clear()


def project_batches(batches, cols: Sequence[int]) -> Iterator[RecordBatch]:
    """`batches` narrowed to `cols`, PRESERVING batch identity: each
    projected batch is the `subset_view` cached on its parent batch, so
    the device copies and group ids cached against a projection are
    owned by the table's long-lived batch and found again by every
    later query, whichever door it came through."""
    from datafusion_tpu.exec.batch import PROJECTION_TAG, subset_view

    cols = list(cols)
    for b in batches:
        yield subset_view(b, cols, tag=PROJECTION_TAG)


class MemoryDataSource(DataSource):
    """In-memory source over prebuilt RecordBatches: a resident table.

    Every scan hands out the SAME RecordBatch objects (`reusable_
    batches`), and so does every projection of it (`with_projection`
    yields the views cached on those batches, never fresh copies).
    The source therefore owns, through its batches' caches, every
    device copy made against them — columns (`device_inputs`), group
    ids — for as long as the source holds the batches; and it owns the
    `SharedScanState` that lets one query's group ids replay for the
    next.  Nothing here is registered with `LEDGER.pin`: the
    copies go when the source does (ROADMAP Queue 3)."""

    reusable_batches = True

    def __init__(self, schema: Schema, record_batches: list[RecordBatch]):
        self._schema = schema
        self._batches = list(record_batches)
        self._shared = SharedScanState()
        # a projection's columns as the table numbers them (None: the
        # table itself)
        self._table_cols: Optional[list] = None

    @property
    def schema(self) -> Schema:
        return self._schema

    def batches(self) -> Iterator[RecordBatch]:
        return iter(self._batches)

    def with_projection(self, projection: Sequence[int]) -> "DataSource":
        cols = list(projection)
        out = MemoryDataSource(
            self._schema.select(cols),
            list(project_batches(self._batches, cols)),
        )
        # one table, one set of encoders
        out._shared = self._shared
        out._table_cols = self._to_table(cols)
        return out

    def _to_table(self, cols):
        """Column positions of this source as the table numbers them
        (None: every column of this source)."""
        mine = self._table_cols
        if cols is None:
            return mine
        return list(cols) if mine is None else [mine[c] for c in cols]

    def shared_state_for(self, core, cols=None) -> dict:
        return self._shared.for_core(core, self._to_table(cols))
