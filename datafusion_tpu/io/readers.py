"""CSV / NDJSON / Parquet batch readers.

Each reader yields `RecordBatch`es of up to `batch_size` rows for a
schema-driven typed parse (header and headerless CSV, like the
reference's `arrow::csv::Reader` usage at `datasource.rs:31-50` /
`examples/csv_sql.rs:49`), carrying validity masks and global
string dictionaries.  `projection` restricts which columns are
parsed/encoded at all — this is where projection push-down pays off on
the host side, before any H2D transfer.
"""

from __future__ import annotations

import json
from collections import deque
from typing import Iterable, Iterator, Optional, Sequence

import numpy as np

from datafusion_tpu.datatypes import DataType, Schema
from datafusion_tpu.errors import ExecutionError, IoError
from datafusion_tpu.exec.batch import RecordBatch, StringDictionary, make_host_batch
from datafusion_tpu.io.io_thread import confined_iter, run_on_io_thread
from datafusion_tpu.testing import faults
from datafusion_tpu.utils.metrics import METRICS

DEFAULT_BATCH_SIZE = 131072


def _project_schema(schema: Schema, projection: Optional[Sequence[int]]) -> Schema:
    return schema if projection is None else schema.select(list(projection))


def _arrow_to_columns(
    table_cols, out_schema: Schema, dicts: list[Optional[StringDictionary]]
):
    """Convert pyarrow chunked arrays to (numpy columns, validity)."""
    columns: list[np.ndarray] = []
    validity: list[Optional[np.ndarray]] = []
    for i, (field, col) in enumerate(zip(out_schema.fields, table_cols)):
        np_dtype = field.data_type.np_dtype
        if field.data_type == DataType.UTF8:
            import pyarrow as pa

            d = dicts[i]
            assert d is not None
            # strictly per-chunk: pyarrow's chunked dictionary
            # unification (combine_chunks / dictionary_encode over a
            # ChunkedArray) segfaults in this environment when chunks
            # carry different local dictionaries — and auto_dict_encode
            # can even produce MIXED chunk types (dict + plain string)
            # in one column.  Per-chunk work also skips the re-hash for
            # chunks that arrive dictionary-encoded from the
            # parquet/csv layer (read_dictionary / auto_dict_encode).
            code_parts: list[np.ndarray] = []
            null_parts: list[np.ndarray] = []
            for chunk in col.chunks:
                if pa.types.is_dictionary(chunk.type):
                    enc = chunk
                else:
                    c = chunk
                    if not pa.types.is_string(c.type) and not pa.types.is_large_string(c.type):
                        # e.g. parquet date32/timestamp columns travel
                        # as ISO strings
                        c = c.cast(pa.string())
                    enc = c.dictionary_encode()
                idx = enc.indices
                values = enc.dictionary.to_pylist()
                if idx.null_count == 0:
                    # no null in the chunk (the array knows): nothing
                    # to fill, no mask to build
                    merged = d.merge_codes(
                        idx.to_numpy(zero_copy_only=False).astype(np.int32),
                        values)
                    isnull = None
                else:
                    local = idx.fill_null(0).to_numpy(zero_copy_only=False)
                    merged = d.merge_codes(local.astype(np.int32), values)
                    isnull = idx.is_null().to_numpy(zero_copy_only=False)
                    merged[isnull] = 0
                code_parts.append(merged)
                null_parts.append(isnull)
            if not code_parts:
                codes, null_mask = np.empty(0, np.int32), None
            elif len(code_parts) == 1:
                codes, null_mask = code_parts[0], null_parts[0]
            else:
                codes = np.concatenate(code_parts)
                null_mask = None if all(m is None for m in null_parts) else (
                    np.concatenate([
                        np.zeros(len(c), bool) if m is None else m
                        for c, m in zip(code_parts, null_parts)]))
            columns.append(codes)
            validity.append(
                None if null_mask is None or not null_mask.any()
                else ~null_mask)
        else:
            import pyarrow as pa

            if col.null_count == 0:
                # the common case, and the array knows it: no mask to
                # build and scan, no filled copy to make
                null_mask = None
                vals = col.to_numpy(zero_copy_only=False)
            else:
                null_mask = col.is_null().to_numpy(zero_copy_only=False)
                fill = False if pa.types.is_boolean(col.type) else 0
                vals = col.fill_null(fill).to_numpy(zero_copy_only=False)
            # copy=False: parquet f64 columns arrive already-typed; the
            # no-op astype would memcpy 48 MB per SF-1 numeric column
            vals = np.asarray(vals).astype(np_dtype, copy=False)
            columns.append(vals)
            validity.append(None if null_mask is None else ~null_mask)
    return columns, validity


class CsvReader:
    """Schema-driven typed CSV reader over pyarrow's csv engine."""

    def __init__(
        self,
        path: str,
        schema: Schema,
        has_header: bool,
        batch_size: int = DEFAULT_BATCH_SIZE,
        projection: Optional[Sequence[int]] = None,
    ):
        self.path = path
        self.schema = schema
        self.has_header = has_header
        self.batch_size = batch_size
        self.projection = list(projection) if projection is not None else None
        self.out_schema = _project_schema(schema, projection)
        # global dictionaries persist across batches
        self.dicts: list[Optional[StringDictionary]] = [
            StringDictionary() if f.data_type == DataType.UTF8 else None
            for f in self.out_schema.fields
        ]

    def batches(self) -> Iterator[RecordBatch]:
        # pyarrow work is confined to the persistent IO threads — scans
        # issued from short-lived threads (server handlers) otherwise
        # intermittently segfault inside pyarrow (io_thread.py
        # docstring).  timed_iter sits INSIDE the confinement so
        # scan.parse measures parse work, not queue wait.
        yield from confined_iter(
            METRICS.timed_iter("scan.parse", self._batches())
        )

    def _batches(self) -> Iterator[RecordBatch]:
        import pyarrow as pa
        import pyarrow.csv as pacsv

        type_map = {
            "Boolean": pa.bool_(),
            "Int8": pa.int8(),
            "Int16": pa.int16(),
            "Int32": pa.int32(),
            "Int64": pa.int64(),
            "UInt8": pa.uint8(),
            "UInt16": pa.uint16(),
            "UInt32": pa.uint32(),
            "UInt64": pa.uint64(),
            "Float32": pa.float32(),
            "Float64": pa.float64(),
            "Utf8": pa.string(),
        }
        names = self.schema.names()
        read_opts = pacsv.ReadOptions(
            column_names=None if self.has_header else names,
            block_size=max(1 << 20, self.batch_size * 64),
        )
        # NOTE: auto_dict_encode is deliberately NOT used — this
        # pyarrow's multithreaded CSV reader emits delta/mixed
        # dictionary chunks that segfault in downstream dictionary
        # APIs; _arrow_to_columns re-encodes per chunk instead
        convert_opts = pacsv.ConvertOptions(
            column_types={f.name: type_map[f.data_type.name] for f in self.schema.fields},
            include_columns=[self.out_schema.fields[i].name for i in range(len(self.out_schema))],
            strings_can_be_null=True,
        )
        try:
            reader = pacsv.open_csv(
                self.path, read_options=read_opts, convert_options=convert_opts
            )
        except (pa.ArrowInvalid, OSError) as e:
            raise IoError(f"cannot open CSV {self.path!r}: {e}") from e
        pending = None
        for arrow_batch in reader:
            tbl = pa.Table.from_batches([arrow_batch])
            pending = tbl if pending is None else _concat(pending, tbl)
            while pending.num_rows >= self.batch_size:
                chunk = pending.slice(0, self.batch_size)
                pending = pending.slice(self.batch_size)
                yield self._to_batch(chunk)
        if pending is not None and pending.num_rows > 0:
            yield self._to_batch(pending)

    def _to_batch(self, tbl) -> RecordBatch:
        faults.check("io.read", path=self.path, format="csv")
        cols = [tbl.column(i) for i in range(tbl.num_columns)]
        columns, validity = _arrow_to_columns(cols, self.out_schema, self.dicts)
        METRICS.add("scan.rows", tbl.num_rows)
        return make_host_batch(self.out_schema, columns, validity, list(self.dicts))


def _concat(a, b):
    import pyarrow as pa

    return pa.concat_tables([a, b])


class NdJsonReader:
    """Newline-delimited JSON reader (declared in the reference DDL,
    `dfparser.rs:33`, but never implemented there)."""

    def __init__(
        self,
        path: str,
        schema: Schema,
        batch_size: int = DEFAULT_BATCH_SIZE,
        projection: Optional[Sequence[int]] = None,
    ):
        self.path = path
        self.schema = schema
        self.batch_size = batch_size
        self.projection = list(projection) if projection is not None else None
        self.out_schema = _project_schema(schema, projection)
        self.dicts: list[Optional[StringDictionary]] = [
            StringDictionary() if f.data_type == DataType.UTF8 else None
            for f in self.out_schema.fields
        ]

    def batches(self) -> Iterator[RecordBatch]:
        yield from METRICS.timed_iter("scan.parse", self._batches())

    def _batches(self) -> Iterator[RecordBatch]:
        try:
            f = open(self.path, "r", encoding="utf-8")
        except OSError as e:
            raise IoError(f"cannot open NDJSON {self.path!r}: {e}") from e
        with f:
            rows: list[dict] = []
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rows.append(json.loads(line))
                except json.JSONDecodeError as e:
                    raise IoError(f"bad NDJSON line in {self.path!r}: {e}") from e
                if len(rows) >= self.batch_size:
                    yield self._rows_to_batch(rows)
                    rows = []
            if rows:
                yield self._rows_to_batch(rows)

    def _rows_to_batch(self, rows: list[dict]) -> RecordBatch:
        faults.check("io.read", path=self.path, format="ndjson")
        METRICS.add("scan.rows", len(rows))
        columns: list[np.ndarray] = []
        validity: list[Optional[np.ndarray]] = []
        for i, field in enumerate(self.out_schema.fields):
            raw = [r.get(field.name) for r in rows]
            isnull = np.fromiter((v is None for v in raw), dtype=bool, count=len(raw))
            if field.data_type == DataType.UTF8:
                codes = self.dicts[i].encode(raw)
                columns.append(codes)
            else:
                filled = [0 if v is None else v for v in raw]
                columns.append(
                    np.asarray(filled).astype(field.data_type.np_dtype)
                )
            validity.append(None if not isnull.any() else ~isnull)
        return make_host_batch(self.out_schema, columns, validity, list(self.dicts))


class ParquetReader:
    """Parquet reader (the TPC-H baseline input; absent in the
    reference, README.md:22)."""

    def __init__(
        self,
        path: str,
        schema: Optional[Schema] = None,
        batch_size: int = DEFAULT_BATCH_SIZE,
        projection: Optional[Sequence[int]] = None,
        row_groups: Optional[Sequence[int]] = None,
    ):
        self.path = path
        self.schema = schema if schema is not None else infer_parquet_schema(path)
        self.batch_size = batch_size
        self.projection = list(projection) if projection is not None else None
        # the file's row groups this reader scans (None: all of them):
        # how a table is dealt to the shards of a mesh
        self.row_groups = None if row_groups is None else list(row_groups)
        self.out_schema = _project_schema(self.schema, projection)
        self.dicts: list[Optional[StringDictionary]] = [
            StringDictionary() if f.data_type == DataType.UTF8 else None
            for f in self.out_schema.fields
        ]

    def batches(self, whole: bool = True) -> Iterator[RecordBatch]:
        """The scan as batches of exactly `batch_size` rows, the last
        one what is left; `whole=False` hands on the file's own cut
        (pyarrow ends a batch at every row group's end where a column
        is read dictionary-encoded) to a caller that re-cuts anyway."""
        # confined for the same reason as CsvReader.batches; the re-cut
        # runs on the parser thread too, inside scan.parse
        pieces = self._pieces()
        yield from confined_iter(METRICS.timed_iter(
            "scan.parse",
            whole_batches(pieces, self.batch_size) if whole else pieces,
        ))

    def _pieces(self) -> Iterator[RecordBatch]:
        import pyarrow as pa
        import pyarrow.parquet as pq

        names = [f.name for f in self.out_schema.fields]
        # read Utf8 columns dictionary-encoded straight off the file —
        # the parquet pages usually are already — instead of re-hashing
        # every batch (~2.5x faster scan on TPC-H lineitem)
        dict_cols = [
            f.name for f in self.out_schema.fields
            if f.data_type == DataType.UTF8
        ]
        try:
            pf = pq.ParquetFile(self.path, read_dictionary=dict_cols)
        except Exception as e:
            raise IoError(f"cannot open Parquet {self.path!r}: {e}") from e
        # read_dictionary only applies to string-physical columns; a
        # date/timestamp column (travels as ISO strings) keeps its type
        # and takes the cast path in _arrow_to_columns
        for arrow_batch in pf.iter_batches(
                batch_size=self.batch_size, columns=names,
                row_groups=self.row_groups):
            faults.check("io.read", path=self.path, format="parquet")
            cols = [pa.chunked_array([arrow_batch.column(j)])
                    for j in range(arrow_batch.num_columns)]
            columns, validity = _arrow_to_columns(cols, self.out_schema, self.dicts)
            METRICS.add("scan.rows", arrow_batch.num_rows)
            yield make_host_batch(self.out_schema, columns, validity, list(self.dicts))


def whole_batches(batches: Iterable[RecordBatch],
                  size: int) -> Iterator[RecordBatch]:
    """A scan's batches re-cut into batches of exactly `size` rows, the
    last one what is left, rows in order.  A Parquet reader cuts the
    batch that straddles a row group's end in two; a piece of another
    capacity is a shape class of its own and ends the aggregate's run
    of foldable batches (`exec/fused.iter_groups`), so the pieces are
    joined here, before anything keeps them.  Streamed, at the numpy
    level (string codes are global by now, no dictionary is unified):
    a batch that arrives whole while nothing waits is handed on as it
    is, every other row is copied once, into the batch that keeps it;
    a validity array is made only where a piece brought one.  Longer
    batches are cut down the same way (the mesh's readers hand over
    several table batches at a time)."""
    held: deque = deque()  # (batch, its first row not yet handed on)
    have = 0

    def take(n: int) -> RecordBatch:
        nonlocal have
        have -= n
        parts = []
        while n:
            b, lo = held.popleft()
            hi = min(b.num_rows, lo + n)
            parts.append((b, lo, hi))
            n -= hi - lo
            if hi < b.num_rows:
                held.appendleft((b, hi))
        first = parts[0][0]
        if parts == [(first, 0, first.num_rows)]:
            return first  # the scan's short last batch, alone
        if len(parts) > 1:
            METRICS.add("scan.recut.pieces", len(parts))
        columns, validity = [], []
        for i in range(len(first.data)):
            columns.append(np.concatenate(
                [np.asarray(b.data[i])[lo:hi] for b, lo, hi in parts]))
            validity.append(
                None if all(b.validity[i] is None for b, _, _ in parts)
                else np.concatenate([
                    np.ones(hi - lo, bool) if b.validity[i] is None
                    else np.asarray(b.validity[i])[lo:hi]
                    for b, lo, hi in parts]))
        return make_host_batch(first.schema, columns, validity,
                               list(first.dicts))

    for b in batches:
        if not held and b.num_rows == size:
            yield b
        elif b.num_rows:
            held.append((b, 0))
            have += b.num_rows
            while have >= size:
                yield take(size)
    if have:
        yield take(have)


def parquet_row_groups(path: str) -> int:
    """How many row groups a Parquet file holds."""

    def _count(p):
        import pyarrow.parquet as pq

        return pq.ParquetFile(p).metadata.num_row_groups

    return run_on_io_thread(_count, path)


def infer_parquet_schema(path: str) -> Schema:
    """Derive an engine Schema from parquet file metadata."""
    from datafusion_tpu.datatypes import Field

    def _read_schema(p):
        import pyarrow.parquet as pq

        return pq.ParquetFile(p).schema_arrow

    arrow_schema = run_on_io_thread(_read_schema, path)
    mapping = {
        "bool": DataType.BOOLEAN,
        "int8": DataType.INT8,
        "int16": DataType.INT16,
        "int32": DataType.INT32,
        "int64": DataType.INT64,
        "uint8": DataType.UINT8,
        "uint16": DataType.UINT16,
        "uint32": DataType.UINT32,
        "uint64": DataType.UINT64,
        "float": DataType.FLOAT32,
        "double": DataType.FLOAT64,
        "string": DataType.UTF8,
        "large_string": DataType.UTF8,
    }
    fields = []
    for f in arrow_schema:
        t = str(f.type)
        if t.startswith("timestamp") or t.startswith("date"):
            dt = DataType.UTF8  # dates travel as ISO strings (order-preserving)
        elif t in mapping:
            dt = mapping[t]
        else:
            raise ExecutionError(f"unsupported parquet type {t!r} for column {f.name!r}")
        fields.append(Field(f.name, dt, f.nullable))
    return Schema(fields)
