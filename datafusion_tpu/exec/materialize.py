"""Result materialization: device batches -> host rows.

Compaction (dropping masked-out rows) happens *here*, at the pipeline
boundary, not inside operators — the fused kernels carry selection
masks instead (contrast the reference's per-batch per-column gather,
`filter.rs:80-111`).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from datafusion_tpu.datatypes import DataType, Schema
from datafusion_tpu.exec.batch import RecordBatch, bucket_capacity
from datafusion_tpu.utils.metrics import METRICS, QUERY_IDS

# device-side compaction pays off when it at least halves the D2H bytes
_COMPACT_FACTOR = 2


_GATHER_JIT = None


def _gather_compact(arrays, idxs):
    """Jitted gather of the live rows to the front (selective filters:
    transfer count rows over the link instead of the whole
    capacity).  One
    module-level jit, cached per (shapes, dtypes)."""
    global _GATHER_JIT
    if _GATHER_JIT is None:
        import jax

        def compact_gather(arrs, idx):
            return tuple(a[idx] for a in arrs)

        _GATHER_JIT = jax.jit(compact_gather)
    return _GATHER_JIT(arrays, idxs)


def _on_device(a) -> bool:
    return hasattr(a, "copy_to_host_async")


_PACKBITS_JIT = None


def _start_mask_pull(batch) -> None:
    """Begin a device mask's trip to host: pack the bool mask to bits
    on device (8x fewer bytes over the link) and start the async copy.
    The packed array is cached on the batch for _fetch_mask."""
    global _PACKBITS_JIT
    m = batch.mask
    if m is None or not _on_device(m) or "packed_mask" in batch.cache:
        return
    if m.shape[0] % 8:
        m.copy_to_host_async()
        return
    if _PACKBITS_JIT is None:
        import jax
        import jax.numpy as jnp

        def mask_packbits(mask):
            bits = mask.reshape(-1, 8).astype(jnp.uint8)
            weights = jnp.asarray([128, 64, 32, 16, 8, 4, 2, 1], jnp.uint8)
            return (bits * weights[None, :]).sum(axis=1, dtype=jnp.uint8)

        _PACKBITS_JIT = jax.jit(mask_packbits)
    packed = _PACKBITS_JIT(m)
    packed.copy_to_host_async()
    batch.cache["packed_mask"] = packed


def _fetch_mask(batch) -> np.ndarray:
    """Host bool mask for a batch (blocking), via the packed-bits copy
    when _start_mask_pull staged one."""
    packed = batch.cache.get("packed_mask")
    if packed is not None:
        return np.unpackbits(np.asarray(packed)).astype(bool)
    return np.asarray(batch.mask)


def iter_with_mask_prefetch(batches):
    """Iterate batches one ahead, starting each batch's mask D2H copy
    as soon as the batch exists: pulling batch N+1 dispatches its
    kernel and overlaps its mask transfer with batch N's processing.
    Callers that feed compact_batch should wrap their scans with this —
    compact_batch must see the mask before it can decide whether to
    compact on device, so an unprefetched mask costs one link
    round-trip per batch."""
    from collections import deque

    pending: deque = deque()
    for b in batches:
        if b.mask is not None and _on_device(b.mask):
            _start_mask_pull(b)
        pending.append(b)
        if len(pending) > 1:
            yield pending.popleft()
    while pending:
        yield pending.popleft()


class _PendingCompact:
    """In-flight batch materialization: device->host copies dispatched,
    not yet awaited.  `resolve()` blocks on the transfers and assembles
    host columns — callers keep one of these per in-flight batch so the
    link transfer overlaps the next batch's parse/compute instead of
    serializing after it."""

    __slots__ = ("batch", "live", "compacted", "dev_pos", "pull", "count")

    def __init__(self, batch, live, compacted, dev_pos, pull, count):
        self.batch = batch
        self.live = live
        self.compacted = compacted
        self.dev_pos = dev_pos
        self.pull = pull
        self.count = count

    def resolve(self):
        batch, live, n = self.batch, self.live, self.batch.num_rows
        pulled: dict[tuple[str, int], np.ndarray] = {}
        # the blob-packed transfer began at dispatch; finish() just
        # blocks on it (one round trip for all device outputs, timed
        # there as `d2h.wait`)
        host_arrays = self.pull.finish()
        for pos, a in zip(self.dev_pos, host_arrays):
            pulled[pos] = a[: self.count] if self.compacted else a

        def select(kind, i, a):
            hit = pulled.get((kind, i))
            if hit is not None:
                if self.compacted:
                    return hit  # already gathered to the live rows
                a = hit
            else:
                a = np.asarray(a)
            if live is not None:
                return a[live]
            return a[:n]

        cols = []
        valids = []
        for i in range(batch.num_columns):
            cols.append(select("col", i, batch.data[i]))
            v = batch.validity[i]
            valids.append(None if v is None else select("val", i, v))
        count = int(live.sum()) if live is not None else n
        return cols, valids, list(batch.dicts), count


def compact_dispatch(batch: RecordBatch) -> _PendingCompact:
    """Start bringing a batch to host: decide compaction, dispatch the
    device gather, and begin every D2H copy asynchronously.  Blocks only
    on the selection mask (one small transfer, usually prefetched by
    `iter_with_mask_prefetch`)."""
    n = batch.num_rows
    live: Optional[np.ndarray] = None
    if batch.mask is not None:
        if _on_device(batch.mask):
            _start_mask_pull(batch)
        live = _fetch_mask(batch)[: batch.capacity]
        live = live & (np.arange(batch.capacity) < n)

    # arrays already resident on device ((position-kind, index) pairs);
    # host arrays (identity passthroughs, host-fn outputs) never travel
    # to the device just to be compacted — they index by `live` directly
    dev_pos: list[tuple[str, int]] = []
    dev_arrays: list = []
    for i, c in enumerate(batch.data):
        if _on_device(c):
            dev_pos.append(("col", i))
            dev_arrays.append(c)
    for i, v in enumerate(batch.validity):
        if v is not None and _on_device(v):
            dev_pos.append(("val", i))
            dev_arrays.append(v)

    compacted = False
    count = int(live.sum()) if live is not None else n
    if live is not None and dev_arrays:
        idx = np.nonzero(live)[0]
        cap_out = bucket_capacity(max(count, 1))
        if cap_out * _COMPACT_FACTOR <= batch.capacity:
            import jax.numpy as jnp

            padded = np.zeros(cap_out, np.int32)
            padded[:count] = idx
            with METRICS.timer("d2h.compact"):
                dev_arrays = list(
                    _gather_compact(tuple(dev_arrays), jnp.asarray(padded))
                )
            METRICS.add("d2h.compacted_batches")
            compacted = True
    # ONE blob-packed D2H per batch, started now; resolve() blocks later
    from datafusion_tpu.exec.batch import device_pull_start

    pull = device_pull_start(tuple(dev_arrays))
    return _PendingCompact(batch, live, compacted, dev_pos, pull, count)


def compact_batch(batch: RecordBatch):
    """Bring a batch to host and drop padding/filtered rows.

    Returns (columns, validity, dicts, num_live_rows); strings stay
    dictionary-coded.  Selection masks compact *on device* when that
    meaningfully shrinks the transfer (the reference gathers per column
    on the host per batch, `filter.rs:80-111`; here the gather is one
    fused device kernel and only live rows cross the link).  The
    synchronous convenience form of compact_dispatch().resolve().
    """
    return compact_dispatch(batch).resolve()


class ResultTable:
    """A fully-materialized query result (decoded, null-aware)."""

    def __init__(self, schema: Schema, columns: list[np.ndarray],
                 validity: list[Optional[np.ndarray]]):
        self.schema = schema
        self.columns = columns
        self.validity = validity

    @property
    def num_rows(self) -> int:
        return len(self.columns[0]) if self.columns else 0

    def column_values(self, i: int) -> list:
        """Python values for column i, None where null."""
        col = self.columns[i]
        valid = self.validity[i]
        out = col.tolist()
        if valid is not None:
            out = [v if ok else None for v, ok in zip(out, valid)]
        return out

    def to_pylist(self) -> list[dict]:
        names = self.schema.names()
        cols = [self.column_values(i) for i in range(len(names))]
        return [dict(zip(names, row)) for row in zip(*cols)] if cols else []

    def to_rows(self) -> list[tuple]:
        cols = [self.column_values(i) for i in range(len(self.schema))]
        return list(zip(*cols)) if cols else []

    def to_csv(self, path: str, header: bool = True) -> None:
        """Materialize to a CSV file (the `PhysicalPlan::Write` sink,
        reference `physicalplan.rs:25-29`)."""
        import csv as _csv

        with open(path, "w", newline="", encoding="utf-8") as fh:
            w = _csv.writer(fh)
            if header:
                w.writerow(self.schema.names())
            for row in self.to_rows():
                w.writerow(["" if v is None else v for v in row])

    def pretty(self, max_rows: int = 50) -> str:
        names = self.schema.names()
        rows = self.to_rows()[:max_rows]
        cells = [[("NULL" if v is None else str(v)) for v in row] for row in rows]
        widths = [len(n) for n in names]
        for row in cells:
            for j, c in enumerate(row):
                widths[j] = max(widths[j], len(c))
        sep = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
        lines = [sep]
        lines.append("|" + "|".join(f" {n:<{w}} " for n, w in zip(names, widths)) + "|")
        lines.append(sep)
        for row in cells:
            lines.append("|" + "|".join(f" {c:<{w}} " for c, w in zip(row, widths)) + "|")
        lines.append(sep)
        if len(self.to_rows()) > max_rows:
            lines.append(f"... ({self.num_rows} rows total)")
        return "\n".join(lines)


def collect_columns(relation):
    """Pull every batch of a Relation and concatenate live rows on host.

    Returns (columns, validity, dicts, total_rows); strings stay
    dictionary-coded (dicts[i] holds the decoder).

    This is also the result-cache capture point: a root relation that
    `ExecutionContext.execute` tagged with `_result_cache_fill`
    (`cache/result.py`) gets the fully-materialized columns handed to
    that hook after a complete, exception-free run — caching never
    changes what this function returns or how batches are pulled.

    A root relation's whole materialisation on the calling thread is
    the `query` stage timer (span `dftpu.query`, with the `qid` its
    other spans share); `query.other` is the part of it that no other
    stage timer of this thread names.
    """
    if getattr(relation, "_telemetry_query", None) is None:
        return _collect_columns(relation)
    qid = getattr(relation, "_query_id", None) or next(QUERY_IDS)
    span = METRICS.timer("query", qid=qid)
    try:
        with span:
            return _collect_columns(relation)
    finally:
        METRICS.observe("query.other", span.self_s)


def _collect_columns(relation):
    import time as _time

    t0 = _time.perf_counter()
    query_label = getattr(relation, "_telemetry_query", None)
    schema = relation.schema
    ncols = len(schema)
    parts: list[list[np.ndarray]] = [[] for _ in range(ncols)]
    vparts: list[list[Optional[np.ndarray]]] = [[] for _ in range(ncols)]
    dicts: list = [None] * ncols
    any_null = [False] * ncols
    total = 0

    def consume(pending_compact):
        nonlocal total
        cols, valids, bdicts, n = pending_compact.resolve()
        if n == 0:
            return
        total += n
        for i in range(ncols):
            parts[i].append(cols[i])
            vparts[i].append(valids[i])
            if valids[i] is not None:
                any_null[i] = True
            if bdicts[i] is not None:
                dicts[i] = bdicts[i]

    # shallow pipeline: overlap batch N+1's kernel dispatch + mask D2H
    # with batch N's transfers instead of ping-ponging on a
    # high-latency link; resolve (the blocking D2H wait) runs one batch
    # behind dispatch so the link transfer overlaps the next batch's
    # parse + compute
    from collections import deque

    pending: deque = deque()
    try:
        for batch in iter_with_mask_prefetch(relation.batches()):
            pending.append(compact_dispatch(batch))
            if len(pending) > 1:
                consume(pending.popleft())
        while pending:
            consume(pending.popleft())
    except Exception as e:
        # failed root query: the telemetry funnel observes the error
        # (SLO error budget, flight event, auto-captured artifact set)
        # before the exception continues to the caller unchanged
        if query_label is not None:
            _query_telemetry(
                relation, query_label, _time.perf_counter() - t0,
                rows=total, error=f"{type(e).__name__}: {e}",
            )
        raise
    columns = []
    validity: list[Optional[np.ndarray]] = []
    for i in range(ncols):
        if parts[i]:
            columns.append(np.concatenate(parts[i]))
        else:
            columns.append(np.empty(0, dtype=schema.field(i).data_type.np_dtype))
        if not any_null[i]:
            validity.append(None)
        else:
            vs = [
                v if v is not None else np.ones(len(p), dtype=bool)
                for v, p in zip(vparts[i], parts[i])
            ]
            validity.append(np.concatenate(vs))
    fill = getattr(relation, "_result_cache_fill", None)
    if fill is not None:
        fill(columns, validity, dicts, total, _time.perf_counter() - t0)
    if query_label is not None:
        _query_telemetry(relation, query_label,
                         _time.perf_counter() - t0, rows=total)
    return columns, validity, dicts, total


def _query_telemetry(relation, label: str, wall_s: float, rows: int,
                     error: "Optional[str]" = None) -> None:
    """Feed one root query's outcome to the telemetry funnel (latency
    histogram, SLO watchdog, flight recorder, slow/failed-query
    artifact capture).  The funnel itself never raises."""
    from datafusion_tpu.obs import trace as obs_trace
    from datafusion_tpu.obs.aggregate import query_completed

    # cold-path phase breakdown: diff the engine's stage timers against
    # the snapshot taken when the query was telemetry-tagged
    # (exec/context.py) — decode/H2D/compile/execute/D2H/other per
    # query, in ms, riding the flight event and slow-query artifact
    phases = None
    before = getattr(relation, "_phase_before", None)
    if before:  # empty snapshot = ledger disabled, no breakdown
        from datafusion_tpu.obs.device import phase_breakdown, phase_ms

        phases = phase_ms(phase_breakdown(before, wall_s)) or None
    tc = obs_trace.current_trace()
    query_completed(
        wall_s, rows=rows,
        # EXPLAIN ANALYZE's _RootTap facade forwards the real tree here
        root=getattr(relation, "_telemetry_root", relation),
        label=label, error=error,
        trace_id=None if tc is None else tc.trace_id,
        # the explain path exports the complete drained span set itself
        export_otlp=not getattr(relation, "_telemetry_skip_otlp", False),
        phases=phases,
    )


def collect(relation) -> ResultTable:
    """Materialize a Relation into a ResultTable (decodes strings)."""
    schema = relation.schema
    columns, validity, dicts, _ = collect_columns(relation)
    decoded = []
    for i in range(len(schema)):
        c = columns[i]
        if schema.field(i).data_type == DataType.UTF8:
            if dicts[i] is not None:
                c = dicts[i].decode(c)
            else:
                c = c.astype(object)
        decoded.append(c)
    return ResultTable(schema, decoded, validity)
