#!/usr/bin/env python
"""Proof that the engine's main path runs on the attached TPU.

One process — the only one that imports JAX, so the only holder of the
chip — drives `ExecutionContext(device="tpu")` -> `register_parquet` ->
`ctx.sql(Q1)` -> `collect`, and the same table behind `ctx.serve()`,
over TPC-H lineitem at SF-10 (60 M rows; `benchmarks/data.py`, seeded),
and checks every answer against an independent pyarrow + numpy oracle.

Stages, each checked, none caught — a failed check raises and the
process exits non-zero without printing a result:

1. cold   Parquet scan -> Q1 -> collect
2. warm   the same rows resident in memory: one pass to upload, then
          three passes with zero new compilations and zero H2D transfers
3. serve  a few dozen concurrent `submit`s of Q1's aggregates under
          `l_quantity < x` (distinct x) through the megabatcher
4. ops    GROUP BY at ~8 k and 100 k groups, int64 ORDER BY over 2^18
          rows, TopK, a dense-int join at the Pallas hash-build kernel's
          window edge (8,192 slots), and a join on sparse keys over 2^22
          slots grouped by a build-side string (the device probe that
          `tpubench`'s q12_sf10_join measures)
4b. q3    TPC-H Q3 over customer, orders and lineitem at SF-1 against
          `tpubench`'s numpy oracle: two pinned builds, the second
          probed by a gathered column, three numeric group keys made
          into groups on the device, `ORDER BY` an alias, `LIMIT 10`
5. mesh   only with >= 4 TPU devices: Q1 over lineitem registered through
          `PartitionedContext.register_resident_parquet` on `make_mesh(4)`,
          a new relation a pass; the second pass ships nothing but a
          round's row counts (the predicate runs in the kernel)

Each stage prints the evidence that the device did the work (launches,
H2D bytes, kernel engagement).  Without a TPU
(`jax.devices()[0].platform != "tpu"`) the script exits 1 before
touching data; it sets no `JAX_PLATFORMS` itself.  It writes only under
`chiprun_out/` and the git-ignored `test/data/bench/`.  The last line
of stdout is one JSON object: {"ok": true, "device": {...}}.

Sums and averages compare at rtol 1e-9 (`suite._assert_tables_match`'s
tolerance): f64 is f32-pair software on this chip, and the device's
reduction order differs from numpy's; group keys and counts are exact.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out")
NORTH_STAR_SF = 10
RTOL = 1e-9
# the contract allows 1200 s, compilation included: past this the
# watchdog dumps every thread's stack to stderr and exits non-zero
WATCHDOG_S = 1150
CUTOFF = "1998-09-02"

_AGGS = (
    "SUM(l_quantity), SUM(l_extendedprice), "
    "SUM(l_extendedprice * (1 - l_discount)), "
    "SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)), "
    "AVG(l_quantity), AVG(l_extendedprice), AVG(l_discount), COUNT(1)"
)


def serve_sql(x: int) -> str:
    """Q1's GROUP BY and aggregates under a numeric predicate: same
    compiled core for every x, so the megabatcher is eligible."""
    return (
        f"SELECT l_returnflag, l_linestatus, {_AGGS} FROM lineitem "
        f"WHERE l_quantity < {x} GROUP BY l_returnflag, l_linestatus"
    )


class SmokeFailure(AssertionError):
    """A stage's check did not hold."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- the independent reference -------------------------------------------


class Q1Oracle:
    """Q1 by a plain chunked pyarrow + numpy pass over the Parquet row
    groups: dictionary codes -> `np.bincount` in f64.  Independent of
    the engine (no datafusion_tpu import).  One pass fills per-cell
    partial sums keyed by (group, l_quantity value, shipdate <= cutoff),
    from which Q1 and every `l_quantity < x` variant follow exactly."""

    QTY = 64  # l_quantity is a whole number in [1, 50]

    def __init__(self, path: str):
        import pyarrow.parquet as pq

        keys: dict = {}
        n_meas = 6  # qty, price, disc_price, charge, discount, count
        cells = np.zeros((0, n_meas))
        pf = pq.ParquetFile(
            path,
            read_dictionary=["l_returnflag", "l_linestatus", "l_shipdate"],
        )
        for rg in range(pf.metadata.num_row_groups):
            t = pf.read_row_group(rg)

            def codes(name):
                col = t.column(name).combine_chunks()
                return (
                    np.asarray(col.indices),
                    col.dictionary.to_pylist(),
                )

            fc, fd = codes("l_returnflag")
            sc, sd = codes("l_linestatus")
            dc, dd = codes("l_shipdate")
            # (flag, status) pair -> dense global group id
            pair = fc.astype(np.int64) * len(sd) + sc
            gmap = np.empty(len(fd) * len(sd), np.int64)
            for i, f in enumerate(fd):
                for j, s in enumerate(sd):
                    gmap[i * len(sd) + j] = keys.setdefault((f, s), len(keys))
            gid = gmap[pair]
            ship_ok = np.array([d <= CUTOFF for d in dd], bool)[dc]
            qty = t.column("l_quantity").to_numpy()
            price = t.column("l_extendedprice").to_numpy()
            disc = t.column("l_discount").to_numpy()
            tax = t.column("l_tax").to_numpy()
            q_int = qty.astype(np.int64)
            require(
                bool((q_int == qty).all() and (q_int >= 0).all()
                     and (q_int < self.QTY).all()),
                "oracle: l_quantity is not a whole number in [0, 64)",
            )
            cell = (gid * self.QTY + q_int) * 2 + ship_ok
            n_cells = len(keys) * self.QTY * 2
            if cells.shape[0] < n_cells:
                cells = np.vstack(
                    [cells, np.zeros((n_cells - cells.shape[0], n_meas))]
                )
            disc_price = price * (1 - disc)
            for m, w in enumerate(
                (qty, price, disc_price, disc_price * (1 + tax), disc, None)
            ):
                cells[:n_cells, m] += np.bincount(
                    cell, weights=w, minlength=n_cells
                )[:n_cells]
        self.keys = keys
        self.cells = cells.reshape(len(keys), self.QTY, 2, n_meas)

    def _rows(self, sel) -> list:
        out = []
        for (flag, status), g in self.keys.items():
            m = sel(self.cells[g]).reshape(-1, self.cells.shape[-1]).sum(axis=0)
            n = int(round(m[5]))
            if n == 0:
                continue
            out.append((flag, status, m[0], m[1], m[2], m[3],
                        m[0] / n, m[1] / n, m[4] / n, n))
        return sorted(out)

    def q1(self) -> list:
        return self._rows(lambda c: c[:, 1])

    def quantity_below(self, x: int) -> list:
        return self._rows(lambda c: c[:x])


def check_rows(got, want, label: str, rtol: float = RTOL) -> None:
    """Keys, integers and row count exact; floats to `rtol`."""
    got, want = sorted(got), sorted(want)
    require(len(got) == len(want),
            f"{label}: {len(got)} rows, oracle has {len(want)}")
    for g, w in zip(got, want):
        require(len(g) == len(w), f"{label}: row widths differ: {g} vs {w}")
        for gv, wv in zip(g, w):
            if isinstance(gv, float) or isinstance(wv, float):
                require(
                    bool(np.isfinite(gv))
                    and abs(gv - wv) <= rtol * abs(wv),
                    f"{label}: {gv!r} vs oracle {wv!r} in {g} vs {w}",
                )
            else:
                require(gv == wv, f"{label}: {gv!r} != {wv!r} in {g} vs {w}")


# -- evidence ---------------------------------------------------------------


def _counts() -> dict:
    from datafusion_tpu.utils.metrics import METRICS

    return dict(METRICS.snapshot()["counts"])


def _delta(before: dict, after: dict, name: str) -> int:
    return after.get(name, 0) - before.get(name, 0)


_EVIDENCE = (
    "device.launches", "h2d.bytes", "device.h2d.transfers",
    "kernel_cache.misses",
)


def evidence(before: dict, after: dict, extra=()) -> dict:
    return {k: _delta(before, after, k) for k in _EVIDENCE + tuple(extra)}


def require_on_device(stage: str, ev: dict) -> None:
    """The stage's work ran on the device, not around it."""
    require(ev["device.launches"] > 0, f"{stage}: no device launch")


# -- stages -------------------------------------------------------------------


def lineitem_path(sf) -> str:
    from benchmarks import data as bdata

    return bdata.lineitem_parquet(sf)


def stage_cold(device: str, sf, oracle: Q1Oracle) -> dict:
    from benchmarks.suite import Q1
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.materialize import collect

    before = _counts()
    ctx = ExecutionContext(device=device, batch_size=1 << 19)
    ctx.register_parquet("lineitem", lineitem_path(sf))
    rows = collect(ctx.sql(Q1)).to_rows()
    ev = evidence(before, _counts())
    check_rows(rows, oracle.q1(), "cold Q1")
    require_on_device("cold", ev)
    require(ev["h2d.bytes"] > 0, "cold: no bytes crossed host->device")
    return {"rows": rows, "evidence": ev}


def resident_lineitem(sf, batch_size: int = 1 << 19):
    """The lineitem rows as a resident in-memory table, built as
    `benchmarks/suite.config3_tpch_q1` builds its warm leg."""
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.datasource import MemoryDataSource

    ctx = ExecutionContext(device="cpu", batch_size=batch_size)
    ctx.register_parquet("lineitem", lineitem_path(sf))
    scan = ctx.datasources["lineitem"]
    batches = list(scan.batches())
    require(all(b.num_rows == batch_size for b in batches[:-1]),
            "resident: the scan handed on a batch cut at a row group's end")
    return MemoryDataSource(scan.schema, batches)


def stage_warm(ctx, oracle: Q1Oracle) -> dict:
    """`ctx` holds the resident table as `lineitem` on its device."""
    from benchmarks.suite import Q1
    from datafusion_tpu.exec.materialize import collect

    rel = ctx.sql(Q1)
    before = _counts()
    check_rows(collect(rel).to_rows(), oracle.q1(), "warm Q1 (upload pass)")
    uploaded = _counts()
    for i in range(3):
        check_rows(collect(rel).to_rows(), oracle.q1(), f"warm Q1 pass {i}")
    ev_upload = evidence(before, uploaded)
    ev = evidence(uploaded, _counts())
    require_on_device("warm", ev)
    require(ev_upload["h2d.bytes"] > 0, "warm: the upload pass shipped nothing")
    require(ev["kernel_cache.misses"] == 0,
            f"warm: {ev['kernel_cache.misses']} new kernels in warm passes")
    require(ev["device.h2d.transfers"] == 0,
            f"warm: {ev['device.h2d.transfers']} H2D transfers in warm passes")
    # a new relation a pass, as a client's `ctx.sql` makes one: over a
    # table that stays the predicate is in the core, so it finds the
    # column copies on the batches, looks its `cmp_table` up once a
    # batch on the device and ships nothing
    batches = sum(1 for _ in ctx.datasources["lineitem"].batches())
    extra = ("h2d.resident_hits", "h2d.resident_misses", "expr.cmp_lookups")
    fresh_before = _counts()
    passes = 2
    for i in range(passes):
        fresh = ctx.sql(Q1)
        require(fresh._host_pred_expr is None and fresh._core_pred is not None,
                "warm: a resident table's predicate went to the host")
        check_rows(collect(fresh).to_rows(), oracle.q1(),
                   f"warm Q1, new relation {i}")
    fresh_ev = evidence(fresh_before, _counts(), extra)
    require(fresh_ev["device.h2d.transfers"] == 0
            and fresh_ev["h2d.bytes"] == 0
            and fresh_ev["h2d.resident_misses"] == 0
            and fresh_ev["h2d.resident_hits"] == passes * batches
            and fresh_ev["expr.cmp_lookups"] == passes * batches,
            f"warm: {passes} new relations over {batches} resident batches "
            f"moved or missed something: {fresh_ev}")
    return {"evidence": ev, "upload": ev_upload, "new_relations": fresh_ev}


def stage_serve(ctx, oracle: Q1Oracle, clients: int = 8,
                per_client: int = 4) -> dict:
    """`clients * per_client` concurrent submits, distinct literals."""
    from benchmarks import serve_load
    from datafusion_tpu.obs.device import LEDGER

    extra = ("serve.megabatch_launches", "serve.megabatch_queries",
             "serve.megabatch_fallbacks", "serve.query_errors",
             "queries_shed")
    xs = list(range(51 - clients * per_client, 51))  # distinct, selective
    before = _counts()
    results: dict = {}
    errors: list = []
    srv = ctx.serve(megabatch_max=clients, window_s=0.25)
    try:
        # pins the table and compiles the solo program
        x0 = xs[0] - 1
        first = srv.submit(serve_sql(x0)).result(timeout=900)
        check_rows(first.to_rows(), oracle.quantity_below(x0),
                   f"serve x={x0} (first)")

        serve_load.closed_loop(srv, serve_sql, clients, per_client,
                               lambda i: xs[i], results, errors,
                               timeout_s=900)
    finally:
        srv.stop()
    require(not errors, f"serve: {len(errors)} submits failed: {errors[:2]}")
    require(len(results) == len(xs),
            f"serve: {len(results)} of {len(xs)} futures resolved")
    for (ci, qi), table in results.items():
        x = xs[ci * per_client + qi]
        check_rows(table.to_rows(), oracle.quantity_below(x), f"serve x={x}")
    ev = evidence(before, _counts(), extra)
    pins = LEDGER.pins_snapshot()
    require_on_device("serve", ev)
    require(bool(pins), "serve: LEDGER.pins_snapshot() is empty")
    require(ev["serve.megabatch_launches"] > 0, "serve: no megabatch launch")
    require(ev["serve.megabatch_fallbacks"] == 0,
            f"serve: {ev['serve.megabatch_fallbacks']} megabatches failed "
            "and fell back to serial execution")
    require(ev["serve.query_errors"] == 0,
            f"serve: {ev['serve.query_errors']} query errors")
    require(ev["queries_shed"] == 0, f"serve: {ev['queries_shed']} shed")
    return {"queries": len(xs) + 1, "evidence": ev,
            "pins": {fp: p["bytes"] for fp, p in pins.items()}}


def _columns(src) -> list:
    """Host columns of a MemoryDataSource, padding stripped."""
    batches = list(src.batches())
    return [
        np.concatenate([np.asarray(b.data[i])[: b.num_rows] for b in batches])
        for i in range(len(batches[0].data))
    ]


def stage_operators(device: str, agg_rows: int = 1 << 21) -> dict:
    """Operator coverage at the largest shapes the (former and present)
    Pallas windows admit, plus the stock high-cardinality paths.
    In-memory tables, numpy oracles."""
    import jax

    from benchmarks import data as bdata
    from datafusion_tpu.datatypes import DataType, Field, Schema
    from datafusion_tpu.exec import pallas
    from datafusion_tpu.exec.batch import make_host_batch
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.datasource import MemoryDataSource
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.exec.pallas import hash_build
    from datafusion_tpu.exec.rowgather import WINDOW_ROWS

    out: dict = {}
    extra = ("join.build.dense", "join.build.pallas_runs",
             "device.launches.sort.run", "device.launches.join.probe",
             "d2h.bytes", "join.probe.rows", "join.host_probe.rows",
             "join.probe.gathers", "join.probe.window.slot",
             "join.probe.window.payload", "expr.cmp_lookups")

    def run(label, src_by_name, sql, want, batch_size=1 << 19):
        ctx = ExecutionContext(device=device, batch_size=batch_size,
                               result_cache=False)
        for name, src in src_by_name.items():
            ctx.register_datasource(name, src)
        before = _counts()
        got = collect(ctx.sql(sql)).to_rows()
        ev = evidence(before, _counts(), extra)
        require(len(got) == len(want),
                f"{label}: {len(got)} rows, oracle has {len(want)}")
        return got, ev

    # GROUP BY at ~8 k groups and at 100 k groups (stock sort-merge)
    for label, groups in (("groupby_8k", 8000), ("groupby_100k", 100_000)):
        _, src = bdata.groupby_batches(agg_rows, groups, 1 << 19, seed=5)
        k, v1, _, v3 = _columns(src)
        cnt = np.bincount(k, minlength=groups)
        s1 = np.bincount(k, weights=v1, minlength=groups)
        lo = np.full(groups, np.iinfo(np.int64).max)
        hi = np.full(groups, np.iinfo(np.int64).min)
        np.minimum.at(lo, k, v3)
        np.maximum.at(hi, k, v3)
        want = [(int(g), float(s1[g]), int(lo[g]), int(hi[g]), int(cnt[g]))
                for g in range(groups) if cnt[g]]
        got, ev = run(
            label, {"t": src},
            "SELECT k, SUM(v1), MIN(v3), MAX(v3), COUNT(1) FROM t GROUP BY k",
            want,
        )
        check_rows(got, want, label)
        require_on_device(label, ev)
        out[label] = ev

    # int64 ORDER BY over 2^18 rows in one run; f64 TopK over the lot
    n_sort = 1 << 18
    _, src = bdata.sort_batches(n_sort, n_sort)
    a, b, x, _ = _columns(src)
    order = np.argsort(b, kind="stable")
    want = list(zip(b[order].tolist(), x[order].tolist()))
    got, ev = run("order_by_i64", {"t": src},
                  "SELECT b, x FROM t ORDER BY b", want, batch_size=n_sort)
    require(got == want, "order_by_i64: rows differ from np.argsort")
    require_on_device("order_by_i64", ev)
    out["order_by_i64"] = ev

    top = np.argsort(-a, kind="stable")[:100]
    want = list(zip(a[top].tolist(), b[top].tolist()))
    got, ev = run("topk", {"t": src},
                  "SELECT a, b FROM t ORDER BY a DESC LIMIT 100", want,
                  batch_size=n_sort)
    require(got == want, "topk: rows differ from np.argsort")
    require_on_device("topk", ev)
    out["topk"] = ev

    # dense-int-key join: 8,192 build slots, the hash-build kernel's
    # window edge; ~2.5 % of probe keys dangle past the build side
    slots = pallas.BUILD_MAX_SLOTS
    rng = np.random.default_rng(23)
    dim_schema = Schema([Field("k", DataType.INT64, False),
                         Field("grp", DataType.INT64, False)])
    fact_schema = Schema([Field("k", DataType.INT64, False),
                          Field("seq", DataType.INT64, False)])
    dim_k = rng.permutation(slots).astype(np.int64)
    dim_grp = rng.integers(0, 1000, slots).astype(np.int64)
    fact_k = rng.integers(0, slots + slots // 40, n_sort).astype(np.int64)
    seq = np.arange(n_sort, dtype=np.int64)
    dim = MemoryDataSource(dim_schema, [
        make_host_batch(dim_schema, [dim_k, dim_grp], [None] * 2, [None] * 2)])
    fact = MemoryDataSource(fact_schema, [
        make_host_batch(fact_schema, [fact_k, seq], [None] * 2, [None] * 2)])
    grp_of = np.empty(slots, np.int64)
    grp_of[dim_k] = dim_grp
    hit = fact_k < slots
    want = list(zip(seq[hit].tolist(), grp_of[fact_k[hit]].tolist()))
    got, ev = run(
        "join_8k_slots", {"fact": fact, "dim": dim},
        "SELECT seq, grp FROM fact JOIN dim ON fact.k = dim.k", want,
        batch_size=n_sort,
    )
    require(sorted(got) == want, "join_8k_slots: rows differ from numpy")
    require(ev["device.launches"] > 0, "join_8k_slots: no device launch")
    require(ev["join.build.dense"] == 1,
            "join_8k_slots: the dense device build did not engage")
    # the kernel engages by its stated rule (exec/pallas): TPU batches
    # and a slot table within BUILD_MAX_SLOTS — never probe-and-carry-on
    engaged = ev["join.build.pallas_runs"] == 1
    require(engaged == pallas.enabled_for(jax.devices(device)[0]),
            f"join_8k_slots: hash_build engaged={engaged}, the rule says "
            f"{not engaged}")
    out["join_8k_slots"] = {**ev, "hash_build_engaged": engaged}

    # a fact-to-fact join as `tpubench`'s q12_sf10_join measures it, at a
    # sixteenth: 2^20 build rows on TPC-H's sparse keys (the first 8 of
    # every 32: 2^22 slots, past the old 2^20 cap and the kernel's window),
    # probed by clustered keys of which a fifth dangle, filtered by a
    # range compare on a build-side string (its truth table read on
    # the device: the join's output is born there) and grouped by that
    # string, whose ids are made on the device, inside the aggregate's
    # own launches
    from datafusion_tpu.exec.batch import StringDictionary

    n_build = 1 << 20
    i = np.arange(n_build, dtype=np.int64)
    build_k = i // 8 * 32 + i % 8 + 1
    prios = StringDictionary()
    prio_codes = prios.encode(["1-URGENT", "2-HIGH", "3-MEDIUM"])[
        rng.integers(0, 3, n_build)]
    probe_k = np.sort(np.where(rng.random(n_sort) < 0.8,
                               rng.choice(build_k, n_sort),
                               rng.integers(9, 4 * n_build, n_sort) | 8))
    o_schema = Schema([Field("ok", DataType.INT64, False),
                       Field("prio", DataType.UTF8, False)])
    l_schema = Schema([Field("lk", DataType.INT64, False),
                       Field("seq", DataType.INT64, False)])
    orders = MemoryDataSource(o_schema, [make_host_batch(
        o_schema, [build_k, prio_codes], [None] * 2, [None, prios])])
    lines = MemoryDataSource(l_schema, [
        make_host_batch(l_schema, [probe_k[lo: lo + (1 << 17)],
                                   seq[lo: lo + (1 << 17)]], [None] * 2,
                        [None] * 2)
        for lo in range(0, n_sort, 1 << 17)])
    slot = np.minimum(np.searchsorted(build_k, probe_k), n_build - 1)
    hit = ((build_k[slot] == probe_k) & (seq >= 1000)
           & (np.asarray(prios.values)[prio_codes[slot]] < "3-MEDIUM"))
    tally = np.bincount(prio_codes[slot[hit]], minlength=3)
    want = [(prios.values[c], int(n)) for c, n in enumerate(tally) if n]
    got, ev = run(
        "join_sparse_4m_slots", {"lines": lines, "orders": orders},
        "SELECT prio, COUNT(1) FROM lines JOIN orders ON lines.lk = orders.ok "
        "WHERE seq >= 1000 AND prio < '3-MEDIUM' GROUP BY prio", want,
        batch_size=1 << 17,
    )
    require(sorted(got) == want, "join_sparse_4m_slots: rows differ from numpy")
    require_on_device("join_sparse_4m_slots", ev)
    require(ev["join.build.dense"] == 1 and ev["join.build.pallas_runs"] == 0,
            "join_sparse_4m_slots: not the device build by the XLA scatter")
    require(ev["join.host_probe.rows"] == 0
            and ev["join.probe.rows"] == n_sort
            and ev["device.launches.join.probe"] == n_sort >> 17,
            f"join_sparse_4m_slots: not every row probed on the device: {ev}")
    # the build's arrays other than its key: `prio`'s codes, no validity
    payload_arrays = 1
    require(ev["join.probe.gathers"]
            == ev["device.launches.join.probe"] * payload_arrays,
            "join_sparse_4m_slots: a probe launch gathers "
            f"{ev['join.probe.gathers']} build arrays in "
            f"{ev['device.launches.join.probe']} launches; the build key "
            "comes from the probe key and `prio` alone is gathered")
    # which launches read through the window, reckoned from the keys:
    # 2^17 sorted keys of this side span half the 32,768-row slot table
    # and half the payload's 8,192 rows, so none does
    windows = [0, 0]
    for lo in range(0, n_sort, 1 << 17):
        part = slice(lo, lo + (1 << 17))
        found = build_k[slot[part]] == probe_k[part]
        for w, (rows, total) in enumerate((
                ((probe_k[part] - 1) >> 7, 4 * n_build >> 7),
                (slot[part][found] >> 7, n_build >> 7))):
            windows[w] += bool(rows.max() - min(rows.min(), total - WINDOW_ROWS)
                               < WINDOW_ROWS)
    require([ev["join.probe.window.slot"], ev["join.probe.window.payload"]]
            == windows,
            f"join_sparse_4m_slots: {ev['join.probe.window.slot']} slot and "
            f"{ev['join.probe.window.payload']} payload lookups took the "
            f"window, the keys say {windows}")
    require(ev["expr.cmp_lookups"] == ev["device.launches.join.probe"],
            f"join_sparse_4m_slots: {ev['expr.cmp_lookups']} string-compare "
            "lookups handed to the device, one a probed batch expected")
    require(0 < ev["d2h.bytes"] <= 1024,
            "join_sparse_4m_slots: more than the answer came back "
            f"({ev['d2h.bytes']} B): group ids not made on the device")
    out["join_sparse_4m_slots"] = ev

    # the kernel alone against its numpy oracle at the same shape
    if engaged:
        pos = dim_k.astype(np.int32)
        live = rng.random(slots) > 0.1
        got_kernel = jax.jit(
            lambda p, l: hash_build.build_slot_table(
                p, l, slots, interpret=pallas.interpret_mode())
        )(pos, live)
        want_kernel = hash_build.build_slot_table_numpy(pos, live, slots)
        for g, w in zip(got_kernel, want_kernel):
            require(bool((np.asarray(g) == w).all()),
                    "hash_build kernel differs from build_slot_table_numpy")
        out["hash_build_kernel"] = (
            f"compiled and matched build_slot_table_numpy at "
            f"slots={slots} rows={slots}"
        )
    return out


Q3_SEED = 20260321


def stage_q3(device: str, sf, batch_size: int = 1 << 17) -> dict:
    """TPC-H Q3 over customer, orders and lineitem (`tpubench`'s data set
    at 6 M lines an SF, and its numpy oracle): the first query builds and
    pins both joins' build sides, the second finds them again; the three
    numeric group keys become groups on the device and ten rows return."""
    from datafusion_tpu.datatypes import DataType, Field, Schema
    from datafusion_tpu.exec.batch import StringDictionary, make_host_batch
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.datasource import MemoryDataSource
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.exec.rowgather import WINDOW_ROWS
    from tpubench.spec import Spec

    spec = Spec(REPO)
    ds = spec.dataset("tpch_customer_orders_lineitem")
    made = ds.generate(Q3_SEED, int(6_000_000 * sf),
                       threads=min(8, os.cpu_count() or 1))
    kinds = {"i64": DataType.INT64, "f64": DataType.FLOAT64,
             "str": DataType.UTF8}
    ctx = ExecutionContext(device=device, batch_size=batch_size,
                           result_cache=False)
    for table, cols in ds.TABLES.items():
        schema = Schema([Field(c, kinds[k], False) for c, k in cols.items()])
        arrays, dicts = [], []
        for name in cols:
            col = made["tables"][table][name]
            d = None
            if isinstance(col, tuple):
                d = StringDictionary()
                col = d.encode([col[1][c] for c in range(len(col[1]))])[col[0]]
            arrays.append(col)
            dicts.append(d)
        n = len(arrays[0])
        ctx.register_datasource(table, MemoryDataSource(schema, [
            make_host_batch(schema, [a[lo: lo + batch_size] for a in arrays],
                            None, dicts)
            for lo in range(0, n, batch_size)]))
    n_orders = len(made["tables"]["orders"]["o_orderkey"])
    params = {"segment": ds.SEGMENT, "date": ds.DATE}
    sql = spec.query("tpch_customer_orders_lineitem", "q3").format(
        **ds.bind("q3", params))
    want = made["oracle"].answer("q3", params)
    extra = ("join.build.dense", "join.build.reuse", "join.host_probe.rows",
             "device.launches.join.probe", "join.probe.gathers",
             "join.probe.window.slot", "join.probe.window.payload",
             "aggregate.device_key.groups", "aggregate.device_key.rows",
             "aggregate.key_pull.bytes", "device.launches.agg.key_ids",
             "device.launches.topk.final", "d2h.bytes",
             "h2d.resident_misses")
    out = {"groups": made["oracle"].groups}
    for label in ("q3_build", "q3_pinned"):
        before = _counts()
        got = collect(ctx.sql(sql)).to_rows()
        ev = evidence(before, _counts(), extra)
        require(len(got) == len(want),
                f"{label}: {len(got)} rows, oracle has {len(want)}")
        # the engine returns the keys, then the aggregate: in the spec's
        # column order, row by row in the spec's row order
        for i, ((k, d, p, r), w) in enumerate(zip(got, want)):
            check_rows([(k, r, d, p)], [w], f"{label} row {i}")
        require_on_device(label, ev)
        require(ev["join.host_probe.rows"] == 0
                and ev["aggregate.key_pull.bytes"] == 0,
                f"{label}: the join or its group keys left the device: {ev}")
        # lineitem is clustered by `l_orderkey` and orders stand in key
        # order: every launch of the first probe reads its slot table
        # (four slots an order) and its payload through the window,
        # where they are past the window's rows (from SF-0.5); `o_custkey`
        # is uniform over customer, so no launch of the second does
        first = ev["device.launches.join.probe"] // 2
        windows = [first if rows > WINDOW_ROWS else 0
                   for rows in (4 * n_orders >> 7, n_orders >> 7)]
        require([ev["join.probe.window.slot"],
                 ev["join.probe.window.payload"]] == windows,
                f"{label}: {ev['join.probe.window.slot']} slot and "
                f"{ev['join.probe.window.payload']} payload lookups took the "
                f"window; {windows} of the first probe's {first} launches "
                "expected, and none of the second's")
        require(ev["aggregate.device_key.groups"] == made["oracle"].groups,
                f"{label}: {ev['aggregate.device_key.groups']} groups on the "
                f"device, the oracle keeps {made['oracle'].groups}")
        out[label] = ev
    require(out["q3_build"]["join.build.dense"] == 2
            and out["q3_pinned"]["join.build.reuse"] == 2
            and out["q3_pinned"]["join.build.dense"] == 0,
            f"q3: two dense builds, then both found again, expected: {out}")
    require(out["q3_pinned"]["h2d.resident_misses"] == 0
            and 0 < out["q3_pinned"]["d2h.bytes"] <= 4096,
            f"q3: the second query moved more than its answer: {out}")
    return out


def stage_mesh(sf, want_rows, n_devices: int = 4,
               batch_size: int = 1 << 19) -> dict:
    """Q1 over lineitem registered as a table that stays, its row groups
    dealt to `n_devices` devices: a new relation a pass, and the second
    pass ships a round's row counts and the predicate's one table and
    nothing else (the predicate is in the core: one `cmp_table` lookup
    a round on every chip)."""
    from benchmarks.suite import Q1
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.io.readers import parquet_row_groups
    from datafusion_tpu.parallel.mesh import make_mesh
    from datafusion_tpu.parallel.partition import PartitionedContext

    ctx = PartitionedContext(mesh=make_mesh(n_devices), batch_size=batch_size,
                             result_cache=False)
    path = lineitem_path(sf)
    ctx.register_resident_parquet("lineitem", path)
    shards = ctx.datasources["lineitem"].partitions
    batches = [sum(1 for _ in p.batches()) for p in shards]
    # a file of fewer row groups than devices (below SF-1) leaves shards empty
    require(sum(map(bool, batches)) == min(n_devices, parquet_row_groups(path)),
            f"mesh: the row groups were dealt as {batches}")
    extra = ("h2d.resident_hits", "h2d.resident_misses", "mesh.rounds",
             "device.launches.mesh.combine", "expr.cmp_lookups")
    evs = []
    for i in range(2):
        before = _counts()
        check_rows(collect(ctx.sql(Q1)).to_rows(), want_rows,
                   f"mesh Q1 pass {i}")
        evs.append(evidence(before, _counts(), extra))
    first, ev = evs
    require(ev["device.launches"] > 0, "mesh: no device launch")
    require(ev["mesh.rounds"] == max(batches)
            and ev["device.launches.mesh.combine"] == 1,
            f"mesh: the second pass ran {ev}")
    require(first["h2d.resident_misses"] == sum(batches)
            and ev["h2d.resident_misses"] == 0
            and ev["h2d.resident_hits"] == sum(batches),
            f"mesh: the second pass placed columns again: {first} then {ev}")
    # the row counts are a put a distinct round shape (and the dead
    # rounds' zeros), the predicate's table one a query: `h2d.bytes`
    # counts neither
    require(ev["h2d.bytes"] == 0
            and ev["device.h2d.transfers"] <= max(batches) + 2
            and ev["expr.cmp_lookups"] == max(batches),
            f"mesh: the second pass shipped more than its row counts: {ev}")
    # where the column copies the queries left on the batches really
    # sit: shard s's on mesh device s, and on no other
    placed = [sorted({str(d) for b in p.batches()
                      for a in _device_copies(b) for d in a.devices()})
              for p in shards]
    want = [[str(d)] if n else []
            for d, n in zip(ctx.mesh.devices.flat, batches)]
    require(placed == want,
            f"mesh: the shards' columns sit on {placed}, the mesh is {want}")
    return {"first": first, "evidence": ev, "devices": placed}


def _device_copies(batch):
    """The column copies `device_inputs` keeps on a batch and on the
    views cached on it."""
    for key, kept in batch.cache.items():
        if hasattr(kept, "cache"):  # a projection's or a core's view
            yield from _device_copies(kept)
        elif key[0] == "device":
            yield from kept[0]


# -- driver -------------------------------------------------------------------


def _cache_entries(cache_dir) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for f in os.listdir(cache_dir) if not f.endswith("-atime"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=NORTH_STAR_SF,
                    help="lineitem scale factor (default 10, the north "
                         "star; never below 1 on the chip)")
    args = ap.parse_args(argv)
    sf = int(args.sf) if args.sf == int(args.sf) else args.sf
    t_start = time.perf_counter()

    import jax
    import jaxlib

    import datafusion_tpu  # noqa: F401 — x64 + compile-cache placement

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version string, not a dependency
        libtpu = "unknown"
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    banner = (f"device: platform={dev0.platform} "
              f"device_kind={dev0.device_kind} count={device['count']}  "
              f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
              f"libtpu={libtpu}")
    print(banner, flush=True)
    if dev0.platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{dev0.platform!r}", file=sys.stderr)
        return 1
    require(sf >= 1, f"--sf {sf}: the chip run never goes below SF-1")
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    # progress goes to stdout and, line by line, to the output directory
    # (the chip tool shows nothing until the command ends)
    os.makedirs(OUT_DIR, exist_ok=True)
    log = open(os.path.join(OUT_DIR, "chip_smoke.log"), "a")
    log.write(banner + "\n")

    def say(line: str) -> None:
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    from datafusion_tpu.native import native_available

    native = native_available()
    say(f"native={native}")
    require(native or os.environ.get("DATAFUSION_TPU_NATIVE") == "0",
            "native library did not build from native/*.cpp")

    compiles = {"seconds": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["seconds"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compiles["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    cache_dir = jax.config.jax_compilation_cache_dir
    entries_before = _cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({entries_before} entries)")
    if sf != NORTH_STAR_SF:
        say("reduced: " + json.dumps(
            {"sf": sf, "of": NORTH_STAR_SF, "why": "--sf on the command line"}
        ))

    report: dict = {"device": device, "sf": sf, "native": native,
                    "cache_dir": cache_dir}
    walls: dict = {}

    def timed(name, fn, *a, **kw):
        say(f"stage {name}: start")
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        walls[name] = round(time.perf_counter() - t0, 1)
        say(f"stage {name}: ok in {walls[name]} s")
        if isinstance(out, dict):
            shown = {k: v for k, v in out.items() if k != "rows"}
            say("  " + json.dumps(shown, default=str))
        return out

    path = timed("generate", lineitem_path, sf)
    oracle = timed("oracle", Q1Oracle, path)

    from datafusion_tpu.exec.batch import link_rate_mbps
    from datafusion_tpu.exec.context import ExecutionContext

    cold = timed("cold", stage_cold, "tpu", sf, oracle)
    report["cold"] = cold["evidence"]
    report["link_probe_mbps"] = link_rate_mbps(dev0)
    say(f"link.probe_mbps={report['link_probe_mbps']:.0f}")

    src = timed("resident", resident_lineitem, sf)
    # result cache off: every pass and every submit must reach the device
    ctx = ExecutionContext(device="tpu", batch_size=1 << 19,
                           result_cache=False)
    ctx.register_datasource("lineitem", src)
    report["warm"] = timed("warm", stage_warm, ctx, oracle)
    report["serve"] = timed("serve", stage_serve, ctx, oracle)
    report["operators"] = timed("operators", stage_operators, "tpu")
    # Q3 at SF-1 at most: `tpubench`'s q3_sf10_join3 is the SF-10 run
    report["q3"] = timed("q3", stage_q3, "tpu", min(sf, 1))
    if device["count"] >= 4:
        report["mesh"] = timed("mesh", stage_mesh, sf, cold["rows"])
    else:
        say("stage mesh: dormant (fewer than 4 TPU devices)")

    stats = dev0.memory_stats() or {}
    report.update(
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        compile_s=round(compiles["seconds"], 1),
        persistent_cache_hits=compiles["cache_hits"],
        cache_entries_before=entries_before,
        cache_entries_after=_cache_entries(cache_dir),
        stage_wall_s=walls,
        total_wall_s=round(time.perf_counter() - t_start, 1),
    )
    say(f"peak_bytes_in_use={report['peak_bytes_in_use']}  "
        f"compile_s={report['compile_s']}  "
        f"persistent_cache_hits={report['persistent_cache_hits']}  "
        f"cache entries {entries_before} -> {report['cache_entries_after']}  "
        f"total {report['total_wall_s']} s")
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
