"""Hash-join physical operator.

Build side = RIGHT input (the planner puts the dimension position
there; LEFT OUTER preserves probe rows, so the probe must be the
left input).  The build side fully materializes once into a
`JoinBuildArtifact`; probe batches stream through one of two paths:

- **dense-int device probe**: single integer key, unique on the build
  side — the build fills a direct-address slot table on device
  (`build_slot_table_xla`, one launch under
  ``device.launches.join.build``) and
  every probe batch runs ONE fused launch
  (``device.launches.join.probe``, program ``jit_join_probe``)
  computing hit mask + payload gather at probe capacity — no host
  round trip, masks carried, zero extra H2D once the artifact is
  resident.  The slot table and the payload stay on the device as
  128-wide rows and are read as a row gather + lane select
  (`exec/rowgather.py`, shared with the string compare's truth
  table), each through a window of 2,048 rows (1 MB of int32) that
  the launch picks from the batch in hand (`take_rows_window`): the
  slot table by the keys that look something up (in range, not NULL,
  selected), the payload, under one decision for all its arrays, by
  the slots that were found.  A probe side clustered by the join key
  (TPC-H's lineitem: a batch's 131,072 keys span 512 KB of the 240 MB
  slot table and its hits 128 KB of each 60 MB payload column) reads
  the windows; one that is not (Q3's `o_custkey` into customer) reads
  the whole tables as before, launch by launch, by what the keys say.
  On a v5e a probe launch over TPC-H's orders went 2.78 -> 0.73 ms
  with one payload column (Q12) and 7.54 -> 1.31 ms with three (Q3),
  and Q3's second probe 0.633 -> 0.658 ms (PERF.md section 6, PR 33).
  ``join.probe.window.slot`` / ``.payload`` count the launches whose
  lookup took the window (beside ``device.launches.join.probe``): the
  flags stay on the device through the scan and are added up and read
  once, 8 B, after its last batch.  The resident payload is the
  build's columns **other than the key**: on a hit the build row's
  key IS the probe row's key, and on a miss it is masked out (INNER)
  or NULL (LEFT OUTER), so the join's output column for the build
  key is the probe batch's own key (cast to the build column's dtype
  where the two differ) and no launch reads the key column — at
  TPC-H's 15 M-row int64 `o_orderkey` its two gathers and the split
  into u32 halves were 2.4 of 3.84 device seconds a query (PERF.md
  section 6, PR 29).
  ``join.probe.gathers`` counts, per launch, the build-side arrays
  (columns and validity arrays) the launch still gathers.
- **host probe**: everything else (multi-key, strings, duplicate
  keys, a build that does not fit).  `core.HashIndex` CSR-expands
  matches per batch; its rows count as ``join.host_probe.rows``.

- **semi probe** (`IN (subquery)`, a decorrelated `EXISTS`; type
  "semi": a probe row is kept once if its key has a match, NULL
  matches nothing): the probe hands on the left batch's own device
  columns and a mask, no build column.  Three builds, by the semi
  join's size rule below.
  A build of at most `_SEMI_DEVICE_MAX` rows on one integer key
  goes to the device as it is, its keys in an array and its padding
  and NULLs marked invalid, and every probe batch is ONE launch
  (``device.launches.join.semi``, program ``jit_semi_probe``) that
  compares each probe key with the build keys inside the batch's own
  key range, moved into one block of 128 (with every build key, a
  block at a time, where the range holds more): no gather and no
  table, so a sparse build (Q18's ~600 order keys over a 60 M-key
  range, which the dense rule below refuses) costs what its size
  does, not what its span does.  A build made on
  the device (the subquery's `HAVING` survivors, `exec/aggregate.py`)
  is probed where it lies, its live rows (below its row count, valid,
  selected) made in one launch (``device.launches.join.semi_build``):
  neither side crosses the link but the selection mask of a filter run
  on the host.  A larger build that is a scan of a table that keeps
  its batches (`reusable_batches`), filtered by the subquery's WHERE
  and cut to its key (TPC-H Q4's `l_commitdate < l_receiptdate` over
  lineitem: ~38 M of 60 M rows kept, ~14 M distinct order keys), is
  marked into a **membership table**: a byte a slot over the build
  key's span (`kmin` .. the greatest key), `[rows, LANES]` on the
  device, zeroed once a query.  The scan's batches are marked a group
  a launch (``device.launches.join.semi_mark``, program
  ``jit_semi_mark``), as the dense aggregate folds a resident table:
  collected by shape class (marking is an OR, so order does not
  matter), at most `fuse_group_max()` a group, each group padded to
  its rung by `exec/fused.pad_group` (a pad counts 0 rows and its
  step marks nothing), stacked in the program and marked batch after
  batch by a `lax.scan` whose carry is the table (TPC-H SF-10's 458
  batches of lineitem: two launches where there were 458 launch calls
  at 0.27 ms of host CPU each, PERF.md section 6).  Each step
  evaluates the subquery's predicate over its batch's resident columns
  (the predicate the pipeline would have evaluated on the host goes
  into the launch: no mask a batch is made or put) and sets the slots
  of the batch's live keys through a window of 1 MB
  where the batch's keys fit one (lineitem is clustered by
  `l_orderkey`: a batch's keys span ~128 KB of the 60 MB table), by
  tile where every block of 128 keys keeps its live ones inside 4 table
  rows (a dense tile a block, placed by one row scatter that ORs:
  `exec/rowgather.py`; every mark of Q4, 0.81 -> 0.12 ms on a v5e,
  PERF.md section 6), else by one element scatter.  The table
  and a running int32, the count of the batches marked by tile, are
  donated, so both are updated in place, step after step; the count
  is read once a query, 4 B, after the probe's last lookup is enqueued
  (``join.semi.mark.tiled``, beside ``join.semi.mark_batches``, the
  batches marked).  Each probe batch is
  then one lookup launch (``device.launches.join.semi_lookup``,
  program ``jit_semi_lookup``): a row gather and lane select of the
  table through the same 1 MB window (orders: a batch of 131,072 keys
  spans 512 KB of it).  Neither side crosses the link; the build's
  batches are iterated, never listed or compacted.  A byte, not a
  bit: marking is a scatter of True, or of tiles under `max`, right
  whatever the duplicates, where a bit table would need an OR of words
  that XLA's scatter has no combiner for; at TPC-H SF-10 the byte
  table is 60 MB of 16 GB.
  A semi build is a query's own result and is never pinned.
  Anything else (a string key, a build that is no such scan, or too
  sparse for a table) takes the host index (`HashIndex.contains`).
  Timers ``join.semi_probe`` (a probe batch, either launch),
  ``join.semi_mark`` (a build batch's preparation, and a group's
  launch); counters
  ``join.semi.build_rows`` (the compare probe's and the host's build),
  ``join.semi.probe_rows``, ``join.semi.mark_rows`` (build rows
  offered to the marks), ``join.semi.mark_bytes`` (what the marks read
  and write: the key and predicate columns over those rows, and the
  table once), ``join.semi.mark_batches`` (the batches marked, pads
  not counted: beside ``device.launches.join.semi_mark`` the batches a
  launch), ``join.semi.mark.tiled`` (the batches marked by tile) and
  ``join.semi.table_slots`` (the table's slots, once a query).

**The one size rule.**  A unique-integer-key build goes to the device
when its direct-address table is no sparser than a hash table would
be roomy — at most `_SLOTS_PER_BUILD_ROW` slots a live build row: an
open-addressing table of (8 B key, 4 B row) entries padded to 16 B,
at half load, spends 32 B a row, which is what 8 slots of 4 B cost — and the table and
the payload (what is placed: not the key column) fit what the device
ledger says is free less what the
probe side has yet to upload (`LEDGER.fits`: capacity less every live
ledger buffer; admission keeps no further reserve, so neither does
this).  It is pinned under the same test.  A dimension table and a
15 M-row fact side are the same case.  A build that stays on the host
(strings, duplicate keys, too sparse) holds no HBM: it is pinned when
its host arrays fit the host's free memory.  One that only found no
room is not pinned, so the next query asks the ledger again.

**The semi join's size rule.**  A build of at most `_SEMI_DEVICE_MAX`
rows takes the compare probe: a probe key is compared with the build
keys of its batch's range, and the build costs what its rows do, not
what its span does (Q18's ~620 order keys over a 60 M-key span: 5 KB,
where a table would be 60 MB).  Past that, where the build is a scan
that can be marked (above), it takes the membership table when the
table is no sparser than the same 32 B a build row that the dense rule
allows — at most `_SEMI_SLOTS_PER_ROW` one-byte slots a row offered to
the marks — and it fits the ledger less what either side has yet to
upload.  The compare probe would cost every probe row a pass over the
build a block of 128 keys at a time (Q4: 14 M keys, a hundred thousand
blocks), and the host index would pull the build over the link each
query (Q4: 300 MB of keys); the table costs a byte a slot, zeroed once
and written at the build's keys.  What the rule refuses takes the host
index.

Artifacts pin in the device ledger under the build subtree's query
fingerprint (``join:<fp>``): a warm query probing the same build side
reuses the resident build — zero H2D for the build side — and a
catalog/data version bump changes the fingerprint, so stale builds are
never probed.  Pin residency charges probing clients by use count
(obs/attribution.py), same as pinned scan tables.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import numpy as np

from datafusion_tpu.datatypes import Schema
from datafusion_tpu.exec.batch import (
    RecordBatch,
    device_inputs,
    device_pull,
    make_host_batch,
    put_compressed,
    subset_view,
)
from datafusion_tpu.exec.relation import Relation
from datafusion_tpu.exec.rowgather import (
    LANES,
    WINDOW_ROWS,
    pad_rows,
    set_rows_window,
    take_rows_window,
)
from datafusion_tpu.join import core as _core
from datafusion_tpu.obs.device import LEDGER, host_fits
from datafusion_tpu.utils.metrics import METRICS
from datafusion_tpu.utils.retry import device_call


# slot positions and build-row indices travel and gather as int32
_MAX_SLOTS = (1 << 31) - 1
# at most this many slots a live build row (module doc)
_SLOTS_PER_BUILD_ROW = 8
# the most rows a semi join's build may hold to take the compare probe;
# each launch compares a probe key with each of them (module doc)
_SEMI_DEVICE_MAX = 4096
# build keys one step of the semi probe compares against at once
_SEMI_BLOCK = 128
# the most one-byte slots of a membership table a build row offered to
# the marks may cost: the dense rule's 32 B a row (module doc)
_SEMI_SLOTS_PER_ROW = 32
# rows of the window the membership table is marked and read through:
# 1 MB of bytes, as `WINDOW_ROWS` rows of int32 are
_SEMI_WINDOW_ROWS = 4 * WINDOW_ROWS


def _device_path_enabled() -> bool:
    return os.environ.get("DATAFUSION_TPU_JOIN_DEVICE", "1") != "0"


def _is_utf8_field(field) -> bool:
    return field.data_type.name == "Utf8"


class JoinBuildArtifact:
    """The materialized build side: compacted host columns + the
    `HashIndex`, plus — on the dense path — the device-resident slot
    table and payload the fused probe launches gather from.  The
    payload (`dev_cols`, `dev_valids`, in column order) leaves the key
    column out: a hit's build key is its probe key, so the probe hands
    it on from its own batch (module doc).  `cols` and `index` stay
    whole: the host probe of the same artifact reads them."""

    __slots__ = ("cols", "valids", "dicts", "n_rows", "index", "dense",
                 "kmin", "num_slots", "device", "dev_kmin", "dev_num_slots",
                 "dev_slot_row", "dev_cols", "dev_valids", "nbytes", "keep",
                 "fingerprint")

    def __init__(self):
        self.dense = False
        self.dev_slot_row = None
        self.fingerprint = None


def _int64_words(col: np.ndarray) -> Optional[tuple]:
    """An int64 payload column as the 32-bit words the device keeps of
    it, or None for any other type: the value itself as int32 where
    every value fits (a date as a day number, a key of a smaller
    table), else (low uint32, high int32).  A row gather of a 64-bit
    table splits the whole table into halves in every launch (the key
    column's cost in PERF.md section 6, PR 29); words are gathered as
    q12's string codes are and put together after (`_take_column`)."""
    if col.dtype != np.int64:
        return None
    i32 = np.iinfo(np.int32)
    if len(col) == 0 or (i32.min <= col.min() and col.max() <= i32.max):
        return (col.astype(np.int32),)
    return (col.astype(np.uint32), (col >> 32).astype(np.int32))


def _int64_of(words):
    """A gathered payload column: an int64 column (a tuple:
    `_int64_words`) put together from its gathered words."""
    import jax.numpy as jnp

    if not isinstance(words, tuple):
        return words
    low = words[0].astype(jnp.int64)
    if len(words) == 1:
        return low
    return (words[1].astype(jnp.int64) << 32) | low


@functools.lru_cache(maxsize=None)
def _probe_fn_for(join_type: str, build_key_dtype: str):
    """One fused probe launch: slot lookup, hit mask, payload gather,
    validity, selection-mask combine — all inside a single jit whose
    program (`jit_join_probe`) is specialised by shapes alone: `kmin`
    and the slot count are arguments, so every dense artifact of one
    shape class shares compiled probes.  The slot table and the
    payload (`pcols`, `pvalids`: the build's columns other than its
    key) are `[rows, LANES]` and are read twice through
    `take_rows_window`: the slot table by the keys that are in range,
    not NULL and selected, and every payload array by the slots found,
    under one decision.

    Returns `(kcol, kval, gath, gval, out_mask, windows)`: `kcol` /
    `kval` are the join's output column for the build key, made from
    the probe key — `kcol` None where the probe key already has
    `build_key_dtype` (the caller hands on the probe's own array),
    else the cast; at miss rows its values are not observable.
    `windows` is int32[2]: whether the slot lookup and whether the
    payload gather took the window."""
    import jax
    import jax.numpy as jnp

    key_dtype = jnp.dtype(build_key_dtype)

    def join_probe(key, kvalid, mask, kmin, num_slots, slot_row, pcols,
                   pvalids):
        # range check in int64 BEFORE the int32 cast: a far-out-of-range
        # probe key must not wrap into a valid slot
        d = key.astype(jnp.int64) - kmin
        live = (d >= 0) & (d < num_slots)
        if kvalid is not None:
            live = live & kvalid
        # a row the selection has dropped (a ragged tail's padding)
        # looks nothing up: it must not stretch the batch's span
        if mask is not None:
            live = live & mask
        found, slot_window = take_rows_window(
            slot_row, d.astype(jnp.int32), live)
        hit = live & (found >= 0)
        inner = join_type == "inner"
        kcol = None if key.dtype == key_dtype else key.astype(key_dtype)
        # only live build rows are in the slot table: a hit's build key
        # is not NULL, whatever the build column's validity array says
        kval = None if inner else hit
        # (a validity array that is None is no table: it stays None)
        (gath, valid), payload_window = take_rows_window(
            (pcols, pvalids), found, hit)
        gath = tuple(_int64_of(c) for c in gath)
        # an INNER join masks its misses out: a build column without
        # NULLs stays without a validity array
        gval = tuple(
            (None if inner else hit) if v is None else hit & v
            for v in valid)
        # `hit` holds the selection mask already
        out_mask = hit if inner else mask
        windows = jnp.stack([slot_window, payload_window]).astype(jnp.int32)
        return kcol, kval, gath, gval, out_mask, windows

    return jax.jit(join_probe)


@functools.lru_cache(maxsize=None)
def _semi_probe_fn():
    """One semi probe launch (`jit_semi_probe`): whether each probe key
    equals a valid build key, AND the probe key's validity and the
    batch's mask.  No [rows, build] array is ever made: the build keys
    that fall within the batch's own key range (a clustered probe side,
    TPC-H's lineitem: a few of a thousand) are moved into one block of
    `_SEMI_BLOCK` by a one-hot select over the build, and every probe
    key is compared with that block; a batch whose range holds more
    compares with the whole build, a block at a time."""
    import jax
    import jax.numpy as jnp

    def compare(key, bkeys, bvalid):
        eq = (key[:, None] == bkeys[None, :]) & bvalid[None, :]
        return jnp.any(eq, axis=1)

    def semi_probe(key, kvalid, mask, bkeys, bvalid):
        key = key.astype(jnp.int64)
        bkeys = bkeys.astype(jnp.int64)
        pad = -bkeys.shape[0] % _SEMI_BLOCK
        if pad:
            bkeys = jnp.pad(bkeys, (0, pad))
            bvalid = jnp.pad(bvalid, (0, pad))
        live = jnp.ones(key.shape[0], bool) if kvalid is None else kvalid
        if mask is not None:
            live = live & mask
        info = jnp.iinfo(jnp.int64)
        lo = jnp.min(jnp.where(live, key, info.max))
        hi = jnp.max(jnp.where(live, key, info.min))
        near = bvalid & (bkeys >= lo) & (bkeys <= hi)
        slot = jnp.cumsum(near.astype(jnp.int32)) - 1
        block = jnp.arange(_SEMI_BLOCK, dtype=jnp.int32)
        pick = (slot[None, :] == block[:, None]) & near[None, :]
        # the window's keys as two 32-bit words, each the one picked or
        # nothing (a 64-bit select-and-sum here crashes XLA:TPU's
        # compiler; AOT for a v5e in the sandbox)
        least = jnp.iinfo(jnp.int32).min
        high, low = ((bkeys >> 32).astype(jnp.int32),
                     bkeys.astype(jnp.uint32).astype(jnp.int32))
        high, low = (jnp.max(jnp.where(pick, w[None, :], least), axis=1)
                     for w in (high, low))
        window = (high.astype(jnp.int64) << 32) | low.astype(
            jnp.uint32).astype(jnp.int64)
        n_near = jnp.sum(near, dtype=jnp.int32)

        def in_window(_):
            return compare(key, window, block < n_near)

        def whole(_):
            def step(j, hit):
                at = (j * _SEMI_BLOCK,)
                return hit | compare(
                    key, jax.lax.dynamic_slice(bkeys, at, (_SEMI_BLOCK,)),
                    jax.lax.dynamic_slice(bvalid, at, (_SEMI_BLOCK,)))

            return jax.lax.fori_loop(0, bkeys.shape[0] // _SEMI_BLOCK, step,
                                     jnp.zeros(key.shape[0], bool))

        hit = jax.lax.cond(n_near <= _SEMI_BLOCK, in_window, whole, None)
        return hit & live

    return jax.jit(semi_probe)


@functools.lru_cache(maxsize=None)
def _semi_mark_fn(core, key_at: int, pred_at: tuple):
    """One mark launch (`jit_semi_mark`) over a group of build batches
    (`exec/fused.pad_group`: entries `(data, validity, mask)` of one
    shape class, and their row counts as one int32 array, 0 for a pad):
    the membership table with the slots of every batch's live keys set,
    and the count of the batches that marked by tile (`set_rows_window`)
    with the group's added.  The entries are stacked in the program and
    marked one after another by a `lax.scan` that carries the table and
    the count; a pad's step marks nothing and counts nothing.  A key is
    live below its batch's row count, selected by its mask, valid,
    inside the table and, where `core` (a `_PipelineCore` of the
    subquery's predicate, no projection) is given, where the predicate
    holds (NULL drops the row, as a WHERE does).  An entry holds the key
    at `key_at` and the predicate's columns at `pred_at`, in
    `core.used_cols` order.  The table and the count are donated: both
    are updated in place, step after step."""
    import jax
    import jax.numpy as jnp

    from datafusion_tpu.exec.fused import stack_entries

    def mark(carry, entry, num_rows, kmin, num_slots, aux, params):
        table, tiled = carry
        data, validity, mask = entry
        key, kvalid = data[key_at], validity[key_at]
        if core is None:
            live = jnp.arange(key.shape[0], dtype=jnp.int32) < num_rows
            if mask is not None:
                live = live & mask
        else:
            live = core._kernel([data[i] for i in pred_at],
                                [validity[i] for i in pred_at], aux,
                                num_rows, mask, params)[2]
        if kvalid is not None:
            live = live & kvalid
        d = key.astype(jnp.int64) - kmin
        live = live & (d >= 0) & (d < num_slots)
        table, by_tile = set_rows_window(table, d.astype(jnp.int32), live,
                                         _SEMI_WINDOW_ROWS)
        return table, tiled + by_tile.astype(jnp.int32)

    def semi_mark(table, tiled, kmin, num_slots, entries, counts, aux,
                  params):
        def step(carry, x):
            entry, num_rows = x
            entry = jax.tree.map(lambda a: a.reshape(-1), entry)
            return jax.lax.cond(
                num_rows > 0,
                lambda c: mark(c, entry, num_rows, kmin, num_slots, aux,
                               params),
                lambda c: c, carry), None

        # each array stacked `[n, capacity]`, then laid out as rows of
        # `LANES`: a step's slice of the stack is then whole (8, 128)
        # tiles of the TPU's layout, where one row of `[n, capacity]` is
        # 512 B of each of capacity / 128 tiles, read apart (a batch
        # 0.189 -> 0.163 ms inside a group of 256 on a v5e, PERF.md
        # section 6).  The barrier keeps the stack one concatenate a
        # leaf: stacked straight into rows, XLA writes it by one update
        # an entry and leaf, 1,536 programs at rung 256, and a set-up
        # that finds them in the compile cache took ~8 s longer
        stacked = jax.tree.map(
            lambda a: a.reshape(a.shape[0], -1, LANES)
            if a.shape[1] % LANES == 0 else a,
            jax.lax.optimization_barrier(stack_entries(entries)))
        carry, _ = jax.lax.scan(step, (table, tiled), (stacked, counts))
        return carry

    return jax.jit(semi_mark, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _semi_lookup_fn():
    """One lookup launch (`jit_semi_lookup`): whether each probe key's
    slot of the membership table is set, AND the key's validity and
    the batch's mask; the table is read through a window of
    `_SEMI_WINDOW_ROWS` rows where the batch's keys fit one."""
    import jax
    import jax.numpy as jnp

    def semi_lookup(key, kvalid, mask, table, kmin, num_slots):
        d = key.astype(jnp.int64) - kmin
        live = (d >= 0) & (d < num_slots)
        for m in (kvalid, mask):
            if m is not None:
                live = live & m
        found, _ = take_rows_window(table, d.astype(jnp.int32), live,
                                    _SEMI_WINDOW_ROWS)
        return found & live

    return jax.jit(semi_lookup)


@functools.lru_cache(maxsize=None)
def _semi_table_fn(rows: int):
    """An empty membership table of `rows` rows of `LANES` bytes, and a
    zero int32: the count of the marks that go by tile."""
    import jax
    import jax.numpy as jnp

    def semi_table():
        return jnp.zeros((rows, LANES), jnp.bool_), jnp.zeros((), jnp.int32)

    return jax.jit(semi_table)


@functools.lru_cache(maxsize=None)
def _live_rows_fn():
    """A column's live rows as one bool array: below `num_rows`, valid
    (None: all) and selected by the mask (None: all)."""
    import jax
    import jax.numpy as jnp

    def live(col, num_rows, valid, mask):
        out = jnp.arange(col.shape[0]) < num_rows
        for m in (valid, mask):
            if m is not None:
                out = out & m
        return out

    return jax.jit(live)


@functools.lru_cache(maxsize=None)
def _window_counts_fn():
    """The launches of one scan whose lookups took the window: the sum
    of the probe launches' `windows`, as int32[2]."""
    import jax
    import jax.numpy as jnp

    def window_counts(windows):
        return jnp.sum(jnp.stack(windows), axis=0, dtype=jnp.int32)

    return jax.jit(window_counts)


def _device_scalar(x, device):
    """`x` placed once, for launches that take it as an argument: a
    numpy scalar handed to a jitted call is a transfer of its own in
    every launch."""
    if device is not None:
        return LEDGER.put(x, device, owner="join.build")
    import jax.numpy as jnp

    return LEDGER.adopt(jnp.asarray(x), owner="join.build")


def _unplaced_bytes(rel: Relation, device) -> int:
    """Host bytes of `rel`'s resident tables (sources that hand out the
    same batches to every scan) not yet copied to `device`: what the
    probe about to start will add to the ledger (`device_inputs` keeps
    those copies on the batch), and so what a build placed before it
    must leave room for.  A streamed source's copies die batch by
    batch and count nothing."""
    slot = ("device", None if device is None else repr(device))
    total = 0
    stack = [rel]
    while stack:
        node = stack.pop()
        ds = getattr(node, "datasource", None)
        if ds is None:
            stack.extend(node.op_children())
        elif getattr(ds, "reusable_batches", False):
            for b in ds.batches():
                if slot not in b.cache:
                    total += sum(
                        a.nbytes for a in (*b.data, *b.validity, b.mask)
                        if isinstance(a, np.ndarray))
    return total


class HashJoinRelation(Relation):
    """INNER / LEFT OUTER equi-join of two child relations."""

    def __init__(self, left: Relation, right: Relation, on, join_type: str,
                 schema: Schema, device=None,
                 build_key: Optional[str] = None):
        self.left = left
        self.right = right
        self.on = [(int(l), int(r)) for l, r in on]
        self.join_type = join_type
        self._schema = schema
        self.device = device
        self.build_key = build_key
        self.children = [left, right]
        self._artifact: Optional[JoinBuildArtifact] = None

    @property
    def device_batches(self) -> bool:
        """Whether this join may hand out batches born on the device
        (the dense probe's output): a consumer then keeps its own work
        there too instead of reading their columns on the host.  Said
        from the key's shape alone (one integer key, no Utf8 on either
        side), before any build: a build that turns out not to qualify
        (`_dense_slots`) yields host batches, which every consumer
        takes as well."""
        if not _device_path_enabled() or len(self.on) != 1:
            return False
        li, ri = self.on[0]
        return all(
            f.data_type.np_dtype.kind in "iu" and not _is_utf8_field(f)
            for f in (self.left.schema.field(li), self.right.schema.field(ri))
        )

    @property
    def schema(self) -> Schema:
        return self._schema

    def op_label(self) -> str:
        on = ", ".join(f"#{l}=#{r}" for l, r in self.on)
        return f"HashJoin[{self.join_type}, on={on}]"

    # -- build ---------------------------------------------------------
    def _build_artifact(self) -> JoinBuildArtifact:
        if self._artifact is not None:
            return self._artifact
        from datafusion_tpu.obs.attribution import (
            current_client,
            note_pin_use,
            register_pin_client,
        )

        fp = self.build_key
        if fp is not None:
            art = LEDGER.pinned(fp)
            if art is not None:
                METRICS.add("join.build.reuse")
                cid = current_client()
                if cid is not None:
                    note_pin_use(fp, cid)
                self._artifact = art
                return art
        art = self._materialize_build()
        art.fingerprint = fp
        if fp is not None and art.keep:
            from datafusion_tpu.obs.attribution import forget_pin

            # the pin's bytes are HBM's: a host index holds none
            LEDGER.pin(fp, art.nbytes if art.dense else 0, owner="join.build",
                       on_evict=lambda: forget_pin(fp), artifact=art)
            cid = current_client()
            if cid is not None:
                register_pin_client(fp, cid)
                note_pin_use(fp, cid)
        self._artifact = art
        return art

    def _materialize_build(self) -> JoinBuildArtifact:
        from datafusion_tpu.exec.materialize import collect_columns

        with METRICS.timer("join.build"):
            cols, valids, dicts, n = collect_columns(self.right)
            art = JoinBuildArtifact()
            art.cols, art.valids, art.dicts, art.n_rows = cols, valids, dicts, n
            art.device = self.device
            r_keys = [k for _, k in self.on]
            art.index = _core.HashIndex(
                [cols[k] for k in r_keys],
                [valids[k] for k in r_keys],
                [dicts[k] for k in r_keys],
            )
            art.nbytes = sum(int(c.nbytes) for c in cols) + sum(
                int(v.nbytes) for v in valids if v is not None
            )
            METRICS.add("join.build.rows", n)
            # the one size rule (module doc)
            slots = self._dense_slots(art)
            if slots is not None:
                payload = self._payload(art)
                if LEDGER.fits(
                        self._placed_bytes(payload, slots[1])
                        + _unplaced_bytes(self.left, self.device)):
                    self._build_dense(art, *slots, payload)
            # a dense candidate that found no room is not kept: the
            # next query asks the ledger again
            art.keep = (art.dense if slots is not None
                        else host_fits(art.nbytes))
            METRICS.add("join.build.bytes", art.nbytes)
        # single-table build sides (the plan->operator boundary fills
        # `_cost_obs`) teach the cost store the dimension's size — the
        # evidence the build-side/order rewrites plan from next time
        obs = getattr(self, "_cost_obs", None)
        if obs is not None:
            from datafusion_tpu import cost as _cost

            _cost.store().observe(obs[0], obs[1], rows=n, nbytes=art.nbytes)
        return art

    def _dense_slots(self, art: JoinBuildArtifact) -> Optional[tuple]:
        """(kmin, num_slots) of the direct-address table when the key
        shape allows the device probe — one integer key, unique among
        live build rows and at most `_SLOTS_PER_BUILD_ROW` slots a row
        apart — else None."""
        if not self.device_batches:
            return None
        ri = self.on[0][1]
        bkey = art.cols[ri]
        # dictionary-coded (Utf8) keys LOOK integral but their codes
        # are per-dictionary — direct-address matching would compare
        # codes, not content; only the host index joins strings
        if bkey.dtype.kind not in "iu" or art.dicts[ri] is not None:
            return None
        if not art.index.unique_keys:
            return None
        valid = art.valids[ri]
        live = bkey if valid is None else bkey[valid]
        if len(live) == 0:
            # empty/all-NULL build: the fused probe gathers payload rows
            # by slot, which needs at least one build row to address;
            # the host index gives "nothing matches" for free instead
            return None
        kmin = int(live.min())
        num_slots = int(live.max()) - kmin + 1
        if num_slots > min(_MAX_SLOTS, _SLOTS_PER_BUILD_ROW * len(live)):
            return None
        return kmin, num_slots

    def _payload(self, art: JoinBuildArtifact) -> tuple:
        """(columns, validity arrays) a dense build of `art` places:
        every column but the key (module doc), an int64 column as the
        tuple of its words (`_int64_words`), no validity as None."""
        ri = self.on[0][1]
        cols = art.cols[:ri] + art.cols[ri + 1:]
        return (tuple(_int64_words(c) or c for c in cols),
                tuple(art.valids[:ri] + art.valids[ri + 1:]))

    @staticmethod
    def _placed_bytes(payload: tuple, num_slots: int) -> int:
        """HBM a dense build holds: the slot table and its payload."""
        import jax

        return pad_rows(num_slots) * 4 + sum(
            int(a.nbytes) for a in jax.tree.leaves(payload))

    def _build_dense(self, art: JoinBuildArtifact, kmin: int,
                     num_slots: int, payload: tuple) -> None:
        """Fill the device-resident slot table and payload columns."""
        ri = self.on[0][1]
        bkey, valid = art.cols[ri], art.valids[ri]
        live = np.ones(art.n_rows, bool) if valid is None else valid
        # a NULL key's value is arbitrary: keep its position on the table
        pos = np.clip(bkey.astype(np.int64) - kmin, 0, num_slots - 1).astype(
            np.int32)
        art.dense = True
        art.kmin, art.num_slots = kmin, num_slots
        # from here on the artifact's bytes are what it holds in HBM
        art.nbytes = self._placed_bytes(payload, num_slots)

        # device residency: slot inputs + payload columns (every column
        # but the key) travel the compressed wire once, at build time;
        # warm probes reuse them.  Payload and slot table are padded to
        # whole `LANES`-wide rows (`take_rows`); no slot and no hit
        # points into the padding
        import jax

        pad = pad_rows(art.n_rows) - art.n_rows
        arrays, layout = jax.tree.flatten(payload)
        dev_pos, dev_live, *placed = put_compressed(
            [pos, live] + [np.pad(a, (0, pad)) for a in arrays],
            self.device, owner="join.build",
        )
        payload = jax.tree.unflatten(layout, placed)

        # one launch fills the slot table and lays it and the payload
        # out as rows; these outputs are what stays resident (the
        # flat uploads go with this frame)
        art.dev_slot_row, (art.dev_cols, art.dev_valids) = LEDGER.adopt(
            device_call(
                _build_jit(pad_rows(num_slots)),
                dev_pos, dev_live, payload, _tag="join.build",
            ), owner="join.build", device=self.device)
        # the probe's two scalars, placed once
        art.dev_kmin, art.dev_num_slots = (
            _device_scalar(np.int64(x), self.device)
            for x in (kmin, num_slots))
        METRICS.add("join.build.dense")

    # -- probe ---------------------------------------------------------
    def batches(self):
        from datafusion_tpu.obs.stats import iter_stats

        if self.join_type == "semi":
            return iter_stats(self, self._semi_batches())
        art = self._build_artifact()
        # a pinned dense artifact is only probeable by an integer key
        # (the fused probe does integer slot arithmetic); any other
        # probe dtype takes the host index, which every artifact has
        dense = (
            art.dense
            and self.left.schema.field(self.on[0][0]).data_type
            .np_dtype.kind in "iu"
            and not _is_utf8_field(self.left.schema.field(self.on[0][0]))
        )
        it = (
            self._dense_batches(art) if dense
            else self._host_batches(art)
        )
        return iter_stats(self, it)

    def _dense_batches(self, art: JoinBuildArtifact):
        li, ri = self.on[0]
        probe_fn = _probe_fn_for(self.join_type, art.cols[ri].dtype.name)
        # what a launch gathers from: the build's arrays other than its
        # key's, which the launch makes from the probe key (module doc)
        gathers = len(art.dev_cols) + sum(
            v is not None for v in art.dev_valids)
        # each launch's two window flags stay on the device until the
        # scan ends: one sum and one 8 B pull a scan, none in the loop
        # (the sum belongs to the pull, as the pull seam's own packing
        # does: it is no launch of a query's operators)
        windows = []
        for batch in self.left.batches():
            with METRICS.timer("join.probe"):
                data, validity, mask = device_inputs(batch, self.device)
                kcol, kval, gath, gval, out_mask, window = device_call(
                    probe_fn,
                    data[li], validity[li], mask, art.dev_kmin,
                    art.dev_num_slots, art.dev_slot_row, art.dev_cols,
                    art.dev_valids,
                    _tag="join.probe",
                )
            windows.append(window)
            METRICS.add("join.probe.rows", batch.num_rows)
            METRICS.add("join.probe.gathers", gathers)
            gath, gval = list(gath), list(gval)
            gath.insert(ri, data[li] if kcol is None else kcol)
            gval.insert(ri, kval)
            yield RecordBatch(
                self._schema,
                list(data) + gath,
                list(validity) + gval,
                list(batch.dicts) + list(art.dicts),
                num_rows=batch.num_rows,
                mask=out_mask,
            )
        if windows:
            slot, payload = device_pull(_window_counts_fn()(tuple(windows)))
            METRICS.add("join.probe.window.slot", int(slot))
            METRICS.add("join.probe.window.payload", int(payload))

    def _host_batches(self, art: JoinBuildArtifact):
        from datafusion_tpu.exec.materialize import (
            compact_batch,
            iter_with_mask_prefetch,
        )

        l_keys = [k for k, _ in self.on]
        for batch in iter_with_mask_prefetch(self.left.batches()):
            with METRICS.timer("join.host_probe"):
                cols, valids, dicts, n = compact_batch(batch)
                METRICS.add("join.host_probe.rows", n)
                if n == 0:
                    continue
                lidx, ridx = art.index.probe(
                    [cols[k] for k in l_keys],
                    [valids[k] for k in l_keys],
                    [dicts[k] for k in l_keys],
                    self.join_type,
                )
                if len(lidx) == 0:
                    continue
                out_cols, out_valids = _core.gather_joined(
                    cols, valids, art.cols, art.valids, lidx, ridx,
                    self.join_type,
                )
                out = make_host_batch(
                    self._schema, out_cols, out_valids,
                    list(dicts) + list(art.dicts),
                )
            yield out


    # -- semi join -----------------------------------------------------
    def _semi_build(self):
        """(device build or None, host `HashIndex` or None) of a semi
        join: the right's one key column as the device probe takes it
        (module doc), else the host index."""
        from datafusion_tpu.exec.materialize import compact_batch

        ri = self.on[0][1]
        with METRICS.timer("join.build"):
            batches = list(self.right.batches())
            rows = sum(b.num_rows for b in batches)
            METRICS.add("join.semi.build_rows", rows)
            if self.device_batches and rows <= _SEMI_DEVICE_MAX:
                b = batches[0] if len(batches) == 1 else None
                if b is not None and not isinstance(b.data[ri], np.ndarray):
                    # born on the device: probed where it lies, a key
                    # live where it is valid, selected and not padding
                    # (a keyed aggregate's padding reads as valid key 0)
                    data, validity, mask = device_inputs(
                        subset_view(b, [ri], tag="semi_build"), self.device)
                    return (data[0], device_call(
                        _live_rows_fn(), data[0], np.int32(b.num_rows),
                        validity[0], mask, _tag="join.semi_build")), None
                keys, valid = [], []
                for b in batches:
                    cols, valids, _, n = compact_batch(b)
                    keys.append(np.asarray(cols[ri], np.int64))
                    valid.append(np.ones(n, bool) if valids[ri] is None
                                 else np.asarray(valids[ri]))
                n = sum(len(k) for k in keys)
                size = max(_SEMI_BLOCK, 1 << max(n - 1, 0).bit_length())
                hk = np.zeros(size, np.int64)
                hv = np.zeros(size, bool)
                if n:
                    hk[:n] = np.concatenate(keys)
                    hv[:n] = np.concatenate(valid)
                return tuple(put_compressed([hk, hv], self.device,
                                            owner="join.build")), None
            cols, valids, dicts, _ = _concat_compacted(
                [compact_batch(b) for b in batches], self.right.schema)
            return None, _core.HashIndex([cols[ri]], [valids[ri]],
                                         [dicts[ri]])

    def _mark_source(self) -> Optional[tuple]:
        """(scan relation, key column of the scan, the pipeline over it or
        None) where the build can be marked into a membership table
        (module doc): one integer key, and the build a scan of a table
        that keeps its batches, under at most a pipeline that filters it
        by a predicate the device can evaluate and cuts it to columns.
        None otherwise."""
        from datafusion_tpu.exec.hostfn import contains_host_fn
        from datafusion_tpu.exec.relation import PipelineRelation
        from datafusion_tpu.plan.expr import Column

        if not self.device_batches:
            return None
        rel, key, pipe = self.right, self.on[0][1], None
        if isinstance(rel, PipelineRelation):
            if rel.projections is not None:
                if not isinstance(rel.projections[key], Column):
                    return None
                key = rel.projections[key].index
            if rel.predicate is not None and contains_host_fn(
                    rel.predicate, rel._metas):
                return None
            pipe, rel = rel, rel.child
        if not getattr(getattr(rel, "datasource", None), "reusable_batches",
                       False):
            return None
        return rel, key, pipe

    def _semi_mark(self, scan, key: int, pipe) -> Optional[tuple]:
        """((table, kmin, slot count), tiled) of the membership table
        marked from `scan`'s batches by the predicate of `pipe` (None:
        every row), all device arrays, `tiled` the int32 count of the
        batches that were marked by tile; None where the size rule (module
        doc) gives the build another probe.  The batches are marked a
        group a launch: collected by shape class (marking is an OR, so
        their order does not matter), a group launched when it holds
        `fuse_group_max()` of them and the rest after the scan's last."""
        from datafusion_tpu.exec.aggregate import _key_bounds
        from datafusion_tpu.exec.expression import compute_aux_values
        from datafusion_tpu.exec.fused import (
            entry_signature,
            fuse_group_max,
            pad_group,
            shared_signature,
        )
        from datafusion_tpu.exec.kernels import parameterize_exprs
        from datafusion_tpu.exec.relation import _PipelineCore, device_scope

        with METRICS.timer("join.build"):
            rows, bounds = 0, []
            for b in scan.datasource.batches():
                rows += b.num_rows
                kb = _key_bounds(b, key)
                if kb is not None:
                    bounds.append(kb)
            if rows <= _SEMI_DEVICE_MAX:
                return None
            kmin = min((lo for lo, _ in bounds), default=0)
            num_slots = max((hi for _, hi in bounds), default=kmin) - kmin + 1
            if num_slots > min(_MAX_SLOTS, _SEMI_SLOTS_PER_ROW * rows):
                return None
            table_rows = pad_rows(num_slots) // LANES
            if not LEDGER.fits(table_rows * LANES
                               + _unplaced_bytes(self, self.device)):
                return None
            core = params = None
            if pipe is not None and pipe.predicate is not None:
                core = _PipelineCore.build(scan.schema, pipe.predicate, None,
                                           pipe.functions, pipe._metas)
                params = parameterize_exprs(_PipelineCore.param_exprs(
                    pipe.predicate, None, pipe._metas))[2]
            used = [] if core is None else core.used_cols
            cols = sorted({key, *used})
            mark_fn = _semi_mark_fn(core, cols.index(key),
                                    tuple(cols.index(c) for c in used))
            with device_scope(self.device):
                table, tiled = LEDGER.adopt(
                    device_call(_semi_table_fn(table_rows),
                                _tag="join.semi_table"),
                    owner="join.semi", cached=False)
            kmin_dev, slots_dev = (_device_scalar(np.int64(x), self.device)
                                   for x in (kmin, num_slots))
        METRICS.add("join.semi.table_slots", num_slots)

        def launch(entries, counts, aux):
            nonlocal table, tiled
            group, counts = pad_group(entries, counts)
            table, tiled = device_call(
                mark_fn, table, tiled, kmin_dev, slots_dev, group, counts,
                aux, params, _tag="join.semi_mark")
            METRICS.add("join.semi.mark_batches", len(entries))

        fuse = fuse_group_max()
        aux_cache: dict = {}
        # shape class -> (entries, row counts, aux) not yet launched
        classes: dict = {}
        for batch in scan.batches():
            with METRICS.timer("join.semi_mark"):
                view = subset_view(batch, cols)
                entry = device_inputs(
                    view, self.device, None if core is None else core.wire_hints)
                aux = () if core is None else tuple(
                    compute_aux_values(core.aux_specs, batch, aux_cache))
                sig = (entry_signature(entry), shared_signature(aux))
                entries, counts, _ = classes.setdefault(sig, ([], [], aux))
                entries.append(entry)
                counts.append(batch.num_rows)
                if len(entries) >= fuse:
                    launch(*classes.pop(sig))
            METRICS.add("join.semi.mark_rows", batch.num_rows)
            METRICS.add("join.semi.mark_bytes", batch.num_rows * sum(
                a.dtype.itemsize for a in (*view.data, *view.validity)
                if a is not None))
        with METRICS.timer("join.semi_mark"):
            for group in classes.values():
                launch(*group)
        METRICS.add("join.semi.mark_bytes", table_rows * LANES)
        return (LEDGER.adopt(table, owner="join.semi", cached=False),
                kmin_dev, slots_dev), tiled

    def _semi_batches(self):
        from datafusion_tpu.exec.materialize import (
            compact_batch,
            iter_with_mask_prefetch,
        )

        li = self.on[0][0]
        source = self._mark_source()
        marked = self._semi_mark(*source) if source is not None else None
        if marked is not None:
            build, tiled = marked
            probe_fn, tag = _semi_lookup_fn(), "join.semi_lookup"
        else:
            build, index = self._semi_build()
            probe_fn, tag = _semi_probe_fn(), "join.semi"
        if build is not None:
            for batch in self.left.batches():
                with METRICS.timer("join.semi_probe"):
                    data, validity, mask = device_inputs(batch, self.device)
                    hit = device_call(probe_fn, data[li], validity[li], mask,
                                      *build, _tag=tag)
                METRICS.add("join.semi.probe_rows", batch.num_rows)
                yield RecordBatch(self._schema, list(data), list(validity),
                                  list(batch.dicts), num_rows=batch.num_rows,
                                  mask=hit)
            if marked is not None:
                # the marks' count stays on the device until the last
                # lookup is enqueued: one 4 B pull a query
                METRICS.add("join.semi.mark.tiled", int(device_pull(tiled)))
            return
        for batch in iter_with_mask_prefetch(self.left.batches()):
            with METRICS.timer("join.host_probe"):
                cols, valids, dicts, n = compact_batch(batch)
                METRICS.add("join.host_probe.rows", n)
                if n == 0:
                    continue
                keep = np.flatnonzero(index.contains(
                    [cols[li]], [valids[li]], [dicts[li]]))
                if len(keep) == 0:
                    continue
                out = make_host_batch(
                    self._schema, [np.asarray(c)[keep] for c in cols],
                    [None if v is None else v[keep] for v in valids],
                    list(dicts))
            yield out


def _concat_compacted(parts: list, schema: Schema) -> tuple:
    """(columns, validity arrays, dictionaries, rows) of compacted
    batches (`compact_batch`), joined end to end."""
    width = len(schema)
    if not parts:
        return ([np.zeros(0, f.data_type.np_dtype) for f in schema.fields],
                [None] * width, [None] * width, 0)
    cols = [np.concatenate([np.asarray(p[0][i]) for p in parts])
            for i in range(width)]
    valids = []
    for i in range(width):
        vs = [p[1][i] for p in parts]
        valids.append(None if all(v is None for v in vs) else np.concatenate(
            [np.ones(p[3], bool) if v is None else np.asarray(v)
             for v, p in zip(vs, parts)]))
    return cols, valids, parts[-1][2], sum(p[3] for p in parts)


_BUILD_JITS: dict = {}


def build_slot_table_xla(pos, live, num_slots: int):
    """Direct-address build: per slot in [0, num_slots), the max build
    row index whose key maps there (-1 = empty) and the number of build
    rows sharing it.  `pos` is int32 slot positions (key - kmin), `live`
    masks rows out.  Two scatters, traceable — call under jit."""
    import jax.numpy as jnp

    n = pos.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    safe = jnp.where(live, pos, num_slots)  # dead rows land off-table
    slot_row = jnp.full(num_slots + 1, -1, jnp.int32).at[safe].max(
        jnp.where(live, rows, -1)
    )
    slot_count = jnp.zeros(num_slots + 1, jnp.int32).at[safe].add(
        live.astype(jnp.int32)
    )
    return slot_row[:num_slots], slot_count[:num_slots]


def build_slot_table_numpy(pos, live, num_slots: int):
    """Numpy parity oracle for `build_slot_table_xla`."""
    pos = np.asarray(pos)
    live = np.asarray(live, bool)
    sel = live & (pos >= 0) & (pos < num_slots)
    slot_row = np.full(num_slots, -1, np.int32)
    slot_count = np.zeros(num_slots, np.int32)
    rows = np.arange(pos.shape[0], dtype=np.int32)
    np.maximum.at(slot_row, pos[sel], rows[sel])
    np.add.at(slot_count, pos[sel], 1)
    return slot_row, slot_count


def _build_jit(num_slots: int):
    """Jitted build, one per slot count: the slot table filled, and it
    and the payload laid out as `LANES`-wide rows."""
    hit = _BUILD_JITS.get(num_slots)
    if hit is None:
        import jax

        def join_build(pos, live, payload):
            slot_row = build_slot_table_xla(pos, live, num_slots)[0]
            return jax.tree.map(lambda a: a.reshape(-1, LANES),
                                (slot_row, payload))

        hit = _BUILD_JITS[num_slots] = jax.jit(join_build)
    return hit
