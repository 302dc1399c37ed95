"""Hash-join physical operator.

Build side = RIGHT input (the planner puts the dimension position
there; LEFT OUTER preserves probe rows, so the probe must be the
left input).  The build side fully materializes once into a
`JoinBuildArtifact`; probe batches stream through one of two paths:

- **dense-int device probe**: single integer key, unique on the build
  side — the build fills a direct-address slot table on device
  (`exec/pallas/hash_build` kernel when it engages, stock-XLA scatter
  otherwise; both launch under ``device.launches.join.build``) and
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
from datafusion_tpu.exec import pallas as _pallas
from datafusion_tpu.exec.batch import (
    RecordBatch,
    device_inputs,
    device_pull,
    make_host_batch,
    put_compressed,
)
from datafusion_tpu.exec.relation import Relation
from datafusion_tpu.exec.rowgather import LANES, pad_rows, take_rows_window
from datafusion_tpu.join import core as _core
from datafusion_tpu.obs.device import LEDGER, host_fits
from datafusion_tpu.utils.metrics import METRICS
from datafusion_tpu.utils.retry import device_call


# slot positions and build-row indices travel and gather as int32
_MAX_SLOTS = (1 << 31) - 1
# at most this many slots a live build row (module doc)
_SLOTS_PER_BUILD_ROW = 8


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
def _window_counts_fn():
    """The launches of one scan whose lookups took the window: the sum
    of the probe launches' `windows`, as int32[2]."""
    import jax
    import jax.numpy as jnp

    def window_counts(windows):
        return jnp.sum(jnp.stack(windows), axis=0, dtype=jnp.int32)

    return jax.jit(window_counts)


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

        # the stated engagement rule (exec/pallas): TPU batches and a
        # slot table within the kernel's window; operands are int32
        use_pallas = (
            _pallas.enabled_for(self.device)
            and num_slots <= _pallas.BUILD_MAX_SLOTS
        )
        if use_pallas:
            METRICS.add("join.build.pallas_runs")
        # one launch fills the slot table and lays it and the payload
        # out as rows; these outputs are what stays resident (the
        # flat uploads go with this frame)
        art.dev_slot_row, (art.dev_cols, art.dev_valids) = LEDGER.adopt(
            device_call(
                _build_jit(pad_rows(num_slots), use_pallas,
                           _pallas.interpret_mode()),
                dev_pos, dev_live, payload, _tag="join.build",
            ), owner="join.build", device=self.device)
        # the probe's two scalars, placed once: a numpy scalar handed to
        # a jitted call is a transfer of its own in every launch
        import jax.numpy as jnp

        art.dev_kmin, art.dev_num_slots = (
            LEDGER.put(np.int64(x), self.device, owner="join.build")
            if self.device is not None
            else LEDGER.adopt(jnp.asarray(np.int64(x)), owner="join.build")
            for x in (kmin, num_slots))
        METRICS.add("join.build.dense")

    # -- probe ---------------------------------------------------------
    def batches(self):
        from datafusion_tpu.obs.stats import iter_stats

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


_BUILD_JITS: dict = {}


def _build_jit(num_slots: int, use_pallas: bool, interpret: bool):
    """Jitted build, one per (slots, kernel-choice): the slot table
    filled, and it and the payload laid out as `LANES`-wide rows."""
    key = (num_slots, use_pallas, interpret)
    hit = _BUILD_JITS.get(key)
    if hit is None:
        import jax

        from datafusion_tpu.exec.pallas import hash_build

        def join_build(pos, live, payload):
            if use_pallas:
                slot_row = hash_build.build_slot_table(
                    pos, live, num_slots, interpret=interpret)[0]
            else:
                slot_row = hash_build.build_slot_table_xla(
                    pos, live, num_slots)[0]
            return jax.tree.map(lambda a: a.reshape(-1, LANES),
                                (slot_row, payload))

        hit = _BUILD_JITS[key] = jax.jit(join_build)
    return hit
