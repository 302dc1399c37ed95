"""Hash-join physical operator.

Build side = RIGHT input (the planner puts the dimension position
there; LEFT OUTER preserves probe rows, so the probe must be the
left input).  The build side fully materializes once into a
`JoinBuildArtifact`; probe batches stream through one of two paths:

- **dense-int device probe**: single integer key, unique on the build
  side, with a small value range — the build fills a direct-address
  slot table on device (`exec/pallas/hash_build` kernel when it
  engages, stock-XLA scatter otherwise; both launch under
  ``device.launches.join.build``) and every probe batch runs ONE fused
  launch (``device.launches.join.probe``) computing hit mask + payload
  gather at probe capacity — no host round trip, masks carried, zero
  extra H2D once the artifact is resident.
- **host probe**: everything else (multi-key, strings, duplicate
  keys).  `core.HashIndex` CSR-expands matches per batch.

Artifacts pin in the device ledger under the build subtree's query
fingerprint (``join:<fp>``): a warm query probing the same dimension
table reuses the resident build — zero H2D for the build side — and a
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
    make_host_batch,
    put_compressed,
)
from datafusion_tpu.exec.relation import Relation
from datafusion_tpu.join import core as _core
from datafusion_tpu.obs.device import LEDGER
from datafusion_tpu.utils.metrics import METRICS
from datafusion_tpu.utils.retry import device_call


def _dense_max_slots() -> int:
    """Largest direct-address table the dense path will build; above it
    (sparse/huge key ranges) the host index keeps the job."""
    return int(os.environ.get("DATAFUSION_TPU_JOIN_DENSE_SLOTS", 1 << 20))


def _pin_max_bytes() -> int:
    """Largest build artifact the ledger pins (dimension tables are
    small; a fact-side build must not squat on HBM accounting)."""
    return int(os.environ.get("DATAFUSION_TPU_JOIN_PIN_MAX", 64 << 20))


def _device_path_enabled() -> bool:
    return os.environ.get("DATAFUSION_TPU_JOIN_DEVICE", "1") != "0"


def _is_utf8_field(field) -> bool:
    return field.data_type.name == "Utf8"


class JoinBuildArtifact:
    """The materialized build side: compacted host columns + the
    `HashIndex`, plus — on the dense path — the device-resident slot
    table and payload columns the fused probe launches gather from."""

    __slots__ = ("cols", "valids", "dicts", "n_rows", "index", "dense",
                 "kmin", "num_slots", "device", "dev_slot_row", "dev_cols",
                 "dev_valids", "nbytes", "fingerprint")

    def __init__(self):
        self.dense = False
        self.dev_slot_row = None
        self.fingerprint = None


@functools.lru_cache(maxsize=256)
def _probe_fn_for(kmin: int, num_slots: int, join_type: str):
    """One fused probe launch: slot lookup, hit mask, payload gather,
    validity, selection-mask combine — all inside a single jit.
    Module-cached so a pinned artifact probed by many relations (and
    by INNER and LEFT queries alike) shares compiled probes."""
    import jax
    import jax.numpy as jnp

    def f(key, kvalid, mask, slot_row, pcols, pvalids):
        # range check in int64 BEFORE the int32 cast: a far-out-of-range
        # probe key must not wrap into a valid slot
        d = key.astype(jnp.int64) - kmin
        inr = (d >= 0) & (d < num_slots)
        safe = jnp.where(inr, d, 0).astype(jnp.int32)
        bidx = jnp.where(inr, slot_row[safe], -1)
        hit = bidx >= 0
        if kvalid is not None:
            hit = hit & kvalid
        sb = jnp.where(hit, bidx, 0)
        gath = tuple(c[sb] for c in pcols)
        gval = tuple(hit if v is None else hit & v[sb] for v in pvalids)
        if join_type == "inner":
            out_mask = hit if mask is None else mask & hit
        else:
            out_mask = mask
        return gath, gval, out_mask

    return jax.jit(f)


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
        if fp is not None and art.nbytes <= _pin_max_bytes():
            from datafusion_tpu.obs.attribution import forget_pin

            LEDGER.pin(fp, art.nbytes, owner="join.build",
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
            self._try_dense(art)
        # single-table build sides (the plan->operator boundary fills
        # `_cost_obs`) teach the cost store the dimension's size — the
        # evidence the build-side/order rewrites plan from next time
        obs = getattr(self, "_cost_obs", None)
        if obs is not None:
            from datafusion_tpu import cost as _cost

            _cost.store().observe(obs[0], obs[1], rows=n, nbytes=art.nbytes)
        return art

    def _try_dense(self, art: JoinBuildArtifact) -> None:
        """Engage the device probe path when the key shape allows it:
        one integer key, unique among live build rows, value range
        small enough to direct-address."""
        if not _device_path_enabled() or len(self.on) != 1:
            return
        li, ri = self.on[0]
        bkey = art.cols[ri]
        pfield = self.left.schema.field(li)
        if bkey.dtype.kind not in "iu" or pfield.data_type.np_dtype.kind not in "iu":
            return
        # dictionary-coded (Utf8) keys LOOK integral but their codes
        # are per-dictionary — direct-address matching would compare
        # codes, not content; only the host index joins strings
        if art.dicts[ri] is not None or _is_utf8_field(pfield):
            return
        if not art.index.unique_keys:
            return
        valid = art.valids[ri]
        live = np.ones(art.n_rows, bool) if valid is None else valid.copy()
        if art.n_rows == 0 or not live.any():
            # empty/all-NULL build: the fused probe gathers payload rows
            # by slot, which needs at least one build row to address;
            # the host index gives "nothing matches" for free instead
            return
        kv = bkey[live].astype(np.int64)
        kmin = int(kv.min())
        num_slots = int(kv.max()) - kmin + 1
        if num_slots > _dense_max_slots():
            return
        pos = (bkey.astype(np.int64) - kmin).astype(np.int32)
        art.dense = True
        art.kmin, art.num_slots = kmin, num_slots

        # device residency: slot inputs + payload columns travel the
        # compressed wire once, at build time; warm probes reuse them
        uploads = [pos, live] + list(art.cols) + [
            v for v in art.valids if v is not None
        ]
        dev = put_compressed(uploads, self.device, owner="join.build")
        pos_d, live_d = dev[0], dev[1]
        ncols = len(art.cols)
        art.dev_cols = tuple(dev[2:2 + ncols])
        vi = 2 + ncols
        dvalids = []
        for v in art.valids:
            if v is None:
                dvalids.append(None)
            else:
                dvalids.append(dev[vi])
                vi += 1
        art.dev_valids = tuple(dvalids)

        # the stated engagement rule (exec/pallas): TPU batches and a
        # slot table within the kernel's window; operands are int32
        use_pallas = (
            _pallas.enabled_for(self.device)
            and num_slots <= _pallas.BUILD_MAX_SLOTS
        )
        if use_pallas:
            METRICS.add("join.build.pallas_runs")
        art.dev_slot_row = device_call(
            _build_jit(num_slots, use_pallas, _pallas.interpret_mode()),
            pos_d, live_d, _tag="join.build",
        )
        art.nbytes += num_slots * 4
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
        li = self.on[0][0]
        probe_fn = _probe_fn_for(art.kmin, art.num_slots, self.join_type)
        for batch in self.left.batches():
            data, validity, mask = device_inputs(batch, self.device)
            gath, gval, out_mask = device_call(
                probe_fn,
                data[li], validity[li], mask, art.dev_slot_row,
                art.dev_cols, art.dev_valids, _tag="join.probe",
            )
            METRICS.add("join.probe.rows", batch.num_rows)
            yield RecordBatch(
                self._schema,
                list(data) + list(gath),
                list(validity) + list(gval),
                list(batch.dicts) + list(art.dicts),
                num_rows=batch.num_rows,
                mask=out_mask,
            )

    def _host_batches(self, art: JoinBuildArtifact):
        from datafusion_tpu.exec.materialize import (
            compact_batch,
            iter_with_mask_prefetch,
        )

        l_keys = [k for k, _ in self.on]
        for batch in iter_with_mask_prefetch(self.left.batches()):
            cols, valids, dicts, n = compact_batch(batch)
            METRICS.add("join.probe.rows", n)
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
            yield make_host_batch(
                self._schema, out_cols, out_valids,
                list(dicts) + list(art.dicts),
            )


_BUILD_JITS: dict = {}


def _build_jit(num_slots: int, use_pallas: bool, interpret: bool):
    """Jitted slot-table build, one per (slots, kernel-choice)."""
    key = (num_slots, use_pallas, interpret)
    hit = _BUILD_JITS.get(key)
    if hit is None:
        import jax

        from datafusion_tpu.exec.pallas import hash_build

        if use_pallas:
            def fn(pos, live):
                return hash_build.build_slot_table(
                    pos, live, num_slots, interpret=interpret
                )[0]
        else:
            def fn(pos, live):
                return hash_build.build_slot_table_xla(pos, live, num_slots)[0]
        hit = _BUILD_JITS[key] = jax.jit(fn)
    return hit
