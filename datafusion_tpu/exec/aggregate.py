"""Grouped aggregation on device.

The reference never implemented aggregation (`context.rs:161`
`unimplemented!()`; even the Avg accumulator is missing from its enum,
`expression.rs:99-105`).  TPU design:

- **Filter fusion**: when the aggregate sits directly over a Selection
  (the planner's shape, `sqlplanner.rs:90-117`), the predicate compiles
  *into the aggregation kernel* — filter + 8-way aggregate is one XLA
  computation per batch (TPC-H Q1's whole body).
- **Group-key encoding (host)**: a persistent `GroupKeyEncoder` maps
  each row's key tuple to a dense, append-only group id.  Fully
  vectorized: per-batch uniques via a mixed-radix pack (or a row-bytes
  view when the pack overflows), matched against the known key set
  with `searchsorted` — no Python loop over uniques, so 10^5-10^6
  groups per batch encode in numpy time.  Dense ids are stable across
  batches, so device accumulators grow by zero padding.
- **Slot deduplication**: aggregates lower to accumulator *slots*
  shared across functions — SUM(x) and AVG(x) share one sum slot and
  one count slot; COUNT(*) rides the per-group row count, and any
  count whose ok-mask turns out to equal the row mask at trace time
  aliases the row-count reduction instead of re-running it.  TPC-H
  Q1's 8 aggregates touch 5 unique sum slots, not 8 sums + 8 counts.
- **Accumulation (device, jitted)**: one fused kernel evaluates every
  slot argument and updates fixed-capacity accumulators.  Small group
  counts (<= DENSE_GROUP_MAX) use a one-hot [rows, G] masked
  broadcast-reduce (spelled as a fused reduction, not a literal f64
  dot — TPU emulates f64 dots catastrophically slowly).
  Larger group counts use **sort-merge aggregation**: XLA scatter is
  serial on TPU, so the state and batch are sorted together by group
  id, runs of equal ids reduce with one segmented scan, and each
  group's total is read at the last row of its run.  Masked-out or
  null rows contribute identity
  elements — the kernel never syncs a mask to the host.
- **Finalization**: AVG = SUM/COUNT; grouped keys observed only in
  filtered-out rows (count 0) are dropped.
- **Distributed**: the accumulators are exactly the per-shard partial
  state; partitioned mode combines them with collectives over the
  mesh (parallel/partition.py) — the partial->final aggregate the
  reference's worker mode planned (`README.md:33-35`).

Accumulator dtypes: integer SUM accumulates in 64-bit (overflow
safety); COUNT is Int64 internally, UInt64 in the output (planner
contract); MIN/MAX keep the argument dtype.
"""

from __future__ import annotations

import contextlib
import contextvars
import weakref
from typing import Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from datafusion_tpu.datatypes import DataType, Schema
from datafusion_tpu.errors import ExecutionError, NotSupportedError
from datafusion_tpu.exec.batch import (
    RecordBatch,
    StringDictionary,
    bucket_capacity,
    device_pull,
    make_host_batch,
)
from datafusion_tpu.exec.expression import Env, ExprCompiler, compute_aux_values
from datafusion_tpu.exec.relation import Relation
from datafusion_tpu.exec.wordsort import key_words, lex_perm
from datafusion_tpu.plan.expr import AggregateFunction, Column, Expr
from datafusion_tpu.utils.metrics import METRICS
from datafusion_tpu.utils.retry import device_call


DENSE_GROUP_MAX = 64
# A scan of a table that keeps its batches folds its keys on the device
# (`_KeyedAccumulator`) rather than through the host encoder's dense ids
# where its key space (possible key tuples: a dictionary's size, an
# integer column's span) is at least `WIDE_KEY_SPAN` and at least one
# `WIDE_KEY_SHARE`-th of the table's rows: the encoder's route holds four
# slots a group and sorts a batch group's rows together with every slot
# (h2o's q5, 10^5 groups of 10^7 rows: 13 M keys a question), so where
# the groups may come near the rows in number its state outgrows them
WIDE_KEY_SPAN = 1 << 16
WIDE_KEY_SHARE = 64

def widen_group_ids(w):
    return w.astype(jnp.int32)


# widen narrow wire-format group ids back to int32 on device
_WIDEN_IDS_JIT = jax.jit(widen_group_ids)


def _ids_of(ids):
    """Group ids as the kernels take them: an int array, or, for a
    batch whose dictionary-coded key columns were born on the device
    (`AggregateRelation._device_group_ids`), the tuple (key columns,
    validities, table), turned into ids here, inside the launch that
    consumes them.  `table` is each key's (radix, dictionary size) and
    then the id of every key tuple: the tuple packed by the radices (a
    NULL coded as the dictionary's size) finds its id by
    compare-and-select, for a table of at most `DENSE_GROUP_MAX` ids
    needs no gather."""
    if not isinstance(ids, tuple):
        return ids
    cols, valids, table = ids
    meta, lut = table[:2 * len(cols)].reshape(-1, 2), table[2 * len(cols):]
    packed = jnp.zeros(cols[0].shape, jnp.int32)
    for i, (c, v) in enumerate(zip(cols, valids)):
        code = c.astype(jnp.int32)
        if v is not None:
            code = jnp.where(v, code, meta[i, 1])
        packed = packed * meta[i, 0] + code
    onehot = packed[:, None] == jnp.arange(lut.shape[0], dtype=jnp.int32)[None, :]
    return jnp.sum(jnp.where(onehot, lut[None, :], 0), axis=1, dtype=jnp.int32)


# serving-path lowering mode (datafusion_tpu/serve.py): keep the
# predicate IN the device core (as parameter slots) instead of routing
# host-evaluable predicates to the host.  Cross-query megabatching
# needs every query in a fused launch to share one device program and
# one set of device inputs; per-query host masks would fork the inputs
# per query.  Contextvar-scoped so a serving dispatch never changes how
# a concurrent ordinary query lowers.
_FORCE_CORE_PRED: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "datafusion_tpu_force_core_pred", default=False
)


@contextlib.contextmanager
def force_core_predicate():
    """Scope in which AggregateRelation keeps predicates in the device
    core (serving megabatch lowering — see comment above)."""
    tok = _FORCE_CORE_PRED.set(True)
    try:
        yield
    finally:
        _FORCE_CORE_PRED.reset(tok)


def group_capacity(n: int) -> int:
    """Accumulator capacity: next power of two, floor 8.  Kept tight
    (unlike row-batch bucketing) because capacities <= DENSE_GROUP_MAX
    take the dense one-hot kernel path — a fused masked reduction
    instead of XLA scatter, which executes serially on both CPU and
    TPU."""
    cap = 8
    while cap < n:
        cap <<= 1
    return cap


def _row_bytes_view(a: np.ndarray) -> np.ndarray:
    """(N, K) int64 -> (N,) opaque-bytes view with a consistent total
    order (memcmp), used for cross-batch key identity."""
    a = np.ascontiguousarray(a)
    return a.view([("", a.dtype)] * a.shape[1]).ravel()


class GroupKeyEncoder:
    """Host-side dense encoder of group-key tuples -> stable group ids.

    Vectorized: the known key set lives in a sorted row-view array
    matched with `searchsorted`; no per-key Python dict operations, so
    encoding stays numpy-speed at 10^6 groups.
    """

    # radix-LUT fast path bound: product of per-component radices must
    # keep the id lookup table at most this many entries (16 MB int32)
    _LUT_MAX = 1 << 22

    def __init__(self, num_keys: int):
        self.num_keys = num_keys
        k = max(2 * num_keys, 1)
        self._arr = np.empty((0, k), dtype=np.int64)  # key rows by group id
        self._sorted_rows = _row_bytes_view(self._arr)  # sorted row view
        self._sorted_ids = np.empty(0, dtype=np.int64)
        # radix-LUT fast path (small non-negative key spaces: dictionary
        # codes, low-cardinality ints): encode = one gather instead of a
        # per-batch sort.  Disabled permanently on the first batch whose
        # key space can't be packed small (negatives / wide ranges).
        self._fast = True
        self._radix: Optional[list[int]] = None
        self._lut: Optional[np.ndarray] = None

    @property
    def num_groups(self) -> int:
        return len(self._arr)

    @staticmethod
    def _to_int_image(c: np.ndarray) -> np.ndarray:
        """Lossless integer image of a key column.  Floats are *bit-cast*
        (a value cast would merge 1.5 and 1.7); -0.0 normalizes to 0.0
        and NaNs to one canonical NaN so SQL equality groups them.
        Integer columns keep their native width (packing upcasts)."""
        if c.dtype.kind == "f":
            c = c.astype(np.float64)
            c = np.where(c == 0.0, 0.0, c)  # -0.0 == 0.0
            c = np.where(np.isnan(c), np.float64(np.nan), c)
            return c.view(np.int64)
        if c.dtype.kind == "b":
            return c.astype(np.int8)
        return c

    def encode(
        self,
        key_cols: list[np.ndarray],
        key_valids: list,
    ) -> np.ndarray:
        """key_cols: per-key numpy arrays (dict codes for strings);
        key_valids: per-key bool validity arrays or None.  Returns int32
        group ids per row.  NULL keys form their own group (SQL
        semantics): each key contributes (value-with-nulls-zeroed,
        isnull flag) to the group tuple.
        """
        if key_cols and len(key_cols[0]) == 0:
            return np.empty(0, dtype=np.int32)  # _pack can't reduce empty
        # components: (value, isnull) per key.  None stands for an
        # all-zero component (no nulls) — the fast path skips it and the
        # general path materializes zeros.  Values keep their native
        # integer width here; packing/stacking upcasts as needed.
        comps: list[Optional[np.ndarray]] = []
        n = len(key_cols[0]) if key_cols else 0
        for c, v in zip(key_cols, key_valids):
            c = self._to_int_image(np.asarray(c))
            if v is None:
                comps.append(c)
                comps.append(None)
            else:
                v = np.asarray(v)
                comps.append(np.where(v, c, 0))
                comps.append(~v)
        if self._fast:
            ids = self._encode_fast(comps, n)
            if ids is not None:
                return ids
            # the key space just outgrew the LUT: fall through to the
            # general path for this and every later batch (ids assigned
            # so far stay valid — _arr is shared between both paths)
            self._rebuild_sorted()
        rows = [
            np.zeros(n, dtype=np.int64) if c is None else c.astype(np.int64)
            for c in comps
        ]
        stacked = np.stack(rows, axis=1)  # (n, 2K)
        # Fast path: pack the key tuple into one int64 (mixed radix), so
        # per-batch uniquing is a single 1-D sort; the pack is per-batch
        # only — cross-batch identity goes through the row-bytes view.
        packed = self._pack(stacked)
        if packed is not None:
            _, first, inv = np.unique(packed, return_index=True, return_inverse=True)
        else:
            _, first, inv = np.unique(
                _row_bytes_view(stacked), return_index=True, return_inverse=True
            )
        urows = stacked[first]  # (U, 2K), per-batch unique keys
        uview = _row_bytes_view(urows)
        order = np.argsort(uview)  # row-bytes order for searchsorted
        sview = uview[order]
        pos = np.searchsorted(self._sorted_rows, sview)
        found = np.zeros(len(sview), dtype=bool)
        in_range = pos < len(self._sorted_rows)
        found[in_range] = self._sorted_rows[pos[in_range]] == sview[in_range]

        lut_sorted = np.empty(len(sview), dtype=np.int64)
        lut_sorted[found] = self._sorted_ids[pos[found]]
        n_new = int((~found).sum())
        if n_new:
            new_ids = np.arange(
                self.num_groups, self.num_groups + n_new, dtype=np.int64
            )
            lut_sorted[~found] = new_ids
            self._arr = np.concatenate([self._arr, urows[order][~found]])
            ins = pos[~found]  # insertion points into the old sorted view
            self._sorted_rows = np.insert(self._sorted_rows, ins, sview[~found])
            self._sorted_ids = np.insert(self._sorted_ids, ins, new_ids)

        lut = np.empty(len(uview), dtype=np.int64)
        lut[order] = lut_sorted
        return lut[inv].astype(np.int32)

    @staticmethod
    def _pack(stacked: np.ndarray) -> Optional[np.ndarray]:
        """Mixed-radix pack of (n, 2K) int64 key parts into (n,) int64;
        None when the combined range could overflow 63 bits."""
        mins = stacked.min(axis=0).tolist()
        maxs = stacked.max(axis=0).tolist()
        # ranges in Python ints: a single int64 column can span > 2^63,
        # which would wrap (and slip past the bail-out) in int64 math
        ranges = [int(mx) - int(mn) + 1 for mn, mx in zip(mins, maxs)]
        total = 1
        for r in ranges:
            total *= r
            if total > (1 << 62):
                return None
        # total <= 2^62 implies every range (and every shifted value)
        # fits comfortably in int64
        packed = np.zeros(stacked.shape[0], dtype=np.int64)
        for k in range(stacked.shape[1]):
            packed = packed * np.int64(ranges[k]) + (stacked[:, k] - np.int64(mins[k]))
        return packed

    def _encode_fast(self, comps, n: int) -> Optional[np.ndarray]:
        """Radix-LUT encode: pack each key tuple into a small int64 with
        FIXED per-component radices (stable across batches, unlike
        `_pack`'s per-batch ranges) and look ids up in a dense table —
        one gather per batch instead of a sort.  Returns None —
        permanently disabling the path — when the key space has
        negatives or would need a LUT past _LUT_MAX."""
        maxs = []
        for c in comps:
            if c is None:
                maxs.append(0)
                continue
            if c.dtype.kind == "b":
                maxs.append(1)
                continue
            lo, hi = int(c.min()), int(c.max())
            if lo < 0:
                self._fast = False
                return None
            maxs.append(hi)
        if self._radix is None or any(
            mx >= r for mx, r in zip(maxs, self._radix)
        ):
            # (re)choose radices: next power of two above the observed
            # max, doubled for growth headroom (string dictionaries keep
            # appending codes); rebuild the LUT from the known groups
            radix = []
            for k, mx in enumerate(maxs):
                seen = mx
                if len(self._arr):
                    seen = max(seen, int(self._arr[:, k].max()))
                if seen == 0:
                    radix.append(1)
                    continue
                r = 1
                while r <= seen:
                    r <<= 1
                radix.append(r * 2)
            total = 1
            for r in radix:
                total *= r
                if total > self._LUT_MAX:
                    self._fast = False
                    return None
            self._radix = radix
            self._lut = np.full(total, -1, dtype=np.int32)
            if len(self._arr):
                self._lut[self._pack_rows(self._arr)] = np.arange(
                    len(self._arr), dtype=np.int32
                )
        packed = self._pack_comps(comps, n)
        ids = self._lut[packed]
        if (ids < 0).any():
            new_packed = np.unique(packed[ids < 0])
            self._lut[new_packed] = np.arange(
                self.num_groups, self.num_groups + len(new_packed), dtype=np.int32
            )
            self._arr = np.concatenate([self._arr, self._unpack_fixed(new_packed)])
            ids = self._lut[packed]
        return ids.astype(np.int32, copy=False)

    def _pack_comps(self, comps, n: int) -> np.ndarray:
        """Horner pack of per-component arrays (None = zeros) with the
        fixed radices; int64 throughout (ranges proven < _LUT_MAX)."""
        packed = np.zeros(n, dtype=np.int64)
        for c, r in zip(comps, self._radix):
            if r == 1:
                continue  # radix 1 => component is globally all-zero
            packed *= np.int64(r)
            if c is not None:
                if c.dtype != np.int64:
                    c = c.astype(np.int64)
                packed += c
        return packed

    def _pack_rows(self, rows2d: np.ndarray) -> np.ndarray:
        packed = np.zeros(rows2d.shape[0], dtype=np.int64)
        for k, r in enumerate(self._radix):
            packed = packed * np.int64(r) + rows2d[:, k]
        return packed

    def _unpack_fixed(self, packed: np.ndarray) -> np.ndarray:
        out = np.empty((len(packed), len(self._radix)), dtype=np.int64)
        rest = packed.copy()
        for k in range(len(self._radix) - 1, -1, -1):
            out[:, k] = rest % self._radix[k]
            rest //= self._radix[k]
        return out

    def _rebuild_sorted(self):
        """Reconstruct the general path's sorted row view from `_arr`
        after the fast path retires (its inserts never ran)."""
        view = _row_bytes_view(self._arr)
        order = np.argsort(view, kind="stable")
        self._sorted_rows = view[order]
        self._sorted_ids = order.astype(np.int64)

    def key_column(self, k: int):
        """(values, validity) of key position k across all groups, in
        group-id order; validity None when no group has a NULL key."""
        vals = self._arr[:, 2 * k].copy()
        isnull = self._arr[:, 2 * k + 1] != 0
        return vals, (None if not isnull.any() else ~isnull)


class _Slot:
    """One deduplicated accumulator column.

    kind: "sum" (also serves AVG), "cnt" (non-null count of one arg),
    "min"/"max", "smin"/"smax" (Utf8 via dictionary ranks).
    """

    __slots__ = ("kind", "arg", "fn", "acc_dtype", "arg_index")

    def __init__(self, kind, arg, fn, acc_dtype, arg_index=None):
        self.kind = kind
        self.arg = arg
        self.fn = fn
        self.acc_dtype = acc_dtype
        self.arg_index = arg_index  # column index for string slots

    @property
    def is_string(self) -> bool:
        return self.kind in ("smin", "smax")


class AggregateSpec:
    """One aggregate function, resolved to its accumulator slots."""

    def __init__(self, expr: AggregateFunction, input_schema: Schema):
        self.name = expr.name.lower()
        if self.name not in ("sum", "count", "min", "max", "avg"):
            raise NotSupportedError(f"unknown aggregate {expr.name!r}")
        if len(expr.args) != 1:
            raise ExecutionError(f"{expr.name} takes one argument")
        self.arg = expr.args[0]
        self.return_type = expr.return_type
        self.count_star = self.name == "count" and expr.count_star
        self.arg_type = self.arg.get_type(input_schema)
        # MIN/MAX over Utf8: the accumulator is the best dictionary
        # *code* per group; comparison rides per-version rank tables
        # (codes are append-ordered, ranks are lexicographic)
        self.is_string = self.arg_type == DataType.UTF8 and self.name in ("min", "max")
        if self.is_string and not isinstance(self.arg, Column):
            raise NotSupportedError(
                f"{expr.name} over a computed Utf8 expression is not supported"
            )
        if self.name in ("sum", "avg") and self.arg_type == DataType.UTF8:
            raise NotSupportedError(f"{expr.name} over Utf8 is not supported")
        # slot references, filled by AggregateRelation._build_slots
        self.sum_slot: Optional[int] = None
        self.cnt_slot: Optional[int] = None  # None => per-group row count
        self.minmax_slot: Optional[int] = None

    @property
    def sum_dtype(self) -> np.dtype:
        npd = self.arg_type.np_dtype
        if self.arg_type.is_signed_integer:
            return np.dtype(np.int64)
        if self.arg_type.is_unsigned_integer:
            return np.dtype(np.uint64)
        return npd


def _min_identity(dtype: np.dtype):
    if dtype.kind == "f":
        return np.asarray(np.inf, dtype)
    if dtype.kind in "iu":
        return np.asarray(np.iinfo(dtype).max, dtype)
    if dtype.kind == "b":
        return np.asarray(True, dtype)
    raise ExecutionError(f"MIN unsupported for {dtype}")


def _max_identity(dtype: np.dtype):
    if dtype.kind == "f":
        return np.asarray(-np.inf, dtype)
    if dtype.kind in "iu":
        return np.asarray(np.iinfo(dtype).min, dtype)
    if dtype.kind == "b":
        return np.asarray(False, dtype)
    raise ExecutionError(f"MAX unsupported for {dtype}")


class _AggregateCore:
    """The compiled, shareable part of an aggregation: specs, slots
    (with their compiled argument closures), the predicate closure, and
    the jitted kernel.  Cached process-wide by plan fingerprint
    (SURVEY §7 recompilation control): a fresh operator tree for a
    semantically identical GROUP BY reuses the already-built jit and
    every executable in its cache."""

    def __init__(self, in_schema, group_expr, aggr_expr, predicate, functions,
                 param_slots=None):
        for g in group_expr:
            if not isinstance(g, Column):
                raise NotSupportedError(f"GROUP BY supports column references, got {g!r}")
            if in_schema.field(g.index).data_type.np_dtype.kind == "O":
                raise NotSupportedError("struct columns cannot be GROUP BY keys")
        self.key_cols = [g.index for g in group_expr]
        self.specs = []
        for a in aggr_expr:
            if not isinstance(a, AggregateFunction):
                raise ExecutionError(f"non-aggregate expression {a!r} in aggr_expr")
            self.specs.append(AggregateSpec(a, in_schema))

        compiler = ExprCompiler(in_schema, functions, param_slots)
        self._pred_fn = compiler.compile(predicate) if predicate is not None else None
        self.slots = self._build_slots(compiler)
        self.aux_specs = compiler.aux_specs
        # ship only the columns the kernel reads (group keys travel as
        # dense ids; a host-routed predicate never reaches this ctor,
        # so its inputs don't appear here and never cross H2D); Env's
        # col_map translates schema indices to subset positions
        used: set[int] = set()
        if predicate is not None:
            predicate.collect_columns(used)
        for a in aggr_expr:
            a.collect_columns(used)
        self.used_cols = sorted(used)
        self.col_map = {c: i for i, c in enumerate(self.used_cols)}
        self.sub_schema = in_schema.select(self.used_cols)
        # per-column codec memory for put_compressed (persists across
        # cold re-runs of the same query shape — see batch.py)
        self.wire_hints: dict = {}
        self.jit = jax.jit(self._kernel)
        self.fused_jit = jax.jit(self._fused_kernel)
        # fused-pass batch-group fold (exec/fused.py): ONE launch per
        # shape-homogeneous group of prepared batches
        self.group_jit = jax.jit(self._fused_group)
        # cross-QUERY megabatch fold (datafusion_tpu/serve.py): one
        # launch runs the batch-group fold for N concurrent queries
        # that share this core (same plan shape, different literal
        # params) over ONE set of device inputs, returning one state
        # per query — one launch and one sync shared by all clients
        self.multi_group_jit = jax.jit(self._multi_fused_group)
        # numeric group keys born on the device (`_KeyedAccumulator`)
        self.keyed_counts_jit = jax.jit(self._keyed_counts)
        self.keyed_rows_jit = jax.jit(self._keyed_rows, static_argnums=(0,))
        self.keyed_store_jit = jax.jit(self._keyed_store)
        self.keyed_fold_jit = jax.jit(self._keyed_fold)
        self.keyed_reduce_jit = jax.jit(self._keyed_reduce)
        self.keyed_outputs_jit = jax.jit(self._keyed_outputs,
                                         static_argnums=(1, 2))
        # HAVING programs, one pair per (predicate, output schema)
        self._having_progs: dict = {}

    def _fused_kernel(self, chunk, state, params):
        """Fold `_kernel` over a chunk of prepared batches in ONE device
        launch: a warm in-memory scan collapses from one launch per
        batch to one per chunk."""
        for cols, valids, aux, num_rows, mask, ids, str_aux in chunk:
            state = self._kernel(
                cols, valids, aux, num_rows, mask, ids, state, str_aux, params
            )
        return state

    @staticmethod
    def param_exprs(predicate, aggr_expr):
        """Exprs compiled into the device kernel, in slot order."""
        return ([] if predicate is None else [predicate]) + list(aggr_expr)

    @staticmethod
    def build(in_schema, group_expr, aggr_expr, predicate, functions):
        from datafusion_tpu.exec.kernels import (
            cached_kernel,
            functions_fingerprint,
            parameterize_exprs,
            schema_fingerprint,
        )

        elig = _AggregateCore.param_exprs(predicate, aggr_expr)
        fps, slot_by_id, _ = parameterize_exprs(elig)
        n_pred = 0 if predicate is None else 1
        key = (
            "aggregate",
            schema_fingerprint(in_schema),
            tuple(group_expr),
            fps[n_pred:],
            fps[0] if n_pred else None,
            functions_fingerprint(functions),
        )
        return cached_kernel(
            key,
            lambda: _AggregateCore(
                in_schema, group_expr, aggr_expr, predicate, functions,
                slot_by_id,
            ),
        )

    def _build_slots(self, compiler: ExprCompiler) -> list[_Slot]:
        """Deduplicate aggregates into accumulator slots.  SUM(x) and
        AVG(x) share one sum slot; their validity counts (and any
        COUNT(x)) share one cnt slot per distinct argument; COUNT(*)
        rides the per-group row count (slot None).  A cnt slot whose
        argument carries no validity further aliases the row-count
        reduction at trace time (see _dense_update/_sortmerge_update)."""
        slots: list[_Slot] = []
        index: dict[tuple, int] = {}

        def get(kind, arg, acc_dtype, arg_index=None):
            key = (kind, arg)
            hit = index.get(key)
            if hit is not None:
                return hit
            index[key] = len(slots)
            slots.append(_Slot(kind, arg, compiler.compile(arg), acc_dtype, arg_index))
            return index[key]

        for s in self.specs:
            if s.is_string:
                kind = "smin" if s.name == "min" else "smax"
                s.minmax_slot = get(kind, s.arg, np.dtype(np.int32), s.arg.index)
            elif s.name in ("sum", "avg"):
                s.sum_slot = get("sum", s.arg, s.sum_dtype)
                s.cnt_slot = get("cnt", s.arg, np.dtype(np.int64))
            elif s.name == "count":
                # COUNT(*) counts rows; COUNT(x) counts non-null x
                s.cnt_slot = None if s.count_star else get(
                    "cnt", s.arg, np.dtype(np.int64)
                )
            else:
                s.minmax_slot = get(
                    s.name, s.arg, np.dtype(s.arg_type.np_dtype)
                )
        return slots

    # -- accumulator state: (counts, tuple(per-slot accumulators)) --
    def _slot_identity(self, sl: _Slot):
        if sl.kind == "smin" or sl.kind == "smax":
            return np.asarray(-1, np.int32)
        if sl.kind in ("sum", "cnt"):
            return np.asarray(0, sl.acc_dtype)
        if sl.kind == "min":
            return _min_identity(sl.acc_dtype)
        return _max_identity(sl.acc_dtype)

    def _init_state(self, capacity: int):
        # cached per capacity: creating the state costs one tiny device
        # launch per slot, which a repeated query would otherwise pay
        # every run; states are functionally consumed, never mutated,
        # so sharing is safe
        cache = getattr(self, "_init_states", None)
        if cache is None:
            cache = self._init_states = {}
        hit = cache.get(capacity)
        if hit is None:
            accs = tuple(
                jnp.full(capacity, jnp.asarray(self._slot_identity(sl)))
                for sl in self.slots
            )
            hit = cache[capacity] = (jnp.zeros(capacity, jnp.int64), accs)
        return hit

    def _grow_state(self, state, new_capacity: int):
        """Dense group ids are stable: growth is identity padding."""
        counts, accs = state
        pad = new_capacity - counts.shape[0]

        def grow(a, fill):
            return jnp.concatenate([a, jnp.full(pad, jnp.asarray(fill, a.dtype))])

        new_accs = tuple(
            grow(acc, self._slot_identity(sl)) for sl, acc in zip(self.slots, accs)
        )
        return grow(counts, 0), new_accs

    def _kernel(self, cols, valids, aux, num_rows, base_mask, ids, state,
                str_aux=(), params=()):
        ids = _ids_of(ids)
        env = Env(cols, valids, aux, self.col_map, params)
        capacity = cols[0].shape[0] if cols else ids.shape[0]
        mask = jnp.arange(capacity, dtype=jnp.int32) < num_rows
        if base_mask is not None:
            mask = mask & base_mask
        if self._pred_fn is not None:
            pv, pvalid = self._pred_fn(env)
            pv = jnp.broadcast_to(pv, (capacity,))
            if pvalid is not None:
                pv = pv & jnp.broadcast_to(pvalid, (capacity,))
            mask = mask & pv

        counts, accs = state
        group_cap = counts.shape[0]
        if group_cap <= DENSE_GROUP_MAX:
            return self._dense_update(env, capacity, mask, ids, counts, accs, str_aux)
        return self._sortmerge_update(env, capacity, mask, ids, counts, accs, str_aux)

    def _slot_inputs(self, env, capacity, mask):
        """(value, ok-mask) per slot, masking padding/filtered/null
        rows.  `ok is mask` when the argument has no validity — update
        paths use that identity to alias the row-count reduction."""
        out = []
        for sl in self.slots:
            v, valid = sl.fn(env)
            v = jnp.broadcast_to(v, (capacity,))
            if valid is None:
                ok = mask
            else:
                ok = mask & jnp.broadcast_to(valid, (capacity,))
            out.append((v, ok))
        return out

    # -- string MIN/MAX rank arithmetic (codes are stable across
    # batches; ranks are valid only within one dictionary version) --
    @staticmethod
    def _rank_sentinel(kind):
        """Identity element in rank space: +inf-like for smin (any real
        rank beats it under minimum), -1 for smax."""
        return jnp.int32(2**31 - 1) if kind == "smin" else jnp.int32(-1)

    @classmethod
    def _codes_to_ranks(cls, kind, codes, str_aux_k):
        """Best-code accumulator -> rank space (-1 = empty -> sentinel)."""
        ranks, _ = str_aux_k
        cap = ranks.shape[0]
        return jnp.where(
            codes >= 0,
            ranks[jnp.clip(codes, 0, cap - 1)],
            cls._rank_sentinel(kind),
        )

    @classmethod
    def _ranks_to_codes(cls, kind, best, str_aux_k):
        """Winning rank -> stable code (-1 when the group is empty)."""
        _, order = str_aux_k
        cap = order.shape[0]
        alive = best != cls._rank_sentinel(kind)
        return jnp.where(alive, order[jnp.clip(best, 0, cap - 1)], -1).astype(
            jnp.int32
        )

    @classmethod
    def _string_combine(cls, kind, acc, batch_best_rank, str_aux_k):
        """Merge a per-group best-rank candidate into a best-code
        accumulator."""
        old_rank = cls._codes_to_ranks(kind, acc, str_aux_k)
        if kind == "smin":
            best = jnp.minimum(batch_best_rank, old_rank)
        else:
            best = jnp.maximum(batch_best_rank, old_rank)
        return cls._ranks_to_codes(kind, best, str_aux_k)

    @staticmethod
    def _seg_scan(vals, start, combines):
        """Segmented inclusive scans of several payload columns at once:
        `start` marks segment heads; the value at each segment's last
        row is the segment reduction.  `combines[i]` reduces `vals[i]`.

        Hillis-Steele doubling inside ONE `fori_loop`: at step d every
        row not yet reached by its segment head folds in the row d
        before it.  O(n log n) work, but the loop body is a handful of
        fixed-shape ops that compile once — `lax.associative_scan`
        unrolls 2 log n levels of distinct strided shapes, and on TPU
        each 64-bit level compiles separately (minutes per column at
        2 M rows; PERF.md, PR 21)."""
        n = start.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)

        def step(t, carry):
            vs, seen = carry
            d = jnp.left_shift(jnp.int32(1), t)
            reach = idx >= d  # rows that have a row d before them
            fold = reach & ~seen
            vs = tuple(
                jnp.where(fold, c(jnp.roll(v, d), v), v)
                for v, c in zip(vs, combines)
            )
            return vs, seen | (reach & jnp.roll(seen, d))

        steps = max(1, (n - 1).bit_length())
        out, _ = jax.lax.fori_loop(0, steps, step, (tuple(vals), start))
        return out

    def _sm_contribs(self, env, capacity, mask, ids, str_aux):
        """Per-batch contribution columns of the sort-merge combine:
        (batch_keys, [row-count contrib, one per non-aliased slot...],
        payload_of).  Split out of the combine so the fused batch-group
        fold can concatenate MANY batches' contributions and pay for
        ONE sort instead of one per batch."""
        # dead rows sort past every real group id (dense int32 ids)
        SENT = jnp.int32(jnp.iinfo(jnp.int32).max)
        inputs = self._slot_inputs(env, capacity, mask)
        batch_keys = jnp.where(mask, ids.astype(jnp.int32), SENT)
        contribs = [mask.astype(jnp.int64)]  # row count
        payload_of: dict[int, int] = {}
        for i, (sl, (v, ok)) in enumerate(zip(self.slots, inputs)):
            if sl.kind == "cnt" and ok is mask:
                continue  # aliases the row count payload
            if sl.is_string:
                # contribute in lexicographic-rank space under the
                # current dict version
                ranks, _ = str_aux[i]
                cap = ranks.shape[0]
                r = ranks[jnp.clip(v.astype(jnp.int32), 0, cap - 1)]
                contrib = jnp.where(ok, r, self._rank_sentinel(sl.kind))
            elif sl.kind == "sum":
                contrib = jnp.where(ok, v, 0).astype(sl.acc_dtype)
            elif sl.kind == "cnt":
                contrib = ok.astype(jnp.int64)
            else:
                ident = (
                    _min_identity(sl.acc_dtype)
                    if sl.kind == "min"
                    else _max_identity(sl.acc_dtype)
                )
                contrib = jnp.where(ok, v.astype(sl.acc_dtype), ident)
            payload_of[i] = len(contribs)
            contribs.append(contrib)
        return batch_keys, contribs, payload_of

    def _sortmerge_update(self, env, capacity, mask, ids, counts, accs, str_aux=()):
        """High-cardinality path (group capacity > DENSE_GROUP_MAX):
        sort-merge aggregation, the scatter-free XLA shape.

        XLA scatter executes serially on TPU, so instead: concatenate
        the dense state (implicit keys 0..G-1) with the batch rows,
        `lax.sort` the (group id, row) pair, gather the payloads by
        the permutation, reduce runs of equal ids with one segmented
        scan (`_seg_scan`), and read each group's total at the last
        row of its run.  Every key in [0, G) appears at least once
        (the state contributes all of them), so that row exists.
        """
        batch_keys, contribs, payload_of = self._sm_contribs(
            env, capacity, mask, ids, str_aux
        )
        return self._sm_combine(
            counts, accs, batch_keys, contribs, payload_of, str_aux
        )

    def _sm_combine(self, counts, accs, batch_keys, contribs, payload_of,
                    str_aux=()):
        """Merge (possibly multi-batch, concatenated) sort-merge
        contributions into the dense state — the sort + segmented-scan
        + compaction half of `_sortmerge_update`."""
        G = counts.shape[0]
        keys = jnp.concatenate([jnp.arange(G, dtype=jnp.int32), batch_keys])

        # payload columns: row count first, then one per non-aliased slot
        payloads = [jnp.concatenate([counts, contribs[0]])]
        combines = [jnp.add]
        for i, (sl, acc) in enumerate(zip(self.slots, accs)):
            p = payload_of.get(i)
            if p is None:
                continue
            if sl.is_string:
                # state codes convert to ranks on entry
                acc_rank = self._codes_to_ranks(sl.kind, acc, str_aux[i])
            else:
                acc_rank = acc
            payloads.append(jnp.concatenate([acc_rank, contribs[p]]))
            combines.append(
                jnp.add if sl.kind in ("sum", "cnt")
                else jnp.minimum if sl.kind in ("min", "smin")
                else jnp.maximum
            )

        # sort the 32-bit (key, row) pair only and gather the payloads
        # by the permutation: a variadic sort carrying every 64-bit
        # payload compiles for minutes on TPU (PERF.md, PR 21)
        skeys, perm = jax.lax.sort(
            (keys, jnp.arange(keys.shape[0], dtype=jnp.int32)), num_keys=1
        )
        start = jnp.concatenate(
            [jnp.ones(1, bool), skeys[1:] != skeys[:-1]]
        )
        reduced = self._seg_scan([p[perm] for p in payloads], start, combines)

        # every key in [0, G) occurs (the state contributes them all),
        # so group g's total sits at the last row holding key g — no
        # second, compacting sort
        ends = jnp.searchsorted(
            skeys, jnp.arange(G, dtype=jnp.int32), side="right"
        ) - 1
        new_counts = reduced[0][ends]
        out = [r[ends] for r in reduced[1:]]

        new_accs = []
        for i, (sl, acc) in enumerate(zip(self.slots, accs)):
            p = payload_of.get(i)
            if p is None:  # cnt aliased to the row count
                new_accs.append(acc + (new_counts - counts))
                continue
            val = out[p - 1]
            if sl.is_string:
                new_accs.append(self._ranks_to_codes(sl.kind, val, str_aux[i]))
            else:
                new_accs.append(val)
        return new_counts, tuple(new_accs)

    def _fused_group(self, entries, row_counts, state, aux, str_aux,
                     params):
        """ONE device launch for a whole batch group (exec/fused.py).

        entries: per-batch (cols, valids, mask|None, ids) pytrees with
        identical structure/shapes; row_counts: int32 [entries], their
        row counts (0 for a dead entry).  Dense-path
        capacities fold with `lax.scan` — the per-batch
        kernel body traces once, not once per batch.  Sort-merge
        capacities instead concatenate every batch's contribution
        columns and run ONE sort + segmented reduce for the whole
        group: n_batches fewer big sorts, the state concat amortized
        across the group (the BENCH_r04 high-cardinality regression was
        exactly per-batch state-sized sorts)."""
        from datafusion_tpu.exec.fused import stack_entries

        counts, _ = state
        G = counts.shape[0]
        if G <= DENSE_GROUP_MAX:
            stacked = stack_entries(entries)

            def body(st, x):
                (cols, valids, mask, ids), num_rows = x
                return self._kernel(
                    cols, valids, aux, num_rows, mask, ids, st, str_aux,
                    params,
                ), None

            state, _ = jax.lax.scan(body, state, (stacked, row_counts))
            return state

        keys_l, contribs_l = [], []
        payload_of: dict[int, int] = {}
        for (cols, valids, mask, ids), num_rows in zip(entries, row_counts):
            ids = _ids_of(ids)
            env = Env(cols, valids, aux, self.col_map, params)
            capacity = cols[0].shape[0] if cols else ids.shape[0]
            m = jnp.arange(capacity, dtype=jnp.int32) < num_rows
            if mask is not None:
                m = m & mask
            if self._pred_fn is not None:
                pv, pvalid = self._pred_fn(env)
                pv = jnp.broadcast_to(pv, (capacity,))
                if pvalid is not None:
                    pv = pv & jnp.broadcast_to(pvalid, (capacity,))
                m = m & pv
            bk, contribs, payload_of = self._sm_contribs(
                env, capacity, m, ids, str_aux
            )
            keys_l.append(bk)
            contribs_l.append(contribs)
        counts, accs = state
        batch_keys = jnp.concatenate(keys_l)
        cat = [
            jnp.concatenate([c[p] for c in contribs_l])
            for p in range(len(contribs_l[0]))
        ]
        return self._sm_combine(
            counts, accs, batch_keys, cat, payload_of, str_aux
        )

    def _multi_fused_group(self, entries, row_counts, states, aux, str_aux,
                           params_list):
        """ONE device launch for N queries × one batch group: the
        serving megabatch (serve.py).  Every query folds the SAME
        stacked entries and row counts — XLA shares the input plumbing
        across the N sub-folds — under its own literal params and
        accumulator state; results de-multiplex per query as a tuple of
        states."""
        return tuple(
            self._fused_group(entries, row_counts, st, aux, str_aux, ps)
            for st, ps in zip(states, params_list)
        )

    # -- numeric group keys born on the device ---------------------------
    # A join's probe output hands the aggregate key columns that exist
    # only on the device, with values no dictionary bounds.  Nothing of
    # such a batch goes to the host to be encoded: the rows the
    # predicate keeps are compacted on the device and appended, key
    # tuple and contributions, to a keyed buffer; one sort + segmented
    # reduce turns the buffer into groups (`_KeyedAccumulator` drives
    # the three programs).  An entry is (cols, valids, mask, key cols,
    # key valids), its row count in the group's counts vector
    # (exec/fused.pad_group); the state is (keys int64[B] a key, key
    # NULL flags bool[B] a key, counts int64[B], accumulators [B] a
    # slot); a row with count 0 is empty.

    def _live_rows(self, cols, valids, aux, num_rows, base_mask, kcols,
                   params):
        """The rows of one batch that the predicate keeps, as a mask."""
        env = Env(cols, valids, aux, self.col_map, params)
        capacity = kcols[0].shape[0]
        mask = jnp.arange(capacity, dtype=jnp.int32) < num_rows
        if base_mask is not None:
            mask = mask & base_mask
        if self._pred_fn is not None:
            pv, pvalid = self._pred_fn(env)
            pv = jnp.broadcast_to(pv, (capacity,))
            if pvalid is not None:
                pv = pv & jnp.broadcast_to(pvalid, (capacity,))
            mask = mask & pv
        return mask

    def _keyed_counts(self, entries, row_counts, aux, params):
        """The most rows the predicate keeps of any one batch of a
        group: all the host learns of them (4 B; it sizes the append's
        compaction)."""
        from datafusion_tpu.exec.fused import stack_entries

        def body(x):
            (cols, valids, mask, kcols, _), num_rows = x
            live = self._live_rows(cols, valids, aux, num_rows, mask, kcols,
                                   params)
            return jnp.sum(live, dtype=jnp.int32)

        return jnp.max(
            jax.lax.map(body, (stack_entries(entries), row_counts)))

    def _slot_identities(self):
        return [jnp.asarray(self._slot_identity(sl)) for sl in self.slots]

    def _keyed_rows(self, width, entries, row_counts, aux, params):
        """A batch group's kept rows as the keyed buffer holds them:
        of each batch the first `width` rows in kept-first order (a
        sort of row numbers; `width` holds every kept row of every
        batch of the group, so what is cut off is dead), the key tuple
        and one contribution a slot.  `width` equal to the batch
        capacity takes the batch as it lies."""
        from datafusion_tpu.exec.fused import stack_entries

        idents = self._slot_identities()

        def body(x):
            (cols, valids, mask, kcols, kvalids), num_rows = x
            capacity = kcols[0].shape[0]
            live = self._live_rows(cols, valids, aux, num_rows, mask, kcols,
                                   params)
            if width < capacity:
                rows = jnp.arange(capacity, dtype=jnp.int32)
                order = jax.lax.sort(jnp.where(live, rows, rows + capacity))
                idx = order[:width]
                idx = jnp.where(idx >= capacity, idx - capacity, idx)
                live = jnp.arange(width, dtype=jnp.int32) < jnp.sum(
                    live, dtype=jnp.int32)

                def take(a):
                    return None if a is None else a[idx]
            else:
                def take(a):
                    return a

            env = Env(tuple(take(c) for c in cols),
                      tuple(take(v) for v in valids), aux, self.col_map,
                      params)
            keys, knull = [], []
            for c, v in zip(kcols, kvalids):
                null = jnp.zeros(width, bool) if v is None else ~take(v)
                null = null & live
                knull.append(null)
                keys.append(jnp.where(live & ~null, take(c),
                                      jnp.zeros((), c.dtype)))
            accs = []
            for sl, ident, (v, ok) in zip(
                    self.slots, idents, self._slot_inputs(env, width, live)):
                if sl.kind == "cnt":
                    accs.append(ok.astype(jnp.int64))
                else:
                    accs.append(jnp.where(ok, v.astype(sl.acc_dtype), ident))
            return (tuple(keys), tuple(knull), live.astype(jnp.int64),
                    tuple(accs))

        return jax.tree.map(
            lambda rows: rows.reshape(-1),
            jax.lax.map(body, (stack_entries(entries), row_counts)))

    @staticmethod
    def _keyed_store(state, rows, offset):
        """`rows` written into the keyed buffer at `offset` (a program
        of its own, so that the one that makes the rows, whose sort
        compiles for a third of a minute, does not depend on the
        buffer's size)."""
        return jax.tree.map(
            lambda buf, new: jax.lax.dynamic_update_slice(
                buf, new, (offset,)),
            state, rows)

    def _keyed_fold(self, state):
        """The buffer's rows merged into groups where they lie: (the
        folded state, bool [rows]: the rows that hold a group's totals,
        int64 [groups, rows in them]).  A group's totals sit at the last
        row of its run of equal key tuples, so the rows have to be in
        key order: where they are already (a scan clustered by its keys,
        TPC-H's lineitem by `l_orderkey`), the fold is a few passes over
        the buffer and moves nothing; where they are not, it sorts them
        (empty rows last; word by word, `exec/wordsort.py`) and gathers
        every column by the permutation first.  A row that holds no
        rows (a dead tail, a padding entry) does not break the order: it
        takes the key tuple of the last row before it that does, and so
        joins that row's run with nothing to add."""
        keys, knull, counts, accs = state
        size = counts.shape[0]
        rows = jnp.arange(size, dtype=jnp.int32)
        first = rows == 0
        full = counts > 0
        cols = list(keys) + list(knull)
        filled = self._seg_scan(cols, full | first,
                                [lambda earlier, _: earlier] * len(cols))
        fkeys, fnull = filled[:len(keys)], filled[len(keys):]
        words = []
        for null, key in zip(fnull, fkeys):
            words += key_words(null) + key_words(key)
        # lexicographic: is any row's tuple below the one before it?
        below = jnp.zeros(size, bool)
        same = jnp.ones(size, bool)
        for w in words:
            prev = jnp.roll(w, 1)
            below = below | (same & (w < prev))
            same = same & (w == prev)
        ordered = ~jnp.any(below & ~first)

        def in_place(_):
            return (tuple(fkeys), tuple(fnull), counts, tuple(accs),
                    first | ~same)

        def sorted_(_):
            sort_words = key_words(~full)
            for null, key in zip(knull, keys):
                sort_words += key_words(null) + key_words(key)
            perm = lex_perm(sort_words)
            start = first
            for w in sort_words:
                w = w[perm]
                start = start | (w != jnp.roll(w, 1))
            return (tuple(k[perm] for k in keys),
                    tuple(n[perm] for n in knull), counts[perm],
                    tuple(a[perm] for a in accs), start)

        keys, knull, counts, accs, start = jax.lax.cond(
            ordered, in_place, sorted_, None)
        combines = [jnp.add] + [
            jnp.add if sl.kind in ("sum", "cnt")
            else jnp.minimum if sl.kind == "min" else jnp.maximum
            for sl in self.slots]
        totals = self._seg_scan([counts] + list(accs), start, combines)
        group = jnp.roll(start, -1).at[size - 1].set(True) & (totals[0] > 0)
        tally = jnp.stack([jnp.sum(group, dtype=jnp.int64),
                           jnp.sum(totals[0], where=group)])
        return (keys, knull, totals[0], tuple(totals[1:])), group, tally

    def _keyed_compact(self, folded, group):
        """A folded state's groups moved to the front (a sort of row
        numbers), the rows after them empty."""
        keys, knull, counts, accs = folded
        size = counts.shape[0]
        rows = jnp.arange(size, dtype=jnp.int32)
        order = jax.lax.sort(jnp.where(group, rows, rows + size))
        src = jnp.where(order >= size, order - size, order)
        live = rows < jnp.sum(group, dtype=jnp.int32)
        return (tuple(jnp.where(live, k[src], jnp.zeros((), k.dtype))
                      for k in keys),
                tuple(live & n[src] for n in knull),
                jnp.where(live, counts[src], 0),
                tuple(jnp.where(live, a[src], ident)
                      for a, ident in zip(accs, self._slot_identities())))

    def _keyed_reduce(self, state):
        """The buffer's rows merged into groups, first in the buffer:
        (state, int64 [groups, rows in them]): the fold and the
        compaction in one launch."""
        folded, group, tally = self._keyed_fold(state)
        return self._keyed_compact(folded, group), tally

    def having_programs(self, having: Expr, out_schema: Schema, functions):
        """(count, take) for a HAVING predicate over this core's output
        columns, or None where it needs a lookup table (a string
        compare) the device programs do not carry.  `count(folded,
        group, key_dtypes)` is how many groups the predicate keeps;
        `take(folded, group, key_dtypes, k)` their output columns, the
        first `k` rows (`_sparse_take`), each with a validity array
        that marks the rows past the kept ones invalid.  The predicate's
        literals are compiled in: one pair a predicate."""
        from datafusion_tpu.exec.kernels import schema_fingerprint

        key = (repr(having), schema_fingerprint(out_schema))
        hit = self._having_progs.get(key)
        if hit is not None or key in self._having_progs:
            return hit
        compiler = ExprCompiler(out_schema, functions)
        pred = compiler.compile(having)
        if compiler.aux_specs:
            self._having_progs[key] = None
            return None

        def keep_of(folded, group, key_dtypes):
            out_keys, out = self._outputs_of(folded, key_dtypes)
            outs = list(out_keys) + list(out)
            env = Env(tuple(v for v, _ in outs), tuple(v for _, v in outs),
                      ())
            pv, pvalid = pred(env)
            keep = group & jnp.broadcast_to(pv, group.shape)
            if pvalid is not None:
                keep = keep & jnp.broadcast_to(pvalid, group.shape)
            return keep, outs

        def count(folded, group, key_dtypes):
            return jnp.sum(keep_of(folded, group, key_dtypes)[0],
                           dtype=jnp.int64)

        def take(folded, group, key_dtypes, k):
            keep, outs = keep_of(folded, group, key_dtypes)
            src, ok = _sparse_take(keep, k)
            return tuple((v[src], ok if m is None else ok & m[src])
                         for v, m in outs)

        hit = self._having_progs[key] = (
            jax.jit(count, static_argnums=(2,)),
            jax.jit(take, static_argnums=(2, 3)))
        return hit

    def _keyed_outputs(self, state, key_dtypes, rows):
        """The output columns of a reduced keyed state's first `rows`
        rows, on the device: ((values, validity) a key, (values,
        validity) an aggregate), by
        `AggregateRelation._numeric_output`'s definitions."""
        return self._outputs_of(jax.tree.map(lambda a: a[:rows], state),
                                key_dtypes)

    def _outputs_of(self, state, key_dtypes):
        keys, knull, counts, accs = state
        out_keys = tuple(
            (k.astype(jnp.dtype(dt)), ~null)
            for k, null, dt in zip(keys, knull, key_dtypes))
        out = []
        for s in self.specs:
            ret = s.return_type.np_dtype
            cnts = counts if s.cnt_slot is None else accs[s.cnt_slot]
            if s.name == "sum":
                out.append((accs[s.sum_slot].astype(ret), cnts > 0))
            elif s.name == "avg":
                mean = accs[s.sum_slot].astype(jnp.float64) / jnp.maximum(
                    cnts, 1)
                out.append((mean.astype(ret), cnts > 0))
            elif s.name == "count":
                out.append((cnts.astype(ret), None))
            else:
                raw = accs[s.minmax_slot]
                ident = (_min_identity if s.name == "min"
                         else _max_identity)(np.dtype(raw.dtype))
                out.append((raw.astype(ret), raw != jnp.asarray(ident)))
        return out_keys, tuple(out)

    def _dense_update(self, env, capacity, mask, ids, counts, accs, str_aux=()):
        """Small-group path: segment reduction against a one-hot
        [rows, G] membership matrix.  Float sums and all counts stack
        into one [rows, S] block and reduce through a single masked
        broadcast-reduce (the fused-reduction spelling below — NOT a
        literal f64 dot, which TPU emulates catastrophically); int sums
        and min/max are fused broadcast-reduces over [rows, G].  Count
        columns whose ok-mask IS the row mask alias the row-count
        reduction row instead of duplicating it.  No scatter anywhere."""
        G = counts.shape[0]
        onehot_b = ids[:, None] == jnp.arange(G, dtype=ids.dtype)[None, :]
        inputs = self._slot_inputs(env, capacity, mask)

        # -- one fused reduction for every f-dtype sum slot + count column --
        mat_cols = [mask.astype(jnp.float64)]  # row 0: row count
        mat_row_of: dict[int, int] = {}  # slot index -> stacked-reduce row
        for i, (sl, (v, ok)) in enumerate(zip(self.slots, inputs)):
            if sl.kind == "sum" and sl.acc_dtype.kind == "f":
                mat_row_of[i] = len(mat_cols)
                mat_cols.append(jnp.where(ok, v, 0.0).astype(jnp.float64))
            elif sl.kind == "cnt":
                if ok is mask:
                    mat_row_of[i] = 0  # alias the row-count column
                else:
                    mat_row_of[i] = len(mat_cols)
                    mat_cols.append(ok.astype(jnp.float64))
        stacked = jnp.stack(mat_cols, axis=1)  # [rows, S]
        # [S, G] segment sums via a masked broadcast-reduce.  This IS
        # the one-hot contraction, but spelled so XLA fuses it as a
        # reduction: the literal f64 dot_general lowers on TPU to a
        # multi-pass bf16-split emulation through while-loops over
        # [rows, G]-sized scratch (~150 ms per fused launch on v5e for
        # the TPC-H Q1 shape vs ~1 ms for this form; HLO at
        # jit(_kernel)/dot_general pins it)
        sums = jnp.sum(
            jnp.where(onehot_b[:, None, :], stacked[:, :, None], 0.0),
            axis=0,
        )  # [S, G]

        new_counts = counts + sums[0].astype(jnp.int64)
        new_accs = []
        for i, (sl, (v, ok), acc) in enumerate(zip(self.slots, inputs, accs)):
            if sl.is_string:
                ranks, _ = str_aux[i]
                cap = ranks.shape[0]
                r = ranks[jnp.clip(v.astype(jnp.int32), 0, cap - 1)]
                sentinel = self._rank_sentinel(sl.kind)
                cell = jnp.where(onehot_b & ok[:, None], r[:, None], sentinel)
                batch_best = (
                    jnp.min(cell, axis=0)
                    if sl.kind == "smin"
                    else jnp.max(cell, axis=0)
                )
                new_accs.append(self._string_combine(sl.kind, acc, batch_best, str_aux[i]))
            elif sl.kind == "sum":
                if i in mat_row_of:
                    contrib = sums[mat_row_of[i]].astype(acc.dtype)
                else:
                    # integer sums: exact int64 broadcast-reduce (an
                    # f64 reduction would round above 2^53)
                    contrib = jnp.sum(
                        jnp.where(
                            onehot_b & ok[:, None], v[:, None].astype(acc.dtype), 0
                        ),
                        axis=0,
                    )
                new_accs.append(acc + contrib)
            elif sl.kind == "cnt":
                new_accs.append(acc + sums[mat_row_of[i]].astype(jnp.int64))
            else:
                ident = (
                    _min_identity(np.dtype(acc.dtype))
                    if sl.kind == "min"
                    else _max_identity(np.dtype(acc.dtype))
                )
                cell = jnp.where(
                    onehot_b & ok[:, None], v[:, None].astype(acc.dtype), ident
                )
                red = jnp.min(cell, axis=0) if sl.kind == "min" else jnp.max(cell, axis=0)
                new_accs.append(
                    jnp.minimum(acc, red) if sl.kind == "min" else jnp.maximum(acc, red)
                )
        return new_counts, tuple(new_accs)


_TAKE_BLOCK = 128


def _sparse_take(keep, k: int):
    """(indices, valid): the positions of the first `k` rows that `keep`
    marks, in order, and which of the `k` are real.  The rows are read
    as blocks of `_TAKE_BLOCK`: a sort of the block numbers finds the
    (at most `k`) blocks that hold a kept row and a sort of those
    blocks' `k` x `_TAKE_BLOCK` row numbers the rows, so a few kept rows
    of many cost two small sorts, not one of every row."""
    size = keep.shape[0]
    rows = jnp.arange(size, dtype=jnp.int32)
    if size % _TAKE_BLOCK or k * _TAKE_BLOCK >= size:
        order = jax.lax.sort(jnp.where(keep, rows, rows + size))[:k]
        ok = order < size
        return jnp.where(ok, order, 0), ok
    blocks = size // _TAKE_BLOCK
    kb = keep.reshape(blocks, _TAKE_BLOCK)
    nums = jnp.arange(blocks, dtype=jnp.int32)
    blk = jax.lax.sort(jnp.where(jnp.any(kb, axis=1), nums,
                                 nums + blocks))[:k]
    real = blk < blocks
    blk = jnp.where(real, blk, 0)
    flat = (kb[blk] & real[:, None]).reshape(-1)
    at = (blk[:, None] * _TAKE_BLOCK
          + jnp.arange(_TAKE_BLOCK, dtype=jnp.int32)[None, :]).reshape(-1)
    n = flat.shape[0]
    near = jnp.arange(n, dtype=jnp.int32)
    order = jax.lax.sort(jnp.where(flat, near, near + n))[:k]
    ok = order < n
    return at[jnp.where(ok, order, 0)], ok


class _DeviceKeys(NamedTuple):
    """What `_group_ids` hands back for a batch whose numeric key
    columns were born on the device: the columns themselves and their
    validity arrays, for the keyed programs (`_KeyedAccumulator`), in
    place of ids."""

    cols: tuple
    valids: tuple


class _KeyedState(NamedTuple):
    """A finished keyed accumulation: the device state and how many
    groups it holds; the groups are first in it, or, where `group` is
    not None, where the fold left them, at the rows `group` marks."""

    state: tuple
    n_groups: int
    group: object = None


class _KeyedAccumulator:
    """Drives one scan's keyed accumulation (`_AggregateCore._keyed_*`).

    Batches collect into groups of one shape class.  A group's count
    launch goes out at once and its answer (4 B a group) is read one
    flush later, so the host never waits on the device's queue in the
    middle of a scan; the append launch that follows is sized by the
    largest count (a power of two, at least `_MIN_WIDTH`).  The buffer
    holds rows, not groups, so the host always knows how full it is:
    where the next append would not fit, the reduce launch folds the
    rows into groups (one more scalar read) and the buffer grows if
    groups alone fill half of it.  The buffer is the ledger's (owner
    `agg.keyed`) and grows only into what `LEDGER.fits`: a scan whose
    groups find no room is refused (`_make_room`).  A scan that knows
    how many rows it will append (`reserve`: a resident table's) asks
    for a buffer that holds them all at its first append, so it folds
    once, at the end; where a batch can drop no row (no predicate, no
    mask) its count is its row count and no count launch goes out."""

    _MIN_WIDTH = 1024
    # batches between flushes: how many probe outputs a scan holds
    # while their counts travel
    _FLUSH_BATCHES = 32

    def __init__(self, rel: "AggregateRelation", core, params,
                 reserve: int = 0):
        self.rel = rel
        self.core = core
        self.params = params
        self.reserve = reserve
        self.chunk: list = []
        # (group, counts, aux, its largest count: a pending pull, or
        # the count)
        self.pending: list = []
        self.state = None
        self.capacity = 0
        self.used = 0
        self.offered = 0  # rows of the batches seen, kept or not
        self.key_dtypes: Optional[tuple] = None

    def add(self, data, validity, aux, num_rows, mask, keys: _DeviceKeys):
        if self.key_dtypes is None:
            self.key_dtypes = tuple(np.dtype(c.dtype) for c in keys.cols)
        self.offered += num_rows
        self.chunk.append(
            ((data, validity, mask, keys.cols, keys.valids), num_rows, aux))
        if len(self.chunk) >= self._FLUSH_BATCHES:
            self.flush()

    def _groups(self):
        """The chunk's batches by shape class (addition does not care
        in which order batches arrive), each padded to its rung:
        (entries, counts, aux)."""
        from datafusion_tpu.exec.fused import (
            entry_signature,
            pad_group,
            shared_signature,
        )

        classes: dict = {}
        for entry, num_rows, aux in self.chunk:
            sig = (entry_signature(entry), shared_signature(aux))
            entries, counts, _ = classes.setdefault(sig, ([], [], aux))
            entries.append(entry)
            counts.append(num_rows)
        self.chunk = []
        for entries, counts, aux in classes.values():
            yield (*pad_group(entries, counts), aux)

    def flush(self, drain: bool = False):
        from datafusion_tpu.exec.batch import device_pull_start
        from datafusion_tpu.exec.relation import device_scope
        from datafusion_tpu.obs.stats import op_timer

        # the groups counted by the flush before this one (at the end,
        # all of them): their counts have long arrived
        done = self.pending
        self.pending = []
        with op_timer(self.rel), device_scope(self.rel.device):
            with METRICS.timer("aggregate.device_key_ids"):
                for group, counts, aux in self._groups():
                    if self.core._pred_fn is None and all(
                            e[2] is None for e in group):
                        # every row of a batch is kept: its count is
                        # its row count
                        self.pending.append(
                            (group, counts, aux, int(counts.max())))
                        continue
                    most = device_call(
                        self.core.keyed_counts_jit, group, counts, aux,
                        self.params, _tag="agg.key_ids")
                    self.pending.append(
                        (group, counts, aux, device_pull_start(most)))
            if drain:
                done, self.pending = done + self.pending, []
            for group, counts, aux, pull in done:
                with METRICS.timer("aggregate.device_key_ids"):
                    most = pull if isinstance(pull, int) else int(
                        pull.finish())
                if most:
                    self._append(group, counts, aux, most)

    def _append(self, group, counts, aux, most: int):
        core = self.core
        capacity = group[0][3][0].shape[0]
        width = min(capacity, group_capacity(max(most, self._MIN_WIDTH)))
        need = len(group) * width
        if self.state is None and self.reserve >= need:
            self._make_room(self.reserve, exact=True)
        if self.used + need > self.capacity:
            if self.used:
                state, self.used, _ = self._reduce()
                self._hold(state)
            self._make_room(self.used + need)
        with METRICS.timer("execute.aggregate"):
            rows = device_call(
                core.keyed_rows_jit, width, group, counts, aux, self.params,
                _tag="agg.group")
            self._hold(device_call(
                core.keyed_store_jit, self.state, rows, np.int32(self.used),
                _tag="agg.store"))
        self.used += need

    def _hold(self, state) -> None:
        from datafusion_tpu.obs.device import LEDGER

        # the query's own, gone with it: not a cached copy
        self.state = LEDGER.adopt(state, owner="agg.keyed", cached=False)

    def _make_room(self, rows: int, exact: bool = False) -> None:
        """A buffer that holds `rows` rows: four times that where the
        device has the room (appends between reduces), down to the
        power of two that just holds them where it has not (every
        append then reduces first); refused where that finds no room
        either.  What is asked of the ledger is the new buffer and the
        reduce's sorted copy of it.  `exact`: the rows are all the scan
        will append (`reserve`), so no more is asked for, and a buffer
        that finds no room for them is simply not made yet."""
        from datafusion_tpu.obs.device import LEDGER

        if 2 * rows <= self.capacity:
            return
        core = self.core
        # a buffer row: each key and its NULL flag, the count, the slots
        row_bytes = sum(d.itemsize + 1 for d in self.key_dtypes) + 8 + sum(
            np.dtype(sl.acc_dtype).itemsize for sl in core.slots)
        if exact:
            if LEDGER.fits(2 * group_capacity(rows) * row_bytes):
                self._grow(group_capacity(rows))
            return
        want = group_capacity(4 * rows)
        least = max(self.capacity, group_capacity(rows))
        while want > least and not LEDGER.fits(2 * want * row_bytes):
            want >>= 1
        if want <= self.capacity:
            return
        if not LEDGER.fits(2 * want * row_bytes):
            raise ExecutionError(
                f"GROUP BY over join output: {rows:,} live rows and groups "
                f"need {2 * want * row_bytes:,} bytes of device memory for "
                f"their buffer, more than is free")
        self._grow(want)

    def _reduce(self, fold_only: bool = False):
        """(the state reduced to groups, how many, the rows in them);
        `fold_only`: ((the folded state, its group rows), ...), the
        groups left where the fold found them (`_keyed_fold`)."""
        from datafusion_tpu.exec.batch import device_pull

        with METRICS.timer("execute.aggregate"):
            if fold_only:
                folded, group, tally = device_call(
                    self.core.keyed_fold_jit, self.state, _tag="agg.reduce")
                state = (folded, group)
            else:
                state, tally = device_call(
                    self.core.keyed_reduce_jit, self.state, _tag="agg.reduce")
            groups, rows = (int(x) for x in device_pull(tally))
        # what the reduce read: of each row that holds anything, its
        # keys and its contributions
        METRICS.add("aggregate.reduce.rows", rows)
        METRICS.add("aggregate.reduce.bytes", rows * self._row_input_bytes())
        return state, groups, rows

    def _row_input_bytes(self) -> int:
        """Of one kept row, the bytes of its key columns and of the
        columns its aggregates are made from."""
        read = set(self.core.key_cols)
        for spec in self.core.specs:
            if not spec.count_star:
                spec.arg.collect_columns(read)
        schema = self.rel.child.schema
        return sum(schema.field(i).data_type.np_dtype.itemsize for i in read)

    def _grow(self, capacity: int):
        """The buffer at `capacity` rows, the new ones empty."""
        core = self.core
        if self.state is None:
            # from the smallest dense state (the core keeps that one)
            self.capacity = group_capacity(1)
            n_keys = len(core.key_cols)
            self.state = (tuple(jnp.zeros(self.capacity, d)
                                for d in self.key_dtypes),
                          (jnp.zeros(self.capacity, bool),) * n_keys,
                          *core._init_state(self.capacity))
        keys, knull, counts, accs = self.state
        pad = capacity - self.capacity
        self._hold((
            tuple(jnp.concatenate([k, jnp.zeros(pad, k.dtype)]) for k in keys),
            tuple(jnp.concatenate([n, jnp.zeros(pad, bool)]) for n in knull),
            *core._grow_state((counts, accs), capacity)))
        self.capacity = capacity

    def finish(self, fold_only: bool = False) -> _KeyedState:
        """The scan's groups; `fold_only`: left where the fold found
        them (a HAVING over them picks its few), else first."""
        self.flush(drain=True)
        if self.state is None:
            self._grow(group_capacity(1))
        state, groups, rows = self._reduce(fold_only)
        METRICS.add("aggregate.device_key.offered", self.offered)
        METRICS.add("aggregate.device_key.rows", rows)
        METRICS.add("aggregate.device_key.groups", groups)
        METRICS.add("aggregate.device_key.input_bytes",
                    rows * self._row_input_bytes())
        if fold_only:
            return _KeyedState(state[0], groups, state[1])
        return _KeyedState(state, groups)


class AggregateRelation(Relation):
    """Executes [Selection +] Aggregate over a child relation in one
    fused kernel; emits a single result batch.

    Group expressions must be column references over the child schema
    (the planner produces exactly that shape today).  The compiled
    core — specs, slots, predicate closure, jitted kernel — is shared
    process-wide across relations with the same plan fingerprint.
    """

    def __init__(
        self,
        child: Relation,
        group_expr: list[Expr],
        aggr_expr: list[Expr],
        out_schema: Schema,
        predicate: Optional[Expr] = None,
        functions=None,
        device=None,
    ):
        self.child = child
        self._schema = out_schema
        self.device = device
        self._functions = functions
        # HAVING over this aggregate's output (`set_having`)
        self._having: Optional[Expr] = None
        self._having_progs = None
        # whether this scan's host batches fold their keys on the device
        # (`_wide_keys`), decided when the scan starts
        self._wide = False
        from datafusion_tpu.exec.hostfn import host_evaluable
        from datafusion_tpu.exec.relation import _is_accelerator

        # Where the predicate is evaluated.  The host takes a numpy-
        # evaluable predicate only where that saves a transfer: on an
        # accelerator, over a source whose batches are streamed and die
        # after the kernel read them.  There its input columns don't
        # travel at all and its mask rides bit-packed with the columns
        # the kernel does read (the Q1 shipdate filter drops ~12 MB of
        # dict codes per SF-1 scan to a 0.75 MB mask), and the core is
        # built as if there were no predicate, so every host-filtered
        # query shape shares one device kernel whatever its literals.
        # Over a source that hands the SAME batches to every query
        # (`_keeps_batches`) there is nothing to save: the predicate's
        # columns are shipped once with the others and found on the
        # batch by every later query (`device_inputs`), while a host
        # mask would be evaluated, put and decoded a batch a query for
        # ever.  So there, as for a child whose batches are born on the
        # device (a join's probe output: the host could only read them
        # by pulling every batch back) and under `_FORCE_CORE_PRED`
        # (serving), the predicate goes to the core and its literals
        # travel as parameter slots and aux tables.
        # No function metas reach this ctor, so predicates containing
        # UDFs conservatively stay on device ({} finds no host_fn).
        host_pred = (
            predicate is not None
            and _is_accelerator(device)
            and not _FORCE_CORE_PRED.get()
            and not getattr(child, "device_batches", False)
            and not self._keeps_batches()
            and host_evaluable(predicate, {}, child.schema)
        )
        self._host_pred_expr = predicate if host_pred else None
        core_pred = None if host_pred else predicate
        self._core_pred = core_pred
        self._group_expr = list(group_expr)
        self.core = _AggregateCore.build(
            child.schema, list(group_expr), list(aggr_expr), core_pred,
            functions,
        )
        # THIS query's literal values for the shared core's parameter
        # slots (identical fingerprints guarantee identical slot order)
        from datafusion_tpu.exec.kernels import parameterize_exprs

        self._params = parameterize_exprs(
            _AggregateCore.param_exprs(core_pred, list(aggr_expr))
        )[2]
        self.key_cols = self.core.key_cols
        self.specs = self.core.specs
        self.slots = self.core.slots
        self._aux_specs = self.core.aux_specs
        self._jit = self.core.jit
        self._aux_cache: dict = {}
        self.encoder = GroupKeyEncoder(len(self.key_cols))
        self._key_dicts: dict[int, StringDictionary] = {}
        self._str_dicts: dict[int, StringDictionary] = {}
        self._str_aux_cache: dict = {}
        # feedback-driven planning (datafusion_tpu/cost): the plan->
        # operator boundary fills these when the scanned table has
        # learned statistics — `_cost_hint` (estimated group count)
        # pre-sizes the accumulator at first flush, `_cost_obs`
        # ((table key, shape)) says where finalize() records actuals
        self._cost_hint: Optional[int] = None
        self._cost_obs: Optional[tuple] = None
        self._cost_planned_cap = 0
        self._cost_replans = 0
        # serializes GroupKeyEncoder mutation: normally only the staging
        # producer encodes, but a cache-pin miss (another relation
        # scanning the same batches overwrote the group_ids slot) makes
        # the consumer re-encode concurrently with the producer
        from datafusion_tpu.analysis import lockcheck

        self._ids_lock = lockcheck.make_lock("exec.aggregate_ids")

    def set_having(self, expr: Expr) -> None:
        """A HAVING predicate (over this aggregate's output) that the
        keyed fold applies to its groups on the device before they leave
        (`having_programs`: only the groups it keeps are compacted and
        handed on).  A pre-filter: the Selection above filters the
        output all the same (`ExecutionContext._selection_input`), so
        the other routes ignore it."""
        self._having = expr
        self._having_progs = self.core.having_programs(
            expr, self._schema, self._functions)

    def _wide_keys(self) -> int:
        """The rows the whole scan appends where its host batches fold
        their keys on the device (0 where they go to the host encoder):
        a table that keeps its batches (their key columns go to the
        device once, like any other column), every key an integer or a
        dictionary code, no string MIN / MAX, and a key space wide for
        the table (`WIDE_KEY_SPAN`, `WIDE_KEY_SHARE`)."""
        if (not self.key_cols or any(sl.is_string for sl in self.slots)
                or not self._keeps_batches()):
            return 0
        ds = getattr(self.child, "datasource", None)
        batches = list(ds.batches()) if ds is not None else []
        if not batches:
            return 0
        space = 1
        for i in self.key_cols:
            if batches[0].dicts[i] is not None:
                span = batches[0].dicts[i].version
            elif np.dtype(self.child.schema.field(i).data_type.np_dtype
                          ).kind in "iu":
                bounds = [b for b in map(lambda b: _key_bounds(b, i), batches)
                          if b is not None]
                span = (max(h for _, h in bounds) - min(l for l, _ in bounds)
                        + 1) if bounds else 1
            else:
                return 0
            space *= span + 1  # and NULL
        rows = sum(b.num_rows for b in batches)
        if space < max(WIDE_KEY_SPAN, rows // WIDE_KEY_SHARE):
            return 0
        return sum(b.capacity for b in batches)

    def _keeps_batches(self) -> bool:
        """Whether every scan of the input (`op_children`: the child,
        the mesh relation's one a partition) hands out the SAME batch
        objects (`datasource.reusable_batches`: an in-memory table, a
        resident pin and their projections, the ingest tables), so
        what `device_inputs` and `_group_ids` leave on a batch is found
        again by the next query.  Read from the scanned sources and
        nothing else; a relation that is no plain scan (a pipeline, a
        sort, a host-probed join) makes new batches a query."""
        scans = self.op_children()
        return bool(scans) and all(
            getattr(getattr(c, "datasource", None), "reusable_batches", False)
            for c in scans
        )

    # -- delegates into the shared core (the partitioned subclass and
    # the multi-host coordinator call these by name) --
    def _kernel(self, *args):
        return self.core._kernel(*args)

    def _slot_identity(self, sl: _Slot):
        return self.core._slot_identity(sl)

    @staticmethod
    def _codes_to_ranks(kind, codes, str_aux_k):
        return _AggregateCore._codes_to_ranks(kind, codes, str_aux_k)

    @staticmethod
    def _ranks_to_codes(kind, best, str_aux_k):
        return _AggregateCore._ranks_to_codes(kind, best, str_aux_k)

    def _init_state(self, capacity: int):
        return self.core._init_state(capacity)

    def _grow_state(self, state, new_capacity: int):
        return self.core._grow_state(state, new_capacity)

    def _compute_str_aux(self, batch: RecordBatch, slots=None):
        """(ranks, rank->code) pair per string min/max slot, padded to a
        bucketed capacity, cached per dictionary version."""
        out = []
        for k, sl in enumerate(self.slots if slots is None else slots):
            if not sl.is_string:
                out.append(None)
                continue
            d = batch.dicts[sl.arg_index]
            if d is None:
                raise ExecutionError(
                    f"column {sl.arg_index} has no dictionary for {sl.kind}"
                )
            self._str_dicts[k] = d
            key = (k, d.version)
            hit = self._str_aux_cache.get(key)
            if hit is None:
                ranks = d.sort_ranks().astype(np.int32)
                order = np.argsort(ranks).astype(np.int32)  # rank -> code
                cap = bucket_capacity(max(len(ranks), 1))
                pr = np.zeros(cap, np.int32)
                pr[: len(ranks)] = ranks
                po = np.zeros(cap, np.int32)
                po[: len(order)] = order
                hit = (pr, po)
                self._str_aux_cache[key] = hit
            out.append(hit)
        return tuple(out)

    @property
    def schema(self) -> Schema:
        return self._schema

    def _pick_capacity(self, current: int) -> int:
        """Accumulator capacity for the observed group count.  Tight
        power-of-two steps while the dense reduce path applies (small G
        keeps the one-hot matrix small); once past DENSE_GROUP_MAX,
        grow with 4x headroom jumps — each distinct capacity compiles a
        fresh sort-merge kernel (two large sorts, expensive to build),
        so the growth ladder must be short."""
        n = max(self.encoder.num_groups, 1)
        needed = group_capacity(n)
        if needed <= max(current, DENSE_GROUP_MAX):
            return max(needed, current)
        return group_capacity(4 * n)

    # -- feedback-driven sizing (datafusion_tpu/cost) -------------------
    def _cost_presize(self, needed: int) -> int:
        """First-flush capacity under a learned group-count hint.

        Normally returns the hint's capacity (>= the chunk's actual
        need), committing to the final route up front.  But the hint is
        checked against the chunk's ALREADY-ENCODED group count first —
        host-side facts, no device work yet — and a miss beyond the
        configured ratio in either direction aborts the pre-sized plan:
        the corrected cardinality is recorded immediately and the
        capacity re-derives from actuals, exactly as a cold run would.
        """
        hint = self._cost_hint
        if not hint:
            return needed
        from datafusion_tpu import cost as _cost

        planned = group_capacity(int(hint))
        actual = max(self.encoder.num_groups, 1)
        ratio = _cost.replan_ratio()
        if planned > needed * ratio or actual > int(hint) * ratio:
            self._note_replan(
                int(hint), actual,
                f"pre-size {planned} aborted, capacity {needed} from actuals",
            )
            return needed
        self._cost_planned_cap = max(planned, needed)
        return self._cost_planned_cap

    def _cost_misestimate(self, needed: int) -> None:
        """A later flush outgrew the pre-sized capacity: the estimate
        undershot.  Record the replan once; growth itself proceeds on
        the normal 4x-headroom ladder."""
        self._cost_planned_cap = 0
        self._note_replan(
            int(self._cost_hint or 0), self.encoder.num_groups,
            f"pre-sized accumulator outgrown, regrow to {needed}",
        )

    def _note_replan(self, estimate: int, actual: int, action: str) -> None:
        from datafusion_tpu import cost as _cost
        from datafusion_tpu.obs import recorder

        self._cost_replans += 1
        METRICS.add("plan.replans")
        recorder.record(
            "query.replan", op="aggregate", estimate=estimate,
            actual=actual, action=action,
        )
        store = _cost.store()
        if self._cost_obs is not None:
            # corrected stats land NOW, not at finalize: a query that
            # fails after the replan still teaches the next one
            store.observe(self._cost_obs[0], self._cost_obs[1],
                          groups=actual)
        store.note_replan("aggregate.capacity", estimate, actual, action)

    def _cost_observe_done(self) -> None:
        """Finalize-time observation: actual group cardinality for the
        (table, GROUP BY shape) this relation was annotated with.
        Lock-free store write; no-op for unannotated relations."""
        obs = self._cost_obs
        if obs is None or not self.key_cols or not self.encoder.num_groups:
            return
        from datafusion_tpu import cost as _cost

        _cost.store().observe(obs[0], obs[1], groups=self.encoder.num_groups)

    def accumulate(self):
        """Run the scan, returning the partial-aggregate device state
        (counts, accumulators), indexed by the encoder's dense ids.

        Partitioned mode calls this per shard and combines states with
        collectives; a worker takes it apart for the coordinator;
        single-device mode (`batches`) finalizes its own scan directly.
        """
        return self._scan(keyed=False)

    def _scan(self, keyed: bool):
        """`accumulate`; with `keyed`, a scan whose numeric group keys
        are born on the device comes back as a `_KeyedState` instead
        (`_device_key_columns`), for `_finalize_keyed` alone."""
        from datafusion_tpu.obs.stats import iter_stats

        # serving megabatch (serve.py): the cross-query fused launch
        # already produced this relation's state — consume it so the
        # normal batches()/finalize path (result capture, telemetry)
        # runs unchanged on top
        injected = self.__dict__.pop("_injected_state", None)
        if injected is not None:
            return injected

        self._adopt_source_state()
        self._wide = self._wide_keys() if keyed else 0
        return self._accumulate_core(
            iter_stats(self.child), self.core, self._params, keyed=keyed
        )

    def _adopt_source_state(self) -> None:
        """Swap this relation's per-query execution state for the one
        its scan's source keeps (`datasource.SharedScanState`) where
        the source hands out the same batches to every query: the
        append-only encoder shared by every query over these GROUP BY
        columns owns the per-batch group-id caches, so ids encoded (and
        uploaded) by ANY earlier query over the table replay for this
        one.  Ids are encoded over all rows and do not depend on the
        predicate (which such a source leaves in the core); groups with
        no surviving row stay out of the answer through the live
        counts."""
        ds = getattr(self.child, "datasource", None)
        owner = getattr(ds, "shared_state_for", None)
        if owner is None or not self._keeps_batches():
            return
        entry = owner(self.core)
        self.encoder = entry["encoder"]
        self._aux_cache = entry["aux"]
        self._str_aux_cache = entry["str_aux"]
        self._ids_lock = entry["lock"]

    def _share_state_of(self, other: "AggregateRelation") -> None:
        """Run on `other`'s encoder, caches and lock (the relations of
        one megabatch: their ids must agree, pinned table or not)."""
        self.encoder = other.encoder
        self._aux_cache = other._aux_cache
        self._str_aux_cache = other._str_aux_cache
        self._ids_lock = other._ids_lock

    def _accumulate_core(self, batches, core, params, keyed: bool = False):
        """The scan loop over one device core: stage, group, launch.
        `keyed`: whether batches that offer `_device_key_columns` go to
        a `_KeyedAccumulator` (the result is then a `_KeyedState`)."""
        from datafusion_tpu.exec.prefetch import pipeline_enabled, staged_pipeline
        from datafusion_tpu.exec.relation import device_scope
        from datafusion_tpu.obs.stats import op_timer

        if pipeline_enabled(self.device):
            # producer thread runs all host prep for batch N+1 (group-id
            # encode, aux tables, wire encode + H2D dispatch) while the
            # consumer below dispatches batch N's kernel; results land
            # in batch.cache / relation caches and are re-read as hits
            def _stage(b):
                if not keyed or self._device_key_columns(b) is None:
                    self._group_ids(b)
                # pin the aux tables computed NOW on the batch: global
                # dictionaries keep growing while later batches parse,
                # so a consumer-side recompute could see a bigger table
                # (correct, but a fresh padded shape => kernel recompile).
                # The owning core rides in the entry (like group_ids'
                # encoder pin) so another relation on the same long-
                # lived batch can never consume this one's aux.
                b.cache["staged_aux"] = (
                    core,
                    tuple(compute_aux_values(core.aux_specs, b, self._aux_cache)),
                    self._compute_str_aux(b, core.slots),
                )
                self._device_inputs(b, core)

            batches = staged_pipeline(batches, _stage)

        from datafusion_tpu.exec.fused import (
            fuse_group_max,
            iter_groups,
            pad_group,
        )

        # batches per flush: prepared inputs accumulate host-side and
        # dispatch as whole batch GROUPS — maximal runs of batches with
        # one shape class — one launch each
        fuse = fuse_group_max()

        state = None
        capacity = 0
        chunk: list = []
        by_key: Optional[_KeyedAccumulator] = None

        def dispatch_chunk(state):
            if len(chunk) == 1:
                c = chunk[0]
                return device_call(
                    core.jit, c[0], c[1], c[2], c[3], c[4], c[5], state,
                    c[6], params, _tag="agg",
                )
            # one launch per shape-homogeneous batch group, padded to
            # the group-size ladder with zero-row (identity) entries so
            # scans of any length reuse a small set of compiled programs
            entries = [(c[0], c[1], c[4], c[5]) for c in chunk]
            shareds = [(c[2], c[6]) for c in chunk]
            for idxs, (aux, str_aux) in iter_groups(entries, shareds):
                if len(idxs) == 1:
                    c = chunk[idxs[0]]
                    state = device_call(
                        core.jit, c[0], c[1], c[2], c[3], c[4], c[5],
                        state, c[6], params, _tag="agg",
                    )
                    continue
                group, counts = pad_group(
                    [entries[i] for i in idxs], [chunk[i][3] for i in idxs])
                METRICS.add("fused.groups")
                METRICS.add("fused.group_batches", len(idxs))
                state = device_call(
                    core.group_jit, group, counts, state, aux, str_aux,
                    params, _tag="agg.group",
                )
            return state

        def flush():
            nonlocal state, capacity
            if not chunk:
                return
            # capacity picked AFTER the whole chunk's keys are encoded,
            # so every id in the chunk fits the accumulator
            needed = self._pick_capacity(capacity)
            if state is None:
                # learned-cardinality pre-size (datafusion_tpu/cost):
                # jump straight to the final capacity — and with it the
                # dense/sort-merge route — instead of climbing
                # the regrow ladder (each rung past the dense bound
                # compiles a fresh sort-merge kernel).  The check
                # against the chunk's already-encoded actuals happens
                # HERE, before any device launch: a wild misestimate
                # aborts the pre-sized plan while it is still cheap
                needed = self._cost_presize(needed)
                capacity = needed
                state = core._init_state(capacity)
            elif needed > capacity:
                if 0 < getattr(self, "_cost_planned_cap", 0) < needed:
                    self._cost_misestimate(needed)
                state = core._grow_state(state, needed)
                capacity = needed
            with METRICS.timer("execute.aggregate"), op_timer(self), \
                    device_scope(self.device):
                state = dispatch_chunk(state)
            if self._op_stats is not None:
                self.stats.attrs["fused_batches"] = (
                    self.stats.attrs.get("fused_batches", 0) + len(chunk)
                )
            chunk.clear()

        for batch in batches:
            for idx in self.key_cols:
                if batch.dicts[idx] is not None:
                    self._key_dicts[idx] = batch.dicts[idx]
            keys = self._device_key_columns(batch) if keyed else None
            ids = self._group_ids(batch) if keys is None else None
            staged = batch.cache.get("staged_aux")
            if staged is not None and staged[0] is core:
                _, aux, str_aux = staged
            else:
                aux = compute_aux_values(core.aux_specs, batch, self._aux_cache)
                str_aux = self._compute_str_aux(batch, core.slots)
            with device_scope(self.device):
                data, validity, mask = self._device_inputs(batch, core)
            if keys is not None:
                if by_key is None:
                    by_key = _KeyedAccumulator(self, core, params,
                                               reserve=self._wide)
                by_key.add(data, validity, tuple(aux), batch.num_rows, mask,
                           keys)
                continue
            chunk.append(
                (data, validity, tuple(aux), np.int32(batch.num_rows), mask,
                 ids, str_aux)
            )
            if len(chunk) >= fuse:
                flush()
        if by_key is not None:
            if state is not None or chunk:
                raise ExecutionError(
                    "a scan handed the aggregate its numeric group keys "
                    "both on the device and on the host")
            return by_key.finish(fold_only=self._having_progs is not None)
        flush()
        if state is None:
            state = core._init_state(group_capacity(1))
        return state

    def _device_view(self, batch: RecordBatch, core=None) -> RecordBatch:
        """The batch's columns as the device kernel sees them: only
        `used_cols` (group keys travel as dense ids, the inputs of a
        host-evaluated predicate not at all, those of a predicate in
        the core like any other column).  The view says nothing of any query's
        literals: it is the batch itself or the `subset_view` cached on
        it, the SAME object for every relation over this batch, so the
        device copies `device_inputs` caches on it belong to the
        table's batch, live as long as it does, and serve every later
        or concurrent query whatever its predicate.  A long-lived batch
        keeps one view (and its copies) per distinct used-column set —
        bounded by query-shape diversity; pin eviction clears the whole
        cache when HBM needs the room.  A streamed scan's host-
        evaluated predicate is not in the view: `_query_mask` /
        `_device_inputs`."""
        from datafusion_tpu.exec.batch import subset_view

        core = self.core if core is None else core
        return subset_view(batch, core.used_cols, tag="agg_subset")

    def _query_mask(self, batch: RecordBatch) -> Optional[np.ndarray]:
        """THIS query's host-evaluated predicate over one batch, as a
        numpy bool array (None without one: always over a source that
        keeps its batches, `_keeps_batches`).  It carries the query's
        literals, so it belongs to the relation, never to the batch."""
        if self._host_pred_expr is None:
            return None
        from datafusion_tpu.exec.hostfn import host_pred_mask

        return host_pred_mask(self._host_pred_expr, batch, {})

    def _device_inputs(self, batch: RecordBatch, core):
        """(data, validity, mask) on the device for one batch of this
        query.  Data and validity come from the literal-independent
        `_device_view`: shipped once per long-lived batch, after which
        a query over it ships nothing (its predicate is in the core);
        a streamed batch ships a query, its host-evaluated mask riding
        in the columns' one `put_compressed` call.  The result sits in
        ONE slot on the batch, pinned by relation and core (a streamed
        batch's mask carries this query's literals), so the consumer
        re-reads what the staging thread placed and a long-lived batch
        holds one entry, not one per query ever run; another
        relation's staging overwrites the slot and this one then asks
        `device_inputs` again, which finds the copies.  The pin is a
        weak reference: a resident batch must not keep every last
        relation (and, through its scan, itself) alive."""
        from datafusion_tpu.exec.batch import device_inputs

        slot = batch.cache.get("agg_inputs")
        if slot is not None and slot[0]() is self and slot[1] is core:
            return slot[2]
        out = device_inputs(
            self._device_view(batch, core), self.device, core.wire_hints,
            query_mask=self._query_mask(batch),
        )
        batch.cache["agg_inputs"] = (weakref.ref(self), core, out)
        return out

    def _ids_slot(self, device):
        # by device, as `device_inputs`' slot is: a batch scanned as a
        # shard of a mesh after a single-device query (or before one)
        # holds its ids where each of them wants them
        return ("group_ids", tuple(self.key_cols),
                None if device is None else repr(device))

    def _group_ids(self, batch: RecordBatch, device=None):
        """Dense group ids for one batch, as the device array.  Cached
        on the batch (keyed by this relation's encoder) so re-scanned
        in-memory batches skip both the host encode and the H2D
        transfer.  `device`: where they go instead of `self.device`
        (the mesh relation places a shard's batch on the shard's chip).

        Serialized by `_ids_lock`: the staging producer thread normally
        does all encoding, but a pin miss (another relation's encode
        overwrote the batch's slot) routes the consumer thread here
        concurrently, and GroupKeyEncoder mutation is not atomic."""
        # one slot per batch, GROUP BY column set and device (a
        # different encoder over the same keys overwrites it), so a
        # long-lived batch holds one id array per key set, not one per
        # query ever run; the entry pins the encoder so the identity
        # check can't hit a recycled object
        if device is None:
            device = self.device
        hit = batch.cache.get(self._ids_slot(device))
        if hit is not None and hit[0] is self.encoder:
            return hit[1]
        with self._ids_lock, METRICS.timer("aggregate.group_ids"):
            return self._group_ids_locked(batch, device)

    def _group_ids_locked(self, batch: RecordBatch, device):
        slot = self._ids_slot(device)
        hit = batch.cache.get(slot)
        if hit is not None and hit[0] is self.encoder:
            return hit[1]
        if self.key_cols:
            ids = self._device_group_ids(batch)
            if ids is not None:
                batch.cache[slot] = (self.encoder, ids)
                return ids
            pulled = [a for idx in self.key_cols
                      for a in (batch.data[idx], batch.validity[idx])
                      if a is not None and not isinstance(a, np.ndarray)]
            if pulled:
                # key columns born on the device that neither device
                # path serves (`_device_key_columns`, `_device_group_ids`)
                # come back to be encoded
                nbytes = sum(int(a.nbytes) for a in pulled)
                METRICS.add("aggregate.key_pull.bytes", nbytes)
                METRICS.add("d2h.bytes", nbytes)
            key_cols = [np.asarray(batch.data[idx]) for idx in self.key_cols]
            key_valids = [
                None if batch.validity[idx] is None else np.asarray(batch.validity[idx])
                for idx in self.key_cols
            ]
            ids_np = self.encoder.encode(key_cols, key_valids)
        else:
            ids_np = np.zeros(batch.capacity, dtype=np.int32)
        # ship ids in the narrowest width that holds the group count and
        # widen on device (H2D bytes 4x/2x smaller for the common small-
        # cardinality GROUP BY); pointless when the target is the host
        # platform itself (no link — see batch._wire_enabled)
        from datafusion_tpu.exec.batch import _wire_enabled

        wire = ids_np
        n_groups = self.encoder.num_groups
        if _wire_enabled(device):
            if n_groups <= 127:
                wire = ids_np.astype(np.int8)
            elif n_groups <= 32767:
                wire = ids_np.astype(np.int16)
        from datafusion_tpu.obs.device import LEDGER

        dev_wire = (
            LEDGER.put(wire, device, owner="agg.ids")
            if device is not None
            else LEDGER.adopt(jnp.asarray(wire), owner="agg.ids")
        )
        ids = (
            dev_wire
            if wire.dtype == np.int32
            else LEDGER.adopt(_WIDEN_IDS_JIT(dev_wire), owner="agg.ids")
        )
        batch.cache[slot] = (self.encoder, ids)
        return ids

    def _device_key_columns(self, batch: RecordBatch):
        """The key columns of a batch for the keyed programs
        (`_KeyedAccumulator`: no column, id or mask of the batch crosses
        the link), else None.  A batch born on the device (a join's
        probe output) offers them where every key is a number or a
        dictionary code, unless all are codes of a space the dense ids
        serve (`_device_group_ids`); a dictionary code groups as its
        string, the dictionary being one object for the scan.  A host
        batch offers its key columns' device copies (shipped once, a
        table that keeps its batches) where the scan folds on the device
        (`_wide_keys`).  String MIN / MAX ride dictionary ranks the keyed
        state does not carry: such an aggregate is encoded on the host.
        Only `batches` asks (`_scan(keyed=True)`): a worker's fragment
        and the mesh merge dense states by the encoder's ids."""
        cols = tuple(batch.data[i] for i in self.key_cols)
        if not cols or any(sl.is_string for sl in self.slots):
            return None
        if any(isinstance(c, np.ndarray) for c in cols):
            if not self._wide:
                return None
            from datafusion_tpu.exec.batch import device_inputs, subset_view

            data, valids, _ = device_inputs(
                subset_view(batch, self.key_cols, tag="agg_keys"),
                self.device)
            return _DeviceKeys(tuple(data), tuple(valids))
        if any(c.dtype.kind not in "iubf" for c in cols):
            return None
        dicts = [batch.dicts[i] for i in self.key_cols]
        if all(d is not None for d in dicts) and int(np.prod(
                [d.version + (batch.validity[i] is not None)
                 for d, i in zip(dicts, self.key_cols)])) <= DENSE_GROUP_MAX:
            return None
        return _DeviceKeys(cols, tuple(batch.validity[i]
                                       for i in self.key_cols))

    def _device_group_ids(self, batch: RecordBatch):
        """What the kernels make group ids from (`_ids_of`) for a batch
        whose key columns were born on the device (a join's probe
        output), or None where the host encode has to do it.  Every
        key has to be dictionary coded, which bounds its values: the
        encoder is shown the whole key space once, on the host (at
        most `DENSE_GROUP_MAX` tuples, the dense kernel's own reach,
        so the accumulator is no larger than those keys could make it;
        tuples no row has stay out of the answer through the live
        counts), and the launch that folds a batch turns its codes
        into ids by that table: no key column comes back to the host
        and no launch is added."""
        cols = tuple(batch.data[i] for i in self.key_cols)
        dicts = [batch.dicts[i] for i in self.key_cols]
        if any(isinstance(c, np.ndarray) for c in cols) or any(
                d is None for d in dicts):
            return None
        valids = tuple(batch.validity[i] for i in self.key_cols)
        sizes = tuple(d.version for d in dicts)
        # a nullable key has one more value, NULL, coded past the last
        radices = tuple(n + (v is not None) for n, v in zip(sizes, valids))
        space = int(np.prod(radices))
        if not 0 < space <= DENSE_GROUP_MAX:
            return None
        key = ("device_ids", radices, sizes)
        hit = self._aux_cache.get(key)
        if hit is None:
            from datafusion_tpu.obs.device import LEDGER

            codes = np.indices(radices).reshape(len(radices), space)
            lut = self.encoder.encode(
                [np.minimum(c, n - 1).astype(np.int32)
                 for c, n in zip(codes, sizes)],
                [None if r == n else c < n
                 for c, r, n in zip(codes, radices, sizes)],
            )
            table = np.concatenate([
                np.array([radices, sizes], np.int32).T.ravel(),
                np.asarray(lut, np.int32)])
            hit = self._aux_cache[key] = (
                LEDGER.put(table, self.device, owner="agg.ids")
                if self.device is not None
                else LEDGER.adopt(jnp.asarray(table), owner="agg.ids"))
        return cols, valids, hit

    @staticmethod
    def _numeric_output(s: AggregateSpec, sums, cnts, live_counts):
        """(values, validity) for a SUM/AVG/COUNT spec from its summed
        and counted per-group arrays — THE definition of these
        aggregates' value/null semantics."""
        if s.name in ("sum", "avg"):
            if s.name == "sum":
                vals = sums.astype(s.return_type.np_dtype)
            else:
                vals = (sums.astype(np.float64) / np.maximum(cnts, 1)).astype(
                    s.return_type.np_dtype
                )
            valid = cnts > 0
        else:  # count
            raw = live_counts if cnts is None else cnts
            vals = raw.astype(s.return_type.np_dtype)
            valid = None
        if valid is not None and bool(np.asarray(valid).all()):
            valid = None
        return vals, valid

    @classmethod
    def _spec_output(cls, s: AggregateSpec, slot_host, live_counts, str_dicts):
        """(values, validity, dict) for one aggregate spec from pulled
        per-slot live-group arrays."""
        if s.is_string:
            codes = slot_host[s.minmax_slot].astype(np.int32)
            valid = codes >= 0
            return (
                np.where(valid, codes, 0).astype(np.int32),
                None if bool(valid.all()) else valid,
                str_dicts.get(s.minmax_slot),
            )
        if s.name in ("sum", "avg", "count"):
            sums = None if s.sum_slot is None else slot_host[s.sum_slot]
            cnts = None if s.cnt_slot is None else slot_host[s.cnt_slot]
            vals, valid = cls._numeric_output(s, sums, cnts, live_counts)
            return vals, valid, None
        if s.name == "min":
            raw = slot_host[s.minmax_slot]
            vals = raw.astype(s.return_type.np_dtype)
            valid = raw != _min_identity(np.dtype(raw.dtype))
        else:
            raw = slot_host[s.minmax_slot]
            vals = raw.astype(s.return_type.np_dtype)
            valid = raw != _max_identity(np.dtype(raw.dtype))
        if bool(np.asarray(valid).all()):
            valid = None
        return vals, valid, None

    def _key_outputs(self, live):
        """Group-key output columns for the live groups, in key order."""
        out_cols, out_valid, out_dicts = [], [], []
        in_schema = self.child.schema
        for k, idx in enumerate(self.key_cols):
            keys, kvalid = self.encoder.key_column(k)
            keys = keys[live]
            f = in_schema.field(idx)
            npd = np.dtype(f.data_type.np_dtype)
            if npd.kind == "f":
                # float keys were bit-cast into the encoder; bit-cast back
                out_cols.append(keys.view(np.float64).astype(npd))
            else:
                out_cols.append(keys.astype(npd))
            out_valid.append(None if kvalid is None else kvalid[live])
            out_dicts.append(self._key_dicts.get(idx))
        return out_cols, out_valid, out_dicts

    def _pull_state(self, state):
        """Pull a device accumulator state's live prefix to host.
        Returns (counts, per-slot host arrays)."""
        counts, accs = state
        # transfer only the live prefix: dense ids mean groups occupy
        # [0, num_groups) of the power-of-two capacity, so slicing on
        # device before D2H cuts transferred bytes by the headroom
        # factor (up to ~8x right after a capacity growth)
        n_groups = self.encoder.num_groups if self.key_cols else 1
        # slice length bucketed to a power of two: every distinct shape
        # compiles a (tiny) slice kernel, so keep the shape set bounded
        cut = min(group_capacity(n_groups), counts.shape[0])
        if cut < counts.shape[0]:
            counts = counts[:cut]
            accs = tuple(a[:cut] for a in accs)
        # ONE blob-packed transfer for the whole result state: each
        # separate device->host copy costs a full link round trip
        counts, accs = device_pull((counts, accs))
        return np.asarray(counts), [np.asarray(a) for a in accs]

    def _finalize_keyed(self, keyed: _KeyedState) -> RecordBatch:
        """The answer of a keyed accumulation, left on the device: one
        launch turns the reduced state into output columns; the host
        knows only how many rows they hold.  Where the groups were left
        where the fold found them (`keyed.group`), HAVING picks its
        groups there: one launch counts them (8 B read), one compacts
        their output columns (`_sparse_take`)."""
        in_schema = self.child.schema
        key_dtypes = tuple(
            np.dtype(in_schema.field(i).data_type.np_dtype).name
            for i in self.key_cols)
        dicts = [self._key_dicts.get(i) for i in self.key_cols] + [
            None] * len(self.specs)
        if keyed.group is not None:
            count, take = self._having_progs
            with METRICS.timer("aggregate.having"):
                kept = int(device_pull(device_call(
                    count, keyed.state, keyed.group, key_dtypes,
                    _tag="agg.having")))
                outs = device_call(
                    take, keyed.state, keyed.group, key_dtypes,
                    group_capacity(kept), _tag="agg.having")
            METRICS.add("aggregate.having.groups", keyed.n_groups)
            METRICS.add("aggregate.having.kept", kept)
            return RecordBatch(self._schema, [v for v, _ in outs],
                               [m for _, m in outs], dicts, num_rows=kept)
        # as many rows as a power of two holds the groups: the state's
        # capacity is the buffer's, several times that
        rows = min(keyed.state[2].shape[0], group_capacity(keyed.n_groups))
        out_keys, out = device_call(
            self.core.keyed_outputs_jit, keyed.state, key_dtypes, rows,
            _tag="agg.outputs")
        cols = [v for v, _ in out_keys] + [v for v, _ in out]
        valids = [v for _, v in out_keys] + [v for _, v in out]
        return RecordBatch(self._schema, cols, valids, dicts,
                           num_rows=keyed.n_groups)

    def finalize(self, state) -> RecordBatch:
        self._cost_observe_done()
        counts, accs = self._pull_state(state)
        n_groups = self.encoder.num_groups if self.key_cols else 1
        if self.key_cols:
            live = np.nonzero(counts[:n_groups] > 0)[0]
        else:
            # global aggregate: always exactly one output row
            live = np.array([0], dtype=np.int64)

        out_cols, out_valid, out_dicts = self._key_outputs(live)
        slot_host = [a[live] for a in accs]
        live_counts = counts[live]
        for s in self.specs:
            vals, valid, d = self._spec_output(
                s, slot_host, live_counts, self._str_dicts
            )
            out_cols.append(vals)
            out_valid.append(valid)
            out_dicts.append(d)

        return make_host_batch(self._schema, out_cols, out_valid, out_dicts)

    def op_label(self) -> str:
        pred = self._host_pred_expr or self._core_pred
        return (
            f"Aggregate[keys={len(self.key_cols)}, slots={len(self.slots)}"
            + (", filtered" if pred is not None else "")
            + (", having" if self._having is not None else "")
            + "]"
        )

    def batches(self) -> Iterator[RecordBatch]:
        state = self._scan(keyed=True)
        yield (self._finalize_keyed(state) if isinstance(state, _KeyedState)
               else self.finalize(state))


def _key_bounds(batch: RecordBatch, col: int) -> "tuple | None":
    """(least, greatest) of a host batch's integer column over its live
    rows (None where it has none), cached on the batch: a table that
    keeps its batches works it out once."""
    key = ("key_bounds", col)
    if key in batch.cache:
        return batch.cache[key]
    vals = np.asarray(batch.data[col])[: batch.num_rows]
    valid = batch.validity[col]
    if valid is not None:
        vals = vals[np.asarray(valid)[: batch.num_rows]]
    out = (int(vals.min()), int(vals.max())) if len(vals) else None
    batch.cache[key] = out
    return out
