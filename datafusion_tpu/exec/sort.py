"""ORDER BY / LIMIT operators.

The reference planned Sort/Limit but left them `unimplemented!()`
(`context.rs:161`).  TPU design, two device paths:

- **Streaming TopK** (`ORDER BY ... LIMIT k`, k <= TOPK_MAX): one
  fused kernel per batch transforms sort keys *on device* (DESC =
  negation / bit-complement, NULLs and padding to max sentinels, Utf8
  via host rank tables passed as aux), sorts the batch together with
  the carried top-k state, and keeps the best k rows as GLOBAL ROW
  IDS — payload columns never travel to the device; the host gathers
  them from the source batches at the end (bit-exact f64 even on
  emulated-f64 backends).  Device state is O(k).  Host-side, scanned
  batches pin until an asynchronously-pulled state snapshot confirms
  they hold no surviving candidates (never blocking on the link), so
  host memory stays bounded near the scan window in the steady state.
- **Run sort + host merge** (full ORDER BY): each batch-bucket-sized
  run sorts on device (multi-key `lax.sort`, stable), and the sorted
  runs merge on the host with a vectorized structured-array
  `searchsorted` merge.  No single all-rows device allocation; the
  device sort buffer is bounded by the run size.

Key transforms (shared by both paths):
- Every ORDER BY key lowers to a (dead, value) operand pair: `dead`
  is True for NULL keys and padding (nulls sort last, as a *separate*
  leading key — a value sentinel would collide with real extremes:
  ~int64.min == int64.max, -(-inf) == +inf), and dead rows' values are
  zeroed so they compare equal among themselves.
- DESC numeric keys sort by their negation (signed ints by bitwise
  complement: -int64.min overflows), so every key is ascending for the
  one fused sort.
- Utf8 keys sort by host-computed rank tables
  (`StringDictionary.sort_ranks`): rank[code] is the value's position
  in sorted order, so code-ranked ascending == lexicographic.

LIMIT over a sort slices the sorted permutation; a bare LIMIT just
stops pulling batches early (no device work at all).
"""

from __future__ import annotations

from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from datafusion_tpu.datatypes import DataType, Schema
from datafusion_tpu.errors import NotSupportedError
from datafusion_tpu.exec.batch import (
    RecordBatch,
    bucket_capacity,
    device_pull,
    make_host_batch,
)
from datafusion_tpu.exec.materialize import compact_batch, iter_with_mask_prefetch
from datafusion_tpu.exec.relation import Relation, device_scope as _device_scope
from datafusion_tpu.exec.wordsort import key_words, lex_perm
from datafusion_tpu.plan.expr import Column, SortExpr
from datafusion_tpu.utils.metrics import METRICS
from datafusion_tpu.utils.retry import device_call

# LIMIT at or below this rides the streaming device TopK; above it the
# query is effectively a full sort and takes the run-merge path.
TOPK_MAX = 65536


@jax.jit
def _run_sort_planes(ops):
    """Stable lexicographic sort of one run's key operands; the
    permutation's significant byte planes, least significant first."""
    cap = ops[0].shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    perm = lax.sort(
        tuple(ops) + (iota,), num_keys=len(ops), is_stable=True
    )[-1]
    nbytes = max(1, ((int(cap) - 1).bit_length() + 7) >> 3)
    return tuple(
        ((perm >> (8 * i)) & 0xFF).astype(jnp.uint8) for i in range(nbytes)
    )


def _np_sort_key(
    values: np.ndarray,
    validity: Optional[np.ndarray],
    kind: str,
    asc: bool,
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side transformed key (run-merge path): a (dead, value)
    operand pair, ascending, nulls last via the dead flag."""
    n = len(values)
    dead = np.zeros(n, bool) if validity is None else ~validity
    if kind == "f":
        k = values.astype(np.float64)
        if not asc:
            k = -k
        k = np.where(dead, 0.0, k)
    else:
        k = values.astype(np.int64)
        if not asc:
            k = ~k  # complement, not negation: -int64.min overflows
        k = np.where(dead, np.int64(0), k)
    return dead, k


class _KeyPlan:
    """How one ORDER BY key lowers onto a column: which column, its
    transform kind, direction, source width, and (for Utf8) a
    rank-table aux slot."""

    __slots__ = ("index", "kind", "asc", "rank_slot", "width")

    def __init__(self, index: int, kind: str, asc: bool,
                 rank_slot: Optional[int], width: int = 64):
        self.index = index
        self.kind = kind  # "f" | "i" | "u64" | "str"
        self.asc = asc
        self.rank_slot = rank_slot
        self.width = width


class _TopKCore:
    """The compiled, shareable part of a streaming TopK: the key
    transform and the jitted merge kernel, cached process-wide by the
    key-plan fingerprint (SURVEY §7 recompilation control) so repeated
    ORDER BY ... LIMIT shapes reuse compiled executables."""

    def __init__(self, key_plans: list[_KeyPlan]):
        self._key_plans = key_plans
        # the kernels see ONLY the key columns (payloads never touch
        # the device — the state carries winning global row ids and the
        # host gathers payloads from the source batches, bit-exactly);
        # _sub_of maps schema column index -> position in the subset
        self.key_cols = sorted({kp.index for kp in key_plans})
        self._sub_of = {c: i for i, c in enumerate(self.key_cols)}
        # single-key fast path: `lax.top_k` on an exact int64 score
        # image (orders of magnitude faster than a multi-operand sort
        # on TPU).  Eligible when the whole key order embeds in int64
        # scores with no collision against the sentinels: float32
        # (bit-image via s32 bitcast; NaNs clamped to "worst"), ints
        # <= 32 bits, string ranks.  float64 keys stay on the sort
        # path — TPU emulates f64 and its bitcast doesn't lower — as do
        # full-width int64/uint64, whose complement image can collide
        # with the sentinels at the extremes.
        kp = key_plans[0] if len(key_plans) == 1 else None
        self.single = kp is not None and (
            (kp.kind == "f" and kp.width == 32)
            or kp.kind == "str"
            # width 33 admits uint32 (SortRelation budgets unsigned
            # sources one extra signed bit)
            or (kp.kind == "i" and kp.width <= 33)
        )
        # wide single-key fast path: float64 / int64 / uint64 keys — the
        # default SQL numeric types — take `lax.top_k` on a FULL-WIDTH
        # int64 score (no index-tiebreak bits: lax.top_k is index-stable
        # on every XLA backend, ties keep ascending row order).  The
        # sentinel ladder lives at int64.min..min+2; a real int key CAN
        # collide there, so the kernel carries a collision flag and the
        # caller replays the scan through the exact sort path when it
        # fires (f64 images can't reach the ladder: the NaN payload
        # bands keep real bit-images > min + 2^51).
        self.wide = (
            kp is not None
            and not self.single
            and (
                (kp.kind == "f" and kp.width == 64)
                or kp.kind == "i"
                or kp.kind == "u64"
            )
        )
        if self.single:
            self.jit = jax.jit(self._topk1_kernel, static_argnums=(0,))
        elif self.wide:
            self.jit = jax.jit(self._topk_wide_kernel, static_argnums=(0,))
        else:
            self.jit = jax.jit(self._topk_kernel, static_argnums=(0,))
        # fused-pass batch-group fold: lax.scan over a stacked group —
        # the whole scan's merge is ONE launch, and the traced body is
        # one kernel, not one per batch (exec/fused.py)
        self.group_jit = jax.jit(self._fused_group, static_argnums=(0,))
        # final-group fold + result-mask merge in ONE launch: the scan's
        # last batch group folds AND the (live-mask, row-ids) result
        # state collapses to a single int64 array inside the same
        # program — the host then pulls ONE array
        self.group_final_jit = jax.jit(self._group_final,
                                       static_argnums=(0,))
        self.final_jit = jax.jit(self._final_merge)
        # cross-query megabatch folds (serve.py / run_topk_megabatch):
        # N queries' states ride ONE stacked scan fold — the per-query
        # state-capacity tuple `ks` is static (bucketed, so concurrent
        # LIMITs usually share one compiled program), and the final
        # variant collapses every state through `_final_merge` inside
        # the same program so the host pulls one packed array per
        # query from a single blob transfer
        self.multi_group_jit = jax.jit(self._multi_group,
                                       static_argnums=(0,))
        self.multi_final_jit = jax.jit(self._multi_group_final,
                                       static_argnums=(0,))
        # per-column codec memory for put_compressed (see batch.py)
        self.wire_hints: dict = {}

    def _final_merge(self, state):
        """Fold the top-k state's (live mask, global row ids) — plus
        the wide path's collision flag — into ONE int64 array:
        [flag, row_id_or_-1 x k].  Dead slots merge to -1, so the host
        recovers the mask as `merged >= 0` from a single transfer."""
        if self.wide:
            _, live, rows, flag = state
            header = flag.astype(jnp.int64)[None]
        else:
            live, rows = state[-2], state[-1]
            header = jnp.zeros(1, jnp.int64)
        return jnp.concatenate(
            [header, jnp.where(live, rows, jnp.int64(-1))]
        )

    def _group_final(self, k, state, entries, rank_tables):
        """The scan's LAST group fold fused with the result merge (see
        `_final_merge`) — one launch ends the pass."""
        return self._final_merge(
            self._fused_group(k, state, entries, rank_tables)
        )

    def _fused_group(self, k, state, entries, rank_tables):
        from datafusion_tpu.exec.fused import stack_entries

        stacked = stack_entries(entries)

        def body(st, x):
            cols, valids, mask, num_rows, row_base, img = x
            if self.single:
                st = self._topk1_kernel(
                    k, st, cols, valids, mask, num_rows, row_base,
                    rank_tables,
                )
            elif self.wide:
                st = self._topk_wide_kernel(
                    k, st, cols, valids, mask, num_rows, row_base,
                    rank_tables, img,
                )
            else:
                st = self._topk_kernel(
                    k, st, cols, valids, mask, num_rows, row_base,
                    rank_tables,
                )
            return st, None

        state, _ = lax.scan(body, state, stacked)
        return state

    def _fold_batch(self, k, state, cols, valids, mask, num_rows,
                    row_base, rank_tables, img):
        """One batch merged into one query's state, routed by path."""
        if self.single:
            return self._topk1_kernel(
                k, state, cols, valids, mask, num_rows, row_base,
                rank_tables,
            )
        if self.wide:
            return self._topk_wide_kernel(
                k, state, cols, valids, mask, num_rows, row_base,
                rank_tables, img,
            )
        return self._topk_kernel(
            k, state, cols, valids, mask, num_rows, row_base,
            rank_tables,
        )

    def _multi_group(self, ks, states, entries, rank_tables):
        """N queries' states folded over ONE stacked batch group (the
        serve-plane TopK megabatch): the scan body merges every
        query's state against the same batch operands, so a group
        costs one launch — and one upload — regardless of how many
        queries ride it.  Megabatched queries share the scan with no
        per-query predicate masks (eligibility in serve._mega_key),
        so the entry tuple is identical for all of them."""
        from datafusion_tpu.exec.fused import stack_entries

        stacked = stack_entries(entries)

        def body(sts, x):
            cols, valids, mask, num_rows, row_base, img = x
            return tuple(
                self._fold_batch(k, st, cols, valids, mask, num_rows,
                                 row_base, rank_tables, img)
                for k, st in zip(ks, sts)
            ), None

        states, _ = lax.scan(body, tuple(states), stacked)
        return states

    def _multi_group_final(self, ks, states, entries, rank_tables):
        """The megabatch's LAST group fold fused with every query's
        result merge — one launch ends the whole cross-query pass,
        and the outputs pack into one int64 array per query."""
        if entries:
            states = self._multi_group(ks, states, entries, rank_tables)
        return tuple(self._final_merge(st) for st in states)

    @staticmethod
    def build(
        key_plans: list[_KeyPlan], force_general: bool = False
    ) -> "_TopKCore":
        from datafusion_tpu.exec.kernels import cached_kernel

        key = (
            "topk",
            force_general,
            tuple(
                (kp.index, kp.kind, kp.asc, kp.rank_slot, kp.width)
                for kp in key_plans
            ),
        )

        def make():
            core = _TopKCore(list(key_plans))
            if force_general and (core.single or core.wide):
                core.single = False
                core.wide = False
                core.jit = jax.jit(core._topk_kernel, static_argnums=(0,))
            return core

        return cached_kernel(key, make)

    # -- single-key score image (device, traced) --
    # base-score ladder, higher = better: real values > NaN values >
    # live NULL-key rows > padding/empty slots.  Real base scores fit
    # 34 signed bits (f32 bit-images and <=32-bit int complements fit
    # 33; string ranks fit 31), so the ladder constants sit safely
    # below them and the per-batch index tiebreak fits alongside in
    # int64.
    _NAN_BASE = -(1 << 34)
    _NULL_BASE = -(1 << 34) - 1
    _DEAD_BASE = -(1 << 34) - 2

    def _score(self, v, valid, row_mask, rank_tables):
        kp = self._key_plans[0]
        if kp.kind == "f":  # float32 only (see eligibility note)
            b = jax.lax.bitcast_convert_type(
                v.astype(jnp.float32), jnp.int32
            )
            # monotone unsigned image in [0, 2^32): negatives flip to
            # [0, 2^31), positives shift ABOVE them (sign-magnitude ->
            # total order; the naive where(b>=0, b, ~b) overlaps signs)
            img = jnp.where(
                b >= 0,
                b.astype(jnp.int64) + jnp.int64(1 << 31),
                (~b).astype(jnp.int64),
            )
            score = ~img if kp.asc else img
            score = jnp.where(jnp.isnan(v), jnp.int64(self._NAN_BASE), score)
        elif kp.kind == "str":
            table = rank_tables[kp.rank_slot]
            cap = table.shape[0]
            rank = table[jnp.clip(v.astype(jnp.int32), 0, cap - 1)].astype(
                jnp.int64
            )
            score = ~rank if kp.asc else rank
        else:  # "i", width <= 32
            k64 = v.astype(jnp.int64)
            score = ~k64 if kp.asc else k64
        if valid is not None:
            score = jnp.where(valid, score, jnp.int64(self._NULL_BASE))
        return jnp.where(row_mask, score, jnp.int64(self._DEAD_BASE))

    def _topk1_kernel(self, k, state, cols, valids, mask, num_rows, row_base,
                      rank_tables):
        """Single-key merge: `lax.top_k` picks the batch's kb best rows,
        then a tiny 2*kb-row stable sort merges them with the carried
        state.  `top_k` tie order is backend-defined, so the row index
        rides in the score's low bits — earlier rows strictly outrank
        later equal-key rows on every backend; the carried state stores
        only the base score (index bits are per-batch).  Payloads never
        enter the state: the winning rows travel as global row ids
        (`row_base` + local index) and the host gathers values."""
        capacity = cols[0].shape[0]
        shift = max(capacity - 1, 1).bit_length()
        assert shift <= 27, "batch capacity too large for the score image"
        row_mask = jnp.arange(capacity, dtype=jnp.int32) < num_rows
        if mask is not None:
            row_mask = row_mask & mask
        kp = self._key_plans[0]
        sub = self._sub_of[kp.index]
        base = self._score(cols[sub], valids[sub], row_mask, rank_tables)
        idx_bits = jnp.int64(capacity - 1) - jnp.arange(capacity, dtype=jnp.int64)
        full = base * jnp.int64(1 << shift) + idx_bits
        # top_k requires k <= capacity: small batches contribute only
        # their kk rows — the merge below works on any k + kk >= k
        kk = min(k, capacity)
        cs, ci = lax.top_k(full, kk)
        cand_base = cs >> shift  # arithmetic shift recovers the base
        cand_live = row_mask[ci]

        skeys, slive, srows = state
        all_score = jnp.concatenate([skeys[0], cand_base])
        all_live = jnp.concatenate([slive, cand_live])
        all_rows = jnp.concatenate([srows, row_base + ci.astype(jnp.int64)])
        iota = jnp.arange(k + kk, dtype=jnp.int32)
        out = lax.sort((~all_score, iota), num_keys=1, is_stable=True)
        perm = out[1][:k]
        return (all_score[perm],), all_live[perm], all_rows[perm]

    # -- wide single-key path (f64 / int64 / uint64) --
    # full-width int64 scores; sentinel ladder at the very bottom:
    # real values > NaN > live NULL-key rows > padding/empty slots.
    _W_DEAD = np.int64(-(2**63))
    _W_NULL = np.int64(-(2**63) + 1)
    _W_NAN = np.int64(-(2**63) + 2)

    def _topk_wide_kernel(
        self, k, state, cols, valids, mask, num_rows, row_base, rank_tables,
        img
    ):
        """Single wide-key merge.  `img` is the host-computed monotone
        int64 bit-image of a float64 key (TPU won't lower the f64
        bitcast; None for integer keys, whose image computes on device).
        Scores use all 64 bits, so a real integer key can land on the
        sentinel ladder — `flag` records that and the caller replays
        the scan through the exact sort path (state threads the flag).
        """
        capacity = cols[0].shape[0]
        row_mask = jnp.arange(capacity, dtype=jnp.int32) < num_rows
        if mask is not None:
            row_mask = row_mask & mask
        kp = self._key_plans[0]
        sub = self._sub_of[kp.index]
        v = cols[sub]
        valid = valids[sub]
        if kp.kind == "f":
            raw = img
        elif kp.kind == "u64":
            raw = lax.bitcast_convert_type(
                v.astype(jnp.uint64) ^ jnp.uint64(1 << 63), jnp.int64
            )
        else:
            raw = v.astype(jnp.int64)
        score = ~raw if kp.asc else raw
        live_real = row_mask if valid is None else (row_mask & valid)
        if kp.kind == "f":
            isnan = jnp.isnan(v)
            collide = live_real & ~isnan & (score <= self._W_NAN)
            score = jnp.where(isnan, self._W_NAN, score)
        else:
            collide = live_real & (score <= self._W_NAN)
        if valid is not None:
            score = jnp.where(valid, score, self._W_NULL)
        score = jnp.where(row_mask, score, self._W_DEAD)

        kk = min(k, capacity)
        cs, ci = lax.top_k(score, kk)  # index-stable ties on all backends
        cand_live = row_mask[ci]

        skeys, slive, srows, flag = state
        all_score = jnp.concatenate([skeys[0], cs])
        all_live = jnp.concatenate([slive, cand_live])
        all_rows = jnp.concatenate([srows, row_base + ci.astype(jnp.int64)])
        iota = jnp.arange(k + kk, dtype=jnp.int32)
        out = lax.sort((~all_score, iota), num_keys=1, is_stable=True)
        perm = out[1][:k]
        return (
            (all_score[perm],),
            all_live[perm],
            all_rows[perm],
            flag | collide.any(),
        )

    @staticmethod
    def f64_image(values: np.ndarray) -> np.ndarray:
        """Host-side monotone int64 image of a float64 column: v1 < v2
        (as floats, NaNs excluded) implies img1 < img2 (as int64).  NaN
        rows keep their natural extreme images; the kernel substitutes
        the NaN sentinel via isnan(v) after applying direction."""
        bits = np.ascontiguousarray(values, dtype=np.float64).view(np.int64)
        u = bits.view(np.uint64)
        flip = np.where(
            bits < 0, ~np.uint64(0), np.uint64(1) << np.uint64(63)
        )
        return (u ^ flip ^ (np.uint64(1) << np.uint64(63))).view(np.int64)

    # -- shared key transform (device, traced) --
    def _device_keys(self, cols, valids, mask, capacity, rank_tables):
        """Transformed ascending sort-key operands: a flat
        [dead0, key0, dead1, key1, ...] list (dead = NULL/padded rows,
        sorting last; their values zeroed so they tie)."""
        keys = []
        for kp in self._key_plans:
            v = cols[self._sub_of[kp.index]]
            valid = valids[self._sub_of[kp.index]]
            if kp.kind == "str":
                table = rank_tables[kp.rank_slot]
                cap = table.shape[0]
                k = table[jnp.clip(v.astype(jnp.int32), 0, cap - 1)].astype(
                    jnp.int64
                )
                if not kp.asc:
                    k = -k
            elif kp.kind == "f":
                k = v.astype(jnp.float64)
                if not kp.asc:
                    k = -k
            elif kp.kind == "u64":
                # uint64 doesn't fit int64: flip the sign bit and
                # reinterpret — order-preserving and lossless
                k = (v.astype(jnp.uint64) ^ jnp.uint64(1 << 63)).view(jnp.int64)
                if not kp.asc:
                    k = ~k
            else:
                k = v.astype(jnp.int64)
                if not kp.asc:
                    k = ~k  # complement, not negation: -int64.min overflows
            dead = ~mask
            if valid is not None:
                dead = dead | ~valid
            keys.append(dead)
            keys.append(jnp.where(dead, jnp.zeros((), k.dtype), k))
        return keys

    # -- streaming TopK path --
    def _topk_kernel(self, k, state, cols, valids, mask, num_rows, row_base,
                     rank_tables):
        """Merge one batch into the carried top-k state.

        state = (keys..., live bits, global row ids) each length k;
        returns the same structure.  The sort sees ONLY the key
        operands; the winning rows travel as
        global row ids and the HOST gathers payload values from the
        source batches afterwards — bit-exact f64 payloads (an
        emulated-f64 device round trip perturbs them ~1e-14), and no
        payload bytes ever cross H2D.
        """
        capacity = cols[0].shape[0]
        row_mask = jnp.arange(capacity, dtype=jnp.int32) < num_rows
        if mask is not None:
            row_mask = row_mask & mask
        bkeys = self._device_keys(cols, valids, row_mask, capacity, rank_tables)
        skeys, slive, srows = state

        ops = []
        for sk, bk in zip(skeys, bkeys):
            ops.append(jnp.concatenate([sk, bk.astype(sk.dtype)]))
        live_col = jnp.concatenate([slive, row_mask])
        rows_col = jnp.concatenate(
            [srows, row_base + jnp.arange(capacity, dtype=jnp.int64)]
        )
        # tiebreak: among equal (dead) keys, real rows beat padding —
        # NULL-key rows tie with empty state slots and must still fill
        # a LIMIT larger than the non-null count
        ops.append(~live_col)
        # word by word: one sort over these operands compiles for
        # minutes on a TPU (exec/wordsort.py)
        perm = lex_perm([w for o in ops for w in key_words(o)])[:k]
        new_keys = tuple(o[perm] for o in ops[:-1])  # drop tiebreak
        return new_keys, live_col[perm], rows_col[perm]



def topk_take(arrays, idx):
    """A TopK's winning rows of a source batch that lives on the device."""
    return tuple(a[idx] for a in arrays)


_TAKE_JIT = jax.jit(topk_take)


class SortRelation(Relation):
    """Device sort / TopK, optionally with a fused selection and
    column projection: under fused-pass planning (exec/fused.py) a
    `[Limit](Sort(Projection(Selection(x))))` chain collapses to ONE
    SortRelation whose `predicate` (host-evaluable — it folds into the
    selection mask without a device round trip) filters and whose
    `output_cols` picks/reorders the gathered output columns, so the
    whole chain is one pass with no per-operator dispatch."""

    def __init__(
        self,
        child: Relation,
        sort_expr: list[SortExpr],
        out_schema: Schema,
        limit: Optional[int] = None,
        device=None,
        predicate=None,
        output_cols: Optional[list[int]] = None,
    ):
        self.child = child
        self.sort_expr = sort_expr
        self._schema = out_schema
        self.limit = limit
        self.device = device
        self.predicate = predicate
        self._out_cols = (
            list(output_cols)
            if output_cols is not None
            else list(range(len(child.schema)))
        )
        for se in sort_expr:
            if not isinstance(se.expr, Column):
                raise NotSupportedError(
                    f"ORDER BY supports column references, got {se.expr!r}"
                )
        in_schema = child.schema
        self._key_plans: list[_KeyPlan] = []
        rank_slots = 0
        for se in sort_expr:
            idx = se.expr.index
            f = in_schema.field(idx)
            if f.data_type == DataType.UTF8:
                self._key_plans.append(_KeyPlan(idx, "str", se.asc, rank_slots))
                rank_slots += 1
                continue
            kind = f.data_type.np_dtype.kind
            if kind == "O":
                raise NotSupportedError("struct columns cannot be ORDER BY keys")
            width = f.data_type.width
            if kind == "u" and width == 64:
                kind = "u64"
            elif kind in ("b", "i", "u"):
                # unsigned 32-bit needs 33 bits as a signed image
                width = width + 1 if kind == "u" else width
                kind = "i"
            else:
                kind = "f"
            self._key_plans.append(_KeyPlan(idx, kind, se.asc, None, width))
        # TopK state capacity bucketed to a power of two (floor 128):
        # every LIMIT in a bucket shares one compiled kernel per batch
        # shape — compiles are the expensive resource on remote devices
        self._kb = 128
        while limit is not None and self._kb < min(limit, TOPK_MAX):
            self._kb <<= 1
        self.core = _TopKCore.build(self._key_plans)
        self._topk_jit = self.core.jit
        # warm-run artifacts per full-sort run, keyed by the run's
        # source batch identities + dictionary versions: the finished
        # permutation (a warm re-query skips the key encode, the sort
        # launch and its D2H pull); the values pin the batch objects so
        # ids stay valid.  FIFO-bounded: multi-run sorts and cold re-scans
        # (fresh batch objects every scan, so their keys can never hit)
        # must not accumulate buffers without bound.
        from collections import OrderedDict

        self._run_ops_cache: OrderedDict = OrderedDict()
        self._run_ops_cache_max = 4
        # second-chance admission: a key must be SEEN twice before its
        # permutation is stored, so one-shot file scans (fresh batch
        # objects every scan — their keys can never repeat) pin nothing.
        # An id()-recycling false positive here merely admits an entry
        # early; entries themselves pin their batches, so a stored key
        # always identifies live objects.
        self._run_seen: OrderedDict = OrderedDict()

    @property
    def schema(self) -> Schema:
        return self._schema

    def _topk_init(self, k, in_schema, core=None):
        core = core if core is not None else self.core
        # cached on the core: building the empty state costs one tiny
        # device launch per column, paid per RUN without the cache;
        # states are functionally consumed, never mutated
        cache = getattr(core, "_init_states", None)
        if cache is None:
            cache = core._init_states = {}
        sig = (k, tuple(str(in_schema.field(i).data_type.np_dtype)
                        for i in range(len(in_schema))))
        hit = cache.get(sig)
        if hit is not None:
            return hit
        hit = self._topk_init_build(k, in_schema, core)
        cache[sig] = hit
        return hit

    def _topk_init_build(self, k, in_schema, core):
        if core.single or core.wide:
            # empty slots carry the dead-sentinel base score (lose always)
            sentinel = _TopKCore._W_DEAD if core.wide else _TopKCore._DEAD_BASE
            keys = [jnp.full(k, sentinel, jnp.int64)]
            base = (tuple(keys), jnp.zeros(k, bool), jnp.zeros(k, jnp.int64))
            if core.wide:
                return base + (jnp.zeros((), bool),)
            return base
        keys = []
        for kp in self._key_plans:
            keys.append(jnp.ones(k, bool))  # dead flag: empty slots last
            keys.append(
                jnp.zeros(k, jnp.float64 if kp.kind == "f" else jnp.int64)
            )
        return tuple(keys), jnp.zeros(k, bool), jnp.zeros(k, jnp.int64)

    def _f64_image_input(self, batch, kp):
        """Device copy of the host-computed f64 key image, cached on the
        batch (re-scanned in-memory sources transfer it once).  Returns
        None when the column is device-resident (no host bytes to
        image) — the caller falls back to the exact sort core."""
        col = batch.data[kp.index]
        if not isinstance(col, np.ndarray):
            return None
        key = ("sort_img", kp.index, None if self.device is None else repr(self.device))
        hit = batch.cache.get(key)
        if hit is None:
            from datafusion_tpu.obs.device import LEDGER

            img = _TopKCore.f64_image(col)
            hit = (
                LEDGER.put(img, self.device, owner="sort.image")
                if self.device is not None
                else LEDGER.adopt(jnp.asarray(img), owner="sort.image")
            )
            batch.cache[key] = hit
        return hit

    # -- fused selection (predicate folded into the sort pass) --
    def _pred_np_mask(self, batch) -> np.ndarray:
        """This query's fused predicate over one batch as a numpy bool
        mask (cached on the batch, pinned by relation — the predicate
        carries per-query literals).  Predicates reach here only when
        host-evaluable (exec/fused.rewrite_sort's condition); it stays
        on the host over a resident table too (unlike the aggregate's):
        the sorted rows return to the host, which holds the columns."""
        hit = batch.cache.get("sort_pred_mask")
        if hit is not None and hit[0] is self:
            return hit[1]
        from datafusion_tpu.exec.hostfn import host_pred_mask

        pm = host_pred_mask(self.predicate, batch, {})
        batch.cache["sort_pred_mask"] = (self, pm)
        return pm

    def _pred_device_mask(self, batch, upstream_dev_mask):
        """Device copy of (upstream mask & predicate), bit-packed over
        the wire and cached per relation — the TopK kernels take it in
        place of the plain upstream mask, so filtering costs no extra
        launch."""
        hit = batch.cache.get("sort_pred_dev_mask")
        if hit is not None and hit[0] is self:
            return hit[1]
        pm = self._pred_np_mask(batch)
        host_mask = batch.mask is not None and not hasattr(
            batch.mask, "copy_to_host_async"
        )
        if host_mask:
            pm = pm & np.asarray(batch.mask)
        from datafusion_tpu.exec.batch import put_compressed

        with _device_scope(self.device):
            m = put_compressed([pm], self.device)[0]
            if batch.mask is not None and not host_mask:
                # upstream mask lives on device: one tiny fused AND
                from datafusion_tpu.exec import relation as _rel

                m = _rel._MASK_AND_JIT(m, upstream_dev_mask)
        batch.cache["sort_pred_dev_mask"] = (self, m)
        return m

    def _pred_batch(self, batch) -> RecordBatch:
        """The batch with the fused predicate folded into its selection
        mask (run-sort path feeds this to compact_batch); cached on the
        batch, pinned by relation."""
        if self.predicate is None:
            return batch
        hit = batch.cache.get("sort_pred_batch")
        if hit is not None and hit[0] is self:
            return hit[1]
        pm = self._pred_np_mask(batch)
        m = pm if batch.mask is None else (np.asarray(batch.mask) & pm)
        wrapped = RecordBatch(
            batch.schema, list(batch.data), list(batch.validity),
            list(batch.dicts), num_rows=batch.num_rows, mask=m,
        )
        batch.cache["sort_pred_batch"] = (self, wrapped)
        return wrapped

    def _topk_batches(self, core=None) -> Iterator[RecordBatch]:
        from datafusion_tpu.exec.batch import device_inputs

        inj = self.__dict__.pop("_injected_topk", None)
        if inj is not None and core is None:
            # serve-plane megabatch (run_topk_megabatch): the
            # cross-query pass already folded this query's state over
            # the SHARED scan — skip the scan, run only the host
            # payload gather
            yield from self._injected_topk_result(inj)
            return
        if core is None:
            core = self.core
        topk_jit = core.jit
        k = self._kb  # bucketed state size; self.limit rows come out
        in_schema = self.child.schema
        state = None
        dicts = [None] * len(in_schema)
        rank_cache: dict = {}
        wide_f64 = core.wide and self._key_plans[0].kind == "f"
        from datafusion_tpu.exec.fused import (
            fuse_group_max,
            iter_groups,
            pad_group,
        )

        fuse = fuse_group_max()
        chunk: list = []

        def dispatch_chunk(state):
            if len(chunk) == 1:
                c = chunk[0]
                args = [k, state, c[0], c[1], c[2], c[3], c[4], c[5]]
                if core.wide:
                    args.append(c[6])
                return device_call(topk_jit, *args, _tag="topk")
            # one launch per shape-homogeneous batch group (lax.scan
            # over the stacked group), padded to the ladder with
            # zero-row entries that merge as all-dead
            entries = [(c[0], c[1], c[2], c[3], c[4], c[6]) for c in chunk]
            shareds = [c[5] for c in chunk]
            for idxs, ranks in iter_groups(entries, shareds):
                if len(idxs) == 1:
                    c = chunk[idxs[0]]
                    args = [k, state, c[0], c[1], c[2], c[3], c[4], c[5]]
                    if core.wide:
                        args.append(c[6])
                    state = device_call(topk_jit, *args, _tag="topk")
                    continue
                group = pad_group(
                    [entries[i] for i in idxs],
                    lambda e: (e[0], e[1], e[2], np.int32(0), e[4], e[5]),
                )
                METRICS.add("fused.groups")
                METRICS.add("fused.group_batches", len(idxs))
                state = device_call(
                    core.group_jit, k, state, tuple(group), ranks,
                    _tag="topk.group",
                )
            return state

        def flush():
            nonlocal state
            if not chunk:
                return
            from datafusion_tpu.obs.stats import op_timer

            with METRICS.timer("execute.sort"), op_timer(self), \
                    _device_scope(self.device):
                state = dispatch_chunk(state)
            chunk.clear()
            # bounded host memory: snapshot the survivors asynchronously
            # and release batches that no longer hold candidates
            try:
                state[1].copy_to_host_async()
                state[2].copy_to_host_async()
                prune_q.append((state[1], state[2], len(bases)))
            except AttributeError:  # non-jax arrays in tests
                pass
            try_prune()

        # per-batch bases into one global row-id space; scanned batches
        # pin until the final gather (payloads come from their host
        # arrays, bit-exact — the device only ever sees the KEY
        # columns).  To keep host memory bounded on long scans, each
        # flush starts an ASYNC pull of the state's row ids; once a
        # pull completes (checked non-blocking — never a sync on the
        # link), batches holding no surviving candidates are released.
        # Safe because the state is monotone: a row absent from the
        # state at any snapshot can never re-enter it.
        from collections import deque

        src_batches: list = []
        bases: list[int] = []
        next_base = 0
        prune_q: deque = deque()

        def try_prune():
            while prune_q:
                live_a, rows_a, upto = prune_q[0]
                if not (
                    getattr(rows_a, "is_ready", lambda: False)()
                    and getattr(live_a, "is_ready", lambda: False)()
                ):
                    return
                prune_q.popleft()
                live_h = np.asarray(live_a)
                rows_h = np.asarray(rows_a)
                win = rows_h[live_h]
                keep: set = set()
                if len(win):
                    base_arr = np.asarray(bases[:upto], dtype=np.int64)
                    hit = np.searchsorted(base_arr, win, side="right") - 1
                    keep = {int(b) for b in np.unique(hit) if 0 <= b < upto}
                for j in range(upto):
                    if j not in keep:
                        src_batches[j] = None

        from datafusion_tpu.obs.stats import iter_stats

        for batch in iter_stats(self.child):
            for i, d in enumerate(batch.dicts):
                if d is not None:
                    dicts[i] = d
            rank_tables = []
            for kp in self._key_plans:
                if kp.kind != "str":
                    continue
                d = batch.dicts[kp.index]
                ranks = (
                    self._rank_table(d, rank_cache, kp.index)
                    if d is not None
                    else np.zeros(1, np.int32)
                )
                rank_tables.append(ranks)
            img = None
            if wide_f64:
                img = self._f64_image_input(batch, self._key_plans[0])
                if img is None:
                    # device-resident f64 key: no host bytes to image —
                    # replay everything through the exact sort core
                    yield from self._topk_batches(
                        _TopKCore.build(self._key_plans, force_general=True)
                    )
                    return
            if state is None:
                state = self._topk_init(k, in_schema, core)
            with _device_scope(self.device):
                data, validity, mask = device_inputs(
                    self._key_view(batch, core), self.device, core.wire_hints
                )
            if self.predicate is not None:
                # fused selection: the predicate mask replaces the
                # upstream mask operand — no extra kernel launch
                mask = self._pred_device_mask(batch, mask)
            src_batches.append(batch)
            bases.append(next_base)
            chunk.append(
                (data, validity, mask, np.int32(batch.num_rows),
                 np.int64(next_base), tuple(rank_tables), img)
            )
            next_base += batch.capacity
            if len(chunk) >= fuse:
                flush()
        if state is None and not chunk:
            yield self._empty_result(in_schema, dicts)
            return
        # fused tail: the last batch group folds AND the result
        # (live-mask, rows) merge happens inside ONE launch
        # (`group_final_jit`), so the host pulls one array
        packed = self._final_flush(core, chunk, state)
        chunk.clear()
        packed_h = np.asarray(device_pull(packed))
        if core.wide and bool(packed_h[0]):
            # an integer key touched the sentinel ladder (values at
            # the extreme two of the 2^64 range): replay the scan
            # through the exact sort path — datasources are
            # re-iterable
            METRICS.add("sort.wide_fallbacks")
            yield from self._topk_batches(
                _TopKCore.build(self._key_plans, force_general=True)
            )
            return
        merged = packed_h[1:]
        # dead slots merged to -1: they separate real rows from
        # dead-key padding when the scan produced fewer than k rows;
        # the state is bucket-sized, so slice down to the actual LIMIT
        take = np.nonzero(merged >= 0)[0][: self.limit]
        win = merged[take]
        yield self._topk_gather(win, src_batches, bases, dicts, in_schema)

    def _topk_gather(self, win, src_batches, bases, dicts, in_schema):
        """Host payload gather: global row id -> (source batch, local
        row).  Payload values come from the source batches' HOST
        arrays — bit-exact, no payload bytes ever crossed the link."""
        base_arr = np.asarray(bases, dtype=np.int64)
        b_idx = np.searchsorted(base_arr, win, side="right") - 1
        local = win - base_arr[b_idx]
        # a source batch born on the device (an aggregate's keyed
        # output) hands over its winners alone: one gather launch and
        # one pull a batch, never a whole column
        host_rows: dict = {}
        for b in np.unique(b_idx):
            src = src_batches[b]
            on_dev = [a for i in self._out_cols
                      for a in (src.data[i], src.validity[i])
                      if a is not None and not isinstance(a, np.ndarray)]
            if on_dev:
                idx = np.zeros(self.limit, np.int32)
                rows = local[b_idx == b]
                idx[: len(rows)] = rows
                with _device_scope(self.device):
                    taken = iter(device_pull(device_call(
                        _TAKE_JIT, tuple(on_dev), idx, _tag="topk.gather")))
                host_rows[b] = {
                    id(a): np.asarray(next(taken))[: len(rows)]
                    for a in on_dev}

        def rows_of(b, a, m):
            """Array `a` of source batch `b` at its winners' rows."""
            if isinstance(a, np.ndarray):
                return a[local[m]]
            return host_rows[b][id(a)]

        out_cols = []
        out_valid = []
        for i in self._out_cols:
            dt = in_schema.field(i).data_type.np_dtype
            vals_i = np.empty(len(win), dtype=dt)
            valid_i = np.ones(len(win), dtype=bool)
            any_null = False
            for b in np.unique(b_idx):
                m = b_idx == b
                src = src_batches[b]
                vals_i[m] = rows_of(b, src.data[i], m)
                if src.validity[i] is not None:
                    valid_i[m] = rows_of(b, src.validity[i], m)
                    any_null = True
            out_cols.append(vals_i)
            out_valid.append(
                None if not any_null or bool(valid_i.all()) else valid_i
            )
        return make_host_batch(
            self._schema, out_cols, out_valid,
            [dicts[i] for i in self._out_cols],
        )

    def _injected_topk_result(self, inj) -> Iterator[RecordBatch]:
        """Consume a megabatch injection: the packed merge result is
        already on the host, so only the payload gather runs here.  A
        set wide-path collision flag replays THIS query solo through
        the exact sort core (counted) — the shared pass cannot replay
        per-query, and datasources are re-iterable."""
        packed_h, src_batches, bases, dicts = inj
        if bool(packed_h[0]):
            METRICS.add("sort.wide_fallbacks")
            yield from self._topk_batches(
                _TopKCore.build(self._key_plans, force_general=True)
            )
            return
        in_schema = self.child.schema
        merged = packed_h[1:]
        take = np.nonzero(merged >= 0)[0][: self.limit]
        win = merged[take]
        if not len(win) and not src_batches:
            yield self._empty_result(in_schema, dicts)
            return
        yield self._topk_gather(win, src_batches, bases, dicts, in_schema)

    def _final_flush(self, core, chunk, state):
        """Dispatch the scan's remaining batch groups, fusing the LAST
        one with the result merge (`_TopKCore._group_final`) so the
        pass ends in one launch whose single int64 output carries rows
        and live mask together.  With an empty tail chunk the merge
        alone dispatches (`final_jit`) — still one launch."""
        from datafusion_tpu.exec.fused import iter_groups, pad_group
        from datafusion_tpu.obs.stats import op_timer

        k = self._kb
        with METRICS.timer("execute.sort"), op_timer(self), \
                _device_scope(self.device):
            if not chunk:
                return device_call(core.final_jit, state,
                                   _tag="topk.final")
            entries = [(c[0], c[1], c[2], c[3], c[4], c[6]) for c in chunk]
            shareds = [c[5] for c in chunk]
            groups = list(iter_groups(entries, shareds))
            for gi, (idxs, ranks) in enumerate(groups):
                group = pad_group(
                    [entries[i] for i in idxs],
                    lambda e: (e[0], e[1], e[2], np.int32(0), e[4], e[5]),
                )
                METRICS.add("fused.groups")
                METRICS.add("fused.group_batches", len(idxs))
                if gi == len(groups) - 1:
                    return device_call(
                        core.group_final_jit, k, state, tuple(group),
                        ranks, _tag="topk.final",
                    )
                state = device_call(
                    core.group_jit, k, state, tuple(group), ranks,
                    _tag="topk.group",
                )

    def _key_view(self, batch: RecordBatch, core) -> RecordBatch:
        """The batch as TopK kernels see it: only the key columns (the
        state carries global row ids; payload columns never travel)."""
        from datafusion_tpu.exec.batch import subset_view

        return subset_view(batch, core.key_cols, tag="topk_key_view")

    def _empty_result(self, in_schema, dicts) -> RecordBatch:
        cols = [
            np.empty(0, dtype=in_schema.field(i).data_type.np_dtype)
            for i in self._out_cols
        ]
        return make_host_batch(
            self._schema, cols, [None] * len(cols),
            [dicts[i] for i in self._out_cols],
        )

    @staticmethod
    def _rank_table(d, cache: dict, idx: int) -> np.ndarray:
        key = (idx, d.version)
        hit = cache.get(key)
        if hit is None:
            ranks = d.sort_ranks().astype(np.int32)
            cap = bucket_capacity(max(len(ranks), 1))
            padded = np.zeros(cap, np.int32)
            padded[: len(ranks)] = ranks
            hit = padded
            cache[key] = hit
        return hit

    # -- run sort + host merge path --
    def _host_keys(self, columns, validity, dicts) -> list[np.ndarray]:
        keys = []
        in_schema = self.child.schema
        for kp, se in zip(self._key_plans, self.sort_expr):
            idx = kp.index
            vals = columns[idx]
            if kp.kind == "str":
                d = dicts[idx]
                vals = d.sort_ranks()[vals] if d is not None else vals
                kind = "i"
            elif kp.kind == "u64":
                vals = (
                    np.ascontiguousarray(vals.astype(np.uint64))
                    ^ np.uint64(1 << 63)
                ).view(np.int64)
                kind = "i"
            else:
                kind = kp.kind
            dead, k = _np_sort_key(vals, validity[idx], kind, se.asc)
            keys.append(dead)
            keys.append(k)
        return keys

    def _sorted_run(self, keys: list[np.ndarray], n: int, cache_key=None,
                    pin=None) -> np.ndarray:
        """Device-sort one run of n rows; returns the permutation.

        Key operands travel through the compressed wire (one blob put);
        all-false dead flags — the no-NULLs common case — drop out of
        the sort entirely (a constant key never reorders anything).
        The padding convention keeps the flag droppable: when a run has
        no nulls, padding rows' VALUE keys are +max sentinels, so they
        sort last without their flag.  `cache_key` stores the warm-run
        artifact, the finished permutation, in _run_ops_cache (`pin`
        holds the source batches alive), so a warm re-query skips the
        key encode."""
        from datafusion_tpu.exec.batch import _wire_enabled, put_compressed

        # second-chance admission: a key must be SEEN twice before its
        # artifact is stored, so one-shot file scans (fresh batch
        # objects every scan) pin nothing
        admit = False
        if cache_key is not None:
            if cache_key in self._run_seen:
                admit = True
            else:
                self._run_seen[cache_key] = True
                while len(self._run_seen) > 32:
                    self._run_seen.popitem(last=False)

        cap = bucket_capacity(n)
        host_ops: list[np.ndarray] = []
        # keys come as (dead-flag, value) pairs per ORDER BY key
        for j in range(0, len(keys), 2):
            dead, val = keys[j], keys[j + 1]
            has_dead = bool(dead[:n].any())
            # NaN values sort ABOVE +inf in XLA's total order, so a
            # +inf padding sentinel cannot sink padding below real NaN
            # rows — keep the flag in that case
            nan_risk = val.dtype.kind == "f" and bool(
                np.isnan(val[:n]).any()
            )
            if has_dead or nan_risk:
                pflag = np.ones(cap, bool)  # padding rows: dead=True
                pflag[:n] = dead[:n]
                host_ops.append(pflag)
                padded = np.zeros(cap, dtype=val.dtype)  # dead tie at 0
                padded[:n] = val[:n]
                host_ops.append(padded)
                continue
            # no NULLs and no NaNs: the all-false flag is a constant
            # key — drop it and sink padding via a +max value sentinel
            # (stability keeps real rows ahead of tying padding)
            pad = (
                np.asarray(np.inf, val.dtype)
                if val.dtype.kind == "f"
                else np.asarray(np.iinfo(val.dtype).max, val.dtype)
            )
            padded = np.full(cap, pad, dtype=val.dtype)
            padded[:n] = val[:n]
            host_ops.append(padded)
        with _device_scope(self.device):
            dev_ops = tuple(put_compressed(host_ops, self.device))
        perm = self._sort_ops(dev_ops, n)
        if admit and _wire_enabled(self.device):
            # cache the PERMUTATION, not the uploaded operands: it is
            # the run's final deterministic artifact, so a warm re-query
            # skips the device sort launch AND its incompressible D2H
            # byte-plane pull — the dominant cost of a warm full sort on
            # real links (BENCH_r05 full_sort at 1.66x CPU was this).
            # Local backends (no link) keep re-sorting: the pull is free
            # there and the cache would only pin memory — and inflate
            # the engine's own CPU baseline leg in the bench protocol.
            self._run_ops_cache[cache_key] = ("perm", perm, pin)
            while len(self._run_ops_cache) > self._run_ops_cache_max:
                self._run_ops_cache.popitem(last=False)
        return perm

    def _sort_ops(self, dev_ops, n: int) -> np.ndarray:
        """Sort device-resident key operands; returns the permutation.

        The permutation crosses D2H as byte planes — ceil(bits/8) bytes
        per row instead of int32's four (a 1M-row capacity needs 20
        bits, so 3 planes): D2H bandwidth is the scarce resource and a
        permutation is incompressible, so shipping only its significant
        bytes is the available win."""
        with _device_scope(self.device):
            planes = device_call(
                _run_sort_planes, tuple(dev_ops), _tag="sort.run"
            )
            host_planes = device_pull(tuple(planes))
        perm = host_planes[0].astype(np.int32)
        for i in range(1, len(host_planes)):
            perm |= host_planes[i].astype(np.int32) << np.int32(8 * i)
        return perm[:n]

    @staticmethod
    def _merge_runs(run_keys: list[np.ndarray], run_perms: list[np.ndarray]):
        """Merge sorted runs on host: vectorized two-way merges via
        structured-array searchsorted (lexicographic on all keys)."""

        def to_struct(keys):
            # heterogeneous fields (bool dead flags, int64/f64 values);
            # numpy sorts/searches structured dtypes lexicographically
            dt = np.dtype([(f"f{i}", k.dtype) for i, k in enumerate(keys)])
            arr = np.empty(len(keys[0]), dt)
            for i, k in enumerate(keys):
                arr[f"f{i}"] = k
            return arr

        items = [
            (to_struct(k), p) for k, p in zip(run_keys, run_perms)
        ]
        while len(items) > 1:
            merged = []
            for i in range(0, len(items) - 1, 2):
                (ka, pa), (kb, pb) = items[i], items[i + 1]
                # position of each b-element among a (stable: a first)
                posb = np.searchsorted(ka, kb, side="left")
                out_len = len(ka) + len(kb)
                idxb = posb + np.arange(len(kb))
                keys = np.empty(out_len, dtype=ka.dtype)
                perms = np.empty((out_len,) + pa.shape[1:], dtype=pa.dtype)
                bmask = np.zeros(out_len, dtype=bool)
                bmask[idxb] = True
                keys[bmask] = kb
                keys[~bmask] = ka
                perms[bmask] = pb
                perms[~bmask] = pa
                merged.append((keys, perms))
            if len(items) % 2:
                merged.append(items[-1])
            items = merged
        return items[0][1]

    def op_label(self) -> str:
        keys = ", ".join(
            f"#{se.expr.index} {'ASC' if se.asc else 'DESC'}"
            for se in self.sort_expr
        )
        # fused-pass boundary markers: the chain this single operator
        # absorbed (EXPLAIN ANALYZE shows the collapse explicitly)
        fused = ""
        if self.predicate is not None:
            fused += "+filter"
        if self._out_cols != list(range(len(self.child.schema))):
            fused += "+project"
        if self.limit is not None and 0 < self.limit <= TOPK_MAX:
            return f"TopK{fused}[{keys}, limit={self.limit}]"
        return f"Sort{fused}[{keys}]"

    def batches(self) -> Iterator[RecordBatch]:
        if (
            self.limit is not None
            and 0 < self.limit <= TOPK_MAX
        ):
            yield from self._topk_batches()
            return

        # full sort: collect per-run host columns, device-sort each run,
        # merge the runs' keys on host
        in_schema = self.child.schema
        run_cols, run_valids, run_perms = [], [], []
        dicts = [None] * len(in_schema)
        total = 0
        pending_cols = None
        pending_valids = None
        pending_n = 0
        run_rows = None
        run_src: list = []

        def flush_run():
            nonlocal pending_cols, pending_valids, pending_n, run_src
            if pending_n == 0:
                return
            cols = [np.concatenate(c) for c in pending_cols]
            valids = [
                None if all(v is None for v in vs) else np.concatenate(
                    [
                        np.ones(len(c), bool) if v is None else v
                        for v, c in zip(vs, cs)
                    ]
                )
                for vs, cs in zip(pending_valids, pending_cols)
            ]
            # cacheable run: unmasked source batches (their live rows
            # are exactly their content) — key on object identity +
            # dictionary versions so re-scans of in-memory sources skip
            # the key encode + H2D entirely
            cache_key = None
            if run_src and all(b.mask is None for b in run_src):
                versions = tuple(
                    (
                        dicts[kp.index].version
                        if dicts[kp.index] is not None
                        else -1
                    )
                    if kp.kind == "str"
                    else -1
                    for kp in self._key_plans
                )
                cache_key = (
                    tuple(id(b) for b in run_src), versions, pending_n,
                    # a fused predicate changes which rows form the run
                    # (its repr carries this query's literal values)
                    None if self.predicate is None else repr(self.predicate),
                )
            hit = (
                self._run_ops_cache.get(cache_key)
                if cache_key is not None
                else None
            )
            from datafusion_tpu.obs.stats import op_timer

            with METRICS.timer("execute.sort"), op_timer(self), \
                    _device_scope(self.device):
                if hit is not None:
                    # cached run permutation: a warm re-query skips
                    # the key encode, the sort, and the D2H pull alike
                    METRICS.add("sort.perm_cache_hits")
                    perm = hit[1]
                else:
                    keys = self._host_keys(cols, valids, dicts)
                    perm = self._sorted_run(
                        keys, len(cols[0]), cache_key, tuple(run_src)
                    )
            run_cols.append(cols)
            run_valids.append(valids)
            run_perms.append(perm)
            pending_cols = None
            pending_valids = None
            pending_n = 0
            run_src = []

        from datafusion_tpu.obs.stats import iter_stats

        for batch in iter_with_mask_prefetch(iter_stats(self.child)):
            for i, d in enumerate(batch.dicts):
                if d is not None:
                    dicts[i] = d
            # fused selection: the predicate folds into the compaction
            # mask (run_src keeps the ORIGINAL batches — the run cache
            # keys on their identity plus the predicate's repr)
            cols, valids, _, n = compact_batch(self._pred_batch(batch))
            if n == 0:
                continue
            run_src.append(batch)
            if run_rows is None:
                # run size: everything up to SORT_RUN_ROWS sorts in ONE
                # device launch (a 16M-row 2-key sort buffer is ~350 MB
                # of HBM — trivial), so the host merge only engages on
                # scans too large for a single sort; one launch + one
                # permutation pull beats per-batch-bucket runs on
                # launch-latency-dominated links
                import os

                run_rows = max(
                    bucket_capacity(batch.capacity),
                    int(os.environ.get(
                        "DATAFUSION_TPU_SORT_RUN_ROWS", str(1 << 24)
                    )),
                )
            if pending_cols is None:
                pending_cols = [[] for _ in cols]
                pending_valids = [[] for _ in cols]
            for i, c in enumerate(cols):
                pending_cols[i].append(c[:n])
                pending_valids[i].append(
                    None if valids[i] is None else valids[i][:n]
                )
            pending_n += n
            total += n
            if pending_n >= run_rows:
                flush_run()
        flush_run()

        if total == 0:
            yield self._empty_result(in_schema, dicts)
            return

        take = total if self.limit is None else min(self.limit, total)
        out_dicts = [dicts[i] for i in self._out_cols]
        if len(run_cols) == 1:
            perm = run_perms[0][:take]
            out_cols = [run_cols[0][i][perm] for i in self._out_cols]
            out_valid = [
                None if run_valids[0][i] is None else run_valids[0][i][perm]
                for i in self._out_cols
            ]
            yield make_host_batch(self._schema, out_cols, out_valid, out_dicts)
            return

        # multi-run: recompute each run's sorted key arrays under the
        # FINAL dictionaries (a dictionary that grew mid-scan changes
        # rank values, but within-run order is rank-version-invariant —
        # ranks are order-isomorphic to the string values), then merge
        run_keys = []
        for ri in range(len(run_cols)):
            perm = run_perms[ri]
            sorted_cols = [c[perm] for c in run_cols[ri]]
            sorted_valids = [
                None if v is None else v[perm] for v in run_valids[ri]
            ]
            run_keys.append(self._host_keys(sorted_cols, sorted_valids, dicts))
        merged = self._merge_runs(
            run_keys,
            [
                np.stack([np.full(len(p), ri), np.arange(len(p))], axis=1)
                for ri, p in enumerate(run_perms)
            ],
        )[:take]
        runs = merged[:, 0]
        rows = merged[:, 1]
        out_cols = []
        out_valid = []
        for i in self._out_cols:
            parts = np.empty(take, dtype=run_cols[0][i].dtype)
            vparts = np.ones(take, dtype=bool)
            any_valid = any(rv[i] is not None for rv in run_valids)
            for ri in range(len(run_cols)):
                m = runs == ri
                if not m.any():
                    continue
                sel = run_perms[ri][rows[m]]
                parts[m] = run_cols[ri][i][sel]
                if run_valids[ri][i] is not None:
                    vparts[m] = run_valids[ri][i][sel]
            out_cols.append(parts)
            out_valid.append(vparts if any_valid else None)
        yield make_host_batch(self._schema, out_cols, out_valid, out_dicts)


class LimitRelation(Relation):
    """Row-limit: stops pulling child batches as soon as enough rows
    are materialized (reference `Limit` plan, `logicalplan.rs:310-315`)."""

    def __init__(self, child: Relation, limit: int, out_schema: Schema):
        self.child = child
        self.limit = limit
        self._schema = out_schema

    @property
    def schema(self) -> Schema:
        return self._schema

    def op_label(self) -> str:
        return f"Limit[{self.limit}]"

    def batches(self) -> Iterator[RecordBatch]:
        remaining = self.limit
        if remaining <= 0:
            return
        from datafusion_tpu.obs.stats import iter_stats

        # NO mask prefetch here: the early return below exists to avoid
        # pulling (parsing, dispatching) any batch past the limit, and a
        # one-ahead prefetch would defeat exactly that
        for batch in iter_stats(self.child):
            cols, valids, dicts, n = compact_batch(batch)
            if n == 0:
                continue
            take = min(n, remaining)
            remaining -= take
            yield make_host_batch(
                batch.schema,
                [c[:take] for c in cols],
                [None if v is None else v[:take] for v in valids],
                dicts,
            )
            if remaining <= 0:
                # stop before pulling (and parsing) another child batch
                return


def run_topk_megabatch(rels: list["SortRelation"]) -> float:
    """ONE scan, N TopK queries: the serve plane's cross-query fused
    pass for `ORDER BY ... LIMIT` shapes (the SortRelation twin of
    serve's Aggregate megabatch).  Preconditions (serve._mega_key):
    every relation shares ``rels[0].core`` (kernel-cache identity —
    same key plans, so same compiled fold) over one table scan with NO
    fused predicate, so the per-batch key operands upload ONCE and
    every batch group folds ALL queries' states in one launch
    (`_TopKCore.multi_group_jit`).  The tail group fuses with every
    query's result merge (`multi_final_jit`) and the packed per-query
    results pull as ONE blob transfer.  Each relation receives an
    ``_injected_topk`` payload; its own `batches()` then skips the
    scan and runs only the host payload gather.  Returns the demux
    pull wall (seconds) for the caller's cost apportionment; launch
    walls are measured by device_call under the caller's scope.

    Raises on mid-scan ineligibility (a device-resident f64 key
    column has no host bytes to image) — the caller falls back to
    solo execution and pops any injections.
    """
    import time as _time

    from datafusion_tpu.exec.batch import device_inputs
    from datafusion_tpu.exec.fused import (
        fuse_group_max,
        iter_groups,
        pad_group,
    )
    from datafusion_tpu.obs.stats import iter_stats, op_timer

    leader = rels[0]
    core = leader.core
    in_schema = leader.child.schema
    device = leader.device
    ks = tuple(r._kb for r in rels)
    wide_f64 = core.wide and leader._key_plans[0].kind == "f"
    states = None
    dicts: list = [None] * len(in_schema)
    rank_cache: dict = {}
    fuse = fuse_group_max()
    chunk: list = []
    src_batches: list = []
    bases: list[int] = []
    next_base = 0

    def groups_of(chunk):
        entries = [(c[0], c[1], c[2], c[3], c[4], c[6]) for c in chunk]
        shareds = [c[5] for c in chunk]
        return entries, list(iter_groups(entries, shareds))

    def flush():
        nonlocal states
        if not chunk:
            return
        entries, groups = groups_of(chunk)
        with METRICS.timer("execute.sort"), op_timer(leader), \
                _device_scope(device):
            for idxs, ranks in groups:
                group = pad_group(
                    [entries[i] for i in idxs],
                    lambda e: (e[0], e[1], e[2], np.int32(0), e[4], e[5]),
                )
                METRICS.add("fused.groups")
                METRICS.add("fused.group_batches", len(idxs))
                METRICS.add("serve.megabatch_launches")
                METRICS.add("serve.megabatch_queries", len(rels))
                states = device_call(
                    core.multi_group_jit, ks, states, tuple(group),
                    ranks, _tag="topk.mega",
                )
        chunk.clear()

    def final_flush():
        # mirrors SortRelation._final_flush: the tail group's fold
        # fuses with every query's result merge in one launch
        entries, groups = groups_of(chunk)
        with METRICS.timer("execute.sort"), op_timer(leader), \
                _device_scope(device):
            st = states
            if not groups:
                METRICS.add("serve.megabatch_launches")
                METRICS.add("serve.megabatch_queries", len(rels))
                return device_call(core.multi_final_jit, ks, st, (), (),
                                   _tag="topk.mega.final")
            for gi, (idxs, ranks) in enumerate(groups):
                group = pad_group(
                    [entries[i] for i in idxs],
                    lambda e: (e[0], e[1], e[2], np.int32(0), e[4], e[5]),
                )
                METRICS.add("fused.groups")
                METRICS.add("fused.group_batches", len(idxs))
                METRICS.add("serve.megabatch_launches")
                METRICS.add("serve.megabatch_queries", len(rels))
                if gi == len(groups) - 1:
                    return device_call(
                        core.multi_final_jit, ks, st, tuple(group),
                        ranks, _tag="topk.mega.final",
                    )
                st = device_call(
                    core.multi_group_jit, ks, st, tuple(group), ranks,
                    _tag="topk.mega",
                )

    for batch in iter_stats(leader.child):
        for i, d in enumerate(batch.dicts):
            if d is not None:
                dicts[i] = d
        rank_tables = []
        for kp in leader._key_plans:
            if kp.kind != "str":
                continue
            d = batch.dicts[kp.index]
            ranks = (
                SortRelation._rank_table(d, rank_cache, kp.index)
                if d is not None
                else np.zeros(1, np.int32)
            )
            rank_tables.append(ranks)
        img = None
        if wide_f64:
            img = leader._f64_image_input(batch, leader._key_plans[0])
            if img is None:
                raise NotSupportedError(
                    "megabatch: device-resident f64 sort key"
                )
        if states is None:
            states = tuple(
                leader._topk_init(kb, in_schema, core) for kb in ks
            )
        with _device_scope(device):
            data, validity, mask = device_inputs(
                leader._key_view(batch, core), device, core.wire_hints
            )
        src_batches.append(batch)
        bases.append(next_base)
        chunk.append(
            (data, validity, mask, np.int32(batch.num_rows),
             np.int64(next_base), tuple(rank_tables), img)
        )
        next_base += batch.capacity
        if len(chunk) >= fuse:
            flush()
    if states is None:
        # empty scan: every query's result is all-dead — no device
        # work at all, each injection carries an all -1 merge
        pull_s = 0.0
        packed_h = []
        for kb in ks:
            p = np.full(1 + kb, np.int64(-1))
            p[0] = 0  # no collision
            packed_h.append(p)
    else:
        packed = final_flush()
        chunk.clear()
        pull_t0 = _time.perf_counter()
        packed_h = [np.asarray(p) for p in device_pull(tuple(packed))]
        pull_s = _time.perf_counter() - pull_t0
    for r, p in zip(rels, packed_h):
        r._injected_topk = (p, src_batches, bases, dicts)
    return pull_s
