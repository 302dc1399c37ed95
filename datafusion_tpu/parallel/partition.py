"""Partitioned query execution over a device mesh.

The distributed design the reference sketched (worker nodes pulling
partition shards, computing partial aggregates, a coordinator
combining them — `README.md:33-35`, `physicalplan.rs`,
`datasource.rs:70-85`) mapped onto TPU hardware:

- a table is a list of partition files (`PartitionedDataSource`);
  partitions assign round-robin to mesh shards;
- each round, every shard's next batch goes to its own mesh device
  the way a batch goes to the one device of `ExecutionContext`
  (`batch.device_inputs`: the column copies stay on the batch, a
  resident table's query ships nothing: its predicate runs in the
  kernel); the per-device arrays
  are assembled, without a copy, into one mesh-sharded array a column,
  and one `shard_map`-ped jitted kernel runs the *same* per-shard
  filter+aggregate update in parallel across devices (partial
  aggregation = data parallelism over rows);
- a second `shard_map` kernel combines partials with `psum` (SUM,
  COUNT, AVG) / all-gather + min/max over the mesh axis — the collective
  replaces the planned Arrow-IPC-over-HTTP partial exchange;
- group ids are dense, global, host-assigned (`GroupKeyEncoder`), and
  partition readers share string dictionaries, so every shard's
  accumulator slot `g` means the same group — combination is pure
  elementwise collectives, no remapping.

Non-aggregate plans over a partitioned table run as a serial union
scan (correct everywhere; the parallel win on a SQL engine is the
aggregate path, where output is small and no inter-shard data motion
is needed until the final combine).
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map as _raw_shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from datafusion_tpu.datatypes import Schema
from datafusion_tpu.errors import ExecutionError, PlanError
from datafusion_tpu.exec.aggregate import (
    AggregateRelation,
    _AggregateCore as _AggCore,
    group_capacity,
)
from datafusion_tpu.exec.batch import (
    RecordBatch,
    bucket_capacity,
    device_inputs,
    pad_to,
)
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.datasource import (
    CsvDataSource,
    DataSource,
    MemoryDataSource,
    ParquetDataSource,
)
from datafusion_tpu.exec.expression import compute_aux_values
from datafusion_tpu.exec.relation import DataSourceRelation, Relation
from datafusion_tpu.parallel.mesh import MESH_AXIS, make_mesh
from datafusion_tpu.parallel.physical import PlanFragment
from datafusion_tpu.plan.expr import Expr
from datafusion_tpu.plan.logical import Aggregate, LogicalPlan, Selection, TableScan
from datafusion_tpu.utils.deadline import Deadline, current_deadline, deadline_scope
from datafusion_tpu.utils.metrics import METRICS
from datafusion_tpu.utils.retry import device_call


def shard_map(f, mesh, in_specs, out_specs):
    # replication checking off: the combine kernel indexes [0] out of
    # psum results, which the checker can't see is replicated
    return _raw_shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def _share_dictionaries(partitions: Sequence[DataSource]) -> None:
    """Make string codes globally consistent across partitions.

    File-backed sources share one set of reader dictionaries (codes are
    assigned lazily, append-only, host-side).  In-memory sources already
    hold encoded batches, so their codes are *remapped* into partition
    0's dictionaries via `StringDictionary.merge_codes`.  Anything else
    is rejected — silently inconsistent codes would mis-group rows.
    """
    if len(partitions) <= 1:
        return
    readers = [getattr(p, "_reader", None) for p in partitions]
    if all(r is not None for r in readers):
        shared = readers[0].dicts
        for r in readers[1:]:
            if len(r.dicts) != len(shared):
                raise ExecutionError("partition schemas disagree")
            r.dicts = shared
        return
    if all(hasattr(p, "_batches") for p in partitions):
        shared_dicts: dict[int, object] = {}
        for b in partitions[0]._batches:
            for i, d in enumerate(b.dicts):
                if d is not None:
                    shared_dicts[i] = d
        for p in partitions[1:]:
            for b in p._batches:
                for i, d in enumerate(b.dicts):
                    if d is None:
                        continue
                    shared = shared_dicts.setdefault(i, d)
                    if shared is d:
                        continue
                    b.data[i] = shared.merge_codes(
                        np.asarray(b.data[i]), d.values
                    )
                    b.dicts[i] = shared
                    # device copies / group ids derived from the old
                    # codes are now stale
                    b.cache.clear()
        return
    raise ExecutionError(
        "cannot make string dictionaries consistent across mixed partition "
        f"source types {sorted({type(p).__name__ for p in partitions})}"
    )


class PartitionedDataSource(DataSource):
    """A table stored as N partition files with a common schema."""

    def __init__(self, partitions: Sequence[DataSource]):
        if not partitions:
            raise ExecutionError("PartitionedDataSource needs >= 1 partition")
        s0 = partitions[0].schema
        for p in partitions[1:]:
            if p.schema.names() != s0.names():
                raise ExecutionError("partition schemas disagree")
        self.partitions = list(partitions)
        _share_dictionaries(self.partitions)

    @property
    def schema(self) -> Schema:
        return self.partitions[0].schema

    def batches(self) -> Iterator[RecordBatch]:
        # serial union scan (the non-aggregate fallback path)
        for p in self.partitions:
            yield from p.batches()

    def with_projection(self, projection: Sequence[int]) -> "PartitionedDataSource":
        return PartitionedDataSource([p.with_projection(projection) for p in self.partitions])

    def to_meta(self) -> dict:
        return {"Partitioned": [p.to_meta() for p in self.partitions]}


# table batches a reader of `register_resident_parquet` parses at a time
_READ_BATCHES = 8


def _padded(batch: RecordBatch, cap: int) -> RecordBatch:
    """`batch` with its host arrays zero-padded to capacity `cap`."""
    return RecordBatch(
        batch.schema,
        [pad_to(np.asarray(a), cap) for a in batch.data],
        [None if v is None else pad_to(np.asarray(v), cap)
         for v in batch.validity],
        list(batch.dicts),
        num_rows=batch.num_rows,
        mask=None if batch.mask is None else pad_to(np.asarray(batch.mask), cap),
    )


class _MeshStacker:
    """Builds `[n_shards, cap]` mesh-sharded device arrays by placing
    each shard's already-padded host column directly on its own mesh
    device (`make_array_from_single_device_arrays`).

    The previous shape of this path — host-stack into a fresh
    `np.zeros([n, cap])`, `jnp.asarray` onto the default device, let
    the jitted shard_map reshard — cost one alloc+copy, one eager
    full-size transfer to device 0, and one cross-device scatter per
    array per round (~100 ms each on the 8-virtual-device bench, the
    bulk of the mesh overhead the round-3 verdict flagged).  Direct
    per-shard placement is also the layout a real multi-chip mesh
    wants: each host feeds its own chips, no gather through chip 0."""

    def __init__(self, mesh):
        self.mesh = mesh
        self.devices = list(mesh.devices.flat)
        self.n = len(self.devices)
        self._sharding = NamedSharding(mesh, P(MESH_AXIS))
        self._fill_cache: dict = {}

    def fill(self, cap: int, dtype, value=0) -> np.ndarray:
        """Cached cap-length constant array (absent shards, padding)."""
        key = (cap, np.dtype(dtype).str, value)
        hit = self._fill_cache.get(key)
        if hit is None:
            hit = np.full(cap, value, dtype)
            hit.setflags(write=False)
            self._fill_cache[key] = hit
        return hit

    def pad(self, arr: np.ndarray, cap: int) -> np.ndarray:
        arr = np.asarray(arr)
        if arr.shape[0] == cap:
            return arr
        out = np.zeros(cap, arr.dtype)
        out[: arr.shape[0]] = arr
        return out

    def put(self, shards: Sequence[np.ndarray], owner: str = "mesh.shard"):
        """One [n, cap] mesh-sharded array from n cap-length host
        arrays (shards[i] lands on mesh device i, no reshard).  The
        per-shard transfers profile through the device ledger and count
        into `h2d.bytes`; the assembled global array is adopted under
        ``owner`` and dies with its round."""
        from datafusion_tpu.obs.device import (
            LEDGER,
            enabled as _ledger_on,
            profile_sync_active,
        )

        # dispatch every shard without per-transfer blocking (the n
        # device links genuinely run in parallel), then — only under
        # profile_sync, same contract as single-device puts — block
        # ONCE on the batch and record one combined transfer event;
        # per-shard profiled transfers would serialize the links they
        # measure
        synced = profile_sync_active()
        with METRICS.timer("h2d.dispatch") as span:
            put = [
                LEDGER.transfer(np.asarray(a)[None], d, profile=False)
                for a, d in zip(shards, self.devices)
            ]
            if synced:
                jax.block_until_ready(put)
        nbytes = sum(int(p.nbytes) for p in put)
        METRICS.add("h2d.bytes", nbytes)
        if _ledger_on():
            # one event for the batch of parallel transfers (it counts
            # once in `device.h2d.transfers`)
            LEDGER.note_h2d(
                nbytes,
                span.wall_s,
                self.devices[0],
                synced=synced,
            )
        return LEDGER.adopt(
            jax.make_array_from_single_device_arrays(
                (self.n,) + np.asarray(shards[0]).shape,
                self._sharding,
                put,
            ),
            owner, cached=False,
        )

    @staticmethod
    def start_pull(arrays) -> None:
        """Begin per-shard D2H copies for mesh-sharded arrays.  Pulling
        a sharded array through np.asarray gathers every shard to one
        buffer first (an all-gather on a real mesh); per-shard copies
        go straight from each device to host."""
        for a in arrays:
            for sh in a.addressable_shards:
                sh.data.copy_to_host_async()

    @staticmethod
    def take(arr, s_i: int) -> np.ndarray:
        """Shard s_i of a mesh-sharded [n, cap] array as a host row."""
        for sh in arr.addressable_shards:
            if sh.index[0].start == s_i:
                return np.asarray(sh.data)[0]
        raise ExecutionError(f"shard {s_i} not addressable")


def _round_robin(parts: Sequence, n_shards: int) -> list[list]:
    assignment: list[list] = [[] for _ in range(n_shards)]
    for i, p in enumerate(parts):
        assignment[i % n_shards].append(p)
    return assignment


class _ShardFeed:
    """Chained batch iterator over one shard's assigned partitions."""

    def __init__(self, relations: list[Relation]):
        from datafusion_tpu.obs.stats import iter_stats

        self._iters = [iter_stats(r) for r in relations]
        self._pos = 0

    def next_batch(self) -> Optional[RecordBatch]:
        while self._pos < len(self._iters):
            batch = next(self._iters[self._pos], None)
            if batch is not None:
                return batch
            self._pos += 1
        return None


def _partitioned_pipeline_jit(core, mesh):
    """Process-wide cached `jax.jit(shard_map(...))` for a pipeline
    core on a mesh (cached on the core like _partitioned_jits)."""
    key = (
        "pipe",
        tuple((d.platform, d.id) for d in mesh.devices.flat),
        tuple(getattr(mesh, "axis_names", ())),
    )
    cache = getattr(core, "_part_jits", None)
    if cache is None:
        cache = core._part_jits = {}
    hit = cache.get(key)
    if hit is not None:
        return hit

    def stacked_kernel(cols, valids, aux, num_rows, masks, params):
        sq = lambda t: t[0]
        out_cols, out_valids, mask = core._kernel(
            [sq(c) for c in cols],
            [None if v is None else sq(v) for v in valids],
            aux,
            sq(num_rows),
            sq(masks),
            params,
        )
        capacity = mask.shape[0]
        ex = lambda t: jnp.broadcast_to(t, (capacity,))[None]
        # shard_map output pytrees can't carry None: absent validity
        # (the all-valid common case) returns a 1-element dummy plane —
        # the host recognizes the shape and never pulls a full one
        out_valids = tuple(
            jnp.ones((1, 1), bool) if v is None else ex(v) for v in out_valids
        )
        return tuple(ex(c) for c in out_cols), out_valids, mask[None]

    spec_sh = P(MESH_AXIS)
    spec_rep = P()
    hit = cache[key] = jax.jit(
        shard_map(
            stacked_kernel,
            mesh=mesh,
            in_specs=(spec_sh, spec_sh, spec_rep, spec_sh, spec_sh,
                      spec_rep),
            out_specs=spec_sh,
        )
    )
    return hit


class PartitionedPipelineRelation(Relation):
    """[Selection +] [Projection] over partitioned input on a device
    mesh: each round, every shard's next batch stacks into
    `[n_shards, cap]` host arrays and ONE `shard_map`-ped kernel runs
    the same fused filter+project update in parallel across devices —
    the data-parallel twin of the partitioned aggregate, for the plan
    shapes that used to fall back to a serial union scan
    (`parallel/partition.py` round-2 note).

    Outputs materialize host-side once per round (one blob-packed pull
    for every shard's computed columns + masks); identity projections
    pass the shard's own host arrays through untouched, so Float64
    passthroughs stay bit-exact exactly like the single-device pipeline.
    """

    def __init__(
        self,
        children: list[Relation],
        predicate: Optional[Expr],
        projections: Optional[list[Expr]],
        out_schema: Schema,
        mesh,
        functions=None,
        function_metas=None,
    ):
        from datafusion_tpu.exec.kernels import parameterize_exprs
        from datafusion_tpu.exec.relation import _PipelineCore

        self.children = children
        self.predicate = predicate
        self.projections = projections
        self._schema = out_schema
        self.mesh = mesh
        self.n_shards = int(np.prod(mesh.devices.shape))
        self._metas = function_metas or {}
        self.core = _PipelineCore.build(
            children[0].schema, predicate, projections, functions, self._metas
        )
        if self.core.host_proj:
            raise PlanError(
                "host-evaluated projections take the serial union scan"
            )
        self._params = parameterize_exprs(
            _PipelineCore.param_exprs(predicate, projections, self._metas)
        )[2]
        self._aux_cache: dict = {}
        # process-wide cached mesh jit (same rationale as the
        # partitioned aggregate's _partitioned_jits: a per-relation
        # jax.jit(shard_map(...)) re-compiles the mesh program on every
        # fresh context)
        self._stacked_jit = _partitioned_pipeline_jit(self.core, mesh)

    @property
    def schema(self) -> Schema:
        return self._schema

    def op_label(self) -> str:
        return (
            f"MeshPipeline[shards={self.n_shards}, "
            f"partitions={len(self.children)}]"
        )

    def batches(self) -> Iterator[RecordBatch]:
        from datafusion_tpu.exec.expression import compute_aux_values as _aux
        from datafusion_tpu.obs.stats import op_timer

        core = self.core
        n = self.n_shards
        feeds = [_ShardFeed(rels) for rels in _round_robin(self.children, n)]
        in_schema = self.children[0].schema
        used = core.used_cols

        stacker = _MeshStacker(self.mesh)
        # the ambient per-query deadline bounds every mesh round (the
        # distributed path already honors it via fragment budgets; the
        # single-host mesh path used to run unbounded)
        deadline = current_deadline()

        while True:
            if deadline is not None:
                deadline.check("partitioned pipeline round")
            round_batches = [f.next_batch() for f in feeds]
            if all(b is None for b in round_batches):
                return
            live = [b for b in round_batches if b is not None]
            cap = max(bucket_capacity(1), *(b.capacity for b in live))

            if core.needs_kernel:
                has_valid = [
                    any(
                        b is not None and b.validity[c] is not None
                        for b in round_batches
                    )
                    for c in used
                ]
                col_shards: list[list[np.ndarray]] = [[] for _ in used]
                valid_shards: list[list[np.ndarray]] = [[] for _ in used]
                mask_shards: list[np.ndarray] = []
                rows_np = np.zeros((n,), np.int32)
                for s_i, b in enumerate(round_batches):
                    if b is None:
                        for j, c in enumerate(used):
                            col_shards[j].append(
                                stacker.fill(
                                    cap, in_schema.field(c).data_type.np_dtype
                                )
                            )
                            if has_valid[j]:
                                valid_shards[j].append(
                                    stacker.fill(cap, bool, False)
                                )
                        mask_shards.append(stacker.fill(cap, bool, False))
                        continue
                    rows_np[s_i] = b.num_rows
                    mask_shards.append(
                        stacker.fill(cap, bool, True)
                        if b.mask is None
                        else stacker.pad(b.mask, cap)
                    )
                    for j, c in enumerate(used):
                        col_shards[j].append(stacker.pad(b.data[c], cap))
                        if has_valid[j]:
                            v = b.validity[c]
                            valid_shards[j].append(
                                stacker.fill(cap, bool, True)
                                if v is None
                                else stacker.pad(v, cap)
                            )
                aux = tuple(_aux(core.aux_specs, live[0], self._aux_cache))
                with METRICS.timer("execute.partitioned_pipeline"), \
                        op_timer(self):
                    out_cols, out_valids, masks = device_call(
                        self._stacked_jit,
                        tuple(stacker.put(s) for s in col_shards),
                        tuple(
                            stacker.put(s) if has_valid[j] else None
                            for j, s in enumerate(valid_shards)
                        ),
                        aux,
                        jnp.asarray(rows_np),
                        stacker.put(mask_shards),
                        self._params,
                    )
                    # per-shard D2H (no cross-device gather); dummy
                    # validity planes (shape [n,1]) never grow
                    stacker.start_pull(
                        list(out_cols)
                        + [v for v in out_valids if v.shape[1] > 1]
                        + [masks]
                    )
            else:
                out_cols, out_valids, masks = (), (), None

            for s_i, b in enumerate(round_batches):
                if b is None:
                    continue
                bc = b.capacity
                if core.proj_fns is None:
                    # filter-only: input columns untouched
                    cols, valids, dicts = b.data, b.validity, b.dicts
                else:
                    cols, valids, dicts = [], [], []
                    dev_i = 0
                    for j in range(len(self.projections)):
                        src = core.identity_proj.get(j)
                        if src is not None:
                            cols.append(b.data[src])
                            valids.append(b.validity[src])
                        else:
                            cols.append(
                                stacker.take(out_cols[dev_i], s_i)[:bc]
                            )
                            ov = out_valids[dev_i]
                            # 1-wide plane = the kernel's all-valid dummy
                            valids.append(
                                None
                                if ov.shape[1] == 1
                                else stacker.take(ov, s_i)[:bc]
                            )
                            dev_i += 1
                        src_d = core.out_dict_sources[j]
                        dicts.append(b.dicts[src_d] if src_d is not None else None)
                mask = (
                    stacker.take(masks, s_i)[:bc]
                    if masks is not None
                    else b.mask
                )
                yield RecordBatch(
                    self._schema,
                    list(cols),
                    list(valids),
                    list(dicts),
                    num_rows=b.num_rows,
                    mask=mask,
                )


def _partitioned_jits(core, mesh):
    """(stacked_update_jit, combine_jit) for an aggregate core on a
    mesh, cached ON the core (cores are process-wide, LRU-bounded —
    exec/kernels.py) so repeated partitioned queries of the same shape
    reuse the compiled mesh executables.  The shard_map bodies close
    over the core only; everything per-query (literals, encoder state)
    arrives as runtime operands."""
    key = (
        tuple((d.platform, d.id) for d in mesh.devices.flat),
        tuple(getattr(mesh, "axis_names", ())),
    )
    cache = getattr(core, "_part_jits", None)
    if cache is None:
        cache = core._part_jits = {}
    hit = cache.get(key)
    if hit is not None:
        return hit

    spec_sh = P(MESH_AXIS)  # leading axis = shard
    spec_rep = P()  # replicated

    # per-round update: each device runs the single-device kernel on
    # its block.  The row inputs (columns, validity, mask, ids) are
    # FLAT `[n_shards * cap]` arrays sharded over the mesh axis, so a
    # device's block is the `[cap]` array `batch.device_inputs` left on
    # it and a round assembles without a copy; the row counts and the
    # state carry a leading shard axis.  NOT donated: device_call may
    # replay the dispatch on a transient failure, and a donated state
    # buffer would already be consumed by the failed attempt.
    def stacked_update(cols, valids, aux, num_rows, masks, ids, state,
                       str_aux, params):
        sq = lambda t: t[0]
        counts, accs = state
        local = (sq(counts), jax.tree.map(sq, accs))
        out = core._kernel(
            list(cols),
            list(valids),
            aux,
            sq(num_rows),
            masks,
            ids,
            local,
            str_aux,
            params,
        )
        ex = lambda t: t[None]
        oc, oa = out
        return ex(oc), jax.tree.map(ex, oa)

    # MIN/MAX meet as all-gather + local reduce, not pmin/pmax: the TPU
    # compiler lowers only the SUM all-reduce for 64-bit operands
    # ("Supported lowering only of Sum all reduce"), and the engine's
    # accumulators are int64/f64.  Gathering is exact; the type is
    # never narrowed.
    def all_min(x):
        return jnp.min(lax.all_gather(x, MESH_AXIS), axis=0)

    def all_max(x):
        return jnp.max(lax.all_gather(x, MESH_AXIS), axis=0)

    def combine(state, str_aux):
        counts, accs = state
        fin_counts = lax.psum(counts, MESH_AXIS)[0]
        fin_accs = []
        for i, (sl, acc) in enumerate(zip(core.slots, accs)):
            if sl.kind in ("sum", "cnt"):
                fin_accs.append(lax.psum(acc, MESH_AXIS)[0])
            elif sl.kind == "min":
                fin_accs.append(all_min(acc)[0])
            elif sl.kind == "max":
                fin_accs.append(all_max(acc)[0])
            else:
                # Utf8 MIN/MAX: partitions share dictionaries in mesh
                # mode (_share_dictionaries), so codes are globally
                # consistent — meet in lexicographic-rank space, then
                # map the winning rank back to its code
                ranks = _AggCore._codes_to_ranks(sl.kind, acc[0], str_aux[i])
                if sl.kind == "smin":
                    best = all_min(ranks)
                else:
                    best = all_max(ranks)
                fin_accs.append(
                    _AggCore._ranks_to_codes(sl.kind, best, str_aux[i])
                )
        return fin_counts, tuple(fin_accs)

    stacked_sm = shard_map(
        stacked_update,
        mesh=mesh,
        in_specs=(spec_sh, spec_sh, spec_rep, spec_sh, spec_sh, spec_sh,
                  spec_sh, spec_rep, spec_rep),
        out_specs=spec_sh,
    )
    stacked_jit = jax.jit(stacked_sm)

    # multi-ROUND fold (the PR 6 batch-group fold lifted to mesh
    # rounds): consecutive rounds of one shape class fold through the
    # shard_map'd update inside ONE jitted program, so a mesh query
    # pays one launch per `_ROUND_FUSE_MAX` rounds instead of one per
    # round.
    def multi_rounds(rounds, state, params):
        for (cols, valids, aux, num_rows, masks, ids, str_aux) in rounds:
            state = stacked_sm(cols, valids, aux, num_rows, masks, ids,
                               state, str_aux, params)
        return state

    multi_jit = jax.jit(multi_rounds)
    combine_jit = jax.jit(
        shard_map(
            combine,
            mesh=mesh,
            in_specs=(spec_sh, spec_rep),
            out_specs=spec_rep,
        )
    )
    hit = cache[key] = (stacked_jit, combine_jit, multi_jit)
    return hit


# rounds folded into one launch.  The fold is unrolled (each round's
# arrays are arguments of their own: stacking them would copy the
# resident table every query), so the program grows with it; the
# consumer's launches are not what sets a mesh query's pace.
_ROUND_FUSE_MAX = 32


@functools.lru_cache(maxsize=256)
def _fill(device, cap: int, dtype: str, value):
    """A `[cap]` constant on `device`: an absent shard's block, a mask
    or validity array where one shard of a round has none."""
    from datafusion_tpu.obs.device import LEDGER

    return LEDGER.put(np.full(cap, value, np.dtype(dtype)), device,
                      owner="mesh.fill")


@functools.lru_cache(maxsize=None)
def _stagers(n_shards: int) -> ThreadPoolExecutor:
    """The threads that stage a round's shards side by side, one a
    shard, for as long as the process lives (a pool a query would
    start and end `n_shards` threads a query)."""
    return ThreadPoolExecutor(n_shards, thread_name_prefix="df-tpu-mesh-stage")


class _Round:
    """One mesh round: shard s's next batch (None where the shard has
    run out), all of one capacity.  `placed`, `aux` and `str_aux` are
    what staging leaves for the consumer."""

    __slots__ = ("batches", "cap", "placed", "aux", "str_aux")

    def __init__(self, batches, cap):
        self.batches = batches
        self.cap = cap
        self.placed = None


class PartitionedAggregateRelation(AggregateRelation):
    """[Selection +] Aggregate over partitioned input on a device mesh.

    Reuses the single-device kernel (`AggregateRelation._kernel`) as the
    per-shard body of a `shard_map` and the single-device scan loop's
    parts around it: a round is staged on `staged_pipeline`'s producer
    (each shard's batch through `batch.device_inputs` and `_group_ids`
    onto that shard's device), the consumer assembles the staged
    per-device arrays into mesh-sharded ones and folds rounds into
    launches; adds the collective final combine.  Residency is the
    batch's (`device_inputs`' cache, the group-id slot): a table whose
    shards hand out the same batches to every query
    (`register_resident_parquet`) ships its columns once.

    Where the predicate runs is `AggregateRelation`'s rule, read from
    every partition's source: over shards that all keep their batches
    it is in the core (a round's staging finds the copies and ships
    nothing, the one `cmp_table` of the shared dictionaries serves
    every chip); over streamed partitions, and over a mesh whose
    shards disagree, the host evaluates it a shard batch and its mask
    rides with that batch's columns.
    """

    def __init__(
        self,
        children: list[Relation],
        group_expr: list[Expr],
        aggr_expr: list[Expr],
        out_schema: Schema,
        mesh,
        predicate: Optional[Expr] = None,
        functions=None,
    ):
        self.children = children  # the base's ctor asks every partition's source
        super().__init__(
            children[0], group_expr, aggr_expr, out_schema,
            predicate=predicate, functions=functions,
        )
        self.mesh = mesh
        self._devices = list(mesh.devices.flat)
        self.n_shards = len(self._devices)
        self._sharding = NamedSharding(mesh, P(MESH_AXIS))
        self._init_stacked_cache: dict = {}
        self._rows_cache: dict = {}
        self._aux_on_mesh: dict = {}
        # how `_stage` goes over a round's shards: one after the other,
        # or side by side where `accumulate` runs it under a producer
        self._stage_map = map
        # the shard_map jits are keyed on the PROCESS-WIDE core (not
        # this relation): a fresh PartitionedContext per query would
        # otherwise rebuild `jax.jit(shard_map(...))` around new bound
        # methods and re-trace + re-compile the whole mesh program
        # every run (~seconds per query — the round-4 mesh-aggregate
        # gap was mostly exactly this)
        self._stacked_jit, self._combine_jit, self._multi_jit = (
            _partitioned_jits(self.core, mesh)
        )

    # -- stacked state management --
    def _init_stacked_state(self, capacity: int):
        # cached per capacity: building + sharding the empty stacked
        # state costs device launches every accumulate() otherwise;
        # states are functionally consumed, never mutated
        hit = self._init_stacked_cache.get(capacity)
        if hit is not None:
            return hit
        counts, accs = self._init_state(capacity)
        tile = lambda t: jnp.broadcast_to(t[None], (self.n_shards,) + t.shape)
        state = self._shard_state((tile(counts), jax.tree.map(tile, accs)))
        self._init_stacked_cache[capacity] = state
        return state

    def _shard_state(self, state):
        from datafusion_tpu.obs.device import LEDGER

        return jax.tree.map(
            lambda t: LEDGER.put(t, self._sharding, owner="mesh.state"), state
        )

    def _grow_stacked_state(self, state, new_capacity: int):
        counts, accs = state
        pad = new_capacity - counts.shape[1]

        def grow(a, fill):
            block = jnp.full((self.n_shards, pad), jnp.asarray(fill, a.dtype))
            return jnp.concatenate([a, block], axis=1)

        new_accs = tuple(
            grow(acc, self._slot_identity(sl))
            for sl, acc in zip(self.slots, accs)
        )
        return self._shard_state((grow(counts, 0), new_accs))

    def op_label(self) -> str:
        return (
            f"MeshAggregate[shards={self.n_shards}, "
            f"partitions={len(self.children)}, keys={len(self.key_cols)}]"
        )

    def _scan(self, keyed: bool):
        # shard states merge by the encoder's dense ids, keyed or not
        return self.accumulate()

    # -- a round: pulled, staged, assembled --
    def _rounds(self) -> Iterator[_Round]:
        """Shard s's next batch, round after round, until every shard
        has run out; a round's batches share one capacity (a resident
        table's already do, and are handed on as they are)."""
        feeds = [
            _ShardFeed(rels)
            for rels in _round_robin(self.children, self.n_shards)
        ]
        while True:
            batches = [f.next_batch() for f in feeds]
            if all(b is None for b in batches):
                return
            cap = max(
                bucket_capacity(1),
                *(b.capacity for b in batches if b is not None),
            )
            yield _Round(
                [b if b is None or b.capacity == cap else _padded(b, cap)
                 for b in batches],
                cap,
            )

    def _stage(self, r: _Round) -> None:
        """The host's part of a round (the producer's, where there is
        one): per shard batch its group ids (encoded and placed where
        the key set is new to the batch), this query's host predicate
        where the partitions are streamed, and `device_inputs` onto the
        shard's device, which ships the columns (that mask with them)
        where the batch does not hold them there and nothing where it
        does; then the round's aux tables (a predicate in the core
        reads its `cmp_table` from them: one for all shards, the
        dictionaries are one set).  Under the producer the
        round's shards are staged side by side, a thread each
        (`_stagers`): their numpy passes and puts release the GIL, and
        each put goes down its own chip's link."""
        with METRICS.timer("mesh.stage"):
            live = None
            for b in r.batches:
                if b is None:
                    continue
                live = b
                for idx in self.key_cols:
                    if b.dicts[idx] is not None:
                        self._key_dicts[idx] = b.dicts[idx]
            r.placed = list(
                self._stage_map(self._stage_shard, self._devices, r.batches))
            # aux / rank tables derive from the (shared) dictionaries;
            # computed after all shards' rows are encoded so versions
            # are current
            r.aux = tuple(
                self._replicated(a) for a in
                compute_aux_values(self._aux_specs, live, self._aux_cache)
            ) if self._aux_specs else ()
            r.str_aux = self._compute_str_aux(live)

    def _replicated(self, table):
        """A round's aux table on every device of the mesh, put once a
        query a distinct table (the shared `_aux_cache` hands every
        round the same numpy object until a dictionary grows).  Left as
        numpy it would travel to each chip again with every round slot
        of every launch: 32 x 4 puts a folded launch (four chips,
        PR 36: 14.8 ms a launch that way, 2.3 ms this way)."""
        hit = self._aux_on_mesh.get(id(table))
        if hit is None or hit[0] is not table:
            from datafusion_tpu.obs.device import LEDGER

            hit = self._aux_on_mesh[id(table)] = (table, LEDGER.put(
                np.asarray(table), NamedSharding(self.mesh, P()),
                owner="mesh.aux", cached=False,
            ))
        return hit[1]

    def _stage_shard(self, dev, b: Optional[RecordBatch]):
        """(cols, valids, mask, ids) of one shard's batch on `dev`;
        None for a shard that has run out.  Shards share the encoder
        (`_group_ids` serializes its misses) and the core's wire hints
        (immutable entries, validated against every batch they are
        tried on)."""
        if b is None:
            return None
        ids = self._group_ids(b, dev)
        return (
            *device_inputs(
                self._device_view(b), dev, self.core.wire_hints,
                query_mask=self._query_mask(b),
            ),
            ids,
        )

    def _assemble(self, r: _Round, dtypes):
        """(cols, valids, rows, mask, ids) of a staged round as
        mesh-sharded arrays: each is the shards' own single-device
        arrays seen as one `[n_shards * cap]` array — no copy, no put
        (`_fill` constants stand in for an absent shard's block)."""
        cap = r.cap

        def glob(of_shard, dtype, value=0):
            return jax.make_array_from_single_device_arrays(
                (self.n_shards * cap,),
                self._sharding,
                [
                    _fill(dev, cap, dtype, value) if a is None else a
                    for dev, a in zip(
                        self._devices,
                        (None if p is None else of_shard(p)
                         for p in r.placed),
                    )
                ],
            )

        # a validity array travels only for columns where some shard
        # carries nulls this round (None otherwise: the all-valid
        # common case never traces those bytes)
        cols = tuple(
            glob(lambda p, c=c: p[0][c], dt) for c, dt in enumerate(dtypes)
        )
        valids = tuple(
            glob(lambda p, c=c: p[1][c], "bool", True)
            if any(p is not None and p[1][c] is not None for p in r.placed)
            else None
            for c in range(len(dtypes))
        )
        rows_dev = self._rows(
            tuple(0 if b is None else b.num_rows for b in r.batches))
        # an absent shard's mask block is all True like a batch without
        # a mask: its zero row count is what keeps it out
        mask = glob(lambda p: p[2], "bool", True)
        ids = glob(lambda p: p[3], "int32")
        return cols, valids, rows_dev, mask, ids

    def _rows(self, rows: tuple):
        """A round's row counts, one a shard, on the mesh (a scan has
        two or three distinct ones: whole batches, tails, a dead
        round's zeros)."""
        hit = self._rows_cache.get(rows)
        if hit is None:
            from datafusion_tpu.obs.device import LEDGER

            hit = self._rows_cache[rows] = LEDGER.put(
                np.asarray(rows, np.int32), self._sharding,
                owner="mesh.rows", cached=False,
            )
        return hit

    # -- the partitioned scan loop --
    def accumulate(self):
        from datafusion_tpu.exec.fused import (
            entry_signature,
            pad_group,
            shared_signature,
        )
        from datafusion_tpu.exec.prefetch import (
            pipeline_enabled,
            staged_pipeline,
        )
        from datafusion_tpu.obs.stats import op_timer

        # a table whose shards hand out the same batches every query
        # keeps ONE encoder a key set (`datasource.SharedScanState`):
        # the ids a batch holds on its device replay for this query
        self._adopt_source_state()
        in_schema = self.child.schema
        dtypes = [
            np.dtype(in_schema.field(i).data_type.np_dtype).name
            for i in self.core.used_cols
        ]
        state = None
        group_cap = 0
        # ambient per-query deadline: bounds every mesh round AND (via
        # the contextvar already being set) the device_call backoffs
        deadline = current_deadline()

        rounds = self._rounds()
        staged = pipeline_enabled(None)
        self._stage_map = _stagers(self.n_shards).map if staged else map
        if staged:
            # one thread pulls rounds (a file-backed shard's parse), the
            # producer stages them (a thread a shard under it), this
            # thread launches: the single-device scan loop's pipeline,
            # a round where it has a batch
            rounds = staged_pipeline(rounds, self._stage)

        # consecutive rounds with one shape class collect here and
        # dispatch as one launch through `self._multi_jit`; a shape-
        # class change and state growth flush first
        round_buf: list = []
        round_sig = None
        str_aux = None
        shard_rows = np.zeros(self.n_shards, np.int64)

        def flush_rounds():
            nonlocal state
            if not round_buf:
                return
            if len(round_buf) == 1:
                (cols, valids, aux, rows_dev, mask, ids, s_aux) = round_buf[0]
                with METRICS.timer("execute.partitioned_aggregate"), \
                        op_timer(self):
                    state = device_call(
                        self._stacked_jit, cols, valids, aux, rows_dev,
                        mask, ids, state, s_aux, self._params,
                        _tag="mesh.stacked",
                    )
                round_buf.clear()
                return
            zero = self._rows((0,) * self.n_shards)
            group = pad_group(
                list(round_buf),
                # dead round: the live round's arrays with a zero row
                # count — every shard contributes identity
                lambda r: (r[0], r[1], r[2], zero, r[4], r[5], r[6]),
            )
            METRICS.add("mesh.fused_round_launches")
            METRICS.add("mesh.fused_rounds", len(round_buf))
            with METRICS.timer("execute.partitioned_aggregate"), \
                    op_timer(self):
                state = device_call(
                    self._multi_jit, tuple(group), state, self._params,
                    _tag="mesh.multi",
                )
            round_buf.clear()

        for r in rounds:
            if deadline is not None:
                deadline.check("partitioned aggregate round")
            if r.placed is None:
                self._stage(r)  # no staging thread on this platform
            METRICS.add("mesh.rounds")
            shard_rows += [0 if b is None else b.num_rows for b in r.batches]
            with METRICS.timer("mesh.assemble"):
                cols, valids, rows_dev, mask, ids = self._assemble(r, dtypes)
            str_aux = r.str_aux
            # capacity picked after the round's keys are encoded (the
            # producer may be further ahead: a larger capacity holds
            # every id of this round too)
            needed = self._pick_capacity(group_cap)
            if state is None:
                group_cap = needed
                state = self._init_stacked_state(group_cap)
            elif needed > group_cap:
                flush_rounds()  # state is about to change shape
                state = self._grow_stacked_state(state, needed)
                group_cap = needed
            sig = (
                entry_signature((cols, valids, rows_dev, mask, ids)),
                shared_signature((r.aux, str_aux)),
            )
            if round_buf and (sig != round_sig
                              or len(round_buf) >= _ROUND_FUSE_MAX):
                flush_rounds()
            round_sig = sig
            round_buf.append((cols, valids, r.aux, rows_dev, mask, ids, str_aux))
        flush_rounds()
        METRICS.add("mesh.shards", self.n_shards)
        METRICS.add("mesh.shard_rows.max", int(shard_rows.max()))
        METRICS.add("mesh.shard_rows.total", int(shard_rows.sum()))

        if state is None:
            state = self._init_stacked_state(group_capacity(1))
            # no rounds ran: dummy 1-entry rank tables (every slot is
            # the -1 empty code, which maps sentinel -> -1 regardless)
            dummy = (np.zeros(1, np.int32), np.zeros(1, np.int32))
            str_aux = tuple(
                dummy if sl.is_string else None for sl in self.slots
            )
        with METRICS.timer("execute.collective_combine"):
            # codes are append-only, so the final round's rank tables
            # cover every code any earlier round accumulated
            return device_call(self._combine_jit, state, str_aux,
                               _tag="mesh.combine")


class DeadlineBoundRelation(Relation):
    """Bounds a relation's entire iteration with a per-query deadline:
    anchors the budget at first pull, checks it before every batch, and
    makes it ambient (`deadline_scope`) around each child pull so
    `device_call` backoffs and the mesh round loops honor it too.  This
    closes the single-host gap: the distributed path already threads a
    budget through fragment requests, but a local mesh query used to
    run unbounded."""

    def __init__(self, inner: Relation, seconds: float):
        self.inner = inner
        self.seconds = seconds

    @property
    def schema(self) -> Schema:
        return self.inner.schema

    def op_label(self) -> str:
        return f"Deadline[{self.seconds}s]"

    def batches(self) -> Iterator[RecordBatch]:
        from datafusion_tpu.obs.stats import iter_stats

        deadline = Deadline.after(self.seconds)
        it = iter(iter_stats(self.inner))
        while True:
            deadline.check("partitioned query")
            # scope set per-pull (not around the generator): contextvar
            # writes inside a generator leak into the consumer otherwise
            with deadline_scope(deadline):
                batch = next(it, None)
            if batch is None:
                return
            yield batch


class PartitionedContext(ExecutionContext):
    """ExecutionContext that executes over a device mesh.

    Aggregates over partitioned tables run the partial-aggregate +
    collective-combine path; every plan fragment round-trips through
    the JSON wire format first (`PlanFragment`), proving the bytes a
    multi-host coordinator would ship.

    `query_deadline_s` (or env DATAFUSION_TPU_QUERY_DEADLINE_S — the
    same knob the distributed coordinator honors) bounds every query's
    iteration end to end, including mesh rounds and device retries.
    """

    def __init__(self, mesh=None, n_devices: Optional[int] = None,
                 batch_size: int = 131072,
                 query_deadline_s: Optional[float] = None,
                 result_cache=None):
        import os

        super().__init__(device=None, batch_size=batch_size,
                         result_cache=result_cache)
        self.mesh = mesh if mesh is not None else make_mesh(n_devices)
        self.last_fragments: list[PlanFragment] = []
        if query_deadline_s is None:
            env = os.environ.get("DATAFUSION_TPU_QUERY_DEADLINE_S")
            # "0" means off (the documented default), not a 0s budget
            query_deadline_s = (float(env) or None) if env else None
        self.query_deadline_s = query_deadline_s
        self._executing = False

    def register_partitioned_csv(
        self, name: str, paths: Sequence[str], schema: Schema, has_header: bool = True
    ) -> None:
        self.register_datasource(
            name,
            PartitionedDataSource(
                [CsvDataSource(p, schema, has_header, self.batch_size) for p in paths]
            ),
        )

    def register_partitioned_parquet(
        self, name: str, paths: Sequence[str], schema: Optional[Schema] = None
    ) -> None:
        self.register_datasource(
            name,
            PartitionedDataSource(
                [ParquetDataSource(p, schema, self.batch_size) for p in paths]
            ),
        )

    def register_resident_parquet(
        self, name: str, path: str, schema: Optional[Schema] = None
    ) -> None:
        """Register one Parquet file as a table that STAYS, sharded
        over the mesh: the file is read once, row group g by the reader
        of shard g mod n, the shards side by side on a thread each (one
        dictionary set for all of them), each shard's rows are kept in memory as batches of `batch_size` rows
        at one capacity (a `MemoryDataSource`: the SAME batch objects
        for every query), and partition s is mesh device s's, as any
        partitioned table's is.  A file with fewer row groups than
        devices leaves shards empty.  Nothing is placed
        here: the first query over the table ships each batch's columns
        to its shard's device, where `batch.device_inputs` keeps them
        on the batch, and every later query, whatever relation runs it,
        finds them there and ships nothing (its predicate is in the
        core, over the copies).  What a shard may come to hold there (its
        columns' bytes) is asked of the ledger per device now, and a
        table whose shard does not fit its chip is refused."""
        from datafusion_tpu.exec.prefetch import staged_prefetch
        from datafusion_tpu.io.readers import (
            infer_parquet_schema,
            parquet_row_groups,
            whole_batches,
        )
        from datafusion_tpu.obs.device import LEDGER

        devices = list(self.mesh.devices.flat)
        n = len(devices)
        if schema is None:
            schema = infer_parquet_schema(path)
        n_groups = parquet_row_groups(path)
        # the readers hand over `_READ_BATCHES` table batches at a time
        # (a row group, if it is no longer): what a reader pays a batch
        # (the chunk's dictionary merged, arrays made) it pays an eighth
        # as often, and `whole_batches` cuts the table's batches anyway
        sources = [
            ParquetDataSource(path, schema, _READ_BATCHES * self.batch_size,
                              row_groups=list(range(s, n_groups, n)))
            for s in range(n)
        ]
        # one dictionary set for all shards, grown under the
        # dictionaries' own lock while the shards are read side by side
        _share_dictionaries(sources)

        def read(src):
            # the reader parses on its IO thread (`io/io_thread.py`) and
            # hands on the file's own cut (joining a row group's million
            # rows to the next one's head first would copy every row
            # twice), a prefetch thread pulls ahead, this one re-cuts:
            # a shard's rounds then all have one shape, each row copied
            # once, into the batch that keeps it
            return list(whole_batches(
                staged_prefetch(src.batches(whole=False), None,
                                wait_timer="pipeline.scan_wait"),
                self.batch_size,
            ))

        with ThreadPoolExecutor(n, thread_name_prefix="df-tpu-shard-read") as pool:
            shards = list(pool.map(read, sources))
        cap = max((b.capacity for bs in shards for b in bs), default=0)
        parts = []
        for s, (dev, batches) in enumerate(zip(devices, shards)):
            batches = [b if b.capacity == cap else _padded(b, cap)
                       for b in batches]
            nbytes = sum(
                int(a.nbytes) for b in batches
                for a in (*b.data, *b.validity) if a is not None
            )
            if not LEDGER.fits(nbytes, dev):
                raise ExecutionError(
                    f"table {name!r}: shard {s} holds {nbytes} bytes of "
                    f"columns and device {dev} has "
                    f"{LEDGER.headroom(dev)} bytes free"
                )
            METRICS.add("mesh.resident.bytes", nbytes)
            parts.append(MemoryDataSource(sources[0].schema, batches))
        for p in parts[1:]:
            # one table, one set of encoders: the ids a batch keeps on
            # its device mean the same group on every shard
            p._shared = parts[0]._shared
        self.register_datasource(name, PartitionedDataSource(parts))

    def _execute_plan(self, plan: LogicalPlan) -> Relation:
        # wrap only the ROOT (execute recurses through self.execute for
        # child plans; nested wrappers would hand every subtree a fresh
        # budget instead of one per-query deadline).  The result-cache
        # seam lives one level up (ExecutionContext.execute): a cache
        # hit replays batches without entering this method at all.
        if self.query_deadline_s is None or self._executing:
            return self._execute_unbounded(plan)
        self._executing = True
        try:
            rel = self._execute_unbounded(plan)
        finally:
            self._executing = False
        return DeadlineBoundRelation(rel, self.query_deadline_s)

    def _execute_unbounded(self, plan: LogicalPlan) -> Relation:
        agg, pred, scan = _match_partitioned_aggregate(plan, self.datasources)
        if agg is not None:
            ds = self.datasources[scan.table_name]
            if scan.projection is not None:
                ds = ds.with_projection(scan.projection)
            try:
                # every fragment round-trips the JSON wire format and the
                # partition source is rebuilt from its meta — the exact
                # path a remote worker takes on receiving a fragment
                self.last_fragments = self._ship_fragments(plan, ds)
                parts = [f.build_datasource(self.batch_size) for f in self.last_fragments]
                _share_dictionaries(parts)
            except PlanError:
                # non-serializable sources (e.g. in-memory) execute the
                # original partition objects directly
                self.last_fragments = []
                parts = ds.partitions
            children = [
                DataSourceRelation(p, table_name=scan.table_name)
                for p in parts
            ]
            return PartitionedAggregateRelation(
                children,
                agg.group_expr,
                agg.aggr_expr,
                agg.schema,
                self.mesh,
                predicate=pred,
                functions=self._jax_functions(),
            )
        pipe = _match_partitioned_pipeline(plan, self.datasources, self.functions)
        if pipe is not None:
            pred, projections, scan, out_schema = pipe
            ds = self.datasources[scan.table_name]
            if scan.projection is not None:
                ds = ds.with_projection(scan.projection)
            try:
                self.last_fragments = self._ship_fragments(plan, ds)
                parts = [f.build_datasource(self.batch_size) for f in self.last_fragments]
                _share_dictionaries(parts)
            except PlanError:
                self.last_fragments = []
                parts = ds.partitions
            children = [
                DataSourceRelation(p, table_name=scan.table_name)
                for p in parts
            ]
            # host-fn plans never get here: _match_partitioned_pipeline
            # rejects them with the same contains_host_fn check the
            # pipeline core uses, so construction cannot PlanError
            return PartitionedPipelineRelation(
                children, pred, projections, out_schema, self.mesh,
                functions=self._jax_functions(),
                function_metas=self.functions,
            )
        return super()._execute_plan(plan)

    def _ship_fragments(self, plan: LogicalPlan, ds: PartitionedDataSource) -> list[PlanFragment]:
        n = len(ds.partitions)
        frags = []
        for i, part in enumerate(ds.partitions):
            frag = PlanFragment(i, n, plan.to_json(), part.to_meta())
            # serialize -> deserialize: the wire format round trip a
            # coordinator->worker hop would perform
            frags.append(PlanFragment.from_json_str(frag.to_json_str()))
        return frags


def _match_partitioned_pipeline(plan: LogicalPlan, datasources: dict, metas):
    """Match [Projection](Selection)(TableScan) over a partitioned
    table; returns (predicate, projections, scan, out_schema) or None.
    Plans whose projections need host evaluation (string/struct
    producers) return None — they take the serial union scan."""
    from datafusion_tpu.exec.hostfn import contains_host_fn
    from datafusion_tpu.plan.logical import Projection

    projections = None
    out_schema = plan.schema
    node = plan
    if isinstance(node, Projection):
        projections = node.expr
        node = node.input
    pred = None
    if isinstance(node, Selection):
        pred = node.expr
        node = node.input
    if not isinstance(node, TableScan):
        return None
    if projections is None and pred is None:
        return None  # bare scan: nothing to parallelize
    ds = datasources.get(node.table_name)
    if not isinstance(ds, PartitionedDataSource):
        return None
    checked = ([] if pred is None else [pred]) + list(projections or [])
    if any(contains_host_fn(e, metas or {}) for e in checked):
        return None
    return pred, projections, node, out_schema


def _match_partitioned_aggregate(plan: LogicalPlan, datasources: dict):
    """Match Aggregate[(Selection)](TableScan over a partitioned table);
    returns (aggregate, predicate, scan) or (None, None, None)."""
    if not isinstance(plan, Aggregate):
        return None, None, None
    inner = plan.input
    pred = None
    if isinstance(inner, Selection):
        pred = inner.expr
        inner = inner.input
    if not isinstance(inner, TableScan):
        return None, None, None
    ds = datasources.get(inner.table_name)
    if not isinstance(ds, PartitionedDataSource):
        return None, None, None
    return plan, pred, inner
