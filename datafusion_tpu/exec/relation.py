"""Relation protocol and the fused pipeline operator.

The reference's operator layer is a volcano-style pull iterator
(`src/execution/relation.rs:27-32`) with separate Filter and Projection
operators that interpret closures per batch.  Here a whole
scan->filter->project fragment executes as **one jitted XLA kernel**
(`PipelineRelation`): the predicate produces a selection mask that is
carried in the batch instead of gathering rows (`filter.rs:80-111`'s
per-column row loop), and projection expressions fuse with it.
"""

from __future__ import annotations

from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from datafusion_tpu.datatypes import DataType, Schema
from datafusion_tpu.exec.batch import RecordBatch
from datafusion_tpu.exec.expression import Env, ExprCompiler, compute_aux_values
from datafusion_tpu.errors import NotSupportedError
from datafusion_tpu.plan.expr import Column, Expr
from datafusion_tpu.utils.metrics import METRICS
from datafusion_tpu.utils.retry import device_call


def device_scope(device):
    """Context manager placing jax computations on `device` (no-op when
    None: JAX's default device — the TPU when one is attached)."""
    from contextlib import nullcontext

    return jax.default_device(device) if device is not None else nullcontext()


def mask_and(a, b):
    return a & b


# tiny fused AND for combining a host predicate mask with a device-
# resident upstream mask (one jit for every shape pair; exec/sort.py
# shares it)
_MASK_AND_JIT = jax.jit(mask_and)


def _is_accelerator(device) -> bool:
    """True when batches execute on a non-CPU device (`device` is a jax
    Device, or None = the JAX default backend)."""
    if device is not None:
        return getattr(device, "platform", "cpu") != "cpu"
    return jax.default_backend() != "cpu"


class Relation:
    """Pull-based iterator of RecordBatches (reference `Relation` trait).

    Every relation doubles as a physical plan node for observability:
    it lazily owns an `OperatorStats` (`.stats`), names itself
    (`op_name`/`op_label`), and exposes its operator children
    (`op_children`) so EXPLAIN ANALYZE can walk the executed tree.
    """

    _op_stats = None

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    def batches(self) -> Iterator[RecordBatch]:
        raise NotImplementedError

    @property
    def stats(self):
        """Per-operator runtime stats (populated only on instrumented
        runs — EXPLAIN ANALYZE / DATAFUSION_TPU_TRACE=1)."""
        st = self._op_stats
        if st is None:
            from datafusion_tpu.obs.stats import OperatorStats

            st = self._op_stats = OperatorStats()
        return st

    def op_name(self) -> str:
        name = type(self).__name__
        for junk in ("Relation", "Exec", "_"):
            name = name.replace(junk, "")
        return name or type(self).__name__

    def op_label(self) -> str:
        """One-line description for the EXPLAIN ANALYZE tree."""
        return self.op_name()

    def op_children(self) -> list["Relation"]:
        kids = getattr(self, "children", None)
        if isinstance(kids, (list, tuple)):
            return [k for k in kids if isinstance(k, Relation)]
        for attr in ("child", "rel", "inner"):
            c = getattr(self, attr, None)
            if isinstance(c, Relation):
                return [c]
        return []


class DataSourceRelation(Relation):
    """Adapts a DataSource into a Relation (reference `relation.rs:34-54`).

    When the scan knows its table name (the plan->operator boundary
    passes it), each complete scan observes into the per-table
    histograms `scan.<table>.latency` (seconds spent *producing*
    batches — parse, decode, dictionary encode) and `scan.<table>.bytes`
    (host bytes scanned), which merge fleet-wide like `query.latency`
    (obs/aggregate.py).  Cost: one perf_counter pair per batch and two
    histogram bumps per scan.
    """

    def __init__(self, datasource, table_name: Optional[str] = None):
        self.datasource = datasource
        self.table_name = table_name

    @property
    def schema(self) -> Schema:
        return self.datasource.schema

    def op_label(self) -> str:
        src = type(self.datasource).__name__.replace("DataSource", "")
        path = getattr(self.datasource, "filename", None) or getattr(
            self.datasource, "path", None
        )
        return f"Scan[{src}{f': {path}' if path else ''}]"

    def batches(self) -> Iterator[RecordBatch]:
        if self.table_name is None:
            return self.datasource.batches()
        return self._observed_batches()

    def _observed_batches(self) -> Iterator[RecordBatch]:
        import time as _time

        from datafusion_tpu.obs.aggregate import observe_scan

        produce_s = 0.0
        nbytes = 0
        rows = 0
        it = self.datasource.batches()
        try:
            while True:
                t0 = _time.perf_counter()
                try:
                    batch = next(it)
                except StopIteration:
                    return
                finally:
                    produce_s += _time.perf_counter() - t0
                rows += batch.num_rows
                for arr in batch.data:
                    if isinstance(arr, np.ndarray):
                        nbytes += arr.nbytes
                for v in batch.validity:
                    if isinstance(v, np.ndarray):
                        nbytes += v.nbytes
                yield batch
        finally:
            # observed once per scan, abandoned scans (bare LIMIT)
            # included — partial work is still work the table cost us
            observe_scan(self.table_name, produce_s, nbytes)
            # ... and the cost store learns the table's cardinality and
            # bytes/row (the planner's row statistics — cost/advisor).
            # `rows_max` semantics there keep an abandoned partial scan
            # from shrinking the learned row count.  Lock-free observe.
            ckey = getattr(self, "_cost_key", None)
            if ckey is not None and rows:
                from datafusion_tpu import cost as _cost

                _cost.store().observe(
                    ckey, "scan",
                    rows=rows, nbytes=nbytes, produce_s=produce_s,
                )


def _host_routed(e, metas, in_schema, host_scalar: bool) -> bool:
    """Should projection expr `e` evaluate on the host instead of inside
    the device kernel?  Always for host-only functions; additionally,
    under `host_scalar` (accelerator devices), for any numpy-evaluable
    scalar expression — it computes on the host instead of shipping
    the computed column back over the device link."""
    from datafusion_tpu.exec.hostfn import contains_host_fn, host_evaluable

    if contains_host_fn(e, metas):
        return True
    if not host_scalar or isinstance(e, Column):
        return False
    return host_evaluable(e, metas, in_schema)


class _PipelineCore:
    """The compiled, shareable part of a pipeline: expression closures
    and the jitted kernel.  Cached process-wide by plan fingerprint
    (SURVEY §7 recompilation control) so a fresh operator tree for a
    semantically identical query reuses the already-built jit — and
    with it every compiled executable in jit's cache."""

    def __init__(self, in_schema, predicate, projections, functions, metas,
                 param_slots=None, host_scalar=False):
        from datafusion_tpu.exec.hostfn import contains_host_fn

        compiler = ExprCompiler(in_schema, functions, param_slots)
        if predicate is not None and contains_host_fn(predicate, metas):
            raise NotSupportedError(
                "host-only functions are not supported in WHERE predicates"
            )
        self.pred_fn = compiler.compile(predicate) if predicate is not None else None
        # projections containing host-only functions (string/struct
        # producers) are evaluated post-kernel against the input batch;
        # bare column references bypass the kernel entirely — the host
        # array passes through untouched.  That keeps Float64 columns
        # EXACT on TPU (f64 is emulated there: even an identity kernel
        # round-trip perturbs values by ~1e-14) and removes their D2H
        # transfer — only computed columns and the mask cross the link.
        # Under `host_scalar` (accelerator devices) scalar arithmetic
        # projections are host-routed too (_host_routed above): the
        # device kernel shrinks to the predicate mask, and no computed
        # column ever crosses D2H.
        self.host_scalar = host_scalar
        self.host_proj: dict[int, Expr] = {}
        self.identity_proj: dict[int, int] = {}
        self.proj_fns = None
        if projections is not None:
            self.proj_fns = []
            for j, e in enumerate(projections):
                if _host_routed(e, metas, in_schema, host_scalar):
                    self.host_proj[j] = e
                    self.proj_fns.append(None)
                elif isinstance(e, Column):
                    self.identity_proj[j] = e.index
                    self.proj_fns.append(None)
                else:
                    self.proj_fns.append(compiler.compile(e))
        self.aux_specs = compiler.aux_specs
        # map projection outputs to source dictionaries (Utf8 passthrough)
        self.out_dict_sources: list[Optional[int]] = []
        if projections is not None:
            for e in projections:
                if (
                    isinstance(e, Column)
                    and in_schema.field(e.index).data_type == DataType.UTF8
                ):
                    self.out_dict_sources.append(e.index)
                else:
                    self.out_dict_sources.append(None)

        # no predicate and nothing to compute on device => the batch
        # never touches the device at all (pure column selection)
        self.needs_kernel = self.pred_fn is not None or (
            self.proj_fns is not None
            and any(f is not None for f in self.proj_fns)
        )
        # ship only the columns the kernel actually reads (jit transfers
        # every argument, used or not — H2D bytes are the scarce
        # resource on remote links); Env's col_map translates schema
        # indices to subset positions
        used: set[int] = set()
        if predicate is not None:
            predicate.collect_columns(used)
        if projections is not None:
            for j, e in enumerate(projections):
                if j in self.identity_proj or j in self.host_proj:
                    continue
                e.collect_columns(used)
        if self.needs_kernel and not used and len(in_schema):
            used.add(0)  # constant predicate: one column carries capacity
        self.used_cols = sorted(used)
        self.col_map = {c: i for i, c in enumerate(self.used_cols)}
        self.sub_schema = in_schema.select(self.used_cols)
        # per-column codec memory for put_compressed; the core persists
        # across cold re-runs of the same query shape, so batch 2+ of
        # every scan skips the encode probe ladder
        self.wire_hints: dict = {}
        self.jit = jax.jit(self._kernel)
        # fused-pass batch-group map (exec/fused.py): one launch runs
        # the filter+project kernel over a whole group of batches
        self.group_jit = jax.jit(self._fused_group)
        # cross-query megabatch map (serve.py / run_pipeline_megabatch):
        # one launch runs N queries' filter+project — same core, each
        # query's literals in its own params slot-tuple — over a whole
        # stacked group; the shared input columns upload once
        self.multi_group_jit = jax.jit(self._multi_fused_group)

    def _fused_group(self, entries, counts, aux, params):
        """ONE launch for a group of prepared batches: `lax.map` of the
        fused kernel over the stacked group and its row counts; outputs
        return per batch (the unstacking slices fuse into the same
        program, so consumers see ordinary per-batch arrays without
        extra dispatches)."""
        from datafusion_tpu.exec.fused import stack_entries

        stacked = stack_entries(entries)

        def body(x):
            (cols, valids, mask), num_rows = x
            out_cols, out_valids, m = self._kernel(
                cols, valids, aux, num_rows, mask, params
            )
            return tuple(out_cols), tuple(out_valids), m

        ys = jax.lax.map(body, (stacked, counts))
        return tuple(
            jax.tree.map(lambda t, i=i: t[i], ys)
            for i in range(len(entries))
        )

    def _multi_fused_group(self, entries, counts, aux, params_list):
        """N queries over ONE stacked batch group in one launch (the
        serve-plane pipeline megabatch): the map body runs the kernel
        once per query against the same stacked inputs — per-query
        literals arrive through ``params_list``, so `WHERE x > ?`
        variants share every uploaded column and the launch itself.
        Outputs return as [query][batch] tuples of (cols, valids,
        mask), matching `_fused_group`'s per-batch shape per query."""
        from datafusion_tpu.exec.fused import stack_entries

        stacked = stack_entries(entries)

        def body(x):
            (cols, valids, mask), num_rows = x
            outs = []
            for params in params_list:
                out_cols, out_valids, m = self._kernel(
                    cols, valids, aux, num_rows, mask, params
                )
                outs.append((tuple(out_cols), tuple(out_valids), m))
            return tuple(outs)

        ys = jax.lax.map(body, (stacked, counts))
        return tuple(
            tuple(
                jax.tree.map(lambda t, i=i: t[i], ys[q])
                for i in range(len(entries))
            )
            for q in range(len(params_list))
        )

    @staticmethod
    def param_exprs(predicate, projections, metas, in_schema=None,
                    host_scalar=False):
        """The exprs that compile into the device kernel, in slot-
        assignment order.  Host-routed projections are excluded: their
        exprs (with each query's own literal values) live on the
        relation (`PipelineRelation._host_proj`), and the cache key
        carries their literal-parameterized fingerprints."""
        elig = [] if predicate is None else [predicate]
        if projections is not None:
            elig.extend(
                e for e in projections
                if not _host_routed(e, metas or {}, in_schema, host_scalar)
            )
        return elig

    @staticmethod
    def build(in_schema, predicate, projections, functions, metas,
              host_scalar=False):
        from datafusion_tpu.exec.kernels import (
            cached_kernel,
            functions_fingerprint,
            parameterize_exprs,
            schema_fingerprint,
        )

        elig = _PipelineCore.param_exprs(
            predicate, projections, metas, in_schema, host_scalar
        )
        fps, slot_by_id, _ = parameterize_exprs(elig)
        fp_of = dict(zip((id(e) for e in elig), fps))
        proj_key = None
        if projections is not None:
            # host-routed exprs key by literal-parameterized fingerprint
            # (their literal VALUES live on each relation, so numeric-
            # literal variants share one compiled core exactly like
            # device-routed exprs do)
            proj_key = tuple(
                ("host", parameterize_exprs([e])[0][0])
                if _host_routed(e, metas or {}, in_schema, host_scalar)
                else fp_of[id(e)]
                for e in projections
            )
        key = (
            "pipeline",
            host_scalar,
            schema_fingerprint(in_schema),
            None if predicate is None else fp_of[id(predicate)],
            proj_key,
            functions_fingerprint(functions),
            tuple(sorted(n for n, m in (metas or {}).items() if m.host_fn)),
        )
        return cached_kernel(
            key,
            lambda: _PipelineCore(
                in_schema, predicate, projections, functions, metas,
                slot_by_id, host_scalar,
            ),
        )

    def _kernel(self, cols, valids, aux, num_rows, base_mask, params=()):
        env = Env(cols, valids, aux, self.col_map, params)
        if cols:
            capacity = cols[0].shape[0]
        elif base_mask is not None:
            capacity = base_mask.shape[0]  # zero-column EmptyRelation batch
        else:
            capacity = 1
        mask = base_mask
        if mask is None:
            mask = jnp.arange(capacity, dtype=jnp.int32) < num_rows
        else:
            mask = mask & (jnp.arange(capacity, dtype=jnp.int32) < num_rows)
        if self.pred_fn is not None:
            pv, pvalid = self.pred_fn(env)
            pv = jnp.broadcast_to(pv, (capacity,))
            if pvalid is not None:
                # SQL: NULL predicate drops the row
                pv = pv & jnp.broadcast_to(pvalid, (capacity,))
            mask = mask & pv
        if self.proj_fns is None:
            # filter-only: columns pass through on the host; the kernel
            # produces just the selection mask
            return [], [], mask
        out_cols, out_valids = [], []
        for f in self.proj_fns:
            if f is None:  # host-evaluated or identity: filled in later
                continue
            v, valid = f(env)
            out_cols.append(jnp.broadcast_to(v, (capacity,)))
            out_valids.append(
                None if valid is None else jnp.broadcast_to(valid, (capacity,))
            )
        return out_cols, out_valids, mask


class PipelineRelation(Relation):
    """Fused [filter +] [projection] over a child relation.

    One `jax.jit`-compiled function evaluates the predicate and all
    projection expressions in a single fused XLA computation per batch.
    The compiled core is shared process-wide by plan fingerprint
    (`_PipelineCore.build`); jit's own cache handles per-(capacity,
    dtypes) specialization and capacity bucketing (exec/batch.py)
    bounds how many variants ever compile.
    """

    def __init__(
        self,
        child: Relation,
        predicate: Optional[Expr],
        projections: Optional[list[Expr]],
        out_schema: Optional[Schema] = None,
        functions: Optional[dict[str, Callable]] = None,
        device=None,
        function_metas=None,
    ):
        self.child = child
        self.predicate = predicate
        self.projections = projections
        self._schema = out_schema if out_schema is not None else child.schema
        self.device = device
        self.functions = functions
        self._metas = function_metas or {}
        host_scalar = _is_accelerator(device)
        # On accelerators a numpy-evaluable predicate runs on the host
        # (mirroring AggregateRelation's host predicate): its input
        # columns never cross H2D and — with projections host-routed
        # under host_scalar — the whole batch often never touches the
        # device.  Predicates containing host-only UDFs keep going to
        # the core so it raises its NotSupportedError contract.
        # Unlike the aggregate's, this rule does not ask whether the
        # source keeps its batches: the output returns to the host row
        # for row, so a device predicate would ship columns only to
        # pull a mask back.
        from datafusion_tpu.exec.aggregate import _FORCE_CORE_PRED
        from datafusion_tpu.exec.hostfn import contains_host_fn, host_evaluable

        host_pred = (
            predicate is not None
            and host_scalar
            and not _FORCE_CORE_PRED.get()
            and not contains_host_fn(predicate, self._metas)
            and host_evaluable(predicate, self._metas, child.schema)
        )
        self._host_pred_expr = predicate if host_pred else None
        core_pred = None if host_pred else predicate
        self.core = _PipelineCore.build(
            child.schema, core_pred, projections, functions, self._metas,
            host_scalar,
        )
        # THIS query's host-routed exprs (with its literal values) —
        # the shared core only records which positions are host-routed
        self._host_proj: dict[int, Expr] = {
            j: e
            for j, e in enumerate(projections or [])
            if _host_routed(e, self._metas, child.schema, host_scalar)
        }
        # THIS query's literal values for the shared core's parameter
        # slots (identical fingerprints guarantee identical slot order)
        from datafusion_tpu.exec.kernels import parameterize_exprs

        self._params = parameterize_exprs(
            _PipelineCore.param_exprs(
                core_pred, projections, self._metas, child.schema, host_scalar
            )
        )[2]
        self._host_dicts: dict[int, "StringDictionary"] = {}
        self._aux_cache: dict = {}

    @property
    def schema(self) -> Schema:
        return self._schema

    def op_label(self) -> str:
        parts = []
        if self.predicate is not None or self._host_pred_expr is not None:
            parts.append("filter")
        if self.projections is not None:
            parts.append("project")
        return f"Pipeline[{'+'.join(parts) or 'pass'}]"

    def batches(self) -> Iterator[RecordBatch]:
        from datafusion_tpu.exec.batch import device_inputs
        from datafusion_tpu.exec.prefetch import pipeline_enabled, staged_pipeline
        from datafusion_tpu.obs.stats import iter_stats

        inj = self.__dict__.pop("_injected_batches", None)
        if inj is not None:
            # serve-plane megabatch (run_pipeline_megabatch): the
            # cross-query pass already ran this query's kernel over
            # the SHARED scan — its assembled output batches replay
            # here with no further device work
            yield from inj
            return
        core = self.core
        batches = iter_stats(self.child)
        if core.needs_kernel and pipeline_enabled(self.device):
            # host prep for batch N+1 (aux tables, wire encode, H2D
            # dispatch) runs on the producer thread while batch N's
            # kernel dispatches below; aux is pinned on the batch so the
            # consumer can't see a later (grown) dictionary version
            def _stage(b):
                # owning core pinned in the entry so no other relation
                # on a shared batch can consume this aux (see the
                # group_ids encoder pin in aggregate.py)
                b.cache["staged_aux"] = (
                    core,
                    tuple(compute_aux_values(core.aux_specs, b, self._aux_cache)),
                )
                device_inputs(
                    self._subset_view(b), self.device, core.wire_hints
                )
                if self._host_pred_expr is not None:
                    self._device_mask(b)

            batches = staged_pipeline(batches, _stage)

        if core.needs_kernel:
            # one launch per batch group
            yield from self._batches_fused(batches)
            return

        # pure column selection: yield a STABLE output batch per child
        # batch (cached, core-pinned like group_ids) so a re-scan of an
        # in-memory source hands downstream operators the same
        # RecordBatch objects — their device copies (device_inputs
        # cache) survive across runs instead of re-shipping every
        # column per query run.  Pinned by RELATION when host-routed
        # exprs exist (their literal values — and the host predicate's —
        # are per-query; the core is shared across literals), by core
        # otherwise
        pin = (
            self if (self._host_proj or self._host_pred_expr is not None)
            else core
        )
        for batch in batches:
            hit = batch.cache.get("pipeline_out")
            if hit is not None and hit[0] is pin:
                yield hit[1]
                continue
            out = self._emit_output(
                batch, [], [], self._effective_mask(batch)
            )
            batch.cache["pipeline_out"] = (pin, out)
            yield out

    def _batches_fused(self, batches) -> Iterator[RecordBatch]:
        """Kernel-path batches: prepared per-batch inputs buffer into
        shape-homogeneous groups of up to `pipeline_group_max()` and
        each group dispatches as ONE device launch (cold scans stop
        paying a dispatch round trip per batch — the csv_scan_filter
        satellite)."""
        from datafusion_tpu.exec.batch import device_inputs
        from datafusion_tpu.exec.fused import (
            entry_signature,
            pad_group,
            pipeline_group_max,
        )
        from datafusion_tpu.obs.stats import op_timer

        core = self.core
        group_max = pipeline_group_max()
        buf: list = []  # (batch, entry, aux)
        cur_sig = None

        def prepare(batch):
            staged = batch.cache.get("staged_aux")
            if staged is not None and staged[0] is core:
                aux = staged[1]
            else:
                aux = tuple(
                    compute_aux_values(core.aux_specs, batch, self._aux_cache)
                )
            # timed + operator-ambient like the per-batch loop, so H2D
            # bytes/time keep attributing to this operator in EXPLAIN
            # ANALYZE (record_h2d reads the ambient op)
            with METRICS.timer("execute.pipeline"), op_timer(self), \
                    device_scope(self.device):
                data, validity, mask_in = device_inputs(
                    self._subset_view(batch), self.device, core.wire_hints
                )
                if self._host_pred_expr is not None:
                    mask_in = self._device_mask(batch)
            return aux, (data, validity, mask_in)

        def flush() -> list:
            if not buf:
                return []
            with METRICS.timer("execute.pipeline"), op_timer(self), \
                    device_scope(self.device):
                if len(buf) == 1:
                    b, e, aux = buf[0]
                    outs = [device_call(
                        core.jit, e[0], e[1], aux, np.int32(b.num_rows),
                        e[2], self._params, _tag="pipeline",
                    )]
                else:
                    group, counts = pad_group(
                        [e for _, e, _ in buf], [b.num_rows for b, _, _ in buf])
                    METRICS.add("fused.groups")
                    METRICS.add("fused.group_batches", len(buf))
                    outs = device_call(
                        core.group_jit, group, counts, buf[0][2],
                        self._params, _tag="pipeline.group",
                    )
            emitted = [
                self._emit_output(b, list(cols), list(valids), mask)
                for (b, _, _), (cols, valids, mask) in zip(buf, outs)
            ]
            buf.clear()
            return emitted

        for batch in batches:
            aux, entry = prepare(batch)
            sig = (entry_signature(entry), tuple(map(id, aux)))
            if buf and (sig != cur_sig or len(buf) >= group_max):
                yield from flush()
            cur_sig = sig
            buf.append((batch, entry, aux))
        yield from flush()

    def _emit_output(self, batch, cols, valids, mask) -> RecordBatch:
        """Assemble one output batch from the kernel's computed columns
        (none for a pure column selection), interleaving identity
        passthroughs and host-routed projections."""
        core = self.core
        if core.proj_fns is None:
            # filter-only: the input columns, untouched
            out_cols, out_valids, dicts = batch.data, batch.validity, batch.dicts
        else:
            dicts = [
                batch.dicts[src] if src is not None else None
                for src in core.out_dict_sources
            ]
            out_cols, out_valids, dicts = self._assemble_outputs(
                batch, cols, valids, list(dicts)
            )
        return RecordBatch(
            self._schema,
            list(out_cols),
            list(out_valids),
            dicts,
            num_rows=batch.num_rows,
            mask=mask,
        )

    def _host_pred_mask(self, batch) -> np.ndarray:
        """This query's host-routed predicate over one batch, as a
        numpy bool mask (cached on the batch, pinned by relation — the
        predicate carries per-query literals).  Predicate inputs are
        host arrays in every shape the planner emits (scans pass host
        columns through; device-computed columns only come from
        non-host-evaluable projections, whose consumers can't route
        here) but one: HAVING over a keyed aggregate's groups, which
        stay on the device.  Such a batch's columns come over first in
        one counted pull (`device_pull`): the few groups the fold kept."""
        hit = batch.cache.get("pipe_pred_mask")
        if hit is not None and hit[0] is self:
            return hit[1]
        from datafusion_tpu.exec.batch import device_pull
        from datafusion_tpu.exec.hostfn import host_pred_mask

        src = batch
        if any(hasattr(a, "copy_to_host_async")
               for a in (*batch.data, *batch.validity)):
            data, valid = device_pull((list(batch.data), list(batch.validity)))
            src = RecordBatch(batch.schema, list(data), list(valid),
                              list(batch.dicts), num_rows=batch.num_rows)
        pm = host_pred_mask(self._host_pred_expr, src, self._metas)
        batch.cache["pipe_pred_mask"] = (self, pm)
        return pm

    def _effective_mask(self, batch):
        """The batch's selection mask with this query's host-routed
        predicate folded in.  A device-resident upstream mask combines
        ON DEVICE (one tiny fused AND) rather than being pulled to the
        host — D2H round trips are the scarce resource."""
        if self._host_pred_expr is None:
            return batch.mask
        pm = self._host_pred_mask(batch)
        if batch.mask is None:
            return pm
        if hasattr(batch.mask, "copy_to_host_async"):  # device mask
            from datafusion_tpu.obs.device import LEDGER

            with device_scope(self.device):
                return _MASK_AND_JIT(
                    LEDGER.put(pm, None, owner="mask"), batch.mask
                )
        return np.asarray(batch.mask) & pm

    def _device_mask(self, batch):
        """Device copy of the effective mask for the kernel path
        (cached on the batch, pinned by relation — per-query literals).
        Travels bit-packed through put_compressed; the kernel's input
        columns keep riding the literal-independent subset-view cache."""
        hit = batch.cache.get("pipe_pred_dev_mask")
        if hit is not None and hit[0] is self:
            return hit[1]
        m = self._effective_mask(batch)
        if m is not None and not hasattr(m, "copy_to_host_async"):
            from datafusion_tpu.exec.batch import put_compressed

            with device_scope(self.device):
                m = put_compressed([m], self.device)[0]
        batch.cache["pipe_pred_dev_mask"] = (self, m)
        return m

    def _subset_view(self, batch) -> RecordBatch:
        """A view batch holding only the kernel's input columns (shared
        helper; caching on the parent keeps device copies alive across
        re-scans of in-memory sources)."""
        from datafusion_tpu.exec.batch import subset_view

        return subset_view(batch, self.core.used_cols)

    def _assemble_outputs(self, batch, dev_cols, dev_valids, dicts):
        """Interleave identity passthroughs (the input arrays, exact)
        and post-kernel host-evaluated projections (string / struct
        producers) with the device kernel's computed outputs."""
        from datafusion_tpu.exec.batch import StringDictionary
        from datafusion_tpu.exec.hostfn import eval_host_expr

        cols, valids = [], []
        dev_i = 0
        for j in range(len(self.projections)):
            src = self.core.identity_proj.get(j)
            if src is not None:
                cols.append(batch.data[src])
                valids.append(batch.validity[src])
                continue
            host_expr = self._host_proj.get(j)
            if host_expr is None:
                cols.append(dev_cols[dev_i])
                valids.append(dev_valids[dev_i])
                dev_i += 1
                continue
            v, valid = eval_host_expr(host_expr, batch, self._metas)
            if self._schema.field(j).data_type == DataType.UTF8:
                d = self._host_dicts.get(j)
                if d is None:
                    d = self._host_dicts[j] = StringDictionary()
                v = d.encode(list(np.asarray(v, dtype=object)))
                dicts[j] = d
            elif isinstance(v, tuple):
                # struct results materialize via their Display form
                # "f1, f2" (the pre-rewrite Point UDT's printing — see
                # golden test_sql_udf_udt.csv)
                # broadcast first: literal args arrive as 0-d scalars
                parts = np.broadcast_arrays(
                    *[np.asarray(x) for x in v],
                    np.empty(batch.capacity),
                )[:-1]
                v = np.asarray(
                    [", ".join(str(x) for x in tup) for tup in zip(*parts)],
                    dtype=object,
                )
            v = np.broadcast_to(np.asarray(v), (batch.capacity,))
            cols.append(v)
            valids.append(
                None if valid is None else np.broadcast_to(valid, (batch.capacity,))
            )
        return cols, valids, dicts


def run_pipeline_megabatch(rels: list["PipelineRelation"]) -> float:
    """ONE scan, N filter/project queries: the serve plane's
    cross-query fused pass for pipeline shapes (the PipelineRelation
    twin of serve's Aggregate megabatch).  Preconditions
    (serve._mega_key): every relation shares ``rels[0].core``
    (kernel-cache identity — literals parameterized into per-query
    ``_params`` slots) over one table scan with no per-query host
    mask, so the input columns upload ONCE and every batch group runs
    ALL queries' kernels in one launch
    (`_PipelineCore.multi_group_jit`).  Each relation receives its
    assembled output batches as ``_injected_batches``; its own
    `batches()` then replays them with no further device work — the
    demux is per-query finalize-time pulls, so this returns 0.0 for
    the caller's demux share.  The query axis pads to its bucket rung
    (duplicate leader params) so concurrent group sizes share
    compiled programs."""
    from datafusion_tpu.exec.fused import (
        bucket_group,
        entry_signature,
        pad_group,
        pipeline_group_max,
    )
    from datafusion_tpu.exec.batch import device_inputs
    from datafusion_tpu.obs.stats import iter_stats, op_timer

    leader = rels[0]
    core = leader.core
    n_live = len(rels)
    n_q = bucket_group(n_live)
    params_list = tuple(r._params for r in rels)
    params_list += (params_list[0],) * (n_q - n_live)
    group_max = pipeline_group_max()
    outs_per_rel: list[list] = [[] for _ in rels]
    buf: list = []  # (batch, entry, aux)
    cur_sig = None

    def flush():
        if not buf:
            return
        with METRICS.timer("execute.pipeline"), op_timer(leader), \
                device_scope(leader.device):
            group, counts = pad_group(
                [e for _, e, _ in buf], [b.num_rows for b, _, _ in buf])
            METRICS.add("fused.groups")
            METRICS.add("fused.group_batches", len(buf))
            METRICS.add("serve.megabatch_launches")
            METRICS.add("serve.megabatch_queries", n_live)
            outs = device_call(
                core.multi_group_jit, group, counts, buf[0][2],
                params_list, _tag="pipeline.mega",
            )
        for q, r in enumerate(rels):
            for (b, _, _), (cols, valids, mask) in zip(buf, outs[q]):
                outs_per_rel[q].append(
                    r._emit_output(b, list(cols), list(valids), mask)
                )
        buf.clear()

    for batch in iter_stats(leader.child):
        staged = batch.cache.get("staged_aux")
        if staged is not None and staged[0] is core:
            aux = staged[1]
        else:
            aux = tuple(
                compute_aux_values(core.aux_specs, batch, leader._aux_cache)
            )
        with METRICS.timer("execute.pipeline"), op_timer(leader), \
                device_scope(leader.device):
            data, validity, mask_in = device_inputs(
                leader._subset_view(batch), leader.device, core.wire_hints
            )
        entry = (data, validity, mask_in)
        sig = (entry_signature(entry), tuple(map(id, aux)))
        if buf and (sig != cur_sig or len(buf) >= group_max):
            flush()
        cur_sig = sig
        buf.append((batch, entry, aux))
    flush()
    for r, outs in zip(rels, outs_per_rel):
        r._injected_batches = outs
    return 0.0
