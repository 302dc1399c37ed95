"""Fused single-launch passes (ROADMAP item 4).

Two independent fusion layers:

- **Plan-chain collapse** (used by `exec/context.py`): an entire
  filter -> project -> aggregate chain — and Sort/Limit over a
  filter+column-projection — lowers to ONE physical operator whose
  kernel evaluates everything, instead of a stack of per-operator
  relations each paying its own per-batch dispatch.  Projection
  expressions inline into the consumers (`substitute_columns`) and
  stacked Selections AND together (`flatten_chain`).

- **Batch-group folding** (used by aggregate/sort/pipeline operators):
  the per-batch device inputs of a whole scan collect host-side and
  dispatch as ONE jitted computation per *batch group* — a run of
  batches with identical (shape class, dtype tuple, aux identity).
  A group is two things: its entries (each batch's device arrays,
  stacked inside the program) and ONE int32 vector of their row
  counts, a numpy array the launch puts once.  State-carrying
  operators fold the group with `lax.scan` over (entries, counts)
  (dense aggregate, TopK) or a concat + single sort-merge
  (high-cardinality aggregate); the pipeline maps the group and
  returns per-batch outputs.  Group sizes bucket to a short ladder:
  `pad_group` repeats entry 0 and gives each pad a count of 0, a
  "dead" entry whose rows are all masked off (an identity
  contribution), so the compile cache holds O(log n) group programs,
  keyed — like every core — by (plan fingerprint, shape class, dtype
  tuple) through `exec/kernels.cached_kernel` + jit's own shape
  cache.

Why: one launch per batch group, not per batch, keeps the launch count
of a scan a function of its shape classes, not of the table's size.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from datafusion_tpu.plan.expr import (
    AggregateFunction,
    BinaryExpr,
    Cast,
    Column,
    Expr,
    IsNotNull,
    IsNull,
    Literal,
    Operator,
    ScalarFunction,
    SortExpr,
)


def fuse_group_max() -> int:
    """Max batches folded into one fused-pass launch (bounds how many
    batches' device inputs are held live at once on cold scans)."""
    return max(1, int(os.environ.get("DATAFUSION_TPU_FUSE_GROUP", "256")))


def pipeline_group_max() -> int:
    """Max batches per fused pipeline (filter/project) launch.  Smaller
    than the aggregate group: the pipeline yields its outputs, so
    grouping trades first-batch latency for launch count."""
    return max(1, int(os.environ.get("DATAFUSION_TPU_FUSE_PIPELINE", "16")))


# group-size ladder: every group pads up to the next rung with dead
# (zero-row) entries, so at most ~33% of a launch is identity work and
# the compile cache holds one program per rung, not one per batch count
_LADDER = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256,
           384, 512)


def bucket_group(n: int) -> int:
    for rung in _LADDER:
        if rung >= n:
            return rung
    return n


# -- batch-group collection ----------------------------------------------


def entry_signature(entry) -> tuple:
    """Hashable (pytree structure, leaf shape/dtype tuple) of a
    prepared per-batch entry — the *shape class* half of the fused-pass
    cache key (the plan-fingerprint half is the operator core)."""
    import jax

    leaves, treedef = jax.tree.flatten(entry)
    return (
        treedef,
        tuple((str(np.asarray(l).dtype) if np.isscalar(l) else str(l.dtype),  # df-lint: ok(DF001) — isscalar gates: only python scalars reach asarray
               tuple(getattr(l, "shape", ())))
              for l in leaves),
    )


def shared_signature(shared) -> tuple:
    """Identity key of a group's shared (not stacked) inputs — aux
    tables, rank tables.  A batch whose dictionaries grew mid-scan gets
    fresh aux objects and starts a new group."""
    import jax

    return tuple(id(l) for l in jax.tree.leaves(shared))


def iter_groups(entries, shareds):
    """Split a chunk of (entry, shared) pairs into maximal consecutive
    runs with one signature; yields (indices, shared) per group."""
    start = 0
    cur = None
    for i, (e, s) in enumerate(zip(entries, shareds)):
        sig = (entry_signature(e), shared_signature(s))
        if cur is None:
            cur = sig
        elif sig != cur:
            yield list(range(start, i)), shareds[start]
            start, cur = i, sig
    if cur is not None:
        yield list(range(start, len(entries))), shareds[start]


def pad_group(entries: list, counts: list) -> tuple:
    """A batch group as its launch takes it: (entries, counts).  The
    entries are padded to their ladder rung with entry 0 again; counts
    is one int32 array of the entries' row counts in which every pad
    counts 0 — a dead entry, whose rows the kernel masks off by its
    count, so it contributes the identity.  The counts travel as ONE
    host value a launch, not one scalar an entry."""
    want = bucket_group(len(entries))
    rows = np.zeros(want, np.int32)
    rows[:len(counts)] = counts
    return tuple(entries) + (entries[0],) * (want - len(entries)), rows


def stack_entries(entries):
    """Stack a group's per-batch pytrees along a new leading axis
    (None leaves — absent validity/mask — are structural, not
    stacked).  Runs inside the fused jit, so the stacks fuse with the
    scan/map body instead of costing separate launches."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda *xs: jnp.stack(xs), *entries)


# -- plan-chain collapse --------------------------------------------------


def substitute_columns(e: Expr, proj: list[Expr]) -> Expr:
    """`e` with every Column(i) replaced by proj[i] — the projection
    inlining that lets a consumer's kernel evaluate the whole
    filter->project chain itself."""
    if isinstance(e, Column):
        return proj[e.index]
    if isinstance(e, Literal):
        return e
    if isinstance(e, Cast):
        return Cast(substitute_columns(e.expr, proj), e.data_type)
    if isinstance(e, IsNull):
        return IsNull(substitute_columns(e.expr, proj))
    if isinstance(e, IsNotNull):
        return IsNotNull(substitute_columns(e.expr, proj))
    if isinstance(e, BinaryExpr):
        return BinaryExpr(
            substitute_columns(e.left, proj),
            e.op,
            substitute_columns(e.right, proj),
        )
    if isinstance(e, ScalarFunction):
        return ScalarFunction(
            e.name, [substitute_columns(a, proj) for a in e.args],
            e.return_type,
        )
    if isinstance(e, AggregateFunction):
        out = AggregateFunction(
            e.name, [substitute_columns(a, proj) for a in e.args],
            e.return_type,
        )
        out.count_star = getattr(e, "count_star", False)
        return out
    if isinstance(e, SortExpr):
        return SortExpr(substitute_columns(e.expr, proj), e.asc)
    raise _Unfusable(f"cannot inline through {type(e).__name__}")


class _Unfusable(Exception):
    """Raised when a chain cannot collapse — callers fall back to the
    unfused per-operator lowering (never an error surface)."""


def flatten_chain(node):
    """Walk a Projection/Selection chain top-down and collapse it to
    (base_plan, predicate, projections, n_nodes):

    - `projections`: the top schema's exprs in terms of base columns
      (None when the chain had no Projection — identity),
    - `predicate`: every Selection AND-ed together, rewritten into base
      columns,
    - `n_nodes`: how many chain nodes collapsed (0 = `node` itself is
      the base).

    Returns None when a node can't inline (unknown expr kinds).
    """
    from datafusion_tpu.plan.logical import Projection, Selection

    pred: Optional[Expr] = None
    proj: Optional[list[Expr]] = None
    n = 0
    try:
        while True:
            if isinstance(node, Projection):
                if proj is None:
                    proj = list(node.expr)
                else:
                    proj = [substitute_columns(e, node.expr) for e in proj]
                if pred is not None:
                    pred = substitute_columns(pred, node.expr)
                node = node.input
            elif isinstance(node, Selection):
                pred = (
                    node.expr
                    if pred is None
                    else BinaryExpr(pred, Operator.And, node.expr)
                )
                node = node.input
            else:
                return node, pred, proj, n
            n += 1
    except _Unfusable:
        return None


def rewrite_aggregate(plan):
    """Collapse Aggregate(over a Projection/Selection chain) into the
    (base, group_expr, aggr_expr, predicate) of ONE fused aggregate
    kernel, or None when the shape doesn't admit it (non-Column group
    keys after inlining, Utf8 MIN/MAX over computed exprs).  Chains the
    planner already fuses (bare Aggregate(Selection(scan))) return
    None too — the default lowering is identical there."""
    flat = flatten_chain(plan.input)
    if flat is None:
        return None
    base, pred, proj, n = flat
    if proj is None:
        return None  # no projection in the chain: default lowering fuses it
    try:
        group_expr = [substitute_columns(g, proj) for g in plan.group_expr]
        aggr_expr = [substitute_columns(a, proj) for a in plan.aggr_expr]
    except _Unfusable:
        return None
    if not all(isinstance(g, Column) for g in group_expr):
        return None
    from datafusion_tpu.datatypes import DataType

    for a in aggr_expr:
        # Utf8 MIN/MAX needs a bare column (dictionary-code accumulator)
        if not isinstance(a, AggregateFunction) or not a.args:
            return None
        arg = a.args[0]
        try:
            utf8 = arg.get_type(base.schema) == DataType.UTF8
        except Exception:  # noqa: BLE001 — type errors mean "don't fuse"
            return None
        if utf8 and a.name.lower() in ("min", "max") and not isinstance(
            arg, Column
        ):
            return None
    return base, group_expr, aggr_expr, pred


def rewrite_sort(sort_plan, limit: Optional[int]):
    """Collapse Sort(over a Projection/Selection chain) — optionally
    under a Limit — into (base, sort_exprs, predicate, output_cols)
    for ONE SortRelation that filters, sorts, and projects in a single
    pass.  Requires column-pure projections (sort output is a gather
    from source batches, so computed projections would need their own
    kernel) and Column sort keys after inlining; the predicate must be
    host-evaluable (it folds into the selection mask without a device
    round trip).  Returns None when any condition fails OR when there
    is nothing to fuse (bare Sort(scan))."""
    from datafusion_tpu.exec.hostfn import host_evaluable

    flat = flatten_chain(sort_plan.input)
    if flat is None:
        return None
    base, pred, proj, n = flat
    if pred is None and proj is None:
        return None  # nothing between Sort and the base
    if proj is not None and not all(isinstance(e, Column) for e in proj):
        return None
    try:
        keys = [
            SortExpr(
                substitute_columns(se.expr, proj) if proj is not None
                else se.expr,
                se.asc,
            )
            for se in sort_plan.expr
        ]
    except _Unfusable:
        return None
    if not all(isinstance(k.expr, Column) for k in keys):
        return None
    if pred is not None and not host_evaluable(pred, {}, base.schema):
        return None
    output_cols = None if proj is None else [e.index for e in proj]
    return base, keys, pred, output_cols
