"""Process-wide prepared-kernel cache.

SURVEY §7 ("Recompilation control"): the compile-cache key must be the
*plan fingerprint* — (expressions, schema, padded shape) — not the
operator instance.  `jax.jit` caches per callable object, so a fresh
operator tree (every new query, every new ExecutionContext) would
re-trace and re-compile kernels that are semantically identical to ones
already built.  Operators therefore build their compiled core (expr
closures + the jitted kernel) through this registry: equal fingerprints
share one core, so a repeated query — even from a brand-new context —
dispatches the already-compiled executable.

(The persistent on-disk XLA cache in __init__.py removes the cost
across processes; this registry removes the re-trace/lookup cost
within a process.)
"""

from __future__ import annotations

import os
from collections import OrderedDict
from typing import Callable

# LRU-bounded: fingerprints embed literal values (WHERE x > <literal>
# compiles a distinct kernel — XLA folds constants), so a long-running
# process with parameterized queries must not pin every variant forever
_MAX_CORES = int(os.environ.get("DATAFUSION_TPU_KERNEL_CACHE_SIZE", 256))
_REGISTRY: OrderedDict = OrderedDict()


def cached_kernel(key, build: Callable):
    """The cached compiled core for `key`, building it on first use;
    least-recently-used cores evict past the registry bound.  Hit/miss
    counters feed the fused-pass observability (EXPLAIN ANALYZE's
    per-query compile-cache line, the Prometheus export): a repeated
    query must show zero misses."""
    from datafusion_tpu.utils.metrics import METRICS

    hit = _REGISTRY.get(key)
    if hit is None:
        METRICS.add("kernel_cache.misses")
        hit = _REGISTRY[key] = build()
        while len(_REGISTRY) > _MAX_CORES:
            _REGISTRY.popitem(last=False)
    else:
        METRICS.add("kernel_cache.hits")
        _REGISTRY.move_to_end(key)
    return hit


def parameterize_exprs(exprs):
    """Literal-parameterized fingerprints for a list of Expr trees.

    SURVEY §7 "Recompilation control": with literal values baked into
    the cache key, `WHERE x > <literal>` compiles a distinct kernel per
    value — parameterized workloads recompile forever and churn the
    LRU.  Here numeric literals become runtime scalar kernel arguments:
    the fingerprint replaces each with a ("param", dtype, slot) marker,
    so one compiled kernel serves every value of `?`.

    Slots are assigned by VALUE-IDENTITY PATTERN, not position: equal
    literal values (same dtype) share a slot, in first-occurrence DFS
    order.  That makes fingerprint equality imply structural kernel
    compatibility — `SUM(x*0.9), AVG(x*0.9)` (pattern [0,0], args
    dedup into one accumulator slot) can never collide with
    `SUM(x*0.8), AVG(x*0.7)` (pattern [0,1], two slots).

    String literals keep their values in the fingerprint: they already
    reach kernels as runtime aux inputs (dictionary codes / compare
    tables), but the aux SPECS embed the string, so cores can only be
    shared between identical string literals.  NULL literals also stay
    in the fingerprint (they compile to a validity constant).

    Returns (fps, slot_by_id, values): one hashable fingerprint per
    expr (None passes through), `slot_by_id` mapping id(Literal node)
    -> slot for the compiler, and the per-slot runtime values as numpy
    scalars.  Callers recompute `values` from their own expr trees —
    identical fingerprints guarantee identical slot assignment.
    """
    from datafusion_tpu.datatypes import DataType
    from datafusion_tpu.plan.expr import (
        AggregateFunction,
        BinaryExpr,
        Cast,
        Column,
        IsNotNull,
        IsNull,
        Literal,
        ScalarFunction,
    )
    import numpy as np

    slot_by_id: dict = {}
    values: list = []
    pattern: dict = {}

    def lit_slot(lit) -> int:
        dt = lit.value.get_datatype()
        key = (repr(dt), repr(lit.value.value))
        slot = pattern.get(key)
        if slot is None:
            slot = pattern[key] = len(values)
            values.append(np.asarray(lit.value.value, dtype=dt.np_dtype))
        slot_by_id[id(lit)] = slot
        return slot

    def fp(e):
        if isinstance(e, Column):
            return ("col", e.index)
        if isinstance(e, Literal):
            if e.value.is_null:
                return ("nulllit", repr(e.value))
            dt = e.value.get_datatype()
            if dt == DataType.UTF8:
                return ("strlit", e.value.value)
            return ("param", repr(dt), lit_slot(e))
        if isinstance(e, Cast):
            return ("cast", repr(e.data_type), fp(e.expr))
        if isinstance(e, IsNull):
            return ("isnull", fp(e.expr))
        if isinstance(e, IsNotNull):
            return ("isnotnull", fp(e.expr))
        if isinstance(e, BinaryExpr):
            return ("bin", e.op, fp(e.left), fp(e.right))
        if isinstance(e, ScalarFunction):
            return ("fn", e.name, tuple(fp(a) for a in e.args))
        if isinstance(e, AggregateFunction):
            return ("agg", e.name, tuple(fp(a) for a in e.args))
        # unknown node: keep it verbatim (its literals stay inline)
        return ("raw", e)

    fps = tuple(None if e is None else fp(e) for e in exprs)
    return fps, slot_by_id, tuple(values)


def schema_fingerprint(schema) -> tuple:
    """Hashable image of a schema as kernels see it (positional
    dtypes + nullability; names ride along for dictionary wiring)."""
    return tuple(
        (f.name, repr(f.data_type), f.nullable) for f in schema.fields
    )


def functions_fingerprint(functions) -> tuple:
    """Hashable image of a UDF registry: jax lowerings are keyed by the
    function objects themselves (two contexts registering the same
    function object share kernels; different lowerings never collide).
    The objects ride in the registry key — NOT `id(fn)`, whose address
    can be reused by a new function after the old one is collected,
    silently dispatching a stale kernel."""
    if not functions:
        return ()
    return tuple(
        sorted(functions.items(), key=lambda kv: kv[0])
    )
