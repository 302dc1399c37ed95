"""Expr tree -> JAX computation compiler.

The reference compiles each Expr into an interpreted Rust closure per
batch (`src/execution/expression.rs:29,244-451`: literal arrays are
re-materialized per batch, casts barely work, nulls are punted).  Here
an Expr tree lowers to a *traceable jax function* over the batch's
column tensors; the operator layer jits one fused kernel per pipeline,
so a WHERE + projection becomes a single XLA computation per
(fragment, dtypes, capacity) — literals are XLA constants (broadcast is
free), casts are `astype`, and nulls are validity bool tensors.

String semantics (no tensor form for Utf8): columns carry int32
dictionary codes.  Equality against a string literal compares codes
(the literal's code is resolved per dictionary version on the host);
ordered comparisons look the code up in a host-computed truth table
(`StringDictionary.compare_table`), packed a bit a code into 128-wide
rows of words and read as a row gather + lane select + bit test
(`exec/rowgather.py`).  Both arrive as *aux inputs* so the jitted
kernel stays pure.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from datafusion_tpu.datatypes import DataType, Schema
from datafusion_tpu.errors import ExecutionError, NotSupportedError
from datafusion_tpu.exec.batch import RecordBatch, bucket_capacity
from datafusion_tpu.exec.rowgather import WORD_BITS, pack_bits, take_bits
from datafusion_tpu.utils.metrics import METRICS
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
)

# -- builtin scalar functions (UDFs merge into this via the context) --
BUILTIN_FUNCTIONS: dict[str, Callable] = {
    "sqrt": jnp.sqrt,
    "abs": jnp.abs,
    "exp": jnp.exp,
    "log": jnp.log,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
}


@dataclass(frozen=True)
class AuxSpec:
    """A host-computed kernel input derived from a string dictionary.

    kind == "eq_code":   int32 scalar, the literal's dictionary code
                         (-1 if absent -> matches nothing)
    kind == "cmp_table": uint32[rows, 128] truth table for an ordered
                         comparison against the literal, one bit a
                         dictionary code (`rowgather.pack_bits` of
                         bool[table_capacity]: a row holds 4,096 codes)
    """

    kind: str
    column: int
    op: str
    literal: str


class Env:
    """Runtime environment a compiled node reads from (all jax values).

    `col_map` optionally translates schema column indices to positions
    in `cols`/`valids`, so callers can ship only the columns a kernel
    actually reads (H2D bytes are the scarce resource on remote links).
    """

    __slots__ = ("_cols", "_valids", "aux", "_map", "params")

    def __init__(self, cols, valids, aux, col_map=None, params=()):
        self._cols = cols
        self._valids = valids
        self.aux = aux
        self._map = col_map
        self.params = params

    @property
    def cols(self):
        return self if self._map is not None else self._cols

    @property
    def valids(self):
        return _Indexer(self._valids, self._map) if self._map is not None else self._valids

    def __getitem__(self, i):  # self.cols[i] with a col_map active
        return self._cols[self._map[i]]


class _Indexer:
    __slots__ = ("_seq", "_map")

    def __init__(self, seq, col_map):
        self._seq = seq
        self._map = col_map

    def __getitem__(self, i):
        return self._seq[self._map[i]]


def _and_valid(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a & b


class ExprCompiler:
    """Compiles Expr trees to (Env) -> (value, validity|None) closures,
    collecting AuxSpecs for string comparisons along the way."""

    def __init__(
        self,
        schema: Schema,
        functions: Optional[dict[str, Callable]] = None,
        param_slots: Optional[dict] = None,
    ):
        self.schema = schema
        self.functions = dict(BUILTIN_FUNCTIONS)
        if functions:
            self.functions.update(functions)
        self.aux_specs: list[AuxSpec] = []
        # id(Literal node) -> runtime parameter slot (kernels.
        # parameterize_exprs): such literals compile to env.params
        # reads instead of baked XLA constants, so one kernel serves
        # every literal value of the same query shape
        self.param_slots = param_slots or {}

    def _add_aux(self, spec: AuxSpec) -> int:
        self.aux_specs.append(spec)
        return len(self.aux_specs) - 1

    def compile(self, expr: Expr) -> Callable[[Env], tuple]:
        if isinstance(expr, Column):
            i = expr.index

            def col_fn(env: Env):
                return env.cols[i], env.valids[i]

            return col_fn

        if isinstance(expr, Literal):
            if expr.value.is_null:

                def null_fn(env: Env):
                    # a null literal: value irrelevant, validity all-false
                    return jnp.zeros((), jnp.int32), jnp.zeros((), bool)

                return null_fn
            dt = expr.value.get_datatype()
            if dt == DataType.UTF8:
                raise NotSupportedError(
                    "bare string literals only appear inside comparisons"
                )
            slot = self.param_slots.get(id(expr))
            if slot is not None:
                np_dtype = dt.np_dtype

                def param_fn(env: Env, j=slot, d=np_dtype):
                    # runtime scalar argument: the value is NOT an XLA
                    # constant, so distinct literals share one kernel
                    return jnp.asarray(env.params[j], d), None

                return param_fn
            v = np.asarray(expr.value.value, dtype=dt.np_dtype)

            def lit_fn(env: Env):
                return jnp.asarray(v), None

            return lit_fn

        if isinstance(expr, Cast):
            return self._compile_cast(expr)

        if isinstance(expr, IsNull):
            inner = self.compile(expr.expr)

            def isnull_fn(env: Env):
                _, valid = inner(env)
                if valid is None:
                    return jnp.zeros((), bool), None
                return ~valid, None

            return isnull_fn

        if isinstance(expr, IsNotNull):
            inner = self.compile(expr.expr)

            def isnotnull_fn(env: Env):
                _, valid = inner(env)
                if valid is None:
                    return jnp.ones((), bool), None
                return valid, None

            return isnotnull_fn

        if isinstance(expr, BinaryExpr):
            return self._compile_binary(expr)

        if isinstance(expr, ScalarFunction):
            fn = self.functions.get(expr.name.lower())
            if fn is None:
                raise ExecutionError(f"no implementation for function {expr.name!r}")
            arg_fns = [self.compile(a) for a in expr.args]

            def func_fn(env: Env):
                vals, valid = [], None
                for af in arg_fns:
                    v, vd = af(env)
                    vals.append(v)
                    valid = _and_valid(valid, vd)
                return fn(*vals), valid

            return func_fn

        if isinstance(expr, AggregateFunction):
            raise ExecutionError(
                "aggregate functions are handled by the aggregate operator, "
                "not the scalar compiler"
            )

        raise NotSupportedError(f"cannot compile expression {expr!r}")

    def _compile_cast(self, expr: Cast) -> Callable:
        src_type = expr.expr.get_type(self.schema)
        dst_type = expr.data_type
        inner = self.compile(expr.expr)
        if src_type == dst_type:
            return inner
        if src_type == DataType.UTF8 or dst_type == DataType.UTF8:
            # the reference can't cast strings either (expression.rs:277-325)
            raise NotSupportedError(f"CAST {src_type!r} -> {dst_type!r} not supported")
        np_dtype = dst_type.np_dtype

        def cast_fn(env: Env):
            v, valid = inner(env)
            return v.astype(np_dtype), valid

        return cast_fn

    def _expr_is_utf8(self, e: Expr) -> bool:
        from datafusion_tpu.errors import DataFusionError

        try:
            return e.get_type(self.schema) == DataType.UTF8
        except DataFusionError:
            # untypeable subtree: not a string, and the real diagnostic
            # belongs to whoever compiles it
            return False

    def _compile_binary(self, expr: BinaryExpr) -> Callable:
        op = expr.op
        # -- string comparisons ride dictionary codes / lookup tables --
        if self._expr_is_utf8(expr.left) or self._expr_is_utf8(expr.right):
            return self._compile_string_comparison(expr)

        lf = self.compile(expr.left)
        rf = self.compile(expr.right)

        if op.is_boolean:
            # SQL three-valued logic: FALSE AND NULL = FALSE,
            # TRUE OR NULL = TRUE — a null operand must not poison a
            # determined result
            is_and = op == Operator.And

            def bool_fn(env: Env):
                lv, lvalid = lf(env)
                rv, rvalid = rf(env)
                if lvalid is None and rvalid is None:
                    return (lv & rv) if is_and else (lv | rv), None
                lva = jnp.ones((), bool) if lvalid is None else lvalid
                rva = jnp.ones((), bool) if rvalid is None else rvalid
                lv_t = lv & lva  # known TRUE
                rv_t = rv & rva
                lv_f = ~lv & lva  # known FALSE
                rv_f = ~rv & rva
                if is_and:
                    value = lv_t & rv_t
                    valid = (lva & rva) | lv_f | rv_f
                else:
                    value = lv_t | rv_t
                    valid = (lva & rva) | lv_t | rv_t
                return value, valid

            return bool_fn
        if op.is_comparison:
            jop = {
                Operator.Eq: lambda a, b: a == b,
                Operator.NotEq: lambda a, b: a != b,
                Operator.Lt: lambda a, b: a < b,
                Operator.LtEq: lambda a, b: a <= b,
                Operator.Gt: lambda a, b: a > b,
                Operator.GtEq: lambda a, b: a >= b,
            }[op]
        else:
            out_type = expr.get_type(self.schema)
            is_int = out_type.is_integer

            def _div(a, b):
                # C-style truncated division for ints (arrow semantics);
                # true division for floats
                return lax.div(a, b) if is_int else a / b

            jop = {
                Operator.Plus: lambda a, b: a + b,
                Operator.Minus: lambda a, b: a - b,
                Operator.Multiply: lambda a, b: a * b,
                Operator.Divide: _div,
                Operator.Modulus: lax.rem,
            }[op]

        def bin_fn(env: Env):
            lv, lvalid = lf(env)
            rv, rvalid = rf(env)
            return jop(lv, rv), _and_valid(lvalid, rvalid)

        return bin_fn

    def _compile_string_comparison(self, expr: BinaryExpr) -> Callable:
        op = expr.op
        # normalize to (column, literal); flip operator if literal is on the left
        flip = {
            Operator.Lt: Operator.Gt,
            Operator.LtEq: Operator.GtEq,
            Operator.Gt: Operator.Lt,
            Operator.GtEq: Operator.LtEq,
            Operator.Eq: Operator.Eq,
            Operator.NotEq: Operator.NotEq,
        }
        left, right = expr.left, expr.right
        if isinstance(left, Literal) and isinstance(right, Column):
            left, right = right, left
            op = flip.get(op)
            if op is None:
                raise NotSupportedError(f"operator {expr.op!r} on strings")
        if not (isinstance(left, Column) and isinstance(right, Literal)):
            raise NotSupportedError(
                "string comparisons support column-vs-literal only "
                f"(got {expr!r})"
            )
        if right.value.is_null:
            raise NotSupportedError("comparison with NULL is always null; use IS NULL")
        if right.value.get_datatype() != DataType.UTF8:
            raise NotSupportedError(f"comparing Utf8 with {right.value!r}")
        col = left.index
        lit = str(right.value.value)
        valid_i = col

        if op in (Operator.Eq, Operator.NotEq):
            aux_i = self._add_aux(AuxSpec("eq_code", col, "=", lit))
            negate = op == Operator.NotEq

            def eq_fn(env: Env):
                code = env.aux[aux_i]
                v = env.cols[col] == code
                if negate:
                    v = ~v
                return v, env.valids[valid_i]

            return eq_fn

        if op in (Operator.Lt, Operator.LtEq, Operator.Gt, Operator.GtEq):
            op_str = {
                Operator.Lt: "<",
                Operator.LtEq: "<=",
                Operator.Gt: ">",
                Operator.GtEq: ">=",
            }[op]
            aux_i = self._add_aux(AuxSpec("cmp_table", col, op_str, lit))

            def cmp_fn(env: Env):
                table = env.aux[aux_i]
                codes = jnp.clip(env.cols[col], 0, table.size * WORD_BITS - 1)
                return take_bits(table, codes), env.valids[valid_i]

            return cmp_fn

        raise NotSupportedError(f"operator {op!r} on strings")


def compute_aux_values(
    specs: list[AuxSpec], batch: RecordBatch, cache: dict
) -> list:
    """Materialize aux inputs for one batch from its dictionaries.

    Cached by (spec index, dictionary version): tables are recomputed
    only when a dictionary has grown.  Tables are padded to a bucketed
    capacity so the jitted kernel recompiles O(log dict size) times, and
    handed over bit-packed in whole rows (`rowgather.pack_bits`; laid
    out here, where it is free: inside the launch it would copy).
    ``expr.cmp_lookups`` counts the `cmp_table` inputs handed out: one
    device lookup a batch each (a call is one batch of one launch).
    """
    out = []
    for i, spec in enumerate(specs):
        d = batch.dicts[spec.column]
        if d is None:
            raise ExecutionError(
                f"column {spec.column} has no dictionary (not a Utf8 column?)"
            )
        key = (i, d.version)
        hit = cache.get(key)
        if hit is not None:
            out.append(hit)
            continue
        if spec.kind == "eq_code":
            val = np.int32(d.code_of(spec.literal))
        else:
            table = d.compare_table(spec.op, spec.literal)
            val = pack_bits(table, bucket_capacity(max(len(table), 1)))
        cache[key] = val
        out.append(val)
    lookups = sum(spec.kind == "cmp_table" for spec in specs)
    if lookups:
        METRICS.add("expr.cmp_lookups", lookups)
    return out
