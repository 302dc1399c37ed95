"""datafusion-tpu: a TPU-native SQL query engine.

A from-scratch rebuild of the capabilities of DataFusion 0.5.1
(reference: /root/reference, Rust) designed TPU-first:

- SQL text -> AST -> logical plan -> physical plan -> execution, with the
  same clean layer boundaries as the reference (`src/lib.rs:24-27`).
- Expression trees compile to jitted XLA computations (one fused kernel
  per operator pipeline) instead of per-expression interpreted closures
  (reference `src/execution/expression.rs:29`).
- Columnar batches are fixed-capacity, padded, validity-masked tensors so
  every shape is static under `jax.jit`.
- Distributed/partitioned execution maps onto a `jax.sharding.Mesh` with
  XLA collectives (psum/pmax) rather than the reference's planned
  etcd+HTTP+Arrow-IPC worker scheme (`scripts/smoketest.sh:30-66`).
"""

# A SQL engine's Int64/Float64 semantics require real 64-bit lanes; JAX
# truncates to 32-bit by default.  Must run before any jax.numpy usage.
from jax import config as _jax_config

_jax_config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: a query engine re-creates identical
# kernels (same plan shape, schema, bucketed batch size) across
# processes and sessions, so compiled executables are kept on disk.
# The cache is placed from outside: where JAX_COMPILATION_CACHE_DIR (or
# an earlier jax.config.update) names a directory, nothing is touched.
# Otherwise it lives at a FIXED path inside the checkout — the path is
# part of the cache's identity, so it must not move between processes.
# CPU-pinned processes (tests, workers) skip it: CPU compiles are cheap,
# and XLA:CPU AOT reloads warn about pseudo-feature mismatches across
# processes.
import os as _os

if (
    not _jax_config.jax_compilation_cache_dir
    and _os.environ.get("JAX_PLATFORMS", "").lower() != "cpu"
):
    _jax_config.update(
        "jax_compilation_cache_dir",
        _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache",
        ),
    )

from datafusion_tpu.errors import (
    DataFusionError,
    ExecutionError,
    InvalidColumnError,
    IoError,
    NotSupportedError,
    ParserError,
    PlanError,
)
from datafusion_tpu.datatypes import (
    DataType,
    Field,
    Schema,
    StructType,
    can_coerce_from,
    get_supertype,
)
from datafusion_tpu.plan.expr import (
    AggregateFunction,
    BinaryExpr,
    Cast,
    Column,
    Expr,
    FunctionMeta,
    FunctionType,
    IsNotNull,
    IsNull,
    Literal,
    Operator,
    ScalarFunction,
    ScalarValue,
    SortExpr,
)
from datafusion_tpu.plan.logical import (
    Aggregate,
    EmptyRelation,
    Limit,
    LogicalPlan,
    Projection,
    Selection,
    Sort,
    TableScan,
)
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.dataframe import DataFrame, f, lit

__version__ = "0.1.0"

__all__ = [
    "DataFusionError",
    "ExecutionError",
    "InvalidColumnError",
    "IoError",
    "NotSupportedError",
    "ParserError",
    "PlanError",
    "DataType",
    "Field",
    "Schema",
    "StructType",
    "can_coerce_from",
    "get_supertype",
    "Expr",
    "Column",
    "Literal",
    "BinaryExpr",
    "IsNull",
    "IsNotNull",
    "Cast",
    "SortExpr",
    "ScalarFunction",
    "AggregateFunction",
    "ScalarValue",
    "Operator",
    "FunctionMeta",
    "FunctionType",
    "LogicalPlan",
    "Projection",
    "Selection",
    "Aggregate",
    "Sort",
    "Limit",
    "TableScan",
    "EmptyRelation",
    "ExecutionContext",
    "DataFrame",
    "f",
    "lit",
    "__version__",
]
