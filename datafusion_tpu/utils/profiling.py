"""Taking a JAX/XLA profile of the engine (SURVEY §5.1).

`trace(dir)` wraps a block in the JAX profiler.  The trace it writes
(TensorBoard / `jax.profiler.ProfileData`) holds the device plane (each
program `jit_<function>`, its operations, the transfers) and, on
`/host:CPU`, every stage timer of `utils/metrics.py` as a `dftpu.<name>`
span on the same clock, nested by thread, with `qid` on the spans that
begin a thread's share of a query:

    from datafusion_tpu.utils.profiling import trace
    with trace("/tmp/q1_profile"):
        ctx.sql_collect(sql)
"""

from __future__ import annotations

from contextlib import contextmanager


@contextmanager
def trace(log_dir: str):
    """Profile a block; writes a TensorBoard-loadable XLA trace."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()

