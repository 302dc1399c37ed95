"""Records the small profiler traces the trace-reduction test reads
(`tests/tpubench/data/`).  Run once on the chip:

    python3 tests/tpubench/record_trace_fixture.py chiprun_out/tpubench/tiny_v5e_engine.xplane.pb

Three requests of one jitted program (four matmul + tanh steps), each
after a host pause with no device work, under the benchmark's own span
names; the second request's pause sits inside an engine stage timer
(`METRICS.timer("pipeline.wait")`, the seam PR 24 made: a
`dftpu.pipeline.wait` span nested in `tpubench.call.sql`).  Busy time, idle
gaps and their labels of both prefixes are all there.
`tiny_v5e.xplane.pb` is this script's recording from before the engine had
spans (PR 22): the same three requests, the benchmark's span names only.
"""

import os
import shutil
import sys
import tempfile
import time


def main(out_path: str) -> int:
    import jax
    import jax.numpy as jnp

    from datafusion_tpu.utils.metrics import METRICS
    from tpubench import trace_reduce

    if jax.devices()[0].platform != "tpu":
        print("no TPU: the fixture is a device trace", file=sys.stderr)
        return 2

    @jax.jit
    def step(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    w = jnp.full((2048, 2048), 0.001, jnp.bfloat16)
    step(x, w).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    trace_dir = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out_path)))
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for i in range(3):
            with jax.profiler.TraceAnnotation("tpubench.request"):
                with jax.profiler.TraceAnnotation("tpubench.call.sql"):
                    if i == 1:
                        with METRICS.timer("pipeline.wait"):
                            time.sleep(0.004)
                    else:
                        time.sleep(0.004)
                with jax.profiler.TraceAnnotation("tpubench.call.collect"):
                    step(x, w).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(trace_reduce.find_xplane(trace_dir), out_path)
    shutil.rmtree(trace_dir)
    loaded = trace_reduce.load(out_path)
    print(trace_reduce.describe(loaded))
    print(trace_reduce.reduce(loaded))
    print(os.path.getsize(out_path), "bytes")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    sys.exit(main(sys.argv[1]))
