"""One-shot cold-path profiler: per-phase wall timeline for TPC-H Q1.

Run:  python scripts/profile_cold.py [sf]
Lineitem is `tpubench`'s `tpch_lineitem` data set (as `chip_smoke.py`
writes it), the query `tpubench`'s Q1 at DELTA 90.  Prints a per-batch
timeline (parse / encode / h2d / dispatch) plus the final blocking wait,
and a raw link-bandwidth measurement.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    sf = float(sys.argv[1]) if len(sys.argv) > 1 else 1
    sf = int(sf) if sf == int(sf) else sf
    import jax

    dev = jax.devices()[0]
    print(f"device: {dev}", flush=True)

    # raw link bandwidth: 64MB H2D and D2H
    a = np.random.default_rng(0).random(8 << 20)  # 64MB f64
    t0 = time.perf_counter()
    d = jax.device_put(a, dev)
    d.block_until_ready()
    t1 = time.perf_counter()
    _ = np.asarray(d)
    t2 = time.perf_counter()
    print(f"H2D 64MB: {t1-t0:.3f}s ({64/(t1-t0):.0f} MB/s)   "
          f"D2H 64MB: {t2-t1:.3f}s ({64/(t2-t1):.0f} MB/s)", flush=True)

    from chip_smoke import lineitem_path, q1_sql
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.materialize import collect

    path = lineitem_path(sf)
    Q1 = q1_sql()

    def cold():
        ctx = ExecutionContext(batch_size=1 << 19)
        ctx.register_parquet("lineitem", path)
        return collect(ctx.sql(Q1))

    # warm the compile caches once, untimed
    t0 = time.perf_counter()
    cold()
    print(f"first cold run (incl compile): {time.perf_counter()-t0:.2f}s", flush=True)

    # instrument the second run: wrap key functions with wall timers
    import datafusion_tpu.exec.aggregate as agg
    import datafusion_tpu.exec.batch as batch_mod

    events = []

    real_device_inputs = batch_mod.device_inputs

    def timed_device_inputs(*args, **kwargs):
        t = time.perf_counter()
        out = real_device_inputs(*args, **kwargs)
        events.append(("device_inputs", t, time.perf_counter()))
        return out

    batch_mod.device_inputs = timed_device_inputs
    agg.device_inputs = timed_device_inputs  # if imported into module

    real_group_ids = agg.AggregateRelation._group_ids

    def timed_group_ids(self, *args, **kwargs):
        t = time.perf_counter()
        out = real_group_ids(self, *args, **kwargs)
        events.append(("group_ids", t, time.perf_counter()))
        return out

    agg.AggregateRelation._group_ids = timed_group_ids

    real_acc = agg.AggregateRelation.accumulate

    def timed_acc(self):
        t = time.perf_counter()
        out = real_acc(self)
        events.append(("accumulate_total", t, time.perf_counter()))
        return out

    agg.AggregateRelation.accumulate = timed_acc

    real_fin = agg.AggregateRelation.finalize

    def timed_fin(self, state):
        t = time.perf_counter()
        out = real_fin(self, state)
        events.append(("finalize", t, time.perf_counter()))
        return out

    agg.AggregateRelation.finalize = timed_fin

    # wrap the parquet reader batch iterator
    import datafusion_tpu.io.readers as readers

    real_batches = readers.ParquetReader.batches

    def timed_batches(self, *args, **kwargs):
        it = real_batches(self, *args, **kwargs)
        while True:
            t = time.perf_counter()
            try:
                b = next(it)
            except StopIteration:
                return
            events.append(("parse", t, time.perf_counter()))
            yield b

    readers.ParquetReader.batches = timed_batches

    # wrap the jitted aggregate kernel dispatch
    from datafusion_tpu.utils import retry

    real_call = retry.device_call

    def timed_call(fn, /, *args, **kwargs):
        t = time.perf_counter()
        out = real_call(fn, *args, **kwargs)
        events.append(("kernel_dispatch", t, time.perf_counter()))
        return out

    retry.device_call = timed_call
    agg.device_call = timed_call

    t_start = time.perf_counter()
    out = cold()
    t_end = time.perf_counter()
    print(f"\ninstrumented cold run: {t_end-t_start:.2f}s, {out.num_rows} rows",
          flush=True)
    base = t_start
    for name, t0, t1 in sorted(events, key=lambda e: e[1]):
        print(f"  {t0-base:7.3f}s +{(t1-t0)*1e3:8.1f}ms  {name}", flush=True)

    # phase sums
    sums = {}
    for name, t0, t1 in events:
        sums[name] = sums.get(name, 0.0) + (t1 - t0)
    print("\nphase sums:", {k: round(v, 3) for k, v in sums.items()}, flush=True)


if __name__ == "__main__":
    main()
