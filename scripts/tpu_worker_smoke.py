"""Multi-host seam on the real accelerator: a CPU coordinator ships
plan fragments to a worker serving them on the attached chip, asserting
parity with the single-process CPU engine.  Prints one JSON line.

A chip belongs to one process.  Run standalone, this script is the
coordinator: it pins itself to the CPU *before* anything imports JAX,
so the `python -m datafusion_tpu.worker --device tpu` child it starts
is the only process that initialises the TPU backend.  `bench.py`
already holds the chip, so its worker leg calls `run_parity` against a
worker served from a thread of its own process instead.

Run:  python scripts/tpu_worker_smoke.py
(Equivalent pytest: DATAFUSION_TPU_TEST_TPU_WORKER=1
 python -m pytest tests/test_distributed.py::TestTpuWorker)
"""

import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROWS_PER_PART = 50_000
N_PARTS = 4


def run_parity(worker_addr, worker_info: str) -> dict:
    """Drive one distributed aggregate through the worker listening at
    `worker_addr` (host, port) and compare with the local CPU engine.
    Raises on any mismatch; returns the result record."""
    import numpy as np

    from datafusion_tpu.datatypes import DataType, Field, Schema
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.datasource import CsvDataSource
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.parallel.coordinator import DistributedContext
    from datafusion_tpu.parallel.partition import PartitionedDataSource

    schema = Schema(
        [
            Field("region", DataType.UTF8, False),
            Field("v", DataType.INT64, False),
            Field("x", DataType.FLOAT64, False),
        ]
    )
    tmp = tempfile.mkdtemp(prefix="tpu_worker_smoke_")
    rng = np.random.default_rng(3)
    regions = ["north", "south", "east", "west"]
    paths = []
    for p in range(N_PARTS):
        path = os.path.join(tmp, f"part{p}.csv")
        with open(path, "w") as f:
            f.write("region,v,x\n")
            for _ in range(ROWS_PER_PART):
                f.write(
                    f"{regions[rng.integers(0, 4)]},"
                    f"{int(rng.integers(-1000, 1000))},"
                    f"{rng.uniform(0, 100):.4f}\n"
                )
        paths.append(path)

    def pds():
        return PartitionedDataSource(
            [CsvDataSource(p, schema, True, 131072) for p in paths]
        )

    host, port = worker_addr
    dctx = DistributedContext([(host, int(port))])
    dctx.register_datasource("t", pds())
    lctx = ExecutionContext(device="cpu")
    lctx.register_datasource("t", pds())
    sql = (
        "SELECT region, COUNT(1), SUM(v), MIN(v), MAX(v), AVG(x) "
        "FROM t WHERE v > -500 GROUP BY region"
    )
    t0 = time.perf_counter()
    got = sorted(collect(dctx.sql(sql)).to_rows())
    elapsed = time.perf_counter() - t0
    want = sorted(collect(lctx.sql(sql)).to_rows())
    if not len(got) == len(want) == 4:
        raise AssertionError(f"{len(got)} groups vs {len(want)}, want 4")
    for g, w in zip(got, want):
        if g[:2] != w[:2]:
            raise AssertionError(f"keys/counts differ: {g} vs {w}")
        np.testing.assert_allclose(
            np.asarray(g[2:], float), np.asarray(w[2:], float), rtol=1e-6
        )
    import jax

    status = dctx.worker_status()[f"{host}:{port}"]
    return {
        "worker_info": worker_info,
        # who holds which backend: the worker's jax.devices() must be
        # the chip; a standalone coordinator's must be CPU only
        "worker_pid": status["pid"],
        "worker_devices": status["devices"],
        "coordinator_pid": os.getpid(),
        "coordinator_devices": [str(d) for d in jax.devices()],
        "rows": ROWS_PER_PART * N_PARTS,
        "partitions": N_PARTS,
        "query_s": round(elapsed, 3),
        "groups": len(got),
        "parity": "exact keys/counts; numeric rtol<=1e-6 vs CPU engine",
    }


def main() -> int:
    # the coordinator must never touch the chip: pin BEFORE any import
    # that imports JAX (the worker child gets the env without the pin)
    worker_env = dict(os.environ)
    worker_env.pop("JAX_PLATFORMS", None)
    worker_env["PYTHONPATH"] = REPO + os.pathsep + worker_env.get("PYTHONPATH", "")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO)

    worker = subprocess.Popen(
        [sys.executable, "-m", "datafusion_tpu.worker",
         "--bind", "127.0.0.1:0", "--device", "tpu"],
        cwd=REPO, env=worker_env, stdout=subprocess.PIPE, text=True,
    )
    try:
        line = worker.stdout.readline()
        if "listening on" not in line:
            raise RuntimeError(f"worker did not start: {line!r}")
        host, port = line.strip().rsplit(" ", 1)[1].rsplit(":", 1)
        info = worker.stdout.readline().strip()
        print(f"worker: {info}", flush=True)
        print(json.dumps(run_parity((host, port), info)))
        return 0
    finally:
        worker.terminate()
        worker.wait(timeout=10)


if __name__ == "__main__":
    sys.exit(main())
