#!/usr/bin/env python
"""Benchmark driver: runs the five BASELINE.md configs and prints ONE
JSON line on stdout (diagnostics on stderr).

Headline metric = config 3, TPC-H Q1 over Parquet lineitem: `value` is
the warm (device-resident steady-state) rows/s, `vs_baseline` the TPU
speedup over this engine's own single-thread CPU path on identical
inputs (the reference publishes no numbers and functionally cannot run
the query — aggregates are `unimplemented!()` there, `context.rs:161`).
Cold (scan-inclusive: Parquet parse, dictionary encode, H2D, kernel,
D2H) is reported separately with a per-phase breakdown under
`configs.tpch_q1_parquet`.

A device run needs the chip: with no TPU and no explicit
`JAX_PLATFORMS=cpu` the driver exits non-zero instead of timing the
CPU under the device's names.  An explicit CPU pin is a logic check —
every config's gates run, and each reports only that it passed.  Every
config's output names the platform, device kind and device count it
ran on.  This process holds the chip; the legs that start children
(config 5's virtual mesh, the adaptive legs) pin them to the CPU, and
the worker leg serves from a thread here.

Env knobs: BENCH_SF (lineitem scale factor for config 3, default 1),
BENCH_CONFIGS (comma list, default
"1,2,3,4,5,3sf10,worker,cache,conc,ingest,joins,adaptive" —
"3sf10" runs Q1 at the north-star SF-10 scale, "worker" runs the
coordinator->worker-on-chip parity smoke, "cache" runs the result-cache
warm-repeat phase, "joins" runs the TPC-H Q3/Q5/Q10/Q12 join shapes
against a pandas-merge oracle, "adaptive" runs the cost-store
cold-vs-trained planning comparison), BENCH_RUNS / BENCH_COLD_RUNS.
"""

import json
import os
import sys


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax

    from benchmarks import suite

    devices = jax.devices()
    suite.log(f"devices: {devices}")
    device_kind = devices[0].platform
    cpu_pinned = os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
    if device_kind != "tpu" and not cpu_pinned:
        print(
            f"bench.py: no TPU (jax.devices()[0].platform == "
            f"{device_kind!r}); set JAX_PLATFORMS=cpu for a logic check",
            file=sys.stderr,
        )
        sys.exit(1)
    device = {
        "platform": device_kind,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    # legs whose children are pinned to the CPU whatever this process holds
    cpu_legs = {"5": {"platform": "cpu", "kind": "cpu", "count": 8},
                "adaptive": {"platform": "cpu", "kind": "cpu", "count": 1}}

    wanted = os.environ.get(
        "BENCH_CONFIGS",
        "1,2,3,4,5,3sf10,worker,cache,conc,ingest,joins,adaptive",
    ).split(",")
    runners = {
        "1": suite.config1_csv_filter,
        "2": suite.config2_groupby,
        "3": suite.config3_tpch_q1,
        "4": suite.config4_sort_topk,
        "5": suite.config5_mesh,
        # the north-star metric is defined at SF-10 (BASELINE.json);
        # SF-1 stays in the run for round-over-round comparability
        "3sf10": lambda dk: suite.config3_tpch_q1(dk, sf=10),
        # coordinator -> worker-on-the-chip smoke: the remote-compute-
        # node seam (reference scripts/smoketest.sh:30-66) exercised on
        # real hardware as part of every bench run
        "worker": suite.config_worker_smoke,
        # warm-repeat phase: result-cache hit rate + warm/cold speedup
        "cache": suite.config_cache,
        # throughput under concurrency: the serving front door (async
        # admission + HBM-pinned tables + cross-query megabatching) vs
        # serialized back-to-back execution of the same workload
        "conc": suite.config_concurrency,
        # streaming ingestion: Q1 view incremental maintenance rate x
        # freshness vs recomputing the view from scratch per delta
        "ingest": suite.config_ingest,
        # multi-table TPC-H shapes (Q3/Q5/Q10/Q12) through the hash
        # join, gated on pandas-merge parity + a warm pinned-probe
        # launches-per-pass ceiling
        "joins": suite.config_joins,
        # feedback-driven planning: same workload cold vs trained
        # (persisted cost store), gated on >=2 decision flips,
        # bit-exact rows, >=1.2x on the mis-defaulted aggregate
        "adaptive": suite.config_adaptive,
    }
    if float(os.environ.get("BENCH_SF", 1)) == 10 and "3" in [
        w.strip() for w in wanted
    ]:
        # BENCH_SF=10 makes config "3" the SF-10 run already — don't
        # run the most expensive config twice under one output key
        wanted = [w for w in wanted if w.strip() != "3sf10"]
    configs = {}
    for key in wanted:
        key = key.strip()
        if key not in runners:
            continue
        result = runners[key](device_kind)
        result["device"] = cpu_legs.get(key, device)
        if device_kind == "cpu":
            # logic check: the gates ran; no number of a CPU run is
            # written under a device metric's name
            result = {k: result[k] for k in ("name", "device", "skipped",
                                             "error") if k in result}
            result["passed"] = "error" not in result
        configs[result["name"]] = result

    if not configs:
        print(json.dumps({
            "error": f"BENCH_CONFIGS={os.environ.get('BENCH_CONFIGS')!r} "
                     f"selected none of {sorted(runners)}"
        }))
        sys.exit(2)
    # headline = the north-star config: Q1 at SF-10, else SF-1
    headline = configs.get("tpch_q1_parquet_sf10") or configs.get(
        "tpch_q1_parquet"
    )
    if headline is None:  # driver ran a subset; promote the first config
        headline = next(iter(configs.values()))
    print(json.dumps({
        "metric": headline["name"] + "_throughput",
        "value": headline.get("value"),
        "unit": headline.get("unit"),
        "vs_baseline": headline.get("vs_baseline"),
        "device": device,
        "configs": configs,
    }))
    failed = sorted(n for n, r in configs.items() if "error" in r)
    if failed:
        print(f"bench.py: failed legs: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
