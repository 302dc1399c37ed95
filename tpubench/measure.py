"""Run cells as the driver does and report medians and spreads.

    python3 -m tpubench.measure --cells q1_sf10_warm,q1_sf10_cold \
        --sets 2 --runs 6 [--seconds N] [--trace 1] [--tag name]

Each run is `python3 -m tpubench ...` in a process of its own, each run of a
set with another seed and every set with the same seeds; this parent never
imports JAX, so it never holds the chip.  For each cell and metric it prints, per set, the median and the spread (distance
between the quartiles over the median), and how far the second set's median
lies from the first's: what the bounds in BENCHMARK.json are set from
(about five times the widest spread).  Every run's last line is kept in
`chiprun_out/tpubench/measure_<tag>.json`.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

import numpy as np

from tpubench.spec import Spec


def quartile_spread(values: list) -> float:
    """(Q3 - Q1) / median, the quartiles as `statistics.quantiles(values,
    n=4)` gives them: what the driver reads (numpy's lie closer together)."""
    if len(values) < 2:
        return 0.0
    q1, med, q3 = statistics.quantiles(values, n=4)
    return float((q3 - q1) / med) if med else float("inf")


def run_once(spec: Spec, cell: str, seed: int, seconds: int, trace: int,
             extra: list) -> dict:
    cmd = spec.bench["command"] + [
        "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace)] + extra
    t = time.time()
    p = subprocess.run(cmd, cwd=spec.root, capture_output=True, text=True)
    out = {"cell": cell, "seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": time.time() - t}
    lines = p.stdout.strip().splitlines()
    try:
        out["line"] = json.loads(lines[-1]) if p.returncode == 0 else None
    except (ValueError, IndexError):
        out["line"] = None
    if out["line"] is None:
        out["stdout_tail"] = lines[-15:]
        out["stderr_tail"] = p.stderr.strip().splitlines()[-30:]
    return out


def summarise(runs: list) -> dict:
    """{metric: {"sets": [{"median", "spread", "n"}...], "shift"}}."""
    by_metric: dict = {}
    for r in runs:
        if r["line"]:
            for name, m in r["line"]["metrics"].items():
                by_metric.setdefault(name, {}).setdefault(
                    r["set"], []).append(m["value"])
    out = {}
    for name, per_set in by_metric.items():
        rows = [{"median": float(np.median(v)), "spread": quartile_spread(v),
                 "n": len(v)} for _, v in sorted(per_set.items())]
        out[name] = {"sets": rows}
        if len(rows) >= 2 and rows[0]["median"]:
            out[name]["shift"] = rows[1]["median"] / rows[0]["median"] - 1
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True, help="comma-separated")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=int, default=0,
                    help="default: run_seconds of BENCHMARK.json")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--first-seed", type=int, default=1)
    ap.add_argument("--tag", default="run")
    ap.add_argument("--budget-s", type=float, default=0,
                    help="start no run that would end later than this many "
                         "seconds after the start (chip time is rationed)")
    args, extra = ap.parse_known_args(argv)
    spec = Spec()
    seconds = args.seconds or spec.bench["run_seconds"]
    out_dir = os.path.join(spec.root, "chiprun_out", "tpubench")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"measure_{args.tag}.json")
    report: dict = {"seconds": seconds, "cells": {}}
    failed, t0, longest = 0, time.time(), 0.0
    for cell in args.cells.split(","):
        spec.cell(cell)
        runs = []
        for s in range(args.sets):
            seed = args.first_seed  # the same seeds in every set, as the driver's
            for _ in range(args.runs):
                if args.budget_s and (time.time() - t0 + longest
                                      > args.budget_s):
                    print(f"{cell} set {s}: no time left for another run",
                          flush=True)
                    continue
                r = run_once(spec, cell, seed, seconds, args.trace, extra)
                longest = max(longest, r["wall_s"])
                r["set"] = s
                seed += 1
                runs.append(r)
                failed += r["line"] is None
                shown = ({k: v["value"] for k, v in r["line"]["metrics"].items()}
                         if r["line"] else r["stderr_tail"])
                print(f"{cell} set {s} seed {r['seed']} rc {r['rc']} "
                      f"wall {r['wall_s']:.1f} s: {json.dumps(shown)}",
                      flush=True)
                report["cells"][cell] = {
                    "runs": runs, "summary": summarise(runs)}
                with open(path, "w") as f:
                    json.dump(report, f, indent=1)
        for name, m in summarise(runs).items():
            sets = "  ".join(f"median {s['median']:.6g} spread "
                             f"{100 * s['spread']:.2f}% (n={s['n']})"
                             for s in m["sets"])
            shift = (f"  second/first {100 * m['shift']:+.2f}%"
                     if "shift" in m else "")
            print(f"== {cell} {name}: {sets}{shift}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
