"""Shared closed-loop load generator for the serving front door.

Both consumers of the serving benchmark protocol — the `concurrency`
bench config (`benchmarks/suite.config_concurrency`) and the CI gate
(`scripts/serve_smoke.py`) — drive the same harness pieces from here,
so the measurement methodology cannot drift between them:

- `launch_floor_plan(ms)`: the injected per-launch latency floor (a
  seeded `device.call` delay rule).  Host-CPU dispatch models no
  device launch at all; the floor stands in for one on CPU-only hosts.
  BOTH legs (serialized and served) run under the same floor.
- `closed_loop(...)`: N client threads, each submitting its slice of
  distinct-literal queries back-to-back; returns the round's wall.
- `warm_rungs(...)`: precompiles every megabatch query-count rung a
  fragmented window can produce, so a timed phase is compile-free.
- `phase_quantiles(...)`: timed-phase-only p50/p99 from the
  cumulative `serve.latency` histogram by subtracting its pre-phase
  snapshot (bucket-wise negative merge).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional


def launch_floor_plan(floor_ms: float) -> dict:
    """Fault-plan JSON injecting `floor_ms` of latency per device
    launch (every `device.call` site hit, unlimited count)."""
    return {"seed": 7, "rules": [{
        "site": "device.call", "op": "delay",
        "seconds": floor_ms / 1e3, "count": 0,
    }]}


def closed_loop(srv, q: Callable[[float], str], clients: int,
                per_client: int, lit_of: Callable[[int], float],
                sink: dict, errors: list,
                timeout_s: float = 300.0,
                client_prefix: str = "c") -> float:
    """One closed-loop round: `clients` threads each submit
    `per_client` queries (literal = `lit_of(global_index)`), blocking
    on each result.  Results land in `sink[(client, i)]`; failures
    append to `errors`.  Returns the round's wall seconds.  Each
    thread submits under its own ``client_id``
    (``<client_prefix><index>``) so per-client metering
    (obs/attribution.py) attributes the round's costs — the smoke's
    conservation gate and the bench's metering record both read them
    back."""

    def client(ci: int):
        cid = f"{client_prefix}{ci}"
        for qi in range(per_client):
            try:
                sink[(ci, qi)] = srv.submit(
                    q(lit_of(ci * per_client + qi)), client_id=cid,
                ).result(timeout=timeout_s)
            except Exception as e:  # noqa: BLE001 — callers gate on `errors`
                errors.append((ci, qi, e))

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(clients)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return time.perf_counter() - t0


def warm_rungs(srv, q: Callable[[float], str], clients: int,
               timeout_s: float = 300.0) -> None:
    """Precompile every megabatch query-count rung a window can
    produce (a straggling client can fragment a round into any group
    size <= clients), so a later timed phase is deterministically
    compile-free."""
    from datafusion_tpu.exec.fused import bucket_group

    for sz in sorted({bucket_group(k) for k in range(1, clients + 1)}):
        tickets = [srv.submit(q(0.84 + sz * 1e-3 + j * 1e-4),
                              client_id="warmup")
                   for j in range(sz)]
        for t in tickets:
            t.result(timeout=timeout_s)


def phase_quantiles(hist, before_snapshot: Optional[dict]):
    """(p50, p99) of the observations a cumulative histogram gained
    since `before_snapshot` (None = since birth): merge the snapshot
    in negated so warm-up/compile latencies don't pollute the timed
    phase."""
    from datafusion_tpu.obs.aggregate import LatencyHistogram

    if hist is None:
        return None, None
    phase = LatencyHistogram.empty_like(hist).merge(hist)
    if before_snapshot is not None:
        phase.merge({
            **before_snapshot,
            "buckets": [-b for b in before_snapshot["buckets"]],
            "count": -before_snapshot["count"],
            "sum_s": -before_snapshot["sum_s"],
        })
    return phase.quantile(0.5), phase.quantile(0.99)
