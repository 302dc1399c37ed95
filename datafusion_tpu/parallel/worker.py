"""Worker node: executes shipped plan fragments.

The reference scaffolds worker nodes that never got built — the binary
is commented out of `Cargo.toml:25-27`, the docker image expects
`/opt/datafusion/bin/worker` (`scripts/docker/worker/Dockerfile`), and
etcd membership wiring is commented in `scripts/smoketest.sh:41-66`.
This is the real thing, TPU-native: a worker receives a `PlanFragment`
(JSON wire format), scans its partition, runs the fused device
aggregation kernel, and returns the *partial aggregate state* —
accumulator arrays plus the group-key table — for the coordinator to
merge.  Arbitrary Projection/Selection fragments return materialized
rows instead.

Requests:  {"type": "ping"}
           {"type": "status"}
           {"type": "execute_fragment", "fragment": <PlanFragment str>}
           {"type": "execute_plan", "fragment": <PlanFragment str>}
           {"type": "shuffle_map", "fragment": ..., "keys": [...],
            "num_parts": P, "side": "L"|"R"}
           {"type": "shuffle_join", "partition": p, "on": [[l,r]...],
            "join_type": ..., "left_blocks": [...], "right_blocks": [...]}
Responses: {"type": "pong", ...} / {"type": "status", ...} /
           {"type": "partial_state", ...} / {"type": "rows", ...} /
           {"type": "shuffle_blocks", ...} /
           {"type": "error", "message": ...}

The two `shuffle_*` kinds are the distributed-join exchange
(parallel/shuffle.py): `shuffle_map` executes a row fragment exactly
like `execute_plan` (same fragment cache — a replayed map task after a
failover re-partitions the cached rows instead of re-scanning) and
splits the rows into hash partitions; `shuffle_join` joins merged
per-partition blocks from both sides with the host `HashIndex` core.
"""

from __future__ import annotations

import os
import socket
from typing import Optional

import numpy as np

from datafusion_tpu import cache as qcache
from datafusion_tpu.cache import fragment_fingerprint
from datafusion_tpu.datatypes import DataType
from datafusion_tpu.errors import DataFusionError, ExecutionError
from datafusion_tpu.exec.aggregate import AggregateRelation
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.materialize import collect_columns
from datafusion_tpu.obs import trace as obs_trace
from datafusion_tpu.parallel.physical import PlanFragment
from datafusion_tpu.parallel.wire import BinWriter, enc_array
from datafusion_tpu.plan.logical import TableScan
from datafusion_tpu.testing import faults
from datafusion_tpu.utils.deadline import Deadline, deadline_scope
from datafusion_tpu.utils.eventloop import LoopServer


def _find_scan(plan) -> TableScan:
    node = plan
    while node is not None:
        if isinstance(node, TableScan):
            return node
        kids = node.children()
        node = kids[0] if kids else None
    raise ExecutionError("fragment plan has no TableScan leaf")


def _copy_raw(x):
    """Deep copy of a raw response payload for the fragment cache:
    array slices returned by a relation would otherwise pin the (much
    larger) buffers they view into."""
    if isinstance(x, np.ndarray):
        return np.array(x, copy=True)
    if isinstance(x, list):
        return [_copy_raw(y) for y in x]
    if isinstance(x, tuple):
        return tuple(_copy_raw(y) for y in x)
    if isinstance(x, dict):
        return {k: _copy_raw(v) for k, v in x.items()}
    return x


def _raw_nbytes(x) -> int:
    """Byte accounting for a raw payload (arrays + string payloads)."""
    if isinstance(x, np.ndarray):
        return x.nbytes
    if isinstance(x, (list, tuple)):
        return sum(_raw_nbytes(y) for y in x)
    if isinstance(x, dict):
        return sum(_raw_nbytes(y) for y in x.values())
    if isinstance(x, str):
        return len(x) + 16
    return 0


def _encode_response(raw: dict, frag: PlanFragment,
                     bw: Optional[BinWriter], cache_hit: bool) -> dict:
    """Raw payload (numpy arrays) -> wire response.  Encoding is
    per-request (the binary-segment writer belongs to one connection),
    so a cached payload re-encodes for every request that hits it; the
    `fragment_id` is the CURRENT request's (merge-side dedup keys on
    it, a cached payload must answer as the fragment that asked)."""
    if raw["type"] == "partial_state":
        out = {
            "type": "partial_state",
            "fragment_id": frag.fragment_id,
            "num_groups": raw["num_groups"],
            "counts": enc_array(raw["counts"], bw),
            "slots": [enc_array(s, bw) for s in raw["slots"]],
            "key_rows": enc_array(raw["key_rows"], bw),
            "key_dicts": raw["key_dicts"],
            "slot_dicts": raw["slot_dicts"],
        }
    else:
        out = {
            "type": "rows",
            "fragment_id": frag.fragment_id,
            "num_rows": raw["num_rows"],
            "columns": [
                {"codes": enc_array(c["codes"], bw), "values": c["values"]}
                if isinstance(c, dict)
                else enc_array(c, bw)
                for c in raw["columns"]
            ],
            "validity": [
                None if v is None else enc_array(v, bw)
                for v in raw["validity"]
            ],
        }
    if cache_hit:
        out["cache_hit"] = True
    return out


class WorkerState:
    def __init__(self, device=None, batch_size: int = 131072):
        import time

        self.device = device
        self.batch_size = batch_size
        self.queries = 0
        self.errors = 0
        self.started = time.time()
        # fragment cache: fingerprint(plan, partition meta, shard, file
        # version) -> raw response payload.  A duplicate dispatch —
        # failover replay, lost response, repeat of the same query — is
        # served from memory instead of re-scanning the partition.
        # None when DATAFUSION_TPU_CACHE=0 (zero overhead).
        self.fragment_cache = qcache.make_store("fragment")
        self.cache_hits = 0
        # cluster agent (cluster/agent.py): lease registration +
        # invalidation apply; None outside cluster mode
        self.cluster_agent = None
        # debug HTTP plane port (obs/httpd.py), when one is serving —
        # advertised in the cluster lease so `datafusion-tpu
        # debug-bundle --cluster` can pull this worker's bundle
        self.debug_port: Optional[int] = None
        # streaming-ingest seam (ingest/__init__.py): a process
        # embedding this worker next to a long-lived ExecutionContext
        # attaches that context's IngestContext here, and the wire
        # grows an `append` request.  None on plain fragment workers —
        # their per-fragment contexts have no tables to append to.
        self.ingest_ctx = None

    def append(self, table: str, columns: dict,
               client: Optional[str] = None) -> dict:
        """Wire append: durable-then-applied on the attached ingest
        context.  The `wal_unavailable` contract crosses the wire
        intact — IngestUnavailableError is a TransientError, so the
        error reply below tells the coordinator to retry, and the
        log's revision dedup absorbs the replay."""
        if self.ingest_ctx is None:
            from datafusion_tpu.errors import IngestUnavailableError

            raise IngestUnavailableError(
                "ingest not enabled on this worker")
        ack = self.ingest_ctx.append(table, columns, client=client or None)
        return {"type": "append_ack", **ack}

    def _gauges(self) -> dict:
        """Point-in-time gauges for the Prometheus rendering: span
        buffer depth plus the fragment cache's levels (and, in cluster
        mode, the lease age / epoch / events-applied gauges)."""
        from datafusion_tpu.utils import breaker as breaker_mod

        gauges = {"obs.span_buffer_depth": obs_trace.buffered()}
        if self.fragment_cache is not None:
            gauges.update(self.fragment_cache.gauges())
        if self.cluster_agent is not None:
            gauges.update(self.cluster_agent.gauges())
        # per-target circuit-breaker states (empty when breakers off)
        gauges.update(breaker_mod.gauges())
        return gauges

    def status(self) -> dict:
        """Operator-facing introspection (the reference's worker image
        EXPOSEd 8080 for a status web UI that never shipped,
        `scripts/docker/worker/Dockerfile`; this is the working
        equivalent over the fragment protocol — `{"type": "status"}`).
        `prometheus` folds the whole counter registry plus span-buffer
        and cache gauges into one scrape-ready text block."""
        import time

        import jax

        from datafusion_tpu.native import native_available
        from datafusion_tpu.obs.export import prometheus_text
        from datafusion_tpu.utils.metrics import METRICS

        snap = METRICS.snapshot()
        return {
            "type": "status",
            "pid": os.getpid(),
            "uptime_s": round(time.time() - self.started, 1),
            "queries": self.queries,
            "errors": self.errors,
            "device": self.device or jax.default_backend(),
            "devices": [str(d) for d in jax.devices()],
            "native": native_available(),
            "batch_size": self.batch_size,
            "cache": {
                "fragment": (
                    None
                    if self.fragment_cache is None
                    else self.fragment_cache.stats()
                ),
                "hits_served": self.cache_hits,
            },
            "cluster": (
                None
                if self.cluster_agent is None
                else self.cluster_agent.snapshot()
            ),
            # the fleet-aggregation payload: latency histograms +
            # counter/gauge registries (obs/aggregate.py) — the same
            # snapshot the cluster heartbeat piggybacks
            "telemetry": self.telemetry_snapshot(),
            "metrics": {
                "timings_s": {
                    k: round(v, 3) for k, v in snap["timings_s"].items()
                },
                "counts": snap["counts"],
            },
            "prometheus": prometheus_text(
                METRICS, extra_gauges=self._gauges()
            ),
        }

    def pinned_fingerprints(self) -> list[str]:
        """The resident-table fingerprints this worker advertises in
        its cluster lease under QoS (pin-aware placement): the HBM
        ledger's ``table:<name>`` pins (serve.py pinned tables; join
        build artifacts pin under plan digests and are deliberately
        NOT advertised — they name no routable table and would bloat
        the lease value) plus the fragment cache's table tags as
        ``table:<name>`` — a worker that has served a table's
        fragments holds its batches warm even without an explicit
        pin.  Sorted for a stable lease value (the agent re-puts only
        on change)."""
        from datafusion_tpu.obs.device import LEDGER

        fps = {fp for fp in LEDGER.pins_snapshot()
               if fp.startswith("table:")}
        if self.fragment_cache is not None:
            fps.update(f"table:{t}" for t in self.fragment_cache.tags())
        return sorted(fps)

    def telemetry_snapshot(self) -> dict:
        """This worker's node snapshot for fleet aggregation, with the
        cluster gauges (lease age, term, epoch) folded in so the
        coordinator's top view renders them per node."""
        from datafusion_tpu.obs.aggregate import node_snapshot

        snap = node_snapshot()
        snap["gauges"].update(self._gauges())
        return snap

    def _relation(self, frag: PlanFragment):
        plan = frag.logical_plan()
        scan = _find_scan(plan)
        # worker scans run on server handler threads: prefer the C++
        # CSV reader there (no pyarrow on the CSV path at all; when the
        # native lib is unavailable the pyarrow leg stays safe via the
        # io_thread confinement).  Scoped per-datasource on purpose —
        # a process embedding a worker keeps its own reader default —
        # while an explicit DATAFUSION_TPU_CSV_READER still wins (the
        # soak test pins "auto" to stress the pyarrow leg).
        import os

        choice = os.environ.get("DATAFUSION_TPU_CSV_READER") or "native"
        ds = frag.build_datasource(self.batch_size, csv_reader=choice)
        # result_cache=False: the per-fragment context must hand back
        # the raw operator tree (the partial-state path introspects it),
        # and fragment-level caching happens one layer up anyway
        ctx = ExecutionContext(device=self.device, batch_size=self.batch_size,
                               result_cache=False)
        # fragments are not fleet queries: their latency records on the
        # serve path below (fragment.latency histogram), not in the
        # coordinator-facing query funnel
        ctx._telemetry = False
        ctx.register_datasource(scan.table_name, ds)
        return ctx.execute(plan), plan

    def _serve_fragment(self, frag: PlanFragment, compute) -> tuple[dict, bool]:
        """Fragment-cache seam: (raw response payload, was_hit).

        The fault site `worker.fragment` guards actual execution — a
        cached serve does no partition scan, so injected execution
        faults don't fire on it (a replayed fragment after a chaos kill
        is exactly the dispatch this cache exists to make free)."""
        import time

        from datafusion_tpu.obs import recorder
        from datafusion_tpu.obs.aggregate import observe_latency

        cache = self.fragment_cache
        key = None
        if cache is not None:
            key = fragment_fingerprint(frag)
            hit = cache.get(key)
            if hit is not None:
                self.cache_hits += 1
                recorder.record("cache.hit", level="fragment",
                                shard=frag.shard)
                # zero-work span marking the free serve in the timeline
                with obs_trace.span("worker.fragment", cache_hit=True,
                                    **frag.span_attrs()):
                    pass
                return hit, True
        faults.check(
            "worker.fragment", shard=frag.shard, fragment_id=frag.fragment_id
        )
        t0 = time.perf_counter()
        try:
            with obs_trace.span("worker.fragment", **frag.span_attrs()):
                raw = compute(frag)
        except Exception as e:
            recorder.record("fragment.error", shard=frag.shard,
                            error=f"{type(e).__name__}: {e}")
            recorder.auto_capture("fragment_failure", lambda: {
                "fragment": frag.span_attrs(),
                "error": f"{type(e).__name__}: {e}",
            })
            raise
        dt = time.perf_counter() - t0
        observe_latency("fragment.latency", dt)
        recorder.record("fragment.serve", shard=frag.shard,
                        wall_s=round(dt, 6))
        if cache is not None:
            stored = _copy_raw(raw)
            # tagged by scanned table so a coordinator's invalidation
            # broadcast (cluster mode) drops exactly the dependents
            cache.put(key, stored, _raw_nbytes(stored),
                      tags=frag.table_names())
        return raw, False

    def execute_fragment(self, fragment_str: str, bw: Optional[BinWriter] = None) -> dict:
        """Partial-aggregate path: returns accumulator state + key table."""
        frag = PlanFragment.from_json_str(fragment_str)
        raw, hit = self._serve_fragment(frag, self._execute_fragment)
        return _encode_response(raw, frag, bw, hit)

    def _execute_fragment(self, frag: PlanFragment) -> dict:
        rel, _plan = self._relation(frag)
        if not isinstance(rel, AggregateRelation):
            raise ExecutionError(
                "execute_fragment needs an Aggregate fragment; "
                f"got {type(rel).__name__} (use execute_plan)"
            )
        counts, accs = rel.accumulate()
        self.queries += 1
        if rel.key_cols:
            n_groups = rel.encoder.num_groups
        else:
            n_groups = 1  # global aggregate: one implicit group
        counts = np.asarray(counts)[:n_groups]
        slots = [np.asarray(a)[:n_groups] for a in accs]

        # the worker's dense group ids are meaningless to the
        # coordinator — ship the key tuples (and the dictionaries the
        # string codes refer to) so it can re-encode into ITS id space
        key_dicts = {}
        for k, idx in enumerate(rel.key_cols):
            d = rel._key_dicts.get(idx)
            key_dicts[str(k)] = None if d is None else d.values
        slot_dicts = {}
        for slot_idx, sl in enumerate(rel.slots):
            if sl.is_string:
                d = rel._str_dicts.get(slot_idx)
                slot_dicts[str(slot_idx)] = [] if d is None else d.values
        return {
            "type": "partial_state",
            "num_groups": n_groups,
            "counts": counts,
            "slots": slots,
            "key_rows": (
                rel.encoder._arr[:n_groups]
                if rel.key_cols
                else np.empty((0, 0), np.int64)
            ),
            "key_dicts": key_dicts,
            "slot_dicts": slot_dicts,
        }

    def execute_plan(self, fragment_str: str, bw: Optional[BinWriter] = None) -> dict:
        """Row-returning path (Projection/Selection fragments): scan,
        filter, project on-device, materialize and ship the rows."""
        frag = PlanFragment.from_json_str(fragment_str)
        raw, hit = self._serve_fragment(frag, self._execute_plan)
        return _encode_response(raw, frag, bw, hit)

    def _execute_plan(self, frag: PlanFragment) -> dict:
        rel, plan = self._relation(frag)
        columns, validity, dicts, total = collect_columns(rel)
        self.queries += 1
        out_cols = []
        for i, f in enumerate(plan.schema.fields):
            c = columns[i]
            if f.data_type == DataType.UTF8:
                # ship dictionary codes + a COMPACT value table holding
                # only the values the result actually references (a
                # selective filter over a high-cardinality column must
                # not drag the whole global dictionary along); codes
                # remap to the compact table and ride the binary frame
                d = dicts[i]
                codes = np.asarray(c, dtype=np.int32)
                if d is None or len(d.values) == 0:
                    out_cols.append({"codes": codes, "values": []})
                else:
                    uniq, inv = np.unique(codes, return_inverse=True)
                    out_cols.append({
                        "codes": inv.astype(np.int32),
                        "values": [d.values[u] for u in uniq],
                    })
            else:
                out_cols.append(c)
        return {
            "type": "rows",
            "num_rows": total,
            "columns": out_cols,
            "validity": list(validity),
        }

    def shuffle_map(self, fragment_str: str, keys: list, num_parts: int,
                    side: str, bw: Optional[BinWriter] = None) -> dict:
        """Map side of the shuffle exchange: run the side's fragment
        (row path, fragment-cached) and split its output into
        `num_parts` hash-partitioned blocks.  Partitioning happens
        AFTER the cache seam on purpose — the cached payload is the
        plain rows result, so `execute_plan` and replayed map tasks
        with different partition counts all share one scan."""
        from datafusion_tpu.parallel import shuffle

        frag = PlanFragment.from_json_str(fragment_str)
        raw, hit = self._serve_fragment(frag, self._execute_plan)
        key_idx = [int(k) for k in keys]
        with obs_trace.span("worker.shuffle_map", side=side,
                            **frag.span_attrs()):
            blocks = shuffle.split_blocks(
                raw, key_idx, int(num_parts),
                (fragment_fingerprint(frag), side, int(num_parts), key_idx),
            )
        out = {
            "type": "shuffle_blocks",
            "fragment_id": frag.fragment_id,
            "side": side,
            "num_rows": raw["num_rows"],
            "blocks": [shuffle.encode_block(b, bw) for b in blocks],
        }
        if hit:
            out["cache_hit"] = True
        return out

    def shuffle_join(self, msg: dict, bw: Optional[BinWriter] = None) -> dict:
        """Reduce side: merge both sides' blocks for one partition
        (duplicate fingerprints drop idempotently) and join them with
        the host `HashIndex` core.  Responds in the standard `rows`
        shape so the coordinator's merge path is shared with the
        row-fragment union."""
        from datafusion_tpu.parallel import shuffle

        partition = int(msg["partition"])
        faults.check("worker.shuffle_join", partition=partition)
        with obs_trace.span("worker.shuffle_join", partition=partition):
            raw = shuffle.reduce_join(
                [shuffle.decode_block(o) for o in msg["left_blocks"]],
                [shuffle.decode_block(o) for o in msg["right_blocks"]],
                [(int(l), int(r)) for l, r in msg["on"]],
                msg.get("join_type", "inner"),
            )
        self.queries += 1
        return {
            "type": "rows",
            "fragment_id": f"{msg.get('query_id', '')}/p{partition}",
            "num_rows": raw["num_rows"],
            "columns": [
                {"codes": enc_array(c["codes"], bw), "values": c["values"]}
                if isinstance(c, dict)
                else enc_array(c, bw)
                for c in raw["columns"]
            ],
            "validity": [
                None if v is None else enc_array(np.asarray(v), bw)
                for v in raw["validity"]
            ],
        }


def _serve_worker_request(state: WorkerState, msg: dict):
    """One decoded request -> ``(response, BinWriter)``.  Runs on the
    event loop's bounded executor — compute concurrency is the pool's
    width, while any number of idle coordinator connections, heartbeat
    probes, and parked pulls cost only file descriptors.  Raises
    `InjectedConnectionAbort` to sever the connection (simulated worker
    death: the peer sees a mid-query EOF, exactly like a killed
    process)."""
    bw = BinWriter()
    # trace adoption: the request's {trace_id, parent_span_id} makes
    # this request's spans chain under the coordinator's dispatch span;
    # finished spans ship back in the response
    adoption = obs_trace.adopt(msg.get("trace"))
    try:
        kind = msg.get("type")
        # the coordinator ships the REMAINING per-query budget in
        # seconds (absolute times don't transfer between hosts);
        # re-anchor it here so device retries under this fragment
        # never sleep past the caller's deadline
        budget = msg.get("deadline_s")
        deadline = None if budget is None else Deadline.after(float(budget))
        if kind == "ping":
            out = {"type": "pong", "queries": state.queries}
        elif kind == "status":
            out = state.status()
        elif kind == "telemetry":
            # the non-cluster fleet-aggregation pull: one round trip
            # returns the node snapshot alone
            out = {"type": "telemetry",
                   "snapshot": state.telemetry_snapshot()}
        elif kind == "flight_dump":
            # the ring, on demand — trace-filtered when the
            # coordinator is assembling one query's artifact set
            # across every involved node
            from datafusion_tpu.obs import recorder

            out = {
                "type": "flight_dump",
                "node": f"worker:{os.getpid()}",
                "events": recorder.events(msg.get("trace_id") or None),
                "events_emitted": recorder.emitted(),
            }
        elif kind == "execute_fragment":
            with adoption, deadline_scope(deadline):
                out = state.execute_fragment(msg["fragment"], bw)
        elif kind == "execute_plan":
            with adoption, deadline_scope(deadline):
                out = state.execute_plan(msg["fragment"], bw)
        elif kind == "shuffle_map":
            with adoption, deadline_scope(deadline):
                out = state.shuffle_map(
                    msg["fragment"], msg["keys"], int(msg["num_parts"]),
                    msg.get("side", ""), bw,
                )
        elif kind == "shuffle_join":
            with adoption, deadline_scope(deadline):
                out = state.shuffle_join(msg, bw)
        elif kind == "append":
            with adoption, deadline_scope(deadline):
                out = state.append(msg["table"], msg["columns"],
                                   msg.get("client"))
        else:
            out = {"type": "error", "message": f"unknown request {kind!r}"}
    except faults.InjectedConnectionAbort:
        raise
    except DataFusionError as e:
        out = {"type": "error", "message": str(e)}
        bw = BinWriter()  # a failed build may have partial segments
        state.errors += 1
    except Exception as e:  # noqa: BLE001 — workers must not die on a bad query
        out = {"type": "error", "message": f"{type(e).__name__}: {e}"}
        bw = BinWriter()
        state.errors += 1
    if adoption.trace_id is not None and isinstance(out, dict):
        out["spans"] = obs_trace.drain(adoption.trace_id)
    return out, bw


class WorkerServer(LoopServer):
    """The worker on the selector event loop (socketserver-compatible
    facade; see `utils/eventloop.py`): the accept/read/write side is
    one thread regardless of connection count, fragment execution runs
    on the bounded pool."""

    worker_state: WorkerState
    http_server = None


def serve_http_status(state: WorkerState, host: str, port: int):
    """The worker's debug HTTP plane (obs/httpd.py): `GET /status`
    (also `/healthz`) returns the same JSON the fragment protocol's
    `{"type": "status"}` request does, `GET /metrics` (and
    `/debug/metrics`) serves the Prometheus text exposition, and the
    full `/debug/*` catalog — flight-recorder dump, HBM ledger
    breakdown, on-demand host profile, one-stop debug bundle — rides
    the same port.  The reference's worker image EXPOSEd 8080 for a
    web UI that never shipped (`scripts/docker/worker/Dockerfile`);
    this is the working operator surface."""
    import os as _os

    from datafusion_tpu.obs.httpd import DebugServer

    return DebugServer(
        port, host,
        label=f"worker:{_os.getpid()}",
        gauges_fn=state._gauges,
        status_fn=state.status,
    )


def serve(bind: str = "127.0.0.1:0", device=None, batch_size: int = 131072,
          http_port: Optional[int] = None, cluster=None,
          lease_ttl_s: Optional[float] = None,
          advertise: Optional[str] = None):
    """Run a worker; returns (server, thread) for embedding, or call
    serve_forever via the CLI entry (python -m datafusion_tpu.worker).
    `http_port` (non-zero) additionally serves GET /status on the same
    host.  `cluster` (service address or comma-separated HA endpoint
    list, `ClusterState`/`ClusterNode`, or client) registers this
    worker in the cluster control plane under a TTL lease kept alive by
    a heartbeat thread that also applies broadcast cache invalidations
    and rides out control-plane failovers (`cluster/agent.py`);
    `advertise` is the
    host[:port] coordinators should DIAL — required knowledge when the
    bind address is a wildcard (0.0.0.0 is not dialable from another
    host) or NAT'd (containers)."""
    from datafusion_tpu.utils.eventloop import ServerLoop, WireConnection

    host, _, port = bind.partition(":")
    state = WorkerState(device=device, batch_size=batch_size)
    loop = ServerLoop(name="df-tpu-worker")

    def on_message(conn, msg):
        if msg.get("type") == "shutdown":
            conn.reply(msg, {"type": "bye"})
            loop.call_later(0.05, loop.stop)  # after the bye flushes
            return
        conn.defer_reply(msg, lambda: _serve_worker_request(state, msg))

    lsock = loop.listen(host, int(port or 0),
                        lambda lp, sock, a: WireConnection(
                            lp, sock, a, on_message))
    server = WorkerServer(loop, lsock)
    server.worker_state = state
    server.http_server = None
    if http_port:
        # negative = ephemeral bind (smoke harnesses read the port
        # back); a bind failure degrades the debug plane, not the node.
        # The debug plane binds LOOPBACK by default regardless of the
        # worker's bind — it serves diagnostics, not queries, and must
        # not leave the host unless the operator says so
        # (DATAFUSION_TPU_DEBUG_BIND=0.0.0.0, plus a bearer token).
        from datafusion_tpu.obs.httpd import debug_bind_host

        try:
            server.http_server = serve_http_status(
                server.worker_state, debug_bind_host(host),
                max(int(http_port), 0)
            )
        except OSError:
            from datafusion_tpu.utils.metrics import METRICS

            METRICS.add("obs.debug_server_errors")
        else:
            server.worker_state.debug_port = server.http_server.port
    if cluster:
        from datafusion_tpu import cluster as _cluster_mod
        from datafusion_tpu.cluster.agent import WorkerClusterAgent

        bound_host, bound_port = server.server_address[:2]
        if advertise:
            adv_host, _, adv_port = advertise.partition(":")
            addr = f"{adv_host or bound_host}:{adv_port or bound_port}"
        else:
            adv_host = bound_host
            if adv_host in ("0.0.0.0", "::", ""):
                # a wildcard bind is not a dialable address; fall back
                # to this host's resolvable name so remote coordinators
                # can reach us (--advertise overrides when that's wrong)
                try:
                    adv_host = socket.gethostbyname(socket.gethostname())
                except OSError:
                    adv_host = socket.gethostname()
            addr = f"{adv_host}:{bound_port}"
        server.worker_state.cluster_agent = WorkerClusterAgent(
            _cluster_mod.connect(cluster),
            addr,
            server.worker_state,
            ttl_s=lease_ttl_s,
        ).start()
    return server


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="datafusion-tpu-worker",
        description="datafusion-tpu worker node (executes plan fragments)",
    )
    ap.add_argument("--bind", default="127.0.0.1:8462",
                    help="host:port to listen on (default 127.0.0.1:8462)")
    ap.add_argument("--device", default=None,
                    help="execution device: cpu | tpu (default: jax default)")
    ap.add_argument("--batch-size", type=int, default=131072)
    # default OFF: several workers commonly share one host (tests, the
    # compose cluster maps container-internal 8080s to distinct host
    # ports); the worker image turns it on explicitly
    ap.add_argument("--http-port", type=int,
                    default=int(os.environ.get(
                        "DATAFUSION_TPU_DEBUG_PORT", "0") or 0),
                    help="debug HTTP plane port (/status, /metrics, "
                         "/debug/* — obs/httpd.py).  Default 0 = "
                         "disabled (env DATAFUSION_TPU_DEBUG_PORT "
                         "overrides); negative = ephemeral; the worker "
                         "image passes 8080")
    # multi-host accelerator bring-up (jax.distributed — the etcd
    # replacement, SURVEY §5.8): workers on a TPU pod join one global
    # mesh before serving fragments
    # cluster control plane (datafusion_tpu/cluster): register under a
    # TTL lease, apply coordinator invalidation broadcasts
    ap.add_argument("--cluster", default=None,
                    help="cluster state service address host:port — or a "
                         "comma-separated HA endpoint list "
                         "host1:p1,host2:p2 (lease refreshes fail over to "
                         "the promoted standby automatically; default: env "
                         "DATAFUSION_TPU_CLUSTER; empty = cluster mode off)")
    ap.add_argument("--advertise", default=None,
                    help="host[:port] coordinators should dial for this "
                         "worker (needed behind 0.0.0.0 binds / NAT; "
                         "default: the bound address)")
    ap.add_argument("--coordinator", default=None,
                    help="jax.distributed coordinator address host:port "
                         "(omit on single-host deployments)")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    args = ap.parse_args(argv)
    faults.set_role("worker")  # role-scoped fault rules (testing/faults.py)
    obs_trace.set_process_role("worker")  # span process labels (obs/trace.py)
    if args.coordinator is not None or args.num_processes is not None:
        from datafusion_tpu.parallel.mesh import initialize_distributed

        initialize_distributed(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
        import jax

        print(
            f"distributed: process {jax.process_index()}/"
            f"{jax.process_count()}, global devices {jax.device_count()}",
            flush=True,
        )
    cluster = args.cluster
    if cluster is None:
        from datafusion_tpu.cluster import cluster_address

        cluster = cluster_address()
    server = serve(args.bind, device=args.device, batch_size=args.batch_size,
                   http_port=args.http_port, cluster=cluster,
                   advertise=args.advertise)
    host, port = server.server_address[:2]
    print(f"worker listening on {host}:{port}", flush=True)
    if server.http_server is not None:
        print(f"worker debug: {server.http_server.url}/debug", flush=True)
    if cluster:
        print(f"worker cluster: registered with {cluster}", flush=True)
    from datafusion_tpu.native import native_available

    print(
        f"worker info: native={native_available()} device={args.device} "
        f"batch_size={args.batch_size}",
        flush=True,
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        agent = server.worker_state.cluster_agent  # type: ignore[attr-defined]
        if agent is not None:
            # revoke the lease so the membership epoch moves now
            agent.close()
    return 0
