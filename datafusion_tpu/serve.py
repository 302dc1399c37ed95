"""Concurrent-query serving front door (ROADMAP item 2).

One ``ExecutionContext.execute`` call owning the device end-to-end caps
the engine at one launch and one host<->device sync per query.  This
module is the path to "heavy traffic from millions of users": an async
front door that admits, batches, and executes many clients' queries
against one engine, built from three pieces the earlier PRs laid down
as substrate:

- **Admission control** — a bounded queue over the existing deadline
  machinery, driven by the PR 11 selector event loop
  (`utils/eventloop.ServerLoop`): every ``submit`` either enqueues
  (``queries_queued``) or sheds (``queries_shed`` +
  ``QueryShedError``) on queue depth, deadline infeasibility (the
  remaining budget cannot cover the observed service EWMA), or HBM
  headroom (capacity known, projected residency over it, eviction
  could not make room).  Queries that reach ``ExecutionContext.execute``
  count ``queries_admitted`` exactly as before, so
  ``admitted + shed == submitted`` holds by construction — the
  counters declared since PR 8 now record real decisions.

- **HBM-pinned resident tables** — the PR 9 ledger promoted from
  observer to allocator (`obs/device.DeviceLedger.pin/evict_pins`):
  the first query over a table materializes it into a long-lived
  resident batch list (``PinnedSource``), whose device copies —
  uploaded once through the normal ``device_inputs`` caches — stay hot
  across queries as a ledger-owned ``pin.<table>`` entry.  Warm
  queries skip H2D entirely (``device.h2d.transfers`` stays flat);
  admission checks ``LEDGER.headroom()`` and eviction runs by owner
  priority, then least-recent use.

- **Plan megabatching** — the PR 6 batch-group signature machinery
  applied *across queries*: compatible concurrent plans (same compiled
  core — i.e. same table, same shape class, literals parameterized
  away) queued within one batching window fuse into ONE XLA launch
  (`_AggregateCore.multi_group_jit`) over one set of pinned device
  inputs, and the per-query accumulator states de-multiplex back to
  their clients.  N users' queries pay one launch and one sync, not N.

Everything here is opt-in: nothing in the engine consults this module
unless a ``Server`` is constructed (``DATAFUSION_TPU_SERVE=0`` is
byte-identical to not importing it).  Env knobs, all prefixed
``DATAFUSION_TPU_SERVE_``: ``QUEUE`` (pending-query depth, default
64), ``WORKERS`` (executor width, default 2), ``WINDOW_MS`` (batching
window, default 2), ``MEGABATCH`` (max queries fused per launch,
default 16; 0 disables fusion), ``PIN`` (1 pins tables, 0 streams),
``DEADLINE_S`` (default per-query budget; unset = none).

Multi-tenant QoS (``DATAFUSION_TPU_QOS=1`` or ``Server(shares=...)``;
see datafusion_tpu/qos.py) upgrades the admission queue to weighted
fair queueing over the per-tenant cost meters and sheds the
over-quota tenant first (``quota`` reason) under queue pressure —
unset, every path above stays byte-identical FIFO.
"""

from __future__ import annotations

import os
import threading
import time
from functools import partial
from typing import Optional

import numpy as np

from datafusion_tpu.errors import QueryShedError
from datafusion_tpu.exec.datasource import DataSource
from datafusion_tpu.obs import recorder
from datafusion_tpu.obs.device import LEDGER
from datafusion_tpu.utils.deadline import Deadline, deadline_scope
from datafusion_tpu.utils.metrics import METRICS, QUERY_IDS


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return default if not v else int(v)


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return default if not v else float(v)


def enabled() -> bool:
    """The master opt-in: ``DATAFUSION_TPU_SERVE=1``.  Consulted only
    by conveniences (``ExecutionContext.serve``); the engine's own
    paths never read it — serving is additive, not a mode switch."""
    return os.environ.get("DATAFUSION_TPU_SERVE", "0") not in ("0", "")


class Ticket:
    """One submitted query's handle: ``result()`` blocks until the
    server fulfills or fails it.  Exactly-once by construction — the
    outcome slot is written exactly once, under the event.

    Beyond the outcome, the ticket is the query's critical-path
    record: monotonic stamps at every serving-chain boundary (submit
    entry, admission, window enqueue, window flush, execution start)
    plus the apportioned launch/demux shares the megabatch path
    charges back, so ``_finish`` can decompose the end-to-end wall
    into the canonical segment chain (obs/attribution.py) without a
    single extra measurement on the hot path."""

    __slots__ = ("sql", "plan", "deadline", "submitted_mono", "_evt",
                 "_table", "_error", "_rel", "signature", "client_id",
                 "entry_mono", "admitted_mono", "enqueued_mono",
                 "flushed_mono", "exec_start_mono", "launch_share_s",
                 "demux_share_s", "qid")

    def __init__(self, sql: str, plan, deadline: Optional[Deadline],
                 signature, client_id: str = "default",
                 entry_mono: Optional[float] = None, qid: int = 0):
        self.sql = sql
        self.qid = qid  # shared by this query's spans (utils/metrics.py)
        self.plan = plan
        self.deadline = deadline
        self.signature = signature
        self.client_id = client_id
        self.submitted_mono = time.monotonic()
        self.entry_mono = (entry_mono if entry_mono is not None
                           else self.submitted_mono)
        self.admitted_mono: Optional[float] = None
        self.enqueued_mono: Optional[float] = None
        self.flushed_mono: Optional[float] = None
        self.exec_start_mono: Optional[float] = None
        self.launch_share_s = 0.0   # apportioned megabatch launch wall
        self.demux_share_s = 0.0    # apportioned blob-pull wall
        self._evt = threading.Event()
        self._table = None
        self._error: Optional[BaseException] = None
        self._rel = None

    @property
    def done(self) -> bool:
        return self._evt.is_set()

    def _fulfill(self, table) -> None:
        if not self._evt.is_set():
            self._table = table
            self._evt.set()

    def _fail(self, exc: BaseException) -> None:
        if not self._evt.is_set():
            self._error = exc
            self._evt.set()

    def result(self, timeout: Optional[float] = None):
        """The materialized ``ResultTable`` (blocking), or raises the
        query's error (``QueryShedError`` included)."""
        if not self._evt.wait(timeout):
            raise TimeoutError(f"query not done within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._table


class PinnedSource(DataSource):
    """A registered DataSource promoted to an HBM-pinnable resident.

    Cold: streams the inner source.  ``ensure()`` materializes the
    scan ONCE into a long-lived batch list and registers it with the
    ledger (``LEDGER.pin``) under ``pin.<table>``; from then on every
    query scans the SAME RecordBatch objects, so the device copies the
    first query uploads (via the normal ``device_inputs`` per-batch
    caches) serve every later query with zero H2D.  Eviction (ledger
    pressure, ``unpin``) drops the resident list — buffers release
    through their finalizers and the next query goes cold again.

    Schema, wire meta, and therefore result-cache fingerprints all
    delegate to the inner source: pinning is invisible to semantics.
    """

    def __init__(self, inner: DataSource, name: str):
        from datafusion_tpu.analysis import lockcheck

        self.inner = inner
        self.name = name
        self.fingerprint = f"table:{name}"
        self._resident = None  # list[RecordBatch] | None
        # residency-change hook (Server wires the pin-manifest save
        # here); invoked OUTSIDE self._lock, after ensure()/_drop()
        self.on_change = None
        self._lock = lockcheck.make_lock("serve.pin_source")
        # cross-query execution state (group-key encoders, aux caches)
        # so ids/aux computed by one query replay for every later or
        # concurrent one.  An in-memory inner source already owns one
        # over the SAME batches and is asked instead, so `ctx.sql` and
        # `Server.submit` agree on ids and keep one id array per batch
        from datafusion_tpu.exec.datasource import SharedScanState

        if hasattr(inner, "shared_state_for"):
            self._shared = inner._shared
            self._state_for = inner.shared_state_for
        else:
            self._shared = SharedScanState()
            self._state_for = self._shared.for_core

    @property
    def schema(self):
        return self.inner.schema

    @property
    def reusable_batches(self) -> bool:
        # resident batches are the same objects every scan
        return self._resident is not None or getattr(
            self.inner, "reusable_batches", False
        )

    def to_meta(self) -> dict:
        return self.inner.to_meta()

    @property
    def data_version(self) -> Optional[int]:
        # appendable inners version per delta; fingerprints fold it in
        # (exec/context.py query_fingerprint reads the REGISTERED source)
        return getattr(self.inner, "data_version", None)

    def with_projection(self, projection) -> "DataSource":
        return _PinnedProjection(self, list(projection))

    def splice_appendable(self, cls):
        """Splice a streaming-appendable source (`cls` is
        ingest.AppendableSource) in UNDER this pin: the appendable
        materializes from the current batches (the SAME objects when
        resident, so their device copies survive), and the pin's
        resident list becomes the appendable's LIVE batch list — every
        later append grows the pinned copy in place, with no divergent
        re-materialization.  Idempotent; called by
        `IngestContext._wrap_source` on first attach."""
        with self._lock:
            if isinstance(self.inner, cls):
                return self.inner
        # materializing may scan a file-backed inner: outside the lock,
        # same discipline as ensure()
        src = cls.wrap(self, name=self.name)
        with self._lock:
            if isinstance(self.inner, cls):
                return self.inner
            self.inner = src
            if self._resident is not None:
                self._resident = src._batches
        return src

    def estimated_bytes(self) -> int:
        """Admission-time residency estimate: resident size when
        materialized, else the backing file's size (0 when unknowable
        — admission then never sheds for this table)."""
        res = self._resident
        if res is not None:
            return _host_bytes(res)
        path = getattr(self.inner, "path", None)
        if path:
            try:
                return os.path.getsize(path)
            except OSError:
                return 0
        batches = getattr(self.inner, "_batches", None)
        if batches:
            return _host_bytes(batches)
        return 0

    def ensure(self) -> bool:
        """Materialize + pin (idempotent).  Returns True when resident."""
        with self._lock:
            if self._resident is not None:
                LEDGER.pinned(self.fingerprint)  # touch: recency/priority
                return True
        # the scan runs OUTSIDE the lock (file-backed tables block on
        # IO); a racing ensure may scan too — last writer loses, both
        # results are equivalent.  An in-memory appendable inner pins
        # its LIVE batch list (not a snapshot copy) so streaming
        # appends keep growing the resident copy in place.
        live = getattr(self.inner, "_batches", None)
        if live is not None and getattr(self.inner, "reusable_batches",
                                        False):
            batches = live
        else:
            batches = list(self.inner.batches())
        with self._lock:
            if self._resident is None:
                self._resident = batches
            else:
                batches = self._resident
        nbytes = _host_bytes(batches)
        LEDGER.pin(
            self.fingerprint, nbytes=nbytes, owner=f"pin.{self.name}",
            on_evict=self._drop, artifact=self,
        )
        METRICS.add("serve.tables_pinned")
        recorder.record("serve.pin", table=self.name, bytes=nbytes,
                        batches=len(batches))
        cb = self.on_change
        if cb is not None:
            cb()
        return True

    def _drop(self) -> None:
        """Ledger eviction hook: release the resident batches and the
        per-core shared state whose batch-keyed caches just became
        unreachable.  The batches' derived-value caches are cleared
        explicitly: an in-memory inner source holds the SAME batch
        objects, so without the clear their device copies would stay
        referenced (and resident) past the eviction."""
        with self._lock:
            res, self._resident = self._resident, None
            self._shared.clear()
        if res is not None:
            for b in res:
                b.cache.clear()
        from datafusion_tpu.obs.attribution import forget_pin

        forget_pin(self.fingerprint)
        METRICS.add("serve.tables_evicted")
        recorder.record("serve.evict", table=self.name)
        cb = self.on_change
        if cb is not None:
            cb()

    @property
    def resident(self) -> bool:
        return self._resident is not None

    def batches(self):
        res = self._resident
        if res is not None:
            # snapshot: the resident list may be an appendable source's
            # live list — a concurrent append must not extend a scan
            # that already started (consistent-cut reads)
            return iter(list(res))
        return self.inner.batches()

    def shared_state_for(self, core, cols=None) -> dict:
        """The cross-query execution state of the relations over this
        table (`SharedScanState`); `cols` maps a projected scan's
        column positions to the table's."""
        return self._state_for(core, cols)


class _PinnedProjection(DataSource):
    """Column projection over a PinnedSource that PRESERVES batch
    identity (``datasource.project_batches``, the code
    ``MemoryDataSource.with_projection`` runs): projected views are
    cached on the parent batches, so the device copies uploaded against
    a projection survive re-scans and other queries, through either
    door."""

    def __init__(self, parent: PinnedSource, cols: list):
        self.parent = parent
        self.cols = cols
        self._schema = parent.schema.select(cols)

    @property
    def schema(self):
        return self._schema

    @property
    def reusable_batches(self) -> bool:
        return self.parent.reusable_batches

    def with_projection(self, projection):
        return _PinnedProjection(
            self.parent, [self.cols[i] for i in projection]
        )

    def to_meta(self) -> dict:
        return self.parent.inner.with_projection(self.cols).to_meta()

    def shared_state_for(self, core) -> dict:
        return self.parent.shared_state_for(core, self.cols)

    def batches(self):
        from datafusion_tpu.exec.datasource import project_batches

        return project_batches(self.parent.batches(), self.cols)


def _host_bytes(batches) -> int:
    total = 0
    for b in batches:
        for arr in list(b.data) + list(b.validity):
            if isinstance(arr, np.ndarray):
                total += arr.nbytes
    return total


class Server:
    """The serving front door over one ``ExecutionContext``.

    Lifecycle: ``start()`` spins the dispatcher event loop on a daemon
    thread; ``submit(sql)`` returns a `Ticket`; ``stop()`` drains (by
    shedding) and shuts the loop down.  Also usable as a context
    manager.  See the module docstring for the admission, pinning, and
    megabatching semantics.
    """

    def __init__(self, ctx, workers: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 window_s: Optional[float] = None,
                 megabatch_max: Optional[int] = None,
                 pin: Optional[bool] = None,
                 default_deadline_s: Optional[float] = None,
                 pin_manifest: Optional[str] = None,
                 shares: Optional[dict] = None):
        from datafusion_tpu import qos as qos_mod
        from datafusion_tpu.analysis import lockcheck
        from datafusion_tpu.utils.eventloop import ServerLoop

        self.ctx = ctx
        self._workers = workers or _env_int("DATAFUSION_TPU_SERVE_WORKERS", 2)
        self._queue_depth = queue_depth or _env_int(
            "DATAFUSION_TPU_SERVE_QUEUE", 64
        )
        self._window_s = (
            window_s if window_s is not None
            else _env_float("DATAFUSION_TPU_SERVE_WINDOW_MS", 2.0) / 1e3
        )
        # adaptive window (datafusion_tpu/cost): an explicitly
        # configured window — kwarg or env — is a contract and stays
        # fixed; the default adapts to the observed arrival spacing
        self._window_adaptive = (
            window_s is None
            and "DATAFUSION_TPU_SERVE_WINDOW_MS" not in os.environ
        )
        self._last_arrival_mono: Optional[float] = None
        self._window_noted_s: Optional[float] = None
        self._megabatch_max = (
            megabatch_max if megabatch_max is not None
            else _env_int("DATAFUSION_TPU_SERVE_MEGABATCH", 16)
        )
        if pin is None:
            pin = os.environ.get("DATAFUSION_TPU_SERVE_PIN", "1") != "0"
        self._pin_enabled = bool(pin)
        if default_deadline_s is None:
            default_deadline_s = _env_float(
                "DATAFUSION_TPU_SERVE_DEADLINE_S", 0.0
            ) or None
        self._default_deadline_s = default_deadline_s
        # durable pin manifest (fingerprints + source paths of resident
        # PinnedSources): written atomically on every residency change,
        # re-materialized by `start()` BEFORE the dispatcher runs — a
        # restarted server rejoins warm instead of sending every tenant
        # back through the cold path.  Defaults beside the control
        # plane's WAL when one is configured; unset = off (no new
        # files, byte-identical serving behavior).
        if pin_manifest is None:
            pin_manifest = os.environ.get(
                "DATAFUSION_TPU_SERVE_PIN_MANIFEST")
            if not pin_manifest:
                wal_dir = os.environ.get("DATAFUSION_TPU_WAL_DIR")
                if wal_dir:
                    pin_manifest = os.path.join(
                        wal_dir, "pin_manifest.json")
        self._pin_manifest_path = pin_manifest or None
        self.pins_rehydrated = 0
        # multi-tenant QoS (datafusion_tpu/qos): weighted fair-share
        # window ordering + over-quota shedding.  None unless
        # DATAFUSION_TPU_QOS=1 or `shares=` was passed explicitly —
        # and a None policy is the byte-identical FIFO path
        self._qos = qos_mod.policy_from_config(shares)
        self._loop = ServerLoop(pool_size=self._workers,
                                name="df-tpu-serve")
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._window: list[Ticket] = []          # loop thread only
        self._window_timer = None                # loop thread only
        self._window_span = None                 # loop thread only
        self._lock = lockcheck.make_lock("serve.server")
        self._pending = 0                        # queued, not yet executing
        # queued-but-undispatched tickets, keyed by identity: stop()
        # sheds these synchronously AFTER the loop thread is dead (a
        # loop-side drain callback could be dropped by the shutdown
        # race — the loop exits on its stop event before running
        # pending callbacks)
        self._queued_tickets: dict = {}
        self._service_ewma_s: Optional[float] = None
        # admission counters are process metrics; per-server totals
        # make conservation (admitted + shed == submitted) assertable
        # on one instance
        self.submitted = 0
        self.admitted = 0
        self.shed = 0

    # -- lifecycle -----------------------------------------------------
    def start(self) -> "Server":
        if self._thread is None:
            # pins re-materialize BEFORE the dispatcher thread exists:
            # a restarted worker advertises ready only after its tables
            # are warm again
            self._rehydrate_pins()
            self._thread = threading.Thread(
                target=self._loop.run, name="df-tpu-serve", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._loop.stop()
        if self._thread is not None:
            self._loop.wait_stopped()
            self._thread = None
        # the loop thread is dead: every ticket still registered as
        # queued (in the window, or in a dropped _enqueue callback)
        # gets a prompt shutdown shed instead of hanging its client.
        # The registration map is NOT cleared here — _shed_ticket's
        # pop is the exactly-once guard, and an executor thread
        # (shut down with wait=False) may still be admitting or
        # deadline-shedding the same tickets concurrently
        with self._lock:
            stranded = list(self._queued_tickets.values())
        for t in stranded:
            if not t.done:
                self._shed_ticket(t, "shutdown")
        self._loop.close()

    def __enter__(self) -> "Server":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- admission (caller thread) -------------------------------------
    def submit(self, sql: str, deadline_s: Optional[float] = None,
               client_id: Optional[str] = None) -> Ticket:
        """Admit one SQL query.  Returns a `Ticket`; raises
        `QueryShedError` when admission refuses it (the counted,
        flight-recorded backpressure decision).  ``client_id`` is the
        metering identity: every shared cost this query incurs —
        launch shares, H2D bytes, pin residency, hedge duplicates —
        apportions back to it (``tenant.<id>.*`` gauges,
        ``/debug/tenants``); unset, costs pool under ``"default"``."""
        qid = next(QUERY_IDS)
        with METRICS.timer("serve.submit", qid=qid):
            return self._submit(sql, deadline_s, client_id, qid)

    def _submit(self, sql: str, deadline_s: Optional[float],
                client_id: Optional[str], qid: int) -> Ticket:
        from datafusion_tpu.errors import NotSupportedError
        from datafusion_tpu.sql import ast
        from datafusion_tpu.sql.parser import parse_sql

        entry_mono = time.monotonic()
        client = str(client_id) if client_id else "default"
        with METRICS.timer("parse"):
            stmt = parse_sql(sql)
        if isinstance(stmt, ast.SqlCreateExternalTable):
            # DDL is control-plane work: run inline, fulfill instantly
            # (not counted as submitted — only queries enter the
            # admitted + shed == submitted conservation)
            out = self.ctx._execute_ddl(stmt)
            t = Ticket(sql, None, None, None, client_id=client)
            t._fulfill(out)
            return t
        if isinstance(stmt, ast.SqlCreateMaterializedView):
            # also DDL-shaped, but the initial build folds the table's
            # current batches through the view core — charge that
            # launch to the registering client like any other work
            from datafusion_tpu.exec.context import DdlResult
            from datafusion_tpu.obs.attribution import client_scope

            with client_scope(client):
                view = self.ingest().create_view(stmt.name, stmt.query_sql)
            t = Ticket(sql, None, None, None, client_id=client)
            t._fulfill(DdlResult(
                f"Registered materialized view {stmt.name} "
                f"({'incremental' if view.incremental else 'recompute'})"))
            return t
        if isinstance(stmt, ast.SqlExplain):
            raise NotSupportedError(
                "EXPLAIN is an interactive statement; run it on the "
                "context, not the serving front door"
            )
        # planning may raise (unknown table, unsupported SQL): a
        # statement that never planned never entered admission, so it
        # counts in NEITHER side of admitted + shed == submitted
        plan = self.ctx._plan(stmt)
        with self._lock:
            self.submitted += 1
        if self._closed:
            raise self._shed_submit(sql, "shutdown", client)

        # 1. deadline feasibility
        deadline = None
        budget = (deadline_s if deadline_s is not None
                  else self._default_deadline_s)
        if budget is not None:
            ewma = self._service_ewma_s
            if budget <= 0 or (ewma is not None and budget < 0.5 * ewma):
                raise self._shed_submit(sql, "deadline", client)
            deadline = Deadline.after(budget)
        # 2. HBM headroom (capacity known, table not yet resident)
        reason = self._check_hbm(plan)
        if reason is not None:
            raise self._shed_submit(sql, reason, client)

        ticket = Ticket(sql, plan, deadline, self._mega_signature(plan),
                        client_id=client, entry_mono=entry_mono, qid=qid)
        # 3. queue depth — checked and RESERVED in one lock acquisition
        # (a read-then-increment across two acquisitions would let N
        # concurrent submitters all pass a depth-1 check), re-checking
        # closed so a racing stop() can't strand a just-registered
        # ticket after its shutdown drain ran
        closed = False
        with self._lock:
            at_depth = self._pending >= self._queue_depth
            if not at_depth:
                self._pending += 1
                self._queued_tickets[id(ticket)] = ticket
                closed = self._closed
                METRICS.gauge("serve.queue_depth", self._pending)
        if at_depth and self._qos is not None:
            # weighted fair shedding: the queue is full, so the tenant
            # furthest over its share pays.  Either a queued victim of
            # the over-quota tenant sheds (freeing the slot for this
            # arrival), or — when the submitter itself is the most
            # over-quota — the arrival sheds with the dedicated
            # "quota" reason and nothing queued is disturbed.  The
            # victim goes through _shed_ticket's exactly-once pop, so
            # admitted + shed == submitted is untouched
            with self._lock:
                queued = list(self._queued_tickets.values())
            victim, incoming_is_victim = self._qos.shed_victim(
                queued, client)
            if incoming_is_victim or victim is None:
                raise self._shed_submit(sql, "quota", client)
            self._shed_ticket(victim, "quota")
            # re-run the reservation for the freed slot; a racing
            # submitter may win it — then this arrival sheds "queue"
            # like any other full-queue refusal
            with self._lock:
                at_depth = self._pending >= self._queue_depth
                if not at_depth:
                    self._pending += 1
                    self._queued_tickets[id(ticket)] = ticket
                    closed = self._closed
                    METRICS.gauge("serve.queue_depth", self._pending)
        if at_depth:
            raise self._shed_submit(sql, "queue", client)
        if closed:
            self._shed_ticket(ticket, "shutdown")
            # a racing stop() drain may have won the shed (the pop is
            # the exactly-once guard) and not yet written the error —
            # the refusal itself must not depend on who shed first
            raise ticket._error if ticket._error is not None else \
                QueryShedError(
                    f"query shed at admission (shutdown): {sql[:80]!r}",
                    reason="shutdown",
                )
        ticket.admitted_mono = time.monotonic()
        METRICS.add("queries_queued")
        recorder.record("serve.queued", plan=type(plan).__name__,
                        client=client)
        self._loop.call_soon(partial(self._enqueue, ticket))
        return ticket

    # -- streaming ingestion (caller thread) ---------------------------
    def ingest(self):
        """The ingest plane behind this server (lazy): the context's
        `IngestContext` with the serving hook installed — applied
        appends grow the HBM-pinned resident copy's ledger accounting
        and re-save the pin manifest."""
        ing = self.ctx.ingest()
        if self._on_append_applied not in ing.on_applied:
            ing.on_applied.append(self._on_append_applied)
        return ing

    def append(self, table: str, columns: dict,
               client_id: Optional[str] = None) -> dict:
        """Streaming append through the front door — durable-then-
        applied (`IngestContext.append` contract: a WAL fault raises
        `IngestUnavailableError` with nothing acknowledged).  View-
        maintenance launches this delta triggers are charged to
        ``client_id`` through the metering scope, exactly like query
        launches."""
        from datafusion_tpu.obs.attribution import client_scope

        client = str(client_id) if client_id else "default"
        with client_scope(client):
            return self.ingest().append(table, columns, client=client)

    def _on_append_applied(self, table: str, batch) -> None:
        """Post-apply ingest hook: the pinned resident list already
        grew in place (it IS the appendable's live batch list after
        `splice_appendable`), so only the ledger's pin accounting and
        the durable manifest need refreshing."""
        ds = self.ctx.datasources.get(table)
        if isinstance(ds, _PinnedProjection):
            ds = ds.parent
        if not isinstance(ds, PinnedSource) or not ds.resident:
            return
        res = ds._resident
        if res is not None:
            LEDGER.set_pin_bytes(ds.fingerprint, _host_bytes(res))
        METRICS.add("serve.pin_appends")
        cb = ds.on_change
        if cb is not None:
            cb()

    def _shed_submit(self, sql: str, reason: str,
                     client: str = "default") -> QueryShedError:
        from datafusion_tpu.obs.attribution import METER

        with self._lock:
            self.shed += 1
        METRICS.add("queries_shed")
        METER.charge(client, "shed", 1.0)
        if self._qos is not None:
            # per-tenant, per-reason shed meter (tenant.<id>.shed_quota
            # and kin on the scrape) — QoS-only so the off path's
            # tenant gauge set stays byte-identical
            METER.charge(client, f"shed_{reason}", 1.0)
        recorder.record("serve.shed", reason=reason, client=client)
        return QueryShedError(
            f"query shed at admission ({reason}): {sql[:80]!r}",
            reason=reason,
        )

    def _shed_ticket(self, t: Ticket, reason: str) -> None:
        """Shed a ticket that already passed queue-depth reservation.
        IDEMPOTENT per ticket: the registration pop is the guard — a
        stop()-time drain racing an executor-side deadline shed (the
        loop's executor shuts down with wait=False, so _run_group can
        still be running) must count the shed and release the queue
        slot exactly ONCE, or ``self._pending`` (the live queue-depth
        gauge ``queries_queued`` feeds) goes negative and conservation
        breaks."""
        from datafusion_tpu.obs.attribution import METER

        with self._lock:
            if self._queued_tickets.pop(id(t), None) is None:
                return  # already shed or already admitted elsewhere
            self.shed += 1
            self._pending -= 1
            METRICS.gauge("serve.queue_depth", self._pending)
        METRICS.add("queries_shed")
        METER.charge(t.client_id, "shed", 1.0)
        if self._qos is not None:
            METER.charge(t.client_id, f"shed_{reason}", 1.0)
        recorder.record("serve.shed", reason=reason, queued=True,
                        client=t.client_id)
        t._fail(QueryShedError(
            f"query shed after queueing ({reason}): {t.sql[:80]!r}",
            reason=reason,
        ))

    def _check_hbm(self, plan) -> Optional[str]:
        """Shed reason "hbm" when a cold table cannot fit the measured
        headroom even after priority eviction; None to admit.  The
        plan's own already-resident tables are protected from the
        eviction pass — evicting them to admit the query that scans
        them would overshoot the cap AND force the cold re-scan
        pinning exists to avoid."""
        if not self._pin_enabled:
            return None
        headroom = LEDGER.headroom()
        if headroom is None:
            return None  # capacity unknown: stay dormant, never guess
        from datafusion_tpu.cache import scan_tables

        need = 0
        protected: list[str] = []
        for tbl in scan_tables(plan):
            ds = self.ctx.datasources.get(tbl)
            if ds is None:
                continue
            pin = ds.parent if isinstance(ds, _PinnedProjection) else ds
            if isinstance(pin, PinnedSource) and pin.resident:
                protected.append(pin.fingerprint)
                continue  # already resident: no new bytes
            est = (pin.estimated_bytes()
                   if isinstance(pin, PinnedSource)
                   else PinnedSource(ds, tbl).estimated_bytes())
            need += est
        if need == 0 or need <= headroom:
            return None
        freed = LEDGER.evict_pins(need - headroom, exclude=protected)
        headroom = LEDGER.headroom()
        if headroom is not None and need > headroom:
            recorder.record("serve.hbm_pressure", need=need,
                            headroom=headroom, freed=freed)
            return "hbm"
        return None

    # -- dispatch (loop thread) ----------------------------------------
    def _enqueue(self, t: Ticket) -> None:
        t.enqueued_mono = time.monotonic()
        # arrival spacing feeds the adaptive window (cost/advisor):
        # loop-thread only, lock-free observe into the cost store
        prev = self._last_arrival_mono
        self._last_arrival_mono = t.enqueued_mono
        if prev is not None:
            from datafusion_tpu import cost as _cost

            _cost.store().observe(
                _cost.SERVE_KEY, "arrivals",
                interval_s=min(t.enqueued_mono - prev, 60.0),
            )
        self._window.append(t)
        if len(self._window) >= max(self._megabatch_max, 1):
            # size-triggered early flush: the window is a MAXIMUM wait,
            # not a fixed tick — a full megabatch's worth of queries
            # dispatches immediately, so closed-loop clients never idle
            # against the timer
            if self._window_timer is not None:
                self._window_timer.cancel()
            self._flush_window()
            return
        if self._window_timer is None:
            self._window_timer = self._loop.call_later(
                self._effective_window_s(), self._flush_window
            )
            # the batching window as a stage timer, once a flush: it
            # opens here and closes in `_flush_window`, both on the
            # loop thread, so what the loop runs meanwhile nests in it
            self._window_span = METRICS.timer("serve.window")
            self._window_span.__enter__()

    def _effective_window_s(self) -> float:
        """The megabatch wait actually armed: the configured window,
        or — when it was left at its default and the cost subsystem is
        on — the learned window from observed arrival spacing (don't
        hold a lone query 2 ms for peers that historically never come;
        stretch a little when arrivals are dense).  Decision recorded
        on change, not per timer."""
        from datafusion_tpu import cost as _cost

        if not self._window_adaptive or not _cost.enabled():
            return self._window_s
        from datafusion_tpu.cost import advisor

        store = _cost.store()
        chosen = advisor.serve_window_s(store, self._window_s)
        if chosen != self._window_s and chosen != self._window_noted_s:
            self._window_noted_s = chosen
            store.note_decision(
                "serve.window_ms", round(chosen * 1e3, 3),
                round(self._window_s * 1e3, 3),
                "observed arrival spacing "
                f"{(store.value(_cost.SERVE_KEY, 'arrivals', 'interval_s') or 0) * 1e3:.2f} ms",
            )
        return chosen

    def _flush_window(self) -> None:
        self._window_timer = None
        span, self._window_span = self._window_span, None
        if span is not None:
            span.__exit__(None, None, None)
        if not self._window:
            return
        batch, self._window = self._window, []
        if self._qos is not None and len(batch) > 1:
            # weighted fair drain: the flushed window re-orders so each
            # tenant's backlog advances in proportion to its configured
            # share (deadline urgency breaks ties within a tenant);
            # with QoS off the FIFO arrival order is untouched
            batch = self._qos.order(batch,
                                    unit_cost_s=self._service_ewma_s)
        now = time.monotonic()
        groups: dict = {}
        singles: list[list[Ticket]] = []
        for t in batch:
            t.flushed_mono = now
        for t in batch:
            if t.signature is None:
                singles.append([t])
            else:
                groups.setdefault(t.signature, []).append(t)
        work = singles + list(groups.values())
        for group in work:
            self._loop.defer(partial(self._run_group, group),
                             self._group_done)

    @staticmethod
    def _group_done(result, exc) -> None:
        if exc is not None:
            # _run_group fails tickets itself; an escape here is a bug
            # in the dispatcher, not a query error
            METRICS.add("serve.dispatch_errors")

    def _mega_signature(self, plan):
        """The cross-query shape class (the PR 6 ``entry_signature``
        idea lifted to plans): same table, same plan shape with
        literals parameterized away.  Queries sharing a signature lower
        to the same compiled core and are megabatch candidates; None =
        not a megabatchable shape (executes solo)."""
        if self._megabatch_max < 2:
            return None
        from datafusion_tpu.exec.kernels import parameterize_exprs
        from datafusion_tpu.plan.logical import (
            Aggregate,
            Limit,
            Projection,
            Selection,
            Sort,
            TableScan,
        )

        if isinstance(plan, Aggregate):
            inner = plan.input
            pred = None
            if isinstance(inner, Selection):
                pred, inner = inner.expr, inner.input
            if not isinstance(inner, TableScan):
                return None
            try:
                exprs = ([pred] if pred is not None else []) + list(
                    plan.aggr_expr
                )
                fps, _, _ = parameterize_exprs(exprs)
            except Exception:  # noqa: BLE001 — unparameterizable plan: solo lane
                return None
            proj = (None if inner.projection is None
                    else tuple(inner.projection))
            return (
                "agg", inner.table_name,
                self.ctx.catalog_version(inner.table_name), proj,
                tuple(repr(g) for g in plan.group_expr), tuple(fps),
                pred is None,
            )
        if isinstance(plan, Limit) and isinstance(plan.input, Sort):
            # ORDER BY ... LIMIT k shape class: the streaming TopK fold
            # megabatches when queries share key plans over one table
            # with no predicate (a per-query predicate would fork the
            # shared fold's mask operand per query).  LIMIT values may
            # differ — the multi-query fold takes a per-query capacity.
            from datafusion_tpu.exec.sort import TOPK_MAX

            if not (0 < plan.limit <= TOPK_MAX):
                return None
            sort = plan.input
            inner = sort.input
            proj_fps = None
            if isinstance(inner, Projection):
                try:
                    proj_fps, _, _ = parameterize_exprs(list(inner.expr))
                except Exception:  # noqa: BLE001 — unparameterizable plan: solo lane
                    return None
                proj_fps, inner = tuple(proj_fps), inner.input
            if not isinstance(inner, TableScan):
                return None
            scan_proj = (None if inner.projection is None
                         else tuple(inner.projection))
            return (
                "topk", inner.table_name,
                self.ctx.catalog_version(inner.table_name), scan_proj,
                proj_fps,
                tuple((repr(se.expr), se.asc) for se in sort.expr),
            )
        if isinstance(plan, (Projection, Selection)):
            # filter/project shape class: per-query literals ride the
            # shared pipeline core's parameter slots, so `WHERE x > ?`
            # variants share one scan and one launch per batch group
            inner = plan
            proj_exprs = None
            if isinstance(inner, Projection):
                proj_exprs, inner = inner.expr, inner.input
            pred = None
            if isinstance(inner, Selection):
                pred, inner = inner.expr, inner.input
            if not isinstance(inner, TableScan):
                return None
            try:
                exprs = ([pred] if pred is not None else []) + list(
                    proj_exprs or []
                )
                fps, _, _ = parameterize_exprs(exprs)
            except Exception:  # noqa: BLE001 — unparameterizable plan: solo lane
                return None
            scan_proj = (None if inner.projection is None
                         else tuple(inner.projection))
            return (
                "pipe", inner.table_name,
                self.ctx.catalog_version(inner.table_name), scan_proj,
                tuple(fps), pred is None, proj_exprs is None,
            )
        return None

    # -- execution (executor threads) ----------------------------------
    def _run_group(self, group: list[Ticket]) -> None:
        with METRICS.timer("serve.group",
                           qids=" ".join(str(t.qid) for t in group)):
            rest = self._run_shared(group)
        # per-ticket materialization fans back out over the executor
        # pool: finalizes of THIS window overlap the next window's
        # megabatch scan instead of serializing behind it, and each
        # client unblocks as soon as ITS result is ready
        for t in rest[1:]:
            self._loop.defer(partial(self._finish, t), self._group_done)
        if rest:
            self._finish(rest[0])

    def _run_shared(self, group: list[Ticket]) -> list[Ticket]:
        """What one flushed group's tickets share on a worker: residency,
        lowering, and the megabatched pass of those that fuse.  Returns
        the tickets to finish, each on its own."""
        from datafusion_tpu.cache import scan_tables
        from datafusion_tpu.exec.aggregate import force_core_predicate
        from datafusion_tpu.obs.attribution import client_scope

        exec_start = time.monotonic()
        ready: list[Ticket] = []
        for t in group:
            t.exec_start_mono = exec_start
            if t.deadline is not None and t.deadline.expired:
                self._shed_ticket(t, "deadline")
                continue
            ready.append(t)
        if not ready:
            return []
        if self._pin_enabled:
            for t in ready:
                for tbl in scan_tables(t.plan):
                    self._ensure_resident(tbl, client_id=t.client_id)
        # lower every plan to a relation (counts queries_admitted)
        executed: list[Ticket] = []
        megabatchable = any(t.signature is not None for t in ready)
        for t in ready:
            admitted = False
            with self._lock:
                if self._queued_tickets.pop(id(t), None) is not None:
                    self._pending -= 1
                    METRICS.gauge("serve.queue_depth", self._pending)
                    # per-server mirror of the queries_admitted
                    # counter's semantics (counted at execute entry,
                    # errors included) so conservation is assertable
                    # on one instance.  Gated on the registration pop:
                    # a stop()-time shutdown shed that beat us here
                    # already counted this ticket on the shed side
                    self.admitted += 1
                    admitted = True
            if not admitted:
                continue  # shed concurrently (shutdown drain won)
            recorder.record("serve.admit", client=t.client_id,
                            plan=type(t.plan).__name__)
            try:
                with deadline_scope(t.deadline), \
                        client_scope(t.client_id):
                    if megabatchable and t.signature is not None:
                        with force_core_predicate():
                            t._rel = self.ctx.execute(t.plan)
                    else:
                        t._rel = self.ctx.execute(t.plan)
                executed.append(t)
            except BaseException as e:  # noqa: BLE001 — delivered to the client
                t._fail(e)
        # split megabatch-eligible aggregates from the rest
        mega_by_core: dict = {}
        rest: list[Ticket] = []
        for t in executed:
            key = self._mega_key(t._rel)
            if key is None:
                rest.append(t)
            else:
                mega_by_core.setdefault(key, []).append(t)
        for ts in mega_by_core.values():
            while len(ts) > 1:
                sub, ts = ts[: self._megabatch_max], ts[self._megabatch_max:]
                if len(sub) < 2:
                    rest.extend(sub)
                    continue
                try:
                    self._run_megabatch(sub)
                except Exception:  # noqa: BLE001 — megabatch is an optimization; serial is the answer path
                    METRICS.add("serve.megabatch_fallbacks")
                    for t in sub:
                        t._rel.__dict__.pop("_injected_state", None)
                        t._rel.__dict__.pop("_injected_topk", None)
                        t._rel.__dict__.pop("_injected_batches", None)
                rest.extend(sub)
            rest.extend(ts)
        return rest

    def _member_weights(self, tickets: list) -> list:
        """Per-member megabatch cost weights from REAL scan row
        counts: each member weighs by the total rows of the tables its
        plan scans (the cost store's `scan` observations, learned from
        earlier passes — the same statistics the planner consults).
        A member whose join also reads a dimension table therefore
        carries its extra rows; members touching only the shared scan
        split evenly, and unknown cardinalities (first pass over a
        table) fall back to the even split — never a zero weight."""
        from datafusion_tpu.cache import scan_tables

        from datafusion_tpu import cost as _cost
        from datafusion_tpu.cost import advisor

        store = _cost.store()
        counts = []
        for t in tickets:
            try:
                known = [
                    advisor.table_rows(
                        store, self.ctx.cost_table_key(n))
                    for n in scan_tables(t.plan)
                ]
            except Exception:  # noqa: BLE001 — weighting must not fail a query
                known = []
            rows = sum(k for k in known if k)
            counts.append(rows if rows and all(known) else None)
        if any(c is None for c in counts):
            return [1.0 / len(tickets)] * len(tickets)
        total = float(sum(counts))
        return [c / total for c in counts]

    def _note_table_rows(self, table: str, rows: int) -> None:
        if table and rows > 0:
            from datafusion_tpu import cost as _cost

            try:
                _cost.store().observe(
                    self.ctx.cost_table_key(table), "scan", rows=int(rows))
            except Exception:  # noqa: BLE001 — stats must not fail serving
                pass

    def _mega_key(self, rel):
        """Concrete megabatch grouping key for an already-lowered
        relation — stricter than the plan signature: the relations must
        share one compiled core (identity) over one table scan, with
        the predicate in the core (no per-query host masks)."""
        from datafusion_tpu.exec.aggregate import AggregateRelation
        from datafusion_tpu.exec.relation import (
            DataSourceRelation,
            PipelineRelation,
        )
        from datafusion_tpu.exec.sort import TOPK_MAX, SortRelation

        if self._megabatch_max < 2:
            return None
        if type(rel) is SortRelation:
            # streaming TopK lane: no fused predicate (the shared fold
            # has ONE mask operand per batch), LIMIT within the TopK
            # window, straight over the scan.  Wide-path eligibility
            # (host-imaged f64 keys) is per-batch — the runner raises
            # mid-scan and the group falls back to solo.
            if rel.predicate is not None:
                return None
            if rel.limit is None or not (0 < rel.limit <= TOPK_MAX):
                return None
            if not isinstance(rel.child, DataSourceRelation):
                return None
            return ("topk", id(rel.core), rel.child.table_name)
        if type(rel) is PipelineRelation:
            # filter/project lane: the predicate must live in the core
            # (per-query literals in params — no per-query host masks)
            # and there must BE device work to share
            if rel._host_pred_expr is not None or not rel.core.needs_kernel:
                return None
            if not isinstance(rel.child, DataSourceRelation):
                return None
            return ("pipe", id(rel.core), rel.child.table_name)
        if type(rel) is not AggregateRelation:
            return None
        if rel._host_pred_expr is not None:
            return None
        child = rel.child
        if not isinstance(child, DataSourceRelation):
            return None
        return (id(rel.core), child.table_name)

    def _run_megabatch(self, tickets: list[Ticket]) -> None:
        """ONE scan, ONE launch per batch group, N queries' states: the
        cross-query fused pass.  Preconditions (``_mega_key``): every
        ticket's relation shares ``tickets[0]._rel.core`` and scans the
        same table.

        Cost apportionment (obs/attribution.py): the whole pass runs
        under a ``shared_scope`` whose members are the tickets'
        clients weighted by REAL scan row counts
        (``_member_weights``): every member consumes the shared scan,
        but a member whose plan ALSO reads other tables (a join's
        dimension side) carries those rows in its weight.  Launch
        walls measured in ``device_call`` and H2D bytes at the ledger
        seam split by those weights automatically; the blob-packed
        demux pull is timed here and split the same way.  Each
        ticket's ``launch_share_s`` / ``demux_share_s`` record its
        share for the critical-path segments."""
        from datafusion_tpu.exec.aggregate import group_capacity
        from datafusion_tpu.exec.expression import compute_aux_values
        from datafusion_tpu.exec.fused import (
            bucket_group,
            fuse_group_max,
            iter_groups,
            pad_group,
        )
        from datafusion_tpu.exec.relation import PipelineRelation, device_scope
        from datafusion_tpu.exec.sort import SortRelation
        from datafusion_tpu.obs.attribution import shared_scope
        from datafusion_tpu.obs.stats import iter_stats
        from datafusion_tpu.utils.retry import device_call

        if type(tickets[0]._rel) is SortRelation:
            return self._run_megabatch_topk(tickets)
        if type(tickets[0]._rel) is PipelineRelation:
            return self._run_megabatch_pipeline(tickets)
        rels = [t._rel for t in tickets]
        weights = self._member_weights(tickets)
        members = tuple(
            (t.client_id, w) for t, w in zip(tickets, weights)
        )
        leader = rels[0]
        core = leader.core
        for r in rels:
            r._adopt_source_state()
            if r is not leader:
                # one encoder/caches for the whole group even when the
                # table is not pinned (cold megabatch): ids must agree
                r._share_state_of(leader)

        n_live = len(rels)
        n_q = bucket_group(n_live)
        params = tuple(r._params for r in rels)
        params += (params[0],) * (n_q - n_live)  # query-axis padding
        device = leader.device
        fuse = fuse_group_max()
        states: Optional[list] = None
        capacity = 0
        chunk: list = []

        def flush():
            nonlocal states, capacity
            if not chunk:
                return
            needed = leader._pick_capacity(capacity)
            if states is None:
                capacity = needed
                init = core._init_state(capacity)
                states = [init] * n_live
            elif needed > capacity:
                states = [core._grow_state(s, needed) for s in states]
                capacity = needed
            entries = [(c[0], c[1], c[3], c[4], c[5]) for c in chunk]
            shareds = [(c[2], c[6]) for c in chunk]
            for idxs, (aux, str_aux) in iter_groups(entries, shareds):
                egroup = pad_group(
                    [entries[i] for i in idxs],
                    lambda e: (e[0], e[1], np.int32(0), e[3], e[4]),
                )
                st_in = tuple(states) + (states[0],) * (n_q - n_live)
                with METRICS.timer("execute.serve_megabatch"), \
                        device_scope(device):
                    out = device_call(
                        core.multi_group_jit, tuple(egroup), st_in, aux,
                        str_aux, params, _tag="serve.megabatch",
                    )
                states = list(out[:n_live])
                METRICS.add("serve.megabatch_launches")
                METRICS.add("serve.megabatch_queries", n_live)
            chunk.clear()

        rows_seen = 0
        with shared_scope(members) as launch_acc:
            for batch in iter_stats(leader.child):
                rows_seen += batch.num_rows
                for idx in core.key_cols:
                    if batch.dicts[idx] is not None:
                        leader._key_dicts[idx] = batch.dicts[idx]
                ids = leader._group_ids(batch)
                staged = batch.cache.get("staged_aux")
                if staged is not None and staged[0] is core:
                    aux = tuple(staged[1])
                    str_aux = staged[2] if len(staged) > 2 else \
                        leader._compute_str_aux(batch, core.slots)
                else:
                    aux = tuple(compute_aux_values(
                        core.aux_specs, batch, leader._aux_cache
                    ))
                    str_aux = leader._compute_str_aux(batch, core.slots)
                with device_scope(device):
                    data, validity, mask = leader._device_inputs(batch, core)
                chunk.append((data, validity, aux,
                              np.int32(batch.num_rows),
                              mask, ids, str_aux))
                if len(chunk) >= fuse:
                    flush()
            flush()
            if states is None:
                states = [core._init_state(group_capacity(1))] * n_live
            else:
                # ONE blob-packed pull for every query's accumulator
                # state: N separate finalize-time pulls would pay N
                # pack launches and N link round trips — the
                # de-multiplex ships as one transfer and finalize
                # slices numpy
                from datafusion_tpu.exec.batch import device_pull

                pull_t0 = time.perf_counter()
                states = list(device_pull(tuple(states)))
                pull_s = time.perf_counter() - pull_t0
                for t, w in zip(tickets, weights):
                    t.demux_share_s += pull_s * w
        # next window's weights see what this pass actually scanned
        self._note_table_rows(leader.child.table_name, rows_seen)
        # the scope's accumulator measured every launch wall the pass
        # dispatched (device_call's own measurement — the same number
        # the meter charged, split by the same weights): each ticket's
        # critical path gets its apportioned share
        for t, w in zip(tickets, weights):
            t.launch_share_s += launch_acc[0] * w
        for r, s in zip(rels, states):
            if r is not leader:
                r._key_dicts.update(leader._key_dicts)
                r._str_dicts.update(leader._str_dicts)
            r._injected_state = s

    def _run_megabatch_topk(self, tickets: list[Ticket]) -> None:
        """ONE scan, N TopK queries (`exec.sort.run_topk_megabatch` —
        the `_run_megabatch` twin for ORDER BY ... LIMIT shapes).
        Cost apportionment matches the aggregate lane: the pass runs
        under a shared scope with real scan-row weights
        (``_member_weights``), launch walls split by device_call's own
        measurement, and the single blob-packed result pull splits as
        each ticket's demux share.  Each relation receives
        ``_injected_topk``; its `batches()` then skips the scan and
        runs only the host payload gather."""
        from datafusion_tpu.exec.sort import run_topk_megabatch
        from datafusion_tpu.obs.attribution import shared_scope

        weights = self._member_weights(tickets)
        members = tuple(
            (t.client_id, w) for t, w in zip(tickets, weights)
        )
        with shared_scope(members) as launch_acc:
            pull_s = run_topk_megabatch([t._rel for t in tickets])
        for t, w in zip(tickets, weights):
            t.launch_share_s += launch_acc[0] * w
            t.demux_share_s += pull_s * w

    def _run_megabatch_pipeline(self, tickets: list[Ticket]) -> None:
        """ONE scan, N filter/project queries
        (`exec.relation.run_pipeline_megabatch`): per-query literals
        ride the shared core's parameter slots, so `WHERE x > ?`
        variants share every upload and every launch.  The demux is
        per-query finalize-time pulls (attributed per client there),
        so only launch walls apportion here."""
        from datafusion_tpu.exec.relation import run_pipeline_megabatch
        from datafusion_tpu.obs.attribution import shared_scope

        weights = self._member_weights(tickets)
        members = tuple(
            (t.client_id, w) for t, w in zip(tickets, weights)
        )
        with shared_scope(members) as launch_acc:
            run_pipeline_megabatch([t._rel for t in tickets])
        for t, w in zip(tickets, weights):
            t.launch_share_s += launch_acc[0] * w

    def _finish(self, t: Ticket) -> None:
        """Materialize one ticket's relation and fulfill it (the
        per-client de-multiplex point for megabatched queries — each
        relation finalizes its OWN state).  Also the attribution
        point: the end-to-end wall decomposes into the canonical
        serving segments from the ticket's stamps + apportioned
        shares, the path feeds the tail explainer, and the serve wall
        — the latency the CLIENT saw, queue wait included — feeds the
        SLO watchdog (the inner materialization wall alone would hide
        exactly the queueing tail serving SLOs exist to catch)."""
        from datafusion_tpu.exec.materialize import collect
        from datafusion_tpu.obs import slo
        from datafusion_tpu.obs.aggregate import observe_latency
        from datafusion_tpu.obs.attribution import (
            client_scope,
            observe_path,
        )

        with METRICS.timer("serve.finish", qid=t.qid):
            try:
                rel = t._rel
                # the ticket's number rides to `collect_columns`' span
                rel._query_id = t.qid
                fin_t0 = time.monotonic()
                with deadline_scope(t.deadline), \
                        client_scope(t.client_id) as launch_acc:
                    table = collect(rel)
                fin_wall = time.monotonic() - fin_t0
                t._fulfill(table)
                t.launch_share_s += launch_acc[0]
                wall = time.monotonic() - t.entry_mono
                observe_latency("serve.latency", wall)
                slo.WATCHDOG.observe(wall)
                segments = self._segments(t, wall, fin_wall, launch_acc[0])
                observe_path(t.client_id, wall, segments)
                # the same account as stage timings, summed over tickets
                # (differences of stamps taken on three threads: no span)
                for name, seconds in segments.items():
                    METRICS.observe("serve.path." + name, seconds)
                METRICS.observe("serve.path.wall", wall)
                ewma = self._service_ewma_s
                self._service_ewma_s = (
                    wall if ewma is None else 0.8 * ewma + 0.2 * wall
                )
                recorder.record("serve.done", ms=round(wall * 1e3, 3),
                                client=t.client_id)
            except BaseException as e:  # noqa: BLE001 — delivered to the client
                METRICS.add("serve.query_errors")
                # the error still counts against error-rate SLOs with the
                # client-visible wall (the funnel's own watchdog feed is
                # suppressed for served queries — see query_completed)
                slo.WATCHDOG.observe(
                    time.monotonic() - t.entry_mono, error=True
                )
                t._fail(e)

    @staticmethod
    def _segments(t: Ticket, wall: float, fin_wall: float,
                  fin_launch_s: float) -> dict:
        """One ticket's canonical critical-path chain (seconds), from
        its lifecycle stamps and apportioned shares:

        - ``admission``: submit entry -> queue-slot reservation
          (parse + plan + feasibility/HBM checks);
        - ``megabatch_window``: parked in the batching window;
        - ``queue_wait``: loop hand-off plus waiting for an executor
          slot behind earlier groups — the segment induced queueing
          grows;
        - ``shared_launch_share``: this query's apportioned slice of
          every launch wall it rode (megabatched or solo);
        - ``demux_pull``: its share of the blob-packed state pull;
        - ``merge``: host-side finalize/materialize minus the launch
          wall already attributed;
        - ``other``: the unaccounted remainder (never negative).
        """
        entry = t.entry_mono
        admitted = t.admitted_mono or entry
        enqueued = t.enqueued_mono or admitted
        flushed = t.flushed_mono or enqueued
        started = t.exec_start_mono or flushed
        seg = {
            "admission": max(admitted - entry, 0.0),
            "megabatch_window": max(flushed - enqueued, 0.0),
            "queue_wait": max(enqueued - admitted, 0.0)
            + max(started - flushed, 0.0),
            "shared_launch_share": t.launch_share_s,
            "demux_pull": t.demux_share_s,
            "merge": max(fin_wall - fin_launch_s, 0.0),
        }
        seg["other"] = max(wall - sum(seg.values()), 0.0)
        return seg

    # -- pinning -------------------------------------------------------
    def _ensure_resident(self, table: str,
                         client_id: str = "default") -> None:
        ds = self.ctx.datasources.get(table)
        if ds is None:
            return
        if isinstance(ds, _PinnedProjection):
            ds = ds.parent
        if not isinstance(ds, PinnedSource):
            pinned = PinnedSource(ds, table)
            # direct slot swap, NOT register_datasource: the data is
            # identical (schema/meta delegate), so catalog versions and
            # cached results must survive the promotion
            self.ctx.datasources[table] = pinned
            ds = pinned
        ds.on_change = self._save_pin_manifest
        newly_resident = not ds.resident
        if newly_resident:
            # pin only when the measured headroom (if known) still
            # covers the estimate — an admission decision made earlier
            # in the window can be stale by dispatch time, and pinning
            # past the cap would overshoot; a denied pin just streams
            # this query cold
            headroom = LEDGER.headroom()
            if headroom is not None and ds.estimated_bytes() > headroom:
                METRICS.add("serve.pin_denied")
                return
        ds.ensure()
        from datafusion_tpu.obs.attribution import (
            note_pin_use,
            register_pin_client,
        )

        if newly_resident:
            # the materializing client is the pin's FALLBACK payer
            # (obs/attribution.py): intervals in which nobody scans the
            # resident still cost somebody — residency is a held cost,
            # not a one-time event
            register_pin_client(ds.fingerprint, client_id)
        # every scan is a use: accrual splits the pin's byte-seconds
        # across the interval's actual readers by these counts
        note_pin_use(ds.fingerprint, client_id)
        # re-attribute the resident batches' cached device copies (and
        # measure them) under the pin's owner tag
        self._retag_pin(ds)

    @staticmethod
    def _retag_pin(pin: PinnedSource) -> None:
        """Re-attribute the resident batches' cached device copies
        under the pin's owner tag and re-measure the pin's accounted
        bytes from what is ACTUALLY device-resident (the pin was
        registered with a host-side estimate before any upload; once
        the first query has populated the caches, eviction accounting
        should reflect the measured residency it would free)."""
        res = pin._resident
        if res is None:
            return
        dev_leaves = []
        for b in res:
            for v in b.cache.values():
                dev_leaves.append(v)
        if not dev_leaves:
            return
        LEDGER.retag(dev_leaves, f"pin.{pin.name}")
        import jax

        measured = sum(
            int(leaf.nbytes)
            for leaf in jax.tree.leaves(dev_leaves)
            if hasattr(leaf, "copy_to_host_async")
        )
        if measured:
            LEDGER.set_pin_bytes(pin.fingerprint, measured)

    # -- pin manifest (durable data plane) -----------------------------
    def _pin_entries(self) -> list:
        out = []
        for table, ds in sorted(self.ctx.datasources.items()):
            if isinstance(ds, _PinnedProjection):
                ds = ds.parent
            if isinstance(ds, PinnedSource) and ds.resident:
                entry = {"table": table, "fingerprint": ds.fingerprint}
                path = getattr(ds.inner, "path", None)
                if path:
                    entry["path"] = str(path)
                out.append(entry)
        return out

    def _save_pin_manifest(self) -> None:
        """Persist the current resident set (atomic tmp -> fsync ->
        rename, so a crash mid-write leaves the old manifest intact).
        Called on every residency change, never under a lock."""
        path = self._pin_manifest_path
        if path is None:
            return
        from datafusion_tpu.utils.wal import atomic_write_json

        try:
            atomic_write_json(path, {"pins": self._pin_entries()})
        except OSError:
            METRICS.add("serve.pin_manifest_errors")

    def _rehydrate_pins(self) -> None:
        """Boot-time pin re-materialization from the manifest: every
        recorded table that is registered in this context gets its
        `_ensure_resident` walk (promotion + materialize + ledger pin)
        before the server starts serving.  Tables the context no longer
        registers — or whose materialization fails — are skipped, not
        fatal: rejoining cold is degraded, not broken."""
        path = self._pin_manifest_path
        if path is None or not self._pin_enabled:
            return
        from datafusion_tpu.utils.wal import read_json

        doc = read_json(path)
        for entry in (doc or {}).get("pins") or []:
            table = str(entry.get("table") or "")
            if not table or table not in self.ctx.datasources:
                METRICS.add("serve.pin_rehydrate_skipped")
                continue
            try:
                self._ensure_resident(table, client_id="rehydrate")
            except Exception:  # noqa: BLE001 — a cold table must not block boot
                METRICS.add("serve.pin_rehydrate_errors")
                continue
            self.pins_rehydrated += 1
            METRICS.add("serve.pins_rehydrated")
            recorder.record("serve.pin_rehydrated", table=table)

    # -- introspection -------------------------------------------------
    def stats(self) -> dict:
        from datafusion_tpu.obs.aggregate import HISTOGRAMS

        counts = METRICS.snapshot()["counts"]
        h = HISTOGRAMS.get("serve.latency")
        with self._lock:
            out = {
                "submitted": self.submitted,
                "shed": self.shed,
                "pending": self._pending,
                "service_ewma_s": self._service_ewma_s,
            }
        out.update({
            "queries_admitted": counts.get("queries_admitted", 0),
            "queries_queued": counts.get("queries_queued", 0),
            "queries_shed": counts.get("queries_shed", 0),
            "megabatch_launches": counts.get(
                "serve.megabatch_launches", 0
            ),
            "megabatch_queries": counts.get("serve.megabatch_queries", 0),
            "tables_pinned": counts.get("serve.tables_pinned", 0),
            "pins": LEDGER.pins_snapshot(),
            "pinned_bytes": LEDGER.pinned_bytes(),
        })
        if h is not None:
            out["p50_s"] = h.quantile(0.5)
            out["p99_s"] = h.quantile(0.99)
            out["queries"] = h.count
        if self._qos is not None:
            out["qos"] = self._qos.snapshot()
        return out
