"""Device data-plane observability: the HBM residency ledger and the
transfer/launch profiler.

PR 8 made the *host* side of the fleet observable; this module is the
instrument panel for the *device* data plane the ROADMAP's next arc
(HBM-pinned serving, cold-path demolition, kernel gates) will be tuned
against.  Three instruments, one module:

- **The ledger** (`LEDGER`): every device placement in the engine goes
  through `LEDGER.put(...)` (the seam replacing raw ``jax.device_put``
  — lint rule DF006 keeps it load-bearing) or registers its outputs
  via `LEDGER.adopt(...)`.  Each tracked buffer records bytes, owner
  tag (table scan, batch cache, mesh state, sort image, ...),
  the placing query's trace id, and its *lifetime* — a
  ``weakref.finalize`` fires when the buffer's Python handle dies, so
  live-bytes and the peak watermark are measured facts, not the
  estimated-peak formula ``benchmarks/suite.py`` used before.  Gauges
  ``device.hbm.live_bytes`` / ``device.hbm.peak_bytes`` ride every
  scrape, `\\hbm` renders the per-owner breakdown, and a leak sweep at
  query completion flags non-cache buffers that outlive their query
  (``device.ledger.leaks`` + a ``device.leak`` flight event).

- **The transfer profiler**: every H2D transfer (timed
  dispatch-to-completion — ``device_put`` is async on accelerators, so
  the put path blocks on the result; see ``DeviceLedger.put``) and D2H
  wait records a trace-correlated flight event (``device.h2d`` /
  ``device.d2h``) with bytes, wall, achieved GB/s, and — when the
  link-rate probe has run — the measured link baseline, plus
  per-operator transfer *time* beside the existing byte counters.

- **The phase breakdown**: per-query deltas of the engine's stage
  timers decompose a cold run into decode (parse+encode) -> H2D ->
  compile -> execute -> D2H -> other, rendered as a one-line bar in
  EXPLAIN ANALYZE and recorded as ``cold_phase_ms`` per bench config —
  ROADMAP item 3's "cold >= 2x CPU" target becomes a measured,
  decomposed gap instead of folklore.

Cost model: like the flight recorder, the put/adopt/release path is
LOCK-FREE — dict stores, int adds, one ``weakref.finalize``
registration per buffer; no locks, no syscalls — so it can ride inside
other subsystems' critical sections (lint rule DF005 and the lockcheck
soak enforce it).  The running live-bytes counter tolerates the
occasional lost increment under concurrent writers (the statsd trade);
``live_bytes()`` recomputes the exact sum from the entry table on
every read (scrape paths), correcting any drift.

``DATAFUSION_TPU_DEVICE_LEDGER=0`` disables everything: the seam
degrades to a bare ``jax.device_put`` and the hot paths are
byte-identical to the unledgered engine.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import time
import weakref
from typing import Any, Optional

from datafusion_tpu.obs.recorder import _env_flag
from datafusion_tpu.obs.recorder import record as _flight_record
from datafusion_tpu.obs.trace import _current_trace
from datafusion_tpu.utils.metrics import METRICS


_ENABLED = _env_flag("DATAFUSION_TPU_DEVICE_LEDGER", True)
# buffers that are not cache-owned and survive this long past their
# query's completion are reported as leaks (two sweeps must see them:
# one marks, a later one past the grace reports)
_LEAK_GRACE_S = float(
    os.environ.get("DATAFUSION_TPU_LEDGER_LEAK_GRACE_S", "5") or 5
)


def enabled() -> bool:
    return _ENABLED


# -- profiling-sync mode ----------------------------------------------
# Jitted launches return after DISPATCH on accelerators; the device
# keeps computing while the host moves on, and the wall lands in
# whichever timer blocks next (d2h.wait).  Always blocking launches
# would serialize real host/device overlap the engine relies on (mesh
# rounds, merge prep), so phase-accurate launch timing is opt-in: the
# phase-breakdown consumers (EXPLAIN ANALYZE, bench cold legs) run
# their query under `profile_sync()`, and `utils/retry.device_call`
# blocks each launch on completion only inside it — the "execute"
# slice then measures device wall, not dispatch, and "d2h" shrinks to
# the true transfer.  Contextvar-scoped so one traced query never
# force-syncs a concurrent one.
_profile_sync_depth: contextvars.ContextVar[int] = contextvars.ContextVar(
    "datafusion_tpu_profile_sync", default=0
)


@contextlib.contextmanager
def profile_sync():
    """Scope in which device launches block on completion for
    phase-accurate 'execute' timing (see comment above)."""
    tok = _profile_sync_depth.set(_profile_sync_depth.get() + 1)
    try:
        yield
    finally:
        _profile_sync_depth.reset(tok)


def profile_sync_active() -> bool:
    return _ENABLED and _profile_sync_depth.get() > 0


def configure(enabled: Optional[bool] = None,
              leak_grace_s: Optional[float] = None) -> None:
    """Test/embedding override of the env-derived knobs."""
    global _ENABLED, _LEAK_GRACE_S
    if enabled is not None:
        _ENABLED = bool(enabled)
    if leak_grace_s is not None:
        _LEAK_GRACE_S = float(leak_grace_s)


def _device_key(device) -> str:
    """Stable short name for a transfer target (a jax Device, a
    Sharding, or None = the default device)."""
    if device is None:
        return "default"
    platform = getattr(device, "platform", None)
    if platform is not None:
        ident = getattr(device, "id", "?")
        return f"{platform}:{ident}"
    return type(device).__name__  # NamedSharding and kin


def _is_device_array(x) -> bool:
    return hasattr(x, "copy_to_host_async")


class _Entry:
    __slots__ = ("nbytes", "owner", "device", "trace_id", "ts", "cached",
                 "candidate_since", "reported", "arr_id")

    def __init__(self, nbytes: int, owner: str, device: str,
                 trace_id: Optional[str], cached: bool, arr_id: int):
        self.nbytes = nbytes
        self.owner = owner
        self.device = device
        self.trace_id = trace_id
        self.ts = time.monotonic()
        self.cached = cached
        self.candidate_since: Optional[float] = None
        self.reported = False
        self.arr_id = arr_id


class _PinEntry:
    """One ledger-owned pinned resident (see the pin section below)."""

    __slots__ = ("fingerprint", "owner", "priority", "on_evict", "artifact",
                 "nbytes", "uses", "last_used", "pinned_at")

    def __init__(self, fingerprint: str, owner: str, priority: int,
                 on_evict, artifact):
        self.fingerprint = fingerprint
        self.owner = owner
        self.priority = int(priority)
        self.on_evict = on_evict
        self.artifact = artifact
        self.nbytes = 0
        self.uses = 0
        self.last_used = time.monotonic()
        self.pinned_at = time.monotonic()


class DeviceLedger:
    """Process-wide registry of live device buffers (see module doc).

    Entries are keyed by a monotonically increasing token; an id() ->
    token side table lets `retag` find the entry for a buffer it still
    holds (id reuse is safe: the finalizer that frees a buffer also
    drops its id mapping).  Every mutator is lock-free — dict set/pop
    and int adds only — by the same contract as the flight recorder.
    """

    def __init__(self):
        self._entries: dict[int, _Entry] = {}
        self._by_id: dict[int, int] = {}
        self._next = itertools.count()
        self._live = 0        # running estimate; exact on live_bytes()
        self._peak = 0
        self._window_peak: Optional[int] = None
        self.leaks_reported = 0

    # -- placement seam ------------------------------------------------
    def put(self, arr, device=None, owner: str = "anon",
            cached: bool = True):
        """THE ``jax.device_put`` seam: place ``arr`` on ``device`` (a
        jax Device, a Sharding, or None for the default), record the
        transfer, and track the resulting buffer's residency under
        ``owner``.  ``cached=False`` marks buffers that should die with
        their query — the leak sweep only ever flags those.  Disabled
        (``DATAFUSION_TPU_DEVICE_LEDGER=0``) this is a bare device_put.

        Timing: ``jax.device_put`` is asynchronous on accelerators, so
        ordinary puts record the *dispatch* wall only (events marked
        ``dispatch_only``, no GB/s claimed) and the engine keeps its
        transfer/host-work overlap: parse of batch N+1 proceeds while
        batch N's DMA is in flight.  Under ``profile_sync()`` (EXPLAIN
        ANALYZE, bench cold legs, i.e. the phase-breakdown consumers)
        the put blocks on completion and the event carries true
        achieved GB/s vs the link baseline.  Call sites that dispatch a
        *batch* of transfers to distinct devices use
        ``transfer(..., profile=False)`` + one ``note_h2d`` so parallel
        links stay parallel."""
        import jax

        if not _ENABLED:
            return jax.device_put(arr, device)
        if _is_device_array(arr):
            # already device-resident: this is a reshard/placement
            # (e.g. mesh state distribution), not a host->device
            # transfer — track residency, but recording it as H2D
            # would count bytes that never crossed the host link
            out = jax.device_put(arr, device)
            self._register(out, owner, cached, device)
            return out
        synced = profile_sync_active()
        # the transfer is a stage-timer interval (utils/metrics.py):
        # the "h2d" phase, the sampling profiler's stage and the
        # `dftpu.h2d.dispatch` span (lock-free, same contract as the
        # ledger bookkeeping)
        with METRICS.timer("h2d.dispatch") as span:
            out = jax.device_put(arr, device)
            if synced:
                jax.block_until_ready(out)
        nbytes = int(getattr(arr, "nbytes", 0) or 0)
        self.note_h2d(nbytes, span.wall_s, device, synced=synced)
        self._register(out, owner, cached, device)
        return out

    def transfer(self, arr, device=None, profile: bool = True):
        """A device_put whose result is *transient* (a wire blob about
        to be consumed by a decode kernel): the transfer is profiled
        (same dispatch-vs-``profile_sync`` timing as ``put``), but no
        residency entry is created — the decoded outputs are what stays resident
        (``adopt`` them instead).  ``profile=False`` dispatches without
        blocking or recording: for fan-out loops placing shards on
        distinct devices, where per-transfer blocking would serialize
        links that genuinely run in parallel — the caller blocks once
        on the batch and records one combined ``note_h2d``."""
        import jax

        if not _ENABLED:
            return jax.device_put(arr, device)
        if not profile:
            return jax.device_put(arr, device)
        synced = profile_sync_active()
        with METRICS.timer("h2d.dispatch") as span:
            out = jax.device_put(arr, device)
            if synced:
                jax.block_until_ready(out)
        nbytes = int(getattr(arr, "nbytes", 0) or 0)
        self.note_h2d(nbytes, span.wall_s, device, synced=synced)
        return out

    def adopt(self, value: Any, owner: str = "anon", cached: bool = True,
              device=None) -> Any:
        """Track every device-array leaf of ``value`` (a pytree) as a
        resident buffer under ``owner`` — for buffers the engine did
        not place directly: decode-kernel outputs, mesh-stacked global
        arrays.  Returns ``value`` unchanged."""
        if not _ENABLED:
            return value
        import jax

        for leaf in jax.tree.leaves(value):
            if _is_device_array(leaf):
                self._register(leaf, owner, cached, device)
        return value

    def retag(self, value: Any, owner: str, cached: bool = True) -> None:
        """Re-attribute already-tracked buffers (a table's copies a
        pin takes over stop being transient)."""
        if not _ENABLED:
            return
        import jax

        for leaf in jax.tree.leaves(value):
            token = self._by_id.get(id(leaf))
            if token is None:
                continue
            e = self._entries.get(token)
            if e is not None:
                e.owner = owner
                e.cached = cached
                e.candidate_since = None

    # -- internals (all lock-free) -------------------------------------
    def _register(self, leaf, owner: str, cached: bool, device) -> None:
        if not _is_device_array(leaf):
            return
        arr_id = id(leaf)
        prior = self._by_id.get(arr_id)
        if prior is not None and prior in self._entries:
            # same live buffer adopted again (replayed fragment, warm
            # re-collect): refresh attribution, never double-count —
            # and a buffer just proven in use is no leak candidate
            e = self._entries[prior]
            e.owner = owner
            e.cached = cached
            e.candidate_since = None
            return
        try:
            nbytes = int(leaf.nbytes)
        except (TypeError, AttributeError):
            return
        token = next(self._next)
        try:
            weakref.finalize(leaf, self._release, token, arr_id, nbytes)
        except TypeError:
            return  # un-weakref-able leaf: transfer profiled, not tracked
        tc = _current_trace.get()
        self._entries[token] = _Entry(
            nbytes, owner, _device_key(device),
            None if tc is None else tc.trace_id, cached, arr_id,
        )
        self._by_id[arr_id] = token
        live = self._live = self._live + nbytes
        if live > self._peak:
            self._peak = live
        wp = self._window_peak
        if wp is not None and live > wp:
            self._window_peak = live
        METRICS.gauge("device.hbm.live_bytes", self._live)
        METRICS.gauge("device.hbm.peak_bytes", self._peak)

    def _release(self, token: int, arr_id: int, nbytes: int) -> None:
        # weakref.finalize callback: may run at arbitrary points (any
        # refcount drop), so it must stay lock-free and never raise
        e = self._entries.pop(token, None)
        if e is None:
            return
        if self._by_id.get(arr_id) == token:
            self._by_id.pop(arr_id, None)
        self._live -= nbytes
        METRICS.gauge("device.hbm.live_bytes", max(self._live, 0))

    def note_h2d(self, nbytes: int, seconds: float, device=None,
                 synced: bool = True) -> None:
        """Record one H2D transfer (or one batch of parallel transfers
        the caller timed as a unit, under its own ``h2d.dispatch``
        stage timer): transfer count, per-operator transfer time, and
        the ``device.h2d`` flight event.  ``synced=False``
        marks a dispatch-only wall (async production put): the event
        claims no GB/s — a dispatch-based rate would read absurdly
        above the link baseline and mislead the overlap-vs-encoding
        diagnosis the events exist for."""
        # event COUNT beside the byte counter: the serving path's
        # warm-pinned-table contract is "zero transfers", and a count
        # is assertable where a ring of flight events is not
        METRICS.add("device.h2d.transfers")
        from datafusion_tpu.obs.attribution import charge_h2d
        from datafusion_tpu.obs.stats import record_h2d_time

        record_h2d_time(seconds)
        # per-client metering: the transferred bytes charge this
        # thread's published charge scope (lock-free, like the rest of
        # this path — obs/attribution.py carries the same DF005
        # contract)
        charge_h2d(nbytes)
        attrs = {
            "bytes": nbytes,
            "ms": round(seconds * 1e3, 3),
        }
        if synced:
            attrs["gbps"] = round(nbytes / max(seconds, 1e-9) / 1e9, 3)
            link = _link_baseline_mbps()
            if link is not None:
                attrs["link_mbps"] = link
        else:
            attrs["dispatch_only"] = True
        _flight_record("device.h2d", **attrs)

    # -- reads (exact; scrape-path cost) -------------------------------
    def live_bytes(self) -> int:
        """Exact sum over the entry table; also corrects the running
        estimate the lock-free writers may have drifted."""
        exact = sum(e.nbytes for e in list(self._entries.values()))
        self._live = exact
        if exact > self._peak:
            self._peak = exact
        wp = self._window_peak
        if wp is not None and exact > wp:
            self._window_peak = exact
        METRICS.gauge("device.hbm.live_bytes", exact)
        METRICS.gauge("device.hbm.peak_bytes", self._peak)
        return exact

    def peak_bytes(self) -> int:
        return self._peak

    def reset_peak(self) -> int:
        """Re-arm the PROCESS-WIDE watermark at the current live level.
        Destructive to monitoring (scrapes and fleet.hbm.peak_bytes
        lose the true high-water mark) — per-run measurements should
        use `begin_peak_window` instead; this is for embedders that own
        the whole process lifecycle."""
        self._peak = self.live_bytes()
        METRICS.gauge("device.hbm.peak_bytes", self._peak)
        return self._peak

    def begin_peak_window(self) -> int:
        """Start a per-run watermark (EXPLAIN ANALYZE, bench cold
        legs): `window_peak_bytes` then reports the high-water mark
        since this call, WITHOUT disturbing the process-wide
        `device.hbm.peak_bytes` gauge monitoring relies on.  One
        window at a time — a new begin re-arms it (concurrent queries
        share the approximation the phase breakdown already
        documents)."""
        self._window_peak = self.live_bytes()
        return self._window_peak

    def window_peak_bytes(self) -> int:
        """High-water mark since `begin_peak_window` (the process-wide
        peak if no window was begun)."""
        wp = self._window_peak
        return self._peak if wp is None else wp

    @property
    def entries(self) -> int:
        return len(self._entries)

    def owners(self) -> dict[str, dict]:
        """Per-owner residency: {owner: {bytes, buffers}}."""
        out: dict[str, dict] = {}
        for e in list(self._entries.values()):
            d = out.setdefault(e.owner, {"bytes": 0, "buffers": 0})
            d["bytes"] += e.nbytes
            d["buffers"] += 1
        return out

    def devices(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for e in list(self._entries.values()):
            out[e.device] = out.get(e.device, 0) + e.nbytes
        return out

    def snapshot(self) -> dict:
        return {
            "live_bytes": self.live_bytes(),
            "peak_bytes": self._peak,
            "buffers": len(self._entries),
            "owners": self.owners(),
            "devices": self.devices(),
            "leaks_reported": self.leaks_reported,
            "pinned_bytes": self.pinned_bytes(),
            "pins": self.pins_snapshot(),
        }

    # -- leak detection ------------------------------------------------
    def sweep(self, trace_id: Optional[str] = None,
              grace_s: Optional[float] = None) -> int:
        """Called at root-query completion: non-cache buffers belonging
        to the completed query (or to no query) become leak candidates;
        candidates from an earlier sweep that are STILL live past the
        grace period report as leaks — counter ``device.ledger.leaks``
        plus a ``device.leak`` flight event.  Two-sweep confirmation
        keeps buffers merely awaiting garbage collection out of the
        report.  Returns the number of leaks newly reported.

        Known limit: with tracing OFF every buffer registers trace-less,
        so concurrent untraced queries cannot be told apart — a
        non-cache buffer legitimately held across >grace seconds by one
        query can be flagged when another completes (each buffer reports
        at most once, and re-adopting it clears candidacy).  Deployments
        running long concurrent untraced queries should enable tracing
        (buffers then scope to their query) or raise
        ``DATAFUSION_TPU_LEDGER_LEAK_GRACE_S``."""
        if not _ENABLED:
            return 0
        grace = _LEAK_GRACE_S if grace_s is None else grace_s
        now = time.monotonic()
        leaks = 0
        for e in list(self._entries.values()):
            if e.cached or e.reported:
                continue
            if e.candidate_since is None:
                # scope candidacy to the completing query's buffers
                # plus trace-less ones: an untraced completion
                # (trace_id None) must NOT candidate a concurrent
                # traced query's in-flight buffers
                if e.trace_id is None or e.trace_id == trace_id:
                    e.candidate_since = now
                continue
            if now - e.candidate_since >= grace:
                e.reported = True
                leaks += 1
                self.leaks_reported += 1
                METRICS.add("device.ledger.leaks")
                _flight_record(
                    "device.leak", owner=e.owner, bytes=e.nbytes,
                    device=e.device, age_s=round(now - e.ts, 3),
                    trace_id_put=e.trace_id,
                )
        return leaks

    def clear(self) -> None:
        """Drop every tracked entry (tests).  Finalizers of still-live
        buffers will later release tokens that no longer exist —
        ``_release`` tolerates that."""
        self._entries.clear()
        self._by_id.clear()
        self._live = 0
        self._peak = 0
        self._window_peak = None
        self.leaks_reported = 0
        pins = getattr(self, "_pins", None)
        if pins is not None:
            pins.clear()
            METRICS.gauge("device.hbm.pinned_bytes", 0)

    # -- pinned residents: the ledger as ALLOCATOR ---------------------
    # The serving path (datafusion_tpu/serve.py, ROADMAP item 2)
    # promotes hot tables from per-query transients to first-class
    # ledger-OWNED residents: a fingerprint -> pinned-artifact map whose
    # entries survive across queries, are accounted as
    # ``device.hbm.pinned_bytes``, and are evicted HERE — by owner
    # priority, then least-recent use — when admission needs headroom.
    # The artifact is opaque to the ledger (serve pins its resident
    # batch list); ``on_evict`` is the owner's release hook: dropping
    # the artifact reference lets the buffers' finalizers run, so
    # live_bytes falls through the same weakref accounting every other
    # buffer uses.  Pin mutations take a small lock (admission/eviction
    # are control-plane paths, never inside the lock-free put/adopt
    # hot path).

    def _pin_lock(self):
        lock = getattr(self, "_pins_lock", None)
        if lock is None:
            from datafusion_tpu.analysis import lockcheck

            lock = self._pins_lock = lockcheck.make_lock("obs.device_pins")
        return lock

    def _pin_map(self) -> dict:
        pins = getattr(self, "_pins", None)
        if pins is None:
            pins = self._pins = {}
        return pins

    def pin(self, fingerprint: str, nbytes: int = 0, owner: str = "pin",
            priority: int = 0, on_evict=None, artifact: Any = None) -> None:
        """Register (or refresh) a pinned resident under `fingerprint`.
        Re-pinning an existing fingerprint updates its artifact/bytes
        in place and keeps its use count."""
        with self._pin_lock():
            pins = self._pin_map()
            e = pins.get(fingerprint)
            if e is None:
                e = pins[fingerprint] = _PinEntry(
                    fingerprint, owner, priority, on_evict, artifact
                )
                METRICS.add("device.pins")
                _flight_record("device.pin", fingerprint=fingerprint,
                               owner=owner, bytes=int(nbytes))
            else:
                e.owner = owner
                e.on_evict = on_evict if on_evict is not None else e.on_evict
                e.artifact = artifact if artifact is not None else e.artifact
            e.nbytes = int(nbytes)
            e.priority = max(e.priority, int(priority))
            self._pin_gauge(pins)

    def pinned(self, fingerprint: str):
        """The pinned artifact for `fingerprint` (None when absent).
        Touches the entry: use count and recency feed eviction order."""
        with self._pin_lock():
            e = self._pin_map().get(fingerprint)
            if e is None:
                return None
            e.uses += 1
            e.priority = max(e.priority, e.uses)
            e.last_used = time.monotonic()
            return e.artifact

    def set_pin_bytes(self, fingerprint: str, nbytes: int) -> None:
        """Update a pin's measured byte accounting (serve re-measures
        after the first query uploads the resident device copies)."""
        with self._pin_lock():
            pins = self._pin_map()
            e = pins.get(fingerprint)
            if e is not None:
                e.nbytes = int(nbytes)
                self._pin_gauge(pins)

    def unpin(self, fingerprint: str, reason: str = "unpin") -> bool:
        """Drop one pin (calling its owner's release hook)."""
        with self._pin_lock():
            pins = self._pin_map()
            e = pins.pop(fingerprint, None)
            self._pin_gauge(pins)
        if e is None:
            return False
        self._evict_entry(e, reason)
        return True

    def _evict_entry(self, e: "_PinEntry", reason: str) -> None:
        METRICS.add("device.pin_evictions")
        _flight_record("device.pin_evict", fingerprint=e.fingerprint,
                       owner=e.owner, bytes=e.nbytes, reason=reason)
        cb = e.on_evict
        e.artifact = None
        if cb is not None:
            try:
                cb()
            except Exception:  # noqa: BLE001 — owner cleanup must not break eviction
                METRICS.add("device.pin_evict_errors")

    def evict_pins(self, need_bytes: int, exclude=()) -> int:
        """Free at least `need_bytes` of pinned residency by dropping
        pins in (priority, least-recently-used) order.  `exclude`
        names fingerprints that must survive (a query's OWN resident
        tables — evicting them to admit that query would both overshoot
        and force the cold re-scan pinning exists to avoid).  Returns
        the accounted bytes freed (the buffers themselves release via
        their finalizers once the owner drops its references)."""
        victims: list[_PinEntry] = []
        skip = frozenset(exclude)
        with self._pin_lock():
            pins = self._pin_map()
            order = sorted(pins.values(),
                           key=lambda e: (e.priority, e.last_used))
            freed = 0
            for e in order:
                if freed >= need_bytes:
                    break
                if e.fingerprint in skip:
                    continue
                pins.pop(e.fingerprint, None)
                victims.append(e)
                freed += e.nbytes
            self._pin_gauge(pins)
        for e in victims:
            self._evict_entry(e, "pressure")
        return sum(e.nbytes for e in victims)

    def pinned_bytes(self) -> int:
        pins = getattr(self, "_pins", None)
        if not pins:
            return 0
        return sum(e.nbytes for e in list(pins.values()))

    def pins_snapshot(self) -> dict:
        """{fingerprint: {owner, bytes, priority, uses}} for the debug
        plane and the ``\\hbm`` console view."""
        pins = getattr(self, "_pins", None)
        if not pins:
            return {}
        return {
            fp: {"owner": e.owner, "bytes": e.nbytes,
                 "priority": e.priority, "uses": e.uses}
            for fp, e in list(pins.items())
        }

    def _pin_gauge(self, pins: dict) -> None:
        METRICS.gauge(
            "device.hbm.pinned_bytes",
            sum(e.nbytes for e in pins.values()),
        )

    def headroom(self, device=None) -> Optional[int]:
        """HBM bytes available before the measured capacity is reached
        (None when capacity is unknowable — admission then never sheds
        on memory, matching the SLO's stay-dormant rule).  With a
        `device` (a jax Device): that chip's own capacity less what the
        ledger holds on it — a mesh shard has to fit ITS chip, whatever
        the others have free."""
        if device is None:
            cap = hbm_capacity_bytes()
            return None if cap is None else cap - self.live_bytes()
        cap = hbm_capacity_bytes(device)
        if cap is None:
            return None
        return cap - self.devices().get(_device_key(device), 0)

    def fits(self, nbytes: int, device=None) -> bool:
        """Whether `nbytes` of new residency fit what is free: the
        test admission applies to a cold table (`need <= headroom`),
        for whoever else places something that stays (a join's build
        side; with `device`, a mesh shard on its own chip).
        `headroom` counts every live ledger buffer, pinned or
        not, so a table's resident copies weigh in from the moment
        they are uploaded; what a caller knows is about to follow it
        adds to `nbytes` itself.  Where the capacity is unknowable the
        device is the host platform and residency is host memory
        (`host_fits`)."""
        free = self.headroom(device)
        if free is None:
            return host_fits(nbytes)
        return int(nbytes) <= free

    # -- rendering -----------------------------------------------------
    def report_text(self) -> str:
        """The ``\\hbm`` console view."""
        snap = self.snapshot()
        lines = [
            f"Device ledger: {snap['buffers']} buffer(s), "
            f"live {_fmt_bytes(snap['live_bytes'])}, "
            f"peak {_fmt_bytes(snap['peak_bytes'])}"
            + ("" if _ENABLED else "  [DISABLED]")
        ]
        for dev, nbytes in sorted(snap["devices"].items()):
            lines.append(f"  device {dev}: {_fmt_bytes(nbytes)}")
        for owner, d in sorted(snap["owners"].items(),
                               key=lambda kv: -kv[1]["bytes"]):
            lines.append(
                f"  owner {owner}: {_fmt_bytes(d['bytes'])} "
                f"in {d['buffers']} buffer(s)"
            )
        for fp, p in sorted(snap["pins"].items(),
                            key=lambda kv: -kv[1]["bytes"]):
            lines.append(
                f"  pinned {fp}: {_fmt_bytes(p['bytes'])} "
                f"(owner {p['owner']}, uses {p['uses']})"
            )
        if snap["leaks_reported"]:
            lines.append(f"  leaks reported: {snap['leaks_reported']}")
        return "\n".join(lines)


def host_fits(nbytes: int) -> bool:
    """Whether `nbytes` fit the host's available memory (true where
    that cannot be read): the size test for what stays on the host."""
    try:
        free = os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        return True
    return int(nbytes) <= free


def hbm_capacity_bytes(device=None) -> Optional[int]:
    """Device memory capacity for the memory-pressure SLO
    (``DATAFUSION_TPU_SLO_*_HBM_FRAC``): the ``DATAFUSION_TPU_HBM_BYTES``
    override (TOTAL across local devices), else the sum of every local
    device's ``memory_stats()['bytes_limit']`` — the ledger's live
    bytes span all local devices (the mesh path shards across them), so
    dividing by one chip's capacity would over-report pressure N-fold
    on an N-device host.  Else None — an unknown capacity keeps the
    objective dormant rather than guessed (the exact anti-pattern the
    ledger replaced in benchmarks/suite.py).  With a `device`: that
    one chip's capacity (the override's even share of the total)."""
    try:
        import jax

        devices = jax.devices()
    except Exception:  # noqa: BLE001 — capacity probing is best-effort by contract
        return None
    env = os.environ.get("DATAFUSION_TPU_HBM_BYTES")
    if env:
        try:
            total = int(float(env))
        except (TypeError, ValueError):
            return None
        return total if device is None else total // max(len(devices), 1)
    total = 0
    for d in devices if device is None else [device]:
        # per-device guard: backends EXPOSE memory_stats but vary
        # wildly in what it returns — None, a partial dict without
        # bytes_limit (CPU/METAL do this), a non-dict, or a raise
        # (NotImplementedError on some plugin backends).  Any of those
        # means the total is unknowable: go cleanly dormant rather
        # than report a partial capacity that would skew the hbm_frac
        # burn rate
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — an opaque backend = unknown, not an error
            return None
        if not isinstance(stats, dict):
            return None
        limit = stats.get("bytes_limit")
        if not isinstance(limit, (int, float)) or limit <= 0:
            return None
        total += int(limit)
    return total or None


def _link_baseline_mbps() -> Optional[float]:
    """The measured link rate, if the probe has already run — this
    PEEKS the cache and never triggers the probe itself (a flight
    event must not cost a 2x1MiB link round trip)."""
    try:
        from datafusion_tpu.exec.batch import _LINK_RATE

        if _LINK_RATE:
            return round(max(_LINK_RATE.values()), 1)
    except ImportError:  # pragma: no cover — circular-import guard
        pass
    return None


def _fmt_bytes(n: float) -> str:
    n = int(n)
    if n >= 1 << 30:
        return f"{n / (1 << 30):.2f}GiB"
    if n >= 1 << 20:
        return f"{n / (1 << 20):.2f}MiB"
    if n >= 1 << 10:
        return f"{n / (1 << 10):.1f}KiB"
    return f"{n}B"


LEDGER = DeviceLedger()


def record_d2h(nbytes: int, seconds: float) -> None:
    """One device->host pull completed (materialize's blocking wait):
    flight event + per-operator transfer time.  The ``d2h.wait`` stage
    timer is the caller's (no double count here)."""
    if not _ENABLED:
        return
    from datafusion_tpu.obs.stats import record_d2h_time

    record_d2h_time(seconds)
    attrs = {
        "bytes": nbytes,
        "ms": round(seconds * 1e3, 3),
        "gbps": round(nbytes / max(seconds, 1e-9) / 1e9, 3),
    }
    link = _link_baseline_mbps()
    if link is not None:
        attrs["link_mbps"] = link
    _flight_record("device.d2h", **attrs)


# -- cold-path phase breakdown ----------------------------------------
# Phases map onto the engine's existing stage timers plus the ones this
# PR adds (device.dispatch in utils/retry.device_call, h2d.dispatch now
# accumulated at the ledger seam).  "decode" covers parse + dictionary
# encode (both inside scan.parse) + the wire-codec encode
# (h2d.encode, timed in put_compressed); "execute" is launch-dispatch wall
# minus attributed XLA compile (compile.xla is only populated while a
# trace session has the jax.monitoring listener installed — plain
# untraced runs fold compile into execute); "other" is the remainder
# of the query wall (host merge, planning, result assembly).
PHASE_ORDER = ("decode", "h2d", "compile", "execute", "d2h", "other")

_PHASE_TIMERS = {
    "decode": ("scan.parse", "h2d.encode"),
    "h2d": ("h2d.dispatch",),
    "compile": ("compile.xla",),
    "execute": ("device.dispatch",),
    "d2h": ("d2h.wait", "d2h.compact"),
}


def phase_snapshot() -> dict[str, float]:
    """Current values of every timer a phase derives from — capture
    before a query, feed to ``phase_breakdown`` after.  Timers are
    process-global: with concurrent queries in flight the breakdown is
    approximate (attributed to whichever root completes).  With the
    ledger disabled the ``h2d.dispatch`` timer never accrues (the seam
    degrades to a bare device_put), so rather than render a bar that
    silently folds H2D into "other" — misleading exactly the
    decode-vs-H2D tuning the bar exists for — both phase functions
    return empty and the consumers skip rendering."""
    if not _ENABLED:
        return {}
    timings = METRICS.timings
    return {
        t: timings.get(t, 0.0)
        for timers in _PHASE_TIMERS.values()
        for t in timers
    }


def phase_breakdown(before: Optional[dict], wall_s: float,
                    ) -> dict[str, float]:
    """Per-phase seconds for one query from the timer deltas since
    ``before`` (None/{} = since process start) and the query wall.
    Empty when the ledger is disabled (see ``phase_snapshot``)."""
    if not _ENABLED:
        return {}
    before = before or {}
    cur = phase_snapshot()
    phases: dict[str, float] = {}
    for name, timers in _PHASE_TIMERS.items():
        phases[name] = max(
            sum(cur[t] - before.get(t, 0.0) for t in timers), 0.0
        )
    # compile happens inside the first dispatch's wall: split it out
    phases["execute"] = max(phases["execute"] - phases["compile"], 0.0)
    accounted = sum(phases.values())
    phases["other"] = max(wall_s - accounted, 0.0)
    return phases


def phase_ms(phases: dict[str, float]) -> dict[str, float]:
    """Milliseconds form for JSON artifacts (BENCH ``cold_phase_ms``,
    flight-dump ``query.phases``)."""
    return {k: round(v * 1e3, 2) for k, v in phases.items()}


def phase_bar(phases: dict[str, float], wall_s: float,
              width: int = 30) -> str:
    """The one-line EXPLAIN ANALYZE bar: each phase's share of the
    query wall as a proportional block run."""
    wall = max(wall_s, 1e-9)
    parts = []
    for name in PHASE_ORDER:
        v = phases.get(name, 0.0)
        frac = v / wall
        if frac < 0.005:
            continue
        blocks = "█" * max(1, round(frac * width))
        parts.append(f"{name} {blocks} {frac * 100:.0f}%")
    return " · ".join(parts) if parts else "(no phases recorded)"
