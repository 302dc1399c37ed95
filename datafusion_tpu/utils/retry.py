"""Transient device-failure retry.

A device runtime can fail a request with a retryable status
mid-flight; the reference never faced this (CPU-only), but SURVEY §5.3
names failure detection/recovery as a rebuild target and the query
engine's natural recovery unit is the *device call*:
dispatches are functionally pure (accumulator state in, state out), so
a failed call simply replays.  Genuine programming errors (trace
errors, shape mismatches) are not transient and re-raise immediately.

Policy: classification is typed (`errors.classify_transient` wraps raw
JAX/XLA errors into the `TransientError` taxonomy once, at this
boundary — the retry decision itself is an `isinstance`); backoff is
capped exponential with FULL jitter (decorrelates a fleet of workers
hammering a recovering transport — a deterministic ladder re-aligns
every client on the same instant); and every sleep is bounded by the
caller's deadline (`utils.deadline`), so retries can never exceed a
query's budget.

**Retry budget** (default off): backoff decorrelates a fleet in time,
but under a *correlated* fault burst (30% of calls failing
everywhere) every client still retries — total offered load amplifies
by 1/(1-p) exactly when the system can least afford it.
`RetryBudget` is a process-global token bucket capping the ratio of
retries to first attempts: each first attempt accrues ``ratio``
tokens, each retry spends one, and a spend that finds the bucket
empty is *denied* — the failure surfaces immediately (and the layer
above decides: coordinator failover, query error) instead of joining
a coordinated retry storm.  Throughput degrades smoothly with the
fault rate rather than collapsing under its own recovery traffic.
Consumers: `device_call` retries here, and the coordinator's fragment
reassignment loop (`parallel/coordinator.py`).  Metrics:
``retry.first_attempts`` / ``retry.budget_spent`` /
``retry.budget_denied`` — the asserted evidence that retry volume
stayed inside the configured ratio.

Tunables (env): DATAFUSION_TPU_RETRY_ATTEMPTS (default 4),
DATAFUSION_TPU_RETRY_BASE_S (default 0.25),
DATAFUSION_TPU_RETRY_CAP_S (default 5.0),
DATAFUSION_TPU_RETRY_BUDGET (retry:first-attempt ratio; unset/0 = no
budget, byte-identical paths), DATAFUSION_TPU_RETRY_BURST (bucket
cap, default max(2, 10*ratio)).
"""

from __future__ import annotations

import os
import random
import time

import numpy as np

from datafusion_tpu.errors import QueryDeadlineError, classify_transient
from datafusion_tpu.testing import faults
from datafusion_tpu.utils.deadline import current_deadline
from datafusion_tpu.utils.metrics import METRICS


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return default if not v else float(v)


def _env_bool(name: str, default: bool = False) -> bool:
    """One truthy-env idiom for every resilience switch (breakers,
    hedging, local fallback) — the accepted token set must not drift
    per call site."""
    v = os.environ.get(name)
    if not v:
        return default
    return v.lower() in ("1", "true", "yes", "on")


_ATTEMPTS = int(_env_float("DATAFUSION_TPU_RETRY_ATTEMPTS", 4))
_BASE_S = _env_float("DATAFUSION_TPU_RETRY_BASE_S", 0.25)
_CAP_S = _env_float("DATAFUSION_TPU_RETRY_CAP_S", 5.0)

# module-level stream so tests can seed it (`seed_backoff`); full
# jitter means the *sequence* is what a deterministic test pins down
_RNG = random.Random()


def seed_backoff(seed: int) -> None:
    """Make the jitter stream deterministic (tests, chaos replays)."""
    global _RNG
    _RNG = random.Random(seed)


def backoff_s(attempt: int, base: "float | None" = None,
              cap: "float | None" = None) -> float:
    """Sleep length before retry `attempt` (1-based): full jitter over
    a capped exponential — uniform in [0, min(cap, base * 2^(a-1))]."""
    base = _BASE_S if base is None else base
    cap = _CAP_S if cap is None else cap
    ceiling = min(cap, base * (2.0 ** (attempt - 1)))
    return _RNG.uniform(0.0, ceiling)


class TokenBucket:
    """Ratio/burst token bucket, shared by the retry budget and the
    hedge budget (`utils/hedge.py`).  Internally locked: an unlocked
    read-modify-write would let concurrent spenders all pass the
    check on ONE remaining token — over-granting exactly during the
    correlated failure storm the budget exists to bound (and breaking
    the CI-asserted retries <= ratio*first+burst invariant).  The
    critical section is two float ops and never nests another lock, so
    spend/earn stay cheap enough for retry and dispatch paths."""

    __slots__ = ("ratio", "burst", "_tokens", "_lock")

    def __init__(self, ratio: float, burst: float, initial: float = 1.0):
        from datafusion_tpu.analysis import lockcheck

        self.ratio = max(0.0, float(ratio))
        self.burst = float(burst)
        self._tokens = min(self.burst, float(initial))
        self._lock = lockcheck.make_lock("utils.token_bucket")

    def earn(self) -> None:
        """One unit of real traffic: accrue `ratio` tokens (capped)."""
        with self._lock:
            self._tokens = min(self.burst, self._tokens + self.ratio)

    def spend(self) -> bool:
        """Consume one token; False = bucket empty, don't."""
        with self._lock:
            if self._tokens < 1.0:
                return False
            self._tokens -= 1.0
            return True

    def refund(self) -> None:
        """Return a spent token (the spender never acted on it)."""
        with self._lock:
            self._tokens = min(self.burst, self._tokens + 1.0)

    @property
    def tokens(self) -> float:
        return self._tokens


class RetryBudget:
    """A `TokenBucket` bounding retries to a ratio of first attempts
    (see module doc), with the metrics the acceptance gates assert.

    Under multi-tenant QoS (``DATAFUSION_TPU_QOS=1``;
    datafusion_tpu/qos.py) the global bucket grows per-tenant child
    buckets: a spend must pass the requesting tenant's child FIRST,
    and a child denial never touches the global bucket — one client's
    retry storm exhausts its own isolation budget while the fleet's
    shared recovery reserve stays intact for everyone else
    (``tenant.<id>.retry_denied`` meter, ``retry.tenant_denied``
    flight event).  QoS off = no children, byte-identical."""

    def __init__(self, ratio: float, burst: "float | None" = None,
                 tenant_buckets=None):
        ratio = max(0.0, float(ratio))
        self._bucket = TokenBucket(
            ratio,
            float(burst) if burst is not None else max(2.0, 10.0 * ratio),
        )
        if tenant_buckets is None:
            from datafusion_tpu import qos

            tenant_buckets = qos.tenant_buckets_from_env(
                self._bucket.ratio, self._bucket.burst
            )
        self._tenants = tenant_buckets

    @property
    def ratio(self) -> float:
        return self._bucket.ratio

    @property
    def burst(self) -> float:
        return self._bucket.burst

    @staticmethod
    def _resolve_client(client: "str | None") -> "str | None":
        """The tenant a budget operation bills: the explicit identity
        (the coordinator passes its captured dispatch scope's) or this
        thread's published charge scope."""
        if client is not None:
            return client
        from datafusion_tpu import qos
        from datafusion_tpu.obs.attribution import current_scope

        return qos.scope_client(current_scope())

    def earn(self, client: "str | None" = None) -> None:
        """One first attempt: accrue `ratio` tokens (capped) — in the
        global bucket and, under QoS, the tenant's child."""
        self._bucket.earn()
        if self._tenants is not None:
            client = self._resolve_client(client)
            if client is not None:
                self._tenants.earn(client)
        METRICS.add("retry.first_attempts")

    def spend(self, client: "str | None" = None) -> bool:
        """One retry wants to happen: True = granted (token consumed),
        False = denied, fail now instead of amplifying the storm."""
        if self._tenants is not None:
            client = self._resolve_client(client)
            if client is not None:
                if not self._tenants.spend(client):
                    # the tenant's own isolation budget is exhausted:
                    # deny WITHOUT consulting (or draining) the global
                    # bucket — that is the isolation contract
                    METRICS.add("retry.budget_denied")
                    METRICS.add("retry.tenant_denied")
                    from datafusion_tpu.obs.attribution import METER
                    from datafusion_tpu.obs.recorder import record

                    METER.charge(client, "retry_denied", 1.0)
                    record("retry.tenant_denied", client=client)
                    return False
                if not self._bucket.spend():
                    # global denial: the child token was never acted on
                    self._tenants.refund(client)
                    METRICS.add("retry.budget_denied")
                    return False
                METRICS.add("retry.budget_spent")
                return True
        if not self._bucket.spend():
            METRICS.add("retry.budget_denied")
            return False
        METRICS.add("retry.budget_spent")
        return True

    @property
    def tokens(self) -> float:
        return self._bucket.tokens

    def tenant_tokens(self, client: str) -> "float | None":
        """`client`'s child-bucket balance (None when QoS is off)."""
        if self._tenants is None:
            return None
        return self._tenants.tokens(client)


def _budget_from_env() -> "RetryBudget | None":
    ratio = _env_float("DATAFUSION_TPU_RETRY_BUDGET", 0.0)
    if ratio <= 0:
        return None
    burst = os.environ.get("DATAFUSION_TPU_RETRY_BURST")
    return RetryBudget(ratio, float(burst) if burst else None)


_BUDGET = _budget_from_env()


def retry_budget() -> "RetryBudget | None":
    """The process-global budget (None = unbudgeted, the default)."""
    return _BUDGET


def set_retry_budget(budget: "RetryBudget | None") -> None:
    """Install/clear the process-global budget (tests, embedders)."""
    global _BUDGET
    _BUDGET = budget


def is_transient(err: Exception) -> bool:
    """Typed transient test (kept as the public name callers know)."""
    return classify_transient(err) is not None


# what a jit call puts on the device itself when it finds it among its
# arguments: numpy arrays, numpy scalars, Python numbers
_HOST_LEAF = (np.ndarray, np.generic, bool, int, float, complex)


def _census(tag, args: tuple, kwargs: dict) -> dict:
    """The stats of a launch's span: the pytree `leaves` it is handed,
    the `host` values among them and the `bytes` of those (8 for a
    Python number), under the launch's `tag`.  The call itself puts each
    host value, outside the ledger seam, so `device.h2d.transfers` never
    counts it.  A static Python number counts too (the census does not
    know a program's static arguments); a string or a dtype is a leaf
    and no host value."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs))
    host = [x for x in leaves if isinstance(x, _HOST_LEAF)]
    stats = {"leaves": len(leaves), "host": len(host),
             "bytes": sum(getattr(x, "nbytes", 8) for x in host)}
    return stats if tag is None else {"tag": tag, **stats}


def device_call(fn, /, *args, _tag=None, **kwargs):
    """Invoke a (pure) device computation, replaying on transient
    runtime failures with capped exponential backoff + full jitter,
    never sleeping past the ambient query deadline.

    ``_tag`` is the launch's kernel identity (``"agg.group"``,
    ``"topk"``, ``"mesh.stacked"``, ...) — it rides the
    ``device.launch`` flight event and a per-kernel launch counter, so
    ``launches_per_pass`` decomposes by kernel instead of being one
    opaque total.  The launch wall accrues to the ``device.dispatch``
    stage timer (the "execute" slice of the cold-path phase breakdown;
    XLA compile inside a traced first call is split back out via the
    ``compile.xla`` listener).  Under ``obs/device.profile_sync()``
    (EXPLAIN ANALYZE) the launch blocks on completion
    so that wall is device execution, not async dispatch; elsewhere it
    is dispatch-only and launches stay asynchronous."""
    attempt = 0
    budget = _BUDGET
    if budget is not None:
        budget.earn()
    while True:
        try:
            faults.check("device.call", attempt=attempt)
            from datafusion_tpu.obs.device import profile_sync_active

            # the launch is a stage-timer interval (utils/metrics.py):
            # the "execute" slice of the phase breakdown, the sampling
            # profiler's stage, and the `dftpu.device.dispatch` span,
            # whose stats are the census of what the launch is handed:
            # taken only while a profile runs, and before the span
            # opens (its own time is in no run's `device.dispatch`)
            with METRICS.timer(
                    "device.dispatch",
                    _stats=lambda: _census(_tag, args, kwargs)) as span:
                out = fn(*args, **kwargs)
                if profile_sync_active():
                    # phase-profiled run (EXPLAIN ANALYZE): block so the
                    # "execute" slice measures device wall, not async
                    # dispatch — production launches stay async (see
                    # obs/device.profile_sync)
                    import jax

                    jax.block_until_ready(out)
            wall = span.wall_s
            # every successful dispatch is one executable launch — the
            # unit the fused-pass work minimizes (launches_per_pass in
            # EXPLAIN ANALYZE / bench derives from this counter);
            # counted AFTER fn so failed attempts/retries don't inflate
            METRICS.add("device.launches")
            if _tag is not None:
                METRICS.add(f"device.launches.{_tag}")
            census = span.stats
            if census is not None:
                METRICS.add("device.dispatch.leaves", census["leaves"])
                METRICS.add("device.dispatch.host_leaves", census["host"])
                METRICS.add("device.dispatch.host_bytes", census["bytes"])
            from datafusion_tpu.obs.attribution import note_launch
            from datafusion_tpu.obs.recorder import record as flight_record
            from datafusion_tpu.obs.stats import record_launch

            record_launch()
            # per-client metering: the launch wall charges this
            # thread's published charge scope (a megabatched launch's
            # shared scope splits it by member weight) — one dict read
            # when serving is off
            note_launch(wall)
            flight_record("device.launch", attempt=attempt, kernel=_tag,
                          ms=round(wall * 1e3, 3))
            return out
        except Exception as e:  # jax.errors.JaxRuntimeError and kin
            transient = classify_transient(e)
            if transient is None:
                raise
            attempt += 1
            if attempt >= _ATTEMPTS:
                raise
            if budget is not None and not budget.spend():
                # retry denied: under a correlated fault burst the
                # budget converts would-be retry amplification into
                # prompt failures the layer above can shed or fail over
                METRICS.add("device.retry_budget_exhausted")
                from datafusion_tpu.obs.recorder import record as flight_record

                flight_record("device.retry_denied", attempt=attempt,
                              error=type(transient).__name__)
                raise
            delay = backoff_s(attempt)
            deadline = current_deadline()
            if deadline is not None and deadline.remaining() < delay:
                raise QueryDeadlineError(
                    f"transient device failure, but the query deadline "
                    f"({deadline.remaining():.3f}s left) cannot cover the "
                    f"{delay:.3f}s retry backoff"
                ) from transient
            METRICS.add("device.transient_retries")
            from datafusion_tpu.obs.recorder import record as flight_record
            from datafusion_tpu.obs.stats import record_retry

            record_retry()  # ambient-operator attribution (EXPLAIN ANALYZE)
            flight_record("device.retry", attempt=attempt,
                          error=type(transient).__name__,
                          backoff_s=round(delay, 4))
            time.sleep(delay)
