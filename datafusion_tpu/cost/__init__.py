"""Feedback-driven planning: cost statistics and adaptive decisions.

The reference pipeline (PAPER.md) plans purely syntactically — every
query lowers the same way regardless of what earlier queries measured.
This package closes that loop: the engine's existing measurement seams
(per-table scan histograms, aggregate group encoders, the join build
path, the serving loop's arrival stream) feed a
persistent :class:`~datafusion_tpu.cost.store.CostStore`, and the
planner reads it back at the next lowering:

=====================  ==============================================
decision               driven by
=====================  ==============================================
aggregation capacity   observed group cardinality per (table, keys):
/ route                the accumulator pre-sizes to the learned group
                       count, picking dense / sort-merge up
                       front instead of climbing the regrow ladder
                       (each rung past the dense bound recompiles)
scan chunk rows        measured link rate vs learned bytes/row — keep
                       one chunk's wire bytes near the link's
                       per-launch sweet spot
join build side /      learned table row counts: build the smaller
order                  input, probe the larger; left-deep dimension
                       joins reorder cheapest-build-first
megabatch window       observed arrival spacing vs the configured
                       wait — don't hold a query for peers that
                       aren't coming
=====================  ==============================================

Every decision records chosen-vs-default with the observation that
drove it (EXPLAIN ANALYZE, ``\\cost``, ``/debug/cost``), and a fused
aggregate whose actual cardinality wildly misses the estimate aborts
the pre-sized plan *before* the device launch and re-derives it from
actuals (``plan.replans`` counter, ``query.replan`` flight event).

``DATAFUSION_TPU_COST=0`` disables every planner decision — lowering
is byte-identical to the static engine.  Observation still flows (the
store is also the serving path's row-weight source, which predates
this subsystem).  ``DATAFUSION_TPU_COST_DIR`` names a directory to
persist the store across restarts; unset keeps it in-memory.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from datafusion_tpu.cost.store import CostStore

# special table key for engine-global (not per-table) observations
SERVE_KEY = "__serve__"

_STORE: Optional[CostStore] = None
_STORE_LOCK = threading.Lock()  # creation only — never on observe


def enabled() -> bool:
    """Are cost-driven planner decisions on?  (Default yes;
    ``DATAFUSION_TPU_COST=0`` restores static planning.)"""
    return os.environ.get("DATAFUSION_TPU_COST", "1") != "0"


def store_path() -> Optional[str]:
    d = os.environ.get("DATAFUSION_TPU_COST_DIR")
    return os.path.join(d, "cost_store.json") if d else None


def store() -> CostStore:
    """The process-wide cost store (created on first use; loads the
    persisted manifest when ``DATAFUSION_TPU_COST_DIR`` is set)."""
    global _STORE
    s = _STORE
    if s is None:
        with _STORE_LOCK:
            s = _STORE
            if s is None:
                s = _STORE = CostStore(store_path())
    return s


def reset_store() -> None:
    """Drop the process store (tests / restart simulation); the next
    `store()` re-reads the persisted manifest."""
    global _STORE
    with _STORE_LOCK:
        _STORE = None


def replan_ratio() -> float:
    """Estimate-vs-actual cardinality ratio beyond which a pre-sized
    fused pass aborts and re-derives its plan from actuals."""
    try:
        return max(float(os.environ.get(
            "DATAFUSION_TPU_COST_REPLAN_RATIO", "8")), 1.5)
    except ValueError:
        return 8.0


def table_key(ctx, name: str) -> str:
    """Stable-across-restarts identity of table `name`'s CURRENT data.

    File-backed sources key by backing-file identity (mtime, size) —
    an externally rewritten file reads/writes fresh entries, and the
    same file re-registered after a restart keeps its learned
    statistics.  Streaming (appendable) tables fold their append
    serial in, so every ingest delta retires the old cardinality.
    In-memory sources have no durable identity and fall back to the
    per-process catalog version (their statistics die with the
    process, as the data does)."""
    ds = ctx.datasources.get(name)
    parts = [name]
    if ds is not None:
        dv = getattr(ds, "data_version", None)
        if dv is not None:
            parts.append(f"d{int(dv)}")
        try:
            from datafusion_tpu.cache import (
                canonical_json,
                digest,
                source_version,
            )

            sv = source_version(ds.to_meta())
            parts.append("s" + digest(canonical_json(sv))[:12])
        except Exception:  # noqa: BLE001 — in-memory / non-serializable
            parts.append(f"c{ctx.catalog_version(name)}")
    return "@".join(parts)


def flush(force: bool = False) -> None:
    """Persist the process store if one exists and is dirty (query
    completion / shutdown seam — cheap no-op otherwise)."""
    s = _STORE
    if s is not None:
        s.flush(force=force)
