"""Decision functions: turn accumulated observations into plans.

Each function answers one planner question and, when it deviates from
the static default, records a chosen-vs-default decision on the store
(rendered by EXPLAIN ANALYZE, ``\\cost`` and ``/debug/cost``).  Every
function degrades to ``None`` / the static default when the store has
nothing relevant — a cold store plans exactly like the static engine.
"""

from __future__ import annotations

import math
from typing import Optional

from datafusion_tpu import cost as _cost

# how far below the probe side a build side must be before swapping a
# join (a rewrite that merely ties isn't worth the restoring
# projection)
_SWAP_FACTOR = 0.5

# scan chunk sizing: aim each chunk's wire bytes at this many seconds
# of measured link time — large enough to amortize a launch round
# trip, small enough to keep the H2D/compute pipeline overlapped
_CHUNK_LINK_S = 4e-3
_CHUNK_MIN_ROWS = 4096
_CHUNK_MAX_ROWS = 1 << 21


def agg_shape(group_names) -> str:
    # sorted: GROUP BY a,b and GROUP BY b,a have identical group
    # cardinality, so they share one learned entry
    return "agg:g=" + ",".join(sorted(group_names))


def agg_group_estimate(store, tkey: str, group_names) -> Optional[int]:
    """Learned distinct-group cardinality for GROUP BY `group_names`
    over table `tkey` (None when never observed)."""
    rec = store.lookup(tkey, agg_shape(group_names))
    if rec is None:
        return None
    g = rec.get("groups_max", rec.get("groups_last"))
    return int(g) if g else None


def table_rows(store, tkey: str) -> Optional[int]:
    """Learned row count of a table (from completed scans, the serve
    path's megabatch passes, or join builds over the bare table)."""
    rec = store.lookup(tkey, "scan")
    if rec is None:
        return None
    rows = rec.get("rows_max", rec.get("rows_last"))
    return int(rows) if rows else None


def scan_chunk_rows(store, tkey: str, device,
                    default_rows: int) -> Optional[int]:
    """Learned scan chunk size: rows per batch such that one chunk's
    host bytes take ~`_CHUNK_LINK_S` on the measured device link.
    None (keep the default) on host-speed links (cpu / collocated
    TPU — `link_rate_mbps` reports inf), when bytes/row was never
    observed, or when the answer lands within 2x of the default
    (avoid chunk-shape churn that recompiles kernels for no win)."""
    from datafusion_tpu.exec.batch import link_rate_mbps

    rate = link_rate_mbps(device)
    if not math.isfinite(rate):
        return None
    rec = store.lookup(tkey, "scan")
    if rec is None:
        return None
    rows, nbytes = rec.get("rows_last"), rec.get("nbytes_last")
    if not rows or not nbytes:
        return None
    bytes_per_row = nbytes / rows
    target = (rate * 1e6 * _CHUNK_LINK_S) / max(bytes_per_row, 1e-9)
    chosen = int(min(max(target, _CHUNK_MIN_ROWS), _CHUNK_MAX_ROWS))
    if default_rows / 2 <= chosen <= default_rows * 2:
        return None
    store.note_decision(
        "scan.chunk", chosen, default_rows,
        f"link {rate:.1f} MB/s x {_CHUNK_LINK_S * 1e3:.0f} ms at "
        f"{bytes_per_row:.0f} B/row",
        table=tkey,
    )
    return chosen


# -- serving megabatch window ----------------------------------------

def serve_window_s(store, configured_s: float) -> float:
    """Adaptive megabatch window from the observed arrival spacing.

    The configured window is a MAXIMUM wait for co-batchable peers.
    When arrivals are much sparser than the window, waiting buys
    nothing but queue_wait (the tail explainer's top segment on idle
    servers) — shrink toward a minimal debounce.  When arrivals are
    dense, a slightly longer window (capped at 2x configured) fills
    megabatches closer to their size trigger."""
    iv = store.value(_cost.SERVE_KEY, "arrivals", "interval_s")
    if not iv:
        return configured_s
    if iv > 4 * configured_s:
        return max(configured_s / 8, 1e-4)
    if iv < configured_s / 4:
        return min(2 * configured_s, configured_s + 2 * iv)
    return configured_s
