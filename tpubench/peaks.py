"""Published peaks of the chips the benchmark runs on, and the least bytes a
query has to read.

Peaks are keyed by the `device_kind` JAX reports.  A kind that is not in
the table is an error, not a default: add it with its source.
"""

from __future__ import annotations

import re

PEAKS = {
    # what JAX calls one TPU v5e chip
    "TPU v5 lite": {
        "hbm_gbps": 819.0,
        "hbm_gb": 16.0,
        "bf16_tflops": 197.0,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}

# bytes of one resident value on the device, by a data set's column kind:
# a string column is held as int32 dictionary codes
RESIDENT_BYTES = {"str": 4, "f64": 8, "i64": 8}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def named_in(sql: str, names) -> list:
    """Those of `names` (tables, or a table's columns) that occur in the
    query text as whole words."""
    words = set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*", sql))
    return [n for n in names if n in words]


def required_bytes(sql: str, schema: dict, rows: int) -> int:
    """The bytes a scan-and-aggregate query cannot avoid reading from
    HBM: every row of every resident column it references, once.  Masks,
    group ids and intermediates are the program's choice and not counted,
    so the roofline share taken against this is a share of the least
    possible time, bounded by HBM bandwidth."""
    return rows * sum(RESIDENT_BYTES[schema[c]]
                      for c in named_in(sql, schema))


def query_scan(sql: str, tables: dict, rows: dict) -> tuple:
    """(rows scanned, least bytes read) of one query over a data set's
    `tables` ({table: schema}) holding `rows` ({table: count}): the sums
    over the tables its text names, each with its own schema and count."""
    named = named_in(sql, tables)
    return (sum(rows[t] for t in named),
            sum(required_bytes(sql, tables[t], rows[t]) for t in named))


def roofline_share(bytes_needed: float, busy_s: float, device_kind: str) -> float:
    """Least time at the HBM peak over the device time taken, 0..1."""
    least_s = bytes_needed / (peak(device_kind)["hbm_gbps"] * 1e9)
    return least_s / busy_s
