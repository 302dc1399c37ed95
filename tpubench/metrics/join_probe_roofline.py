"""Kernels: the least time the chip could take for the window's join
probes (`probe_bytes` at the HBM peak) over the device time of the
operations whose program name contains `join_probe` among the trace's
`device_ops`.  None where the trace names no such program.

The bytes are those of the work, not of the implementation: per probed row
the key (8 B) and one slot of the direct-address table (4 B), each payload
value gathered from the build side (its columns other than the key, which
the probe side holds already), and each value written: the payload again
and one byte of mask.  The probe side's other columns pass through
untouched and are not counted."""
import importlib.util
import os

from tpubench.peaks import RESIDENT_BYTES, roofline_share

PROGRAM = "join_probe"
KEY_BYTES, SLOT_BYTES, MASK_BYTES = 8, 4, 1
# the one join in the tree: lineitem probes orders on its key
DATASET, BUILD_TABLE, BUILD_KEY = "tpch_orders_lineitem", "orders", "o_orderkey"


def _build_side() -> dict:
    """{column: kind} of the build table, from the data set's `TABLES`."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "datasets", DATASET + ".py")
    spec = importlib.util.spec_from_file_location("_join_probe_dataset", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.TABLES[BUILD_TABLE]


def probe_bytes(rows: int, build_side: dict, key: str) -> int:
    """Least bytes moved to probe `rows` rows against `build_side`
    ({column: kind}) on its column `key`."""
    payload = sum(RESIDENT_BYTES[kind] for name, kind in build_side.items()
                  if name != key)
    return rows * (KEY_BYTES + SLOT_BYTES + 2 * payload + MASK_BYTES)


def read(run):
    if not run.trace:
        return None
    seconds = sum(s for name, s in run.trace["device_ops"] if PROGRAM in name)
    rows = run.counts.get("join.probe.rows", 0)
    if not seconds or not rows:
        return None
    return 100 * roofline_share(probe_bytes(rows, _build_side(), BUILD_KEY),
                                seconds, run.device["kind"])
