"""Hash joins (ROADMAP multi-table arc).

Two physical strategies behind one `HashJoinRelation` (relation.py):

- **dense-int device path**: a single integer key whose build-side
  range is small direct-addresses a slot table built on device (one
  stock-XLA scatter launch) and probed inside one fused launch per batch —
  payload gather, validity, selection mask all in the same launch.
- **host path**: the general fallback (multi-key, strings, duplicate
  keys) — a `HashIndex` (core.py) over the build rows, probed with
  numpy CSR expansion per batch.

The build side is always the RIGHT input (dimension position); built
artifacts pin in the device ledger keyed by the build subtree's query
fingerprint, so serving-tier queries probing the same dimension table
reuse one resident build across queries until a catalog or data
version bump changes the fingerprint.

`core.py` also owns the deterministic key-partition hash the shuffle
exchange (parallel/shuffle.py) uses — both sides of a distributed
join must agree on it byte-for-byte across workers.
"""

from datafusion_tpu.join.core import HashIndex, partition_of
from datafusion_tpu.join.relation import HashJoinRelation

__all__ = ["HashIndex", "HashJoinRelation", "partition_of"]
