#!/usr/bin/env bash
# One-command smoketest (mirror of the reference's
# scripts/smoketest.sh:15-23,68-89: tests + example + golden console
# diff with `diff -bBZ -I seconds`).  Runs hermetically on the CPU
# backend; pass SMOKETEST_DEVICE=tpu to exercise an attached chip.
set -euo pipefail
cd "$(dirname "$0")/.."

test_dir="$(mktemp -d)"
trap 'echo "CLEANUP: Removing ${test_dir}"; rm -rf "${test_dir}"' EXIT

export JAX_PLATFORMS="${SMOKETEST_DEVICE:-cpu}"
if [ "$JAX_PLATFORMS" = "cpu" ]; then
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
fi

echo "== native build =="
make -C native

if [ "${SMOKETEST_SKIP_TESTS:-0}" != "1" ]; then
  echo "== unit tests (8-device CPU mesh) =="
  python -m pytest tests/ -q
else
  echo "== unit tests skipped (SMOKETEST_SKIP_TESTS=1; CI runs them in the test matrix) =="
fi

echo "== analysis check (self-lint + plan verifier + lockcheck report) =="
./scripts/analysis_check.sh

echo "== chaos smoke (distributed query under a seeded fault plan) =="
python scripts/chaos_smoke.py

echo "== gray smoke (SIGSTOP'd worker mid-workload: hedged dispatch + breakers + retry budget) =="
python scripts/gray_smoke.py

echo "== trace smoke (EXPLAIN ANALYZE + merged worker trace + flight-recorder artifact + OTLP export) =="
python scripts/trace_smoke.py

echo "== debug smoke (host profiler per-phase frames + debug HTTP plane + debug-bundle CLI on a 2-worker cluster) =="
python scripts/debug_smoke.py

echo "== cache smoke (result + fragment caches, invalidation, off-switch) =="
python scripts/cache_smoke.py

echo "== kernel smoke (no-recompile-on-repeat with equal rows, Pallas interpret parity) =="
python scripts/kernel_smoke.py

echo "== cluster smoke (failover + control plane: shared membership, shared cache tier, invalidation broadcast, fleet telemetry aggregation, primary/standby HA) =="
python scripts/cluster_smoke.py

echo "== scale smoke (3-replica quorum election under SIGKILL, lease-deadline shipping, parked-watch fan-out on the event loop) =="
python scripts/scale_smoke.py

echo "== crash smoke (WAL durability: full-fleet kill -9 recovery, pin rehydration, 30% seeded wal.* disk-fault soak) =="
python scripts/crash_smoke.py

echo "== serve smoke (closed-loop concurrent clients: admission control, pinned-table H2D skip, megabatched launches, 3x throughput gate) =="
python scripts/serve_smoke.py

echo "== qos smoke (multi-tenant overload: weighted fair-share admission, noisy-neighbor p99 isolation, quota sheds, byte-identical FIFO with QoS off) =="
python scripts/qos_smoke.py

echo "== ingest smoke (streaming appends: kill -9 mid-append + ingest-log recovery, 30% seeded wal fsync faults, live view subscription) =="
python scripts/ingest_smoke.py

echo "== join smoke (2-worker shuffle joins: Q3-shaped 3-table exact, SIGKILL failover, warm pinned-build zero-H2D probe) =="
python scripts/join_smoke.py

echo "== adaptive smoke (cost-store feedback loop: cold-vs-trained decision flips across a restart, bit-exact, replan on poisoned stats) =="
python scripts/adaptive_smoke.py

echo "== example (reference csv_sql.rs workload) =="
python examples/csv_sql.py > "${test_dir}/example_output.txt"
grep -q "City: " "${test_dir}/example_output.txt"

echo "== golden console smoketest =="
# fixtures were mounted at /test/data in the reference's docker
# harness; rewrite to this checkout (smoketest.sh:68-83)
sed "s#'/test/data/#'$(pwd)/test/data/#" test/data/smoketest.sql \
  > "${test_dir}/smoketest.sql"
python -m datafusion_tpu.cli --script "${test_dir}/smoketest.sql" \
  > "${test_dir}/smoketest_output.txt"
diff -bBZ -I seconds "${test_dir}/smoketest_output.txt" \
  test/data/smoketest-expected.txt

echo "SMOKETEST PASSED"
