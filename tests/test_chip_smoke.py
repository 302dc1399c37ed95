"""`chip_smoke.py` on the CPU: the device refusal, the stage functions
and the pyarrow + numpy oracle against the engine at SF-0.01, and that
a failed check reaches the exit code.  The chip run itself is the
builder's and the driver's (`python chip_smoke.py` through the tool)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

import chip_smoke
from datafusion_tpu.exec.context import ExecutionContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SF = 0.01


@pytest.fixture(scope="module")
def oracle():
    return chip_smoke.Q1Oracle(chip_smoke.lineitem_path(SF))


@pytest.fixture(scope="module")
def resident_ctx():
    ctx = ExecutionContext(device="cpu", result_cache=False)
    ctx.register_datasource(
        "lineitem", chip_smoke.resident_lineitem(SF, batch_size=1 << 13))
    return ctx


def test_refuses_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 1
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    # no result line: the last stdout line is not the JSON verdict
    assert '"ok"' not in proc.stdout


def test_oracle_matches_pandas(oracle):
    # the oracle itself against a third, dumber computation
    import pandas as pd

    df = pd.read_parquet(chip_smoke.lineitem_path(SF))
    df = df[df.l_shipdate <= chip_smoke.CUTOFF]
    g = df.groupby(["l_returnflag", "l_linestatus"])
    want = sorted(
        (f, s, float(d.l_quantity.sum()), len(d)) for (f, s), d in g
    )
    got = [(r[0], r[1], r[2], r[9]) for r in oracle.q1()]
    chip_smoke.check_rows(got, want, "oracle vs pandas")


def test_cold_stage_agrees_with_oracle(oracle):
    out = chip_smoke.stage_cold("cpu", SF, oracle)
    assert out["evidence"]["device.launches"] > 0
    assert out["evidence"]["h2d.bytes"] > 0
    assert len(out["rows"]) == len(oracle.q1()) > 0


def test_warm_stage_is_compile_and_transfer_free(resident_ctx, oracle):
    out = chip_smoke.stage_warm(resident_ctx, oracle)
    assert out["evidence"]["kernel_cache.misses"] == 0
    assert out["evidence"]["device.h2d.transfers"] == 0
    # two new relations: every batch found twice, looked up twice
    fresh = out["new_relations"]
    assert fresh["h2d.bytes"] == 0 and fresh["device.h2d.transfers"] == 0
    assert fresh["h2d.resident_hits"] == fresh["expr.cmp_lookups"] > 0


def test_serve_stage_megabatches(resident_ctx, oracle):
    out = chip_smoke.stage_serve(resident_ctx, oracle,
                                 clients=4, per_client=2)
    assert out["queries"] == 9
    assert out["evidence"]["serve.megabatch_launches"] > 0
    assert out["pins"]


def test_operator_stage():
    out = chip_smoke.stage_operators("cpu", agg_rows=1 << 17)
    assert out["join_8k_slots"]["join.build.dense"] == 1
    assert out["order_by_i64"]["device.launches.sort.run"] == 1
    # the join past 2^20 slots: every row probed by a device launch
    sparse = out["join_sparse_4m_slots"]
    assert sparse["join.host_probe.rows"] == 0
    assert sparse["join.probe.rows"] == 1 << 18
    assert sparse["device.launches.join.probe"] == 2
    # each batch's sorted keys span half of either table: no window
    assert sparse["join.probe.window.slot"] == 0
    assert sparse["join.probe.window.payload"] == 0


def test_q3_stage(monkeypatch):
    # 60,000 lines, 15,000 orders, 1,500 customers.  The cost store keys
    # an in-memory table by its name: what the operator stage learned of
    # its own `orders` would swap this join's sides at this size
    monkeypatch.setenv("DATAFUSION_TPU_COST", "0")
    out = chip_smoke.stage_q3("cpu", SF, batch_size=1 << 13)
    assert out["q3_build"]["join.build.dense"] == 2
    assert out["q3_pinned"]["join.build.reuse"] == 2
    assert out["q3_pinned"]["aggregate.device_key.groups"] == out["groups"] > 50
    assert out["q3_pinned"]["aggregate.key_pull.bytes"] == 0
    assert out["q3_pinned"]["device.launches.join.probe"] == 2 * 8
    # 469 rows of slots, 118 of payload: read whole, by shape
    assert out["q3_pinned"]["join.probe.window.slot"] == 0
    assert out["q3_pinned"]["join.probe.window.payload"] == 0


def test_q4_stage():
    # 60,000 lines in batches of 8,192, 15,000 orders; the last batch of
    # lines (2,656) is a shape class of its own: two groups
    out = chip_smoke.stage_q4("cpu", SF, batch_size=1 << 13)
    for label in ("q4_first", "q4_resident"):
        assert out[label]["device.launches.join.semi_mark"] == 2
        assert out[label]["join.semi.mark_batches"] == 8
        assert out[label]["join.semi.mark.tiled"] == 8
        assert out[label]["device.launches.join.semi_lookup"] == 2
        assert out[label]["join.semi.mark_rows"] == 60_000
        assert out[label]["join.host_probe.rows"] == 0
    assert len(out["counts"]) == 5


def test_mesh_stage_places_four_shards(oracle):
    # conftest gives 8 virtual CPU devices; the stage takes four
    out = chip_smoke.stage_mesh(SF, oracle.q1(), batch_size=1 << 13)
    assert len(out["devices"]) == 4
    assert out["first"]["h2d.resident_misses"] > 0
    # a new relation: the table's batches held the copies
    assert out["evidence"]["h2d.resident_misses"] == 0
    assert out["evidence"]["h2d.resident_hits"] == \
        out["first"]["h2d.resident_misses"]
    assert out["evidence"]["h2d.bytes"] == 0
    assert out["evidence"]["expr.cmp_lookups"] == \
        out["evidence"]["mesh.rounds"]


def test_wrong_answer_fails_the_stage(oracle):
    rows = oracle.q1()
    bad = [rows[0][:2] + (rows[0][2] * (1 + 1e-6),) + rows[0][3:]] + rows[1:]
    with pytest.raises(chip_smoke.SmokeFailure, match="vs oracle"):
        chip_smoke.check_rows(bad, rows, "tampered")
    with pytest.raises(chip_smoke.SmokeFailure, match="rows"):
        chip_smoke.check_rows(rows[1:], rows, "short")


def test_stage_without_a_launch_fails():
    with pytest.raises(chip_smoke.SmokeFailure, match="no device launch"):
        chip_smoke.require_on_device("cold", {"device.launches": 0})
    chip_smoke.require_on_device("cold", {"device.launches": 3})


def test_failed_stage_reaches_the_exit_code():
    # no stage is wrapped in a catch-all: main() lets a failed check
    # propagate, and an uncaught exception is a non-zero exit
    code = (
        "import chip_smoke, jax\n"
        "class Dev:\n"
        "    platform = 'tpu'; device_kind = 'fake'\n"
        "    def memory_stats(self): return {}\n"
        "jax.devices = lambda *a: [Dev()]\n"
        "def boom(*a, **k): raise chip_smoke.SmokeFailure('stage failed')\n"
        "chip_smoke.lineitem_path = boom\n"
        "raise SystemExit(chip_smoke.main(['--sf', '1']))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "SmokeFailure: stage failed" in proc.stderr
    assert '"ok"' not in proc.stdout

