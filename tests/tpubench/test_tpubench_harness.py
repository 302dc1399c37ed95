"""The harness end to end in the sandbox: the rehearsal prints the
contract's last line and no time under a device metric's name; without a
TPU there is no result."""

import os

import pytest

from bench_helpers import (REPO, STAR2_ENTRY_POINTS, add_star2, copy_benchmark,
                           edit_json, run_harness, snapshot_files)
from tpubench.spec import Spec, SpecError

CELLS = [w["name"] for w in Spec(REPO).bench["workloads"]]
LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                  "compared"}
ROWS = "20000"


@pytest.fixture
def root(tmp_path):
    return copy_benchmark(tmp_path)


def _counter_metrics(cell, kind):
    spec = Spec(REPO)
    return {m["name"] for m in spec.metrics_of(cell, kind)
            if m["source"] == "program_counter"}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_prints_the_contract_line_with_counts_only(
        capsys, root, cell):
    code, line, out = run_harness(
        capsys, root, "--workload", cell, "--seed", "3", "--seconds", "1",
        "--trace", "1", "--rehearse-rows", ROWS)
    assert code == 0
    assert set(line) == LAST_LINE_KEYS  # no breakdown: no device plane on a CPU
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": line["device"]["count"],
                              "memory_peak_bytes": 0}
    # counts are what a CPU run can say; times, rates and shares are not
    assert set(line["metrics"]) <= _counter_metrics(cell, "per_layer")
    if Spec(REPO).traffic(Spec(REPO).cell(cell)["traffic"])["entry"] != "serve":
        # (queries of milliseconds bunch up: a serving window here may fuse
        # three or more by chance, into a program the warm-up did not make)
        assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["launches_per_query"]["value"] > 0
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert "REHEARSAL" in out
    # progress also went to the log under chiprun_out/, and the trace is gone
    assert os.path.getsize(os.path.join(root, "chiprun_out", "tpubench",
                                        cell + ".log")) > 0
    assert not os.path.exists(os.path.join(
        root, "test", "data", "bench", "tpubench", "trace", cell))


def test_untraced_rehearsal_names_no_end_to_end_number(capsys, root):
    code, line, _ = run_harness(
        capsys, root, "--workload", "q1_sf10_warm", "--seed", "4",
        "--seconds", "1", "--trace", "0", "--rehearse-rows", ROWS)
    assert code == 0 and set(line) == LAST_LINE_KEYS
    assert line["metrics"] == {} and line["correct"] is True


def test_queries_sent_together_to_the_server_run_fused(tmp_path):
    """What the serving cell's warm-up counts on: two same-year queries
    submitted together reach one serving window and run as one program
    (the harness tries up to `TOGETHER_TRIES` times), so the window's
    chance fusions find their programs made."""
    from datafusion_tpu.utils.metrics import METRICS
    from tpubench import data as tdata
    from tpubench import entries, traffic
    from tpubench.harness import TOGETHER_TRIES

    spec = Spec(REPO)
    ds = spec.dataset("tpch_lineitem")
    path = str(tmp_path / "lineitem.parquet")
    tdata.write_parquet(ds.generate(2, 6_000, threads=1)["tables"]["lineitem"],
                        path, 2_000)
    maker = traffic.RequestMaker(
        spec.traffic("q6_streams8"),
        lambda t, p: spec.query("tpch_lineitem", t).format(**ds.bind(t, p)))
    entry = spec.entry("serve")("cpu", {}, {"lineitem": path}, entries.Spans())
    try:
        def fused():
            return METRICS.snapshot()["counts"].get("serve.megabatch_queries", 0)

        for group in maker.grid(["year"], 2):
            before = fused()
            for _ in range(TOGETHER_TRIES):
                assert len(entry.send_together(group)) == 2
                if fused() - before == 2:
                    break
            assert fused() - before == 2, group[0].queries[0].sql
    finally:
        entry.close()


FACT_ROWS, DIM_ROWS = 4_000, 1_000


@pytest.mark.parametrize("entry", STAR2_ENTRY_POINTS)
def test_a_deployment_of_two_tables_arrives_as_files(capsys, root, entry):
    """A data set of two tables with its oracle, a configuration, a mix, a
    cell and an entry point, added to the copy as files (BENCHMARK.json is
    the one file edited), run right under every entry point; rows and bytes
    are those of both tables, each at its own count."""
    before = snapshot_files(root)
    add_star2(root)
    code, line, _ = run_harness(
        capsys, root, "--workload", "star2." + entry, "--seed", "2147483659",
        "--seconds", "0.5", "--trace", "1", "--rehearse-rows", str(FACT_ROWS))
    assert code == 0 and line["correct"] is True and line["failed"] == 0
    queries = line["attempted"]
    assert queries > 0
    # rows_per_s's numerator: the rows of the tables the query names
    assert line["metrics"]["rows_scanned"]["value"] == \
        (FACT_ROWS + DIM_ROWS) * queries
    # f_key, f_v of fact and d_key, d_grp of dim: 8 bytes a value resident
    assert line["metrics"]["bytes_needed"]["value"] == \
        (FACT_ROWS * 16 + DIM_ROWS * 16) * queries
    assert line["compared"]["device.launches"]["value"] >= queries
    made = sorted(os.listdir(os.path.join(root, "test", "data", "bench",
                                          "tpubench")))
    assert [f for f in made if f.endswith(".parquet")] == [
        f"star2_{FACT_ROWS}_seed2147483659.dim.parquet",
        f"star2_{FACT_ROWS}_seed2147483659.fact.parquet"]
    after = snapshot_files(root)
    assert all(after[p] == content for p, content in before.items())


def test_a_counter_the_guard_holds_at_zero_makes_the_run_not_correct(
        capsys, root):
    """`guarantees.device.must_be_zero` is the configuration's: a counter
    named there that the window bumps reads `correct: false`, with every
    answer right."""
    edit_json(os.path.join(root, "tpubench", "configs", "tpch_lineitem_sf10.json"),
              lambda d: d["guarantees"]["device"]["must_be_zero"].append(
                  "queries_admitted"))
    code, line, _ = run_harness(
        capsys, root, "--workload", "q1_sf10_warm", "--seed", "3",
        "--seconds", "0.3", "--trace", "0", "--rehearse-rows", ROWS)
    assert code == 0 and line["correct"] is False and line["failed"] == 0
    assert list(line)[-1] == "compared"
    assert line["compared"]["wrong_answers"] == {"value": 0, "at_most": 0}
    assert 0 <= line["compared"]["worst_rel_gap"]["value"] <= 1e-9
    admitted = line["compared"]["queries_admitted"]
    assert admitted["value"] > 0 and admitted["at_most"] == 0
    assert line["compared"]["device.launches"]["at_least"] == 1


@pytest.mark.parametrize("strip", [
    lambda d: d["guarantees"].update(device="every request reaches the device"),
    lambda d: d["guarantees"]["device"].pop("must_be_zero"),
    lambda d: d.pop("guarantees"),
])
def test_a_configuration_without_the_machine_read_guard_does_not_load(
        capsys, root, strip):
    edit_json(os.path.join(root, "tpubench", "configs", "h2o_g1_1e7.json"), strip)
    with pytest.raises(SpecError, match="must_launch"):
        Spec(root).config("h2o_g1_1e7")
    with pytest.raises(SpecError):
        run_harness(capsys, root, "--workload", "h2o_1e7_groupby", "--seed",
                    "3", "--seconds", "0.3", "--trace", "0",
                    "--rehearse-rows", ROWS)


def test_every_run_prints_each_number_compared_beside_its_limit(capfd, root):
    from tpubench.harness import main

    assert main(["--workload", "q1_sf10_warm", "--seed", "5", "--seconds",
                 "0.2", "--trace", "0", "--rehearse-rows", ROWS], root=root) == 0
    err = capfd.readouterr().err.strip().splitlines()
    assert [" ".join(l.split()[:3] + l.split()[4:]) for l in err[-5:]] == [
        "tpubench compared: wrong_answers at_most 0",
        "tpubench compared: worst_rel_gap at_most 1e-09",
        "tpubench compared: device.launches at_least 1",
        "tpubench compared: aggregate.host_routed_slots at_most 0",
        "tpubench compared: sort.host_routed_runs at_most 0"]
    values = [float(l.split()[3]) for l in err[-5:]]
    assert values[0] == 0 and 0 <= values[1] <= 1e-9 and values[2] >= 1
    assert values[3:] == [0, 0]


def test_the_same_seed_sends_the_same_requests(capsys, root):
    def sqls():
        _, _, out = run_harness(
            capsys, root, "--workload", "q6_sf10_streams", "--seed", "11",
            "--seconds", "0.2", "--trace", "0", "--rehearse-rows", "4000")
        log = os.path.join(root, "chiprun_out", "tpubench", "q6_sf10_streams.log")
        detail = [l for l in open(log) if "] first requests " in l][-1]
        return detail.split("] first requests ", 1)[1]

    assert sqls() == sqls()


def test_a_wrong_answer_is_counted_and_not_correct(capsys, root, monkeypatch):
    from tpubench import check

    monkeypatch.setattr(check, "RTOL", 0.0)
    monkeypatch.setattr(check.diff_rows, "__defaults__", (0.0, None))
    code, line, _ = run_harness(
        capsys, root, "--workload", "q1_sf10_warm", "--seed", "3",
        "--seconds", "0.3", "--trace", "0", "--rehearse-rows", ROWS)
    # at rtol 0 the engine's sums differ from numpy's in the last digits
    assert code == 0 and line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    assert line["compared"]["wrong_answers"]["value"] == line["attempted"]
    gap = line["compared"]["worst_rel_gap"]
    assert 0 < gap["value"] < 1e-12 and gap["at_most"] == 0.0


def test_without_a_tpu_there_is_no_result(capsys, root):
    edit_json(os.path.join(root, "tpubench", "configs", "tpch_lineitem_sf10.json"),
              lambda d: d.update(rows=2000))
    code, line, out = run_harness(
        capsys, root, "--workload", "q1_sf10_warm", "--seed", "1",
        "--seconds", "1", "--trace", "0")
    assert code == 2 and line is None
    assert not any(l.lstrip().startswith("{") for l in out.splitlines())


def test_rehearsal_needs_an_explicit_cpu_pin(capsys, root, monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS")
    code, line, _ = run_harness(
        capsys, root, "--workload", "q1_sf10_warm", "--seed", "1",
        "--seconds", "1", "--trace", "0", "--rehearse-rows", ROWS)
    assert code == 2 and line is None


def test_the_harness_sets_no_engine_variable():
    import subprocess
    import sys

    src = subprocess.run(
        [sys.executable, "-c",
         "import os, pathlib; print(sum('DATAFUSION_TPU_' in p.read_text() "
         "for p in pathlib.Path('tpubench').rglob('*.py') "
         "if p.name != 'aot_rehearsal.py'))"],
        cwd=REPO, capture_output=True, text=True, check=True)
    assert src.stdout.strip() == "0"
