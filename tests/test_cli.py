"""Console CLI tests, including the reference's golden smoketest.

The golden file `test/data/smoketest-expected.txt` is the output the
pre-rewrite reference console produced (`scripts/smoketest.sh:68-89`
diffs with `diff -bBZ -I seconds`); the rewrite never re-attached it.
Here it passes: DDL executes, geo UDFs exist, rows print.
"""

import io
import os
import subprocess
import sys

from datafusion_tpu.cli import Console, make_context, run_script

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "test", "data")


def _run_sql_text(sql_text: str, tmp_path) -> list[str]:
    script = tmp_path / "script.sql"
    script.write_text(sql_text)
    out = io.StringIO()
    console = Console(make_context(), out=out)
    run_script(console, str(script))
    return out.getvalue().splitlines()


def _strip_timing(lines: list[str]) -> list[str]:
    # the golden harness ignores timing lines (diff -I seconds)
    return [l.rstrip() for l in lines if "seconds" not in l and l.strip()]


class TestGoldenSmoketest:
    def test_smoketest_matches_golden_output(self, tmp_path):
        sql = open(os.path.join(DATA, "smoketest.sql")).read()
        # the docker harness mounted fixtures at /test/data; rewrite to
        # this checkout's path
        sql = sql.replace("'/test/data/", f"'{DATA}/")
        got = _strip_timing(_run_sql_text(sql, tmp_path))
        want = open(os.path.join(DATA, "smoketest-expected.txt")).read().splitlines()
        # the golden file's first line is the banner, printed by main()
        want = [l.rstrip() for l in want if l.strip() and l != "DataFusion Console"]
        assert got == want


class TestConsole:
    def test_ddl_then_query(self, tmp_path):
        lines = _run_sql_text(
            "CREATE EXTERNAL TABLE people (id INT, first_name VARCHAR(100)) "
            f"STORED AS CSV WITH HEADER ROW LOCATION '{DATA}/people.csv';\n"
            "SELECT id, first_name FROM people WHERE id > 1;",
            tmp_path,
        )
        assert lines.count("Executing query ...") == 2
        assert not any(l.startswith("Error") for l in lines)
        data_lines = _strip_timing(lines)[2:]
        assert data_lines and all("\t" in l for l in data_lines)

    def test_error_does_not_kill_console(self, tmp_path):
        lines = _run_sql_text(
            "SELECT * FROM nonexistent;\nSELECT 1 + 1;",
            tmp_path,
        )
        assert any(l.startswith("Error:") for l in lines)

    def test_multiline_statement_accumulates(self, tmp_path):
        lines = _run_sql_text(
            "CREATE EXTERNAL TABLE people (id INT, first_name VARCHAR(100))\n"
            "STORED AS CSV WITH HEADER ROW\n"
            f"LOCATION '{DATA}/people.csv';\n"
            "SELECT COUNT(1)\nFROM people;",
            tmp_path,
        )
        assert lines.count("Executing query ...") == 2
        assert not any(l.startswith("Error") for l in lines)


class TestCliSubprocess:
    def test_script_mode_end_to_end(self, tmp_path):
        script = tmp_path / "s.sql"
        script.write_text(
            "CREATE EXTERNAL TABLE cities (city VARCHAR(100), lat DOUBLE, lng DOUBLE) "
            f"STORED AS CSV WITHOUT HEADER ROW LOCATION '{DATA}/uk_cities.csv';\n"
            "SELECT city, lat + lng FROM cities WHERE lat > 52.0;\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        proc = subprocess.run(
            [sys.executable, "-m", "datafusion_tpu.cli", "--script", str(script)],
            capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.startswith("DataFusion Console")
        assert proc.stdout.count("Executing query ...") == 2

    def test_interactive_quit(self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        proc = subprocess.run(
            [sys.executable, "-m", "datafusion_tpu.cli"],
            input="SELECT 1 + 2;\nquit\n",
            capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr
        assert "Executing query ..." in proc.stdout

    def test_interactive_pty_ctrl_c_clears_ctrl_d_exits(self, tmp_path):
        """Line-editor behavior under a real terminal (reference
        linereader.rs:47-103): Ctrl-C abandons a half-typed statement
        and returns to a fresh prompt; Ctrl-D exits; history persists
        to the history file."""
        import pty
        import select
        import time as _time

        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            PYTHONPATH=REPO,
            HOME=str(tmp_path),  # history file lands here
        )
        pid, fd = pty.fork()
        if pid == 0:  # child: exec the CLI on the pty
            os.chdir(REPO)
            os.execvpe(
                sys.executable,
                [sys.executable, "-m", "datafusion_tpu.cli"],
                env,
            )
        out = b""

        def read_until(marker: bytes, timeout=60.0):
            nonlocal out
            deadline = _time.monotonic() + timeout
            while marker not in out:
                rest = deadline - _time.monotonic()
                assert rest > 0, f"timeout waiting for {marker!r}; got {out!r}"
                r, _, _ = select.select([fd], [], [], rest)
                if r:
                    try:
                        out += os.read(fd, 4096)
                    except OSError:
                        break
            return out

        try:
            read_until(b"datafusion> ")
            os.write(fd, b"SELECT 1 +\n")  # half a statement
            # the bare continuation prompt only appears after a newline
            # ("datafusion> " would false-match a plain "> " search)
            read_until(b"\n> ")
            # let readline enter its read loop before interrupting (the
            # prompt prints a beat before the handler is in place)
            _time.sleep(0.3)
            os.write(fd, b"\x03")  # Ctrl-C: abandon the buffer
            try:
                read_until(b"^C", timeout=10.0)
            except AssertionError:
                os.write(fd, b"\x03")  # rare: signal landed pre-loop
                read_until(b"^C", timeout=30.0)
            read_until(b"datafusion> ")  # fresh prompt, session alive
            os.write(fd, b"SELECT 2 + 3;\n")
            read_until(b"Executing query ...")
            read_until(b"5")
            read_until(b"datafusion> ")
            _time.sleep(0.3)  # same settle as before Ctrl-C
            os.write(fd, b"\x04")  # Ctrl-D: exit
            deadline = _time.monotonic() + 60
            retried = False
            while True:
                done, status = os.waitpid(pid, os.WNOHANG)
                if done:
                    break
                if not retried and _time.monotonic() > deadline - 50:
                    os.write(fd, b"\x04")
                    retried = True
                assert _time.monotonic() < deadline, "CLI did not exit on Ctrl-D"
                _time.sleep(0.05)
            assert os.waitstatus_to_exitcode(status) == 0
        finally:
            os.close(fd)
            try:
                os.kill(pid, 9)
            except ProcessLookupError:
                pass
        hist = tmp_path / ".datafusion_tpu_history"
        assert hist.exists(), "readline history file not written"
        assert "SELECT 2 + 3;" in hist.read_text()


class TestStatementSplitting:
    def test_semicolon_inside_string_literal(self, tmp_path):
        # a ';' inside a SQL string literal must not terminate the
        # statement (quote-aware splitting): a LOCATION path with ';'
        import shutil

        src = os.path.join(DATA, "people.csv")
        dst = tmp_path / "people;v2.csv"
        shutil.copy(src, dst)
        lines = _run_sql_text(
            "CREATE EXTERNAL TABLE people (id INT, first_name VARCHAR(100)) "
            f"STORED AS CSV WITH HEADER ROW LOCATION '{dst}';\n"
            "SELECT COUNT(1) FROM people;\n",
            tmp_path,
        )
        assert lines.count("Executing query ...") == 2
        assert not any(l.startswith("Error") for l in lines)

    def test_escaped_quote_in_literal(self):
        from datafusion_tpu.sql.parser import split_statements_partial

        stmts, rest = split_statements_partial("SELECT 'it''s;ok'; SELECT 2")
        assert stmts == ["SELECT 'it''s;ok'"]
        assert rest == " SELECT 2"

    def test_comment_with_apostrophe_does_not_open_literal(self):
        from datafusion_tpu.sql.parser import split_statements_partial

        stmts, rest = split_statements_partial(
            "-- don't trip on this\nSELECT 1;\nSELECT 2;\n"
        )
        assert stmts == ["SELECT 1", "SELECT 2"]
        assert rest.strip() == ""
        # a tail ending mid-comment keeps its raw text so appended
        # input continues the comment until a newline arrives
        stmts, rest = split_statements_partial("SELECT 1; -- note")
        assert stmts == ["SELECT 1"]
        assert rest == " -- note"

    def test_block_comment_with_semicolon(self):
        from datafusion_tpu.sql.parser import (
            split_statements,
            split_statements_partial,
        )

        assert split_statements("SELECT /* a;b */ 1;") == ["SELECT  1"]
        # unclosed block comment: raw tail kept so a REPL can close it
        stmts, rest = split_statements_partial("SELECT 1; /* note")
        assert stmts == ["SELECT 1"]
        assert rest == " /* note"

    def test_script_trailing_comment_no_error(self, tmp_path):
        lines = _run_sql_text("SELECT 1 + 1;\n-- trailing comment\n", tmp_path)
        assert lines.count("Executing query ...") == 1
        assert not any(l.startswith("Error") for l in lines)


class TestTimingMode:
    def test_timing_toggle_and_output(self, tmp_path):
        import io

        from datafusion_tpu.cli import Console, make_context

        out = io.StringIO()
        csv = tmp_path / "t.csv"
        csv.write_text("a,b\n1,2.5\n3,4.5\n")
        c = Console(make_context("cpu"), out=out)
        c.execute("\\timing")
        c.execute(
            f"CREATE EXTERNAL TABLE t (a INT, b DOUBLE) STORED AS CSV "
            f"WITH HEADER ROW LOCATION '{csv}'"
        )
        c.execute("SELECT a, b FROM t WHERE a > 0")
        text = out.getvalue()
        assert "Timing is on." in text
        assert "Timing: " in text
        assert "parse=" in text
        assert "Counters: " in text and "scan.rows=2" in text
        c.execute("\\timing")
        assert "Timing is off." in out.getvalue()

    def test_timing_as_bare_script_line(self, tmp_path):
        # psql convention: a backslash command is a LINE, no semicolon —
        # it must not fall into the statement splitter
        import io

        from datafusion_tpu.cli import Console, make_context, run_script

        csv = tmp_path / "t.csv"
        csv.write_text("a\n1\n")
        script = tmp_path / "s.sql"
        script.write_text(
            "\\timing\n"
            f"CREATE EXTERNAL TABLE t (a INT) STORED AS CSV WITH HEADER ROW "
            f"LOCATION '{csv}';\n"
            "SELECT a FROM t;\n"
        )
        out = io.StringIO()
        c = Console(make_context("cpu"), out=out)
        run_script(c, str(script))
        text = out.getvalue()
        assert "Timing is on." in text
        assert "Error" not in text
        assert "Timing: " in text


class TestProfilerTrace:
    def test_trace_writes_profile(self, tmp_path, monkeypatch):
        """`utils.profiling.trace(dir)` around one `ctx.sql` + `collect`
        writes a profile that holds the engine's stage timers as
        `dftpu.*` spans on the host plane, nested inside the query's."""
        import numpy as np

        from datafusion_tpu.datatypes import DataType, Field, Schema
        from datafusion_tpu.exec.batch import make_host_batch
        from datafusion_tpu.exec.context import ExecutionContext
        from datafusion_tpu.exec.datasource import MemoryDataSource
        from datafusion_tpu.exec.materialize import collect
        from datafusion_tpu.utils.profiling import trace
        from spans_helper import host_spans

        # the chip's path on the CPU: staged pipeline, compressed wire
        monkeypatch.setenv("DATAFUSION_TPU_PREFETCH", "1")
        monkeypatch.setenv("DATAFUSION_TPU_WIRE", "always")
        schema = Schema([Field("k", DataType.INT64, False),
                         Field("x", DataType.FLOAT64, False)])
        batches = [
            make_host_batch(schema, [np.arange(100) % 3, np.arange(100.0)])
            for _ in range(3)
        ]
        ctx = ExecutionContext(device="cpu", result_cache=False)
        ctx.register_datasource("t", MemoryDataSource(schema, batches))
        out_dir = str(tmp_path / "prof")
        with trace(out_dir):
            table = collect(ctx.sql(
                "SELECT k, SUM(x), COUNT(1) FROM t WHERE x > 1 GROUP BY k"))
        assert table.num_rows == 3
        spans = host_spans(out_dir)
        names = {s.name for s in spans}
        assert {"dftpu.query", "dftpu.parse", "dftpu.h2d.encode",
                "dftpu.pipeline.wait", "dftpu.pipeline.stage",
                "dftpu.device.dispatch", "dftpu.h2d.dispatch"} <= names
        (query,) = [s for s in spans if s.name == "dftpu.query"]
        assert query.stats["qid"] >= 1
        parse = next(s for s in spans if s.name == "dftpu.parse")
        assert parse.thread == query.thread and parse.end <= query.start
        for name, same_thread in (("dftpu.pipeline.wait", True),
                                  ("dftpu.device.dispatch", True),
                                  ("dftpu.h2d.encode", False),
                                  ("dftpu.pipeline.stage", False)):
            inner = [s for s in spans if s.name == name]
            assert inner
            for s in inner:  # inside the query's span in time,
                assert query.start <= s.start and s.end <= query.end
                # on its thread or on a prefetch thread
                assert (s.thread == query.thread) == same_thread


class TestReferenceBenches:
    def test_runs_and_reports_all_five_targets(self, capsys):
        # the reference's commented-out bench list, revived
        # (/root/reference/Cargo.toml:50-68)
        import json

        from benchmarks.reference_benches import main

        main()
        out = json.loads(capsys.readouterr().out.strip())
        assert set(out) == {
            "read_csv_ms", "filter_primitive_ms", "sql_ms",
            "dataframe_ms", "udf_udt_ms",
        }
        assert all(v > 0 for v in out.values())
