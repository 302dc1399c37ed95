"""Shared by the tests of the benchmark (`tests/tpubench/`): a copy of the
benchmark in a temporary root, and a run of the harness in this process."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def copy_benchmark(tmp_path) -> str:
    """BENCHMARK.json and tpubench/ copied under `tmp_path`; the root."""
    root = str(tmp_path / "root")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "tpubench"),
                    os.path.join(root, "tpubench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def edit_json(path: str, change) -> None:
    with open(path) as f:
        doc = json.load(f)
    change(doc)
    with open(path, "w") as f:
        json.dump(doc, f)


def run_harness(capsys, root, *argv) -> tuple:
    """(exit code, parsed last stdout line or None, all stdout)."""
    from tpubench.harness import main

    capsys.readouterr()
    code = main(list(argv), root=root)
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1] if out.strip() else ""
    try:
        return code, json.loads(last), out
    except ValueError:
        return code, None, out
