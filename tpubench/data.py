"""From a data set's columns to the file the engine is given.

Every cell starts from one Parquet file: a cold cell reads it in every
request, a resident cell has the engine's own reader turn it into the
table it keeps (`entries.SqlEntry`), so nothing here imitates the
reader.  The file is written once per (data set, rows, seed) under the
git-ignored `test/data/bench/tpubench/`, with the oracle's cubes beside
it where the oracle has any, and found again by later runs of that seed;
the newest `KEEP_FILES` are kept.
"""

from __future__ import annotations

import os

import numpy as np

KEEP_FILES = 4  # Parquet files kept per data set (SF-10 is ~0.6 GB each)


def data_dir(root: str) -> str:
    return os.path.join(root, "test", "data", "bench", "tpubench")


def write_parquet(columns: dict, path: str, row_group_rows: int) -> None:
    """One Parquet file with the writer's defaults (snappy, dictionary
    pages), strings as plain string columns, written beside `path` and
    renamed into place."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    def arrow(col, lo, hi):
        if isinstance(col, tuple):
            return pa.DictionaryArray.from_arrays(
                pa.array(col[0][lo:hi]), pa.array(list(col[1])))
        return pa.array(col[lo:hi])

    first = next(iter(columns.values()))
    rows = len(first[0] if isinstance(first, tuple) else first)
    tmp = path + ".tmp"
    writer = None
    try:
        for lo in range(0, rows, row_group_rows):
            hi = min(rows, lo + row_group_rows)
            tbl = pa.table({n: arrow(c, lo, hi) for n, c in columns.items()})
            if writer is None:
                # store_schema=False: a reader sees `string`, as from any
                # other writer, not Arrow's dictionary type
                writer = pq.ParquetWriter(tmp, tbl.schema, store_schema=False)
            writer.write_table(tbl, row_group_size=row_group_rows)
    finally:
        if writer is not None:
            writer.close()
    os.replace(tmp, path)


def _prune(directory: str, prefix: str, keep: int) -> None:
    files = sorted(
        (f for f in os.listdir(directory)
         if f.startswith(prefix) and f.endswith(".parquet")),
        key=lambda f: os.path.getmtime(os.path.join(directory, f)),
    )
    for f in files[:-keep]:
        for path in (os.path.join(directory, f),
                     os.path.join(directory, f[:-len(".parquet")] + ".npz")):
            if os.path.exists(path):
                os.remove(path)


def prepare(dataset, name: str, seed: int, rows: int, root: str,
            threads: int) -> dict:
    """{"path", "columns", "oracle", "cached": False}, made from the seed;
    or, where the file of this (data set, rows, seed) and its oracle's
    cubes are there already, {"path", "oracle", "cached": True}.  numpy
    only, so that it can run on a thread; `parquet_file` does the writing."""
    directory = data_dir(root)
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"{name}_{rows}_seed{seed}.parquet")
    cubes = path[:-len(".parquet")] + ".npz"
    restore = getattr(dataset.Oracle, "from_arrays", None)
    if restore and os.path.exists(path) and os.path.exists(cubes):
        os.utime(path)  # newest: the last to be pruned
        with np.load(cubes) as z:
            return {"path": path, "oracle": restore(dict(z)), "cached": True}
    return {"path": path, "cached": False,
            **dataset.generate(seed, rows, threads)}


def parquet_file(made: dict, row_group_rows: int) -> str:
    """The path of the run's file, written now unless `prepare` found
    it.  Call it on a thread that lives as long as the process: pyarrow's
    native state does not survive the death of a thread that used it
    (`datafusion_tpu/io/io_thread.py`)."""
    path = made["path"]
    if made["cached"]:
        return path
    write_parquet(made.pop("columns"), path, row_group_rows)
    if hasattr(made["oracle"], "arrays"):
        np.savez(path[:-len(".parquet")] + ".npz", **made["oracle"].arrays())
    _prune(os.path.dirname(path), os.path.basename(path).split("_seed")[0],
           KEEP_FILES)
    return path
