"""From a data set's tables to the files the engine is given.

Every cell starts from one Parquet file per table: a cold cell reads them
in every request, a resident cell has the engine's own reader turn each
into the table it keeps (`entries/sql.py`), so nothing here imitates the
reader.  The files are written once per (data set, rows, seed) under the
git-ignored `test/data/bench/tpubench/`, with the oracle's cubes beside
them (once per data set) where the oracle has any, and found again by
later runs of that seed; the newest `KEEP_FILES` sets are kept.
"""

from __future__ import annotations

import os
import re

import numpy as np

KEEP_FILES = 4  # sets of files kept per data set (SF-10 lineitem is ~0.6 GB)


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
    """Keep the `keep` newest sets of `<prefix>_seed<n>.*` files: a set is
    one seed's table files and its oracle's arrays."""
    sets: dict = {}  # "<prefix>_seed<n>" -> its files
    for f in os.listdir(directory):
        m = re.match(re.escape(prefix) + r"_seed\d+(?=\.)", f)
        if m:
            sets.setdefault(m.group(), []).append(os.path.join(directory, f))
    for stem in sorted(sets, key=lambda s: max(map(os.path.getmtime, sets[s]))
                       )[:-keep]:
        for path in sets[stem]:
            os.remove(path)


def prepare(dataset, name: str, seed: int, rows: int, root: str,
            threads: int) -> dict:
    """{"stem", "paths", "tables", "oracle", "cached": False}, made from
    the seed with `rows` rows in the data set's first table; or, where the
    files of this (data set, rows, seed) and its oracle's arrays are there
    already, {"stem", "paths", "oracle", "cached": True}.  `paths` is
    {table: file}, each `<stem>.<table>.parquet`; the arrays are
    `<stem>.npz`.  numpy only, so that it can run on a thread;
    `parquet_files` does the writing."""
    directory = data_dir(root)
    os.makedirs(directory, exist_ok=True)
    stem = os.path.join(directory, f"{name}_{rows}_seed{seed}")
    paths = {table: f"{stem}.{table}.parquet" for table in dataset.TABLES}
    restore = getattr(dataset.Oracle, "from_arrays", None)
    if restore and all(map(os.path.exists, [stem + ".npz", *paths.values()])):
        for path in paths.values():
            os.utime(path)  # newest: the last to be pruned
        with np.load(stem + ".npz") as z:
            return {"stem": stem, "paths": paths, "oracle": restore(dict(z)),
                    "cached": True}
    return {"stem": stem, "paths": paths, "cached": False,
            **dataset.generate(seed, rows, threads)}


def parquet_files(made: dict, row_group_rows: int) -> tuple:
    """({table: path}, {table: rows}) of the run's files, written now
    unless `prepare` found them; the row counts are the files' own, so a
    run that found them says the same without the columns.  Call it on a
    thread that lives as long as the process: pyarrow's native state does
    not survive the death of a thread that used it
    (`datafusion_tpu/io/io_thread.py`)."""
    import pyarrow.parquet as pq

    paths = made["paths"]
    if not made["cached"]:
        tables = made.pop("tables")
        for table, path in paths.items():
            write_parquet(tables[table], path, row_group_rows)
        stem = made["stem"]
        if hasattr(made["oracle"], "arrays"):
            np.savez(stem + ".npz", **made["oracle"].arrays())
        _prune(os.path.dirname(stem),
               os.path.basename(stem).split("_seed")[0], KEEP_FILES)
    return paths, {t: pq.read_metadata(p).num_rows for t, p in paths.items()}
