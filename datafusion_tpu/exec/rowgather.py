"""A lookup table read on the device as whole rows, through a window
where the indices in hand fit one.

A table that a launch indexes by a batch of scattered positions stays
on the device as `[n / 128, 128]` and is read as a gather of rows with
the lane selected afterwards: 131,072 scattered int32 values cost a
v5e 0.37 ms that way from a 60 MB table against 1.15 ms as an element
gather, and an element gather costs 0.95-1.15 ms from a table of any
size, 16 KB included (PERF.md section 6, PR 28).  Reshaping a 1-D
table inside the launch would copy all of it in every launch, so the
owner of a table lays it out once, on the host or where it is built.

Three users: the join's device probe (`join/relation.py`: slot table and
build payload), the semi join's membership table (a byte a slot, marked
by `set_rows_window`, by tile where the keys are clustered, and read
through a window of as many bytes as the int32 tables' window holds)
and the ordered string-vs-literal compare
(`exec/expression.py`: the per-literal truth table over dictionary
codes, `AuxSpec("cmp_table")`).

**The window** (`take_rows_window`, the join's probe).  A row gather
costs by where its table lies: 131,072 clustered indices read a 240 MB
table from HBM in 2.02 ms and a 1 MB piece of it in 0.33 ms (PERF.md
section 6, PR 28).  Traffic that is clustered by the lookup key
(TPC-H's lineitem by `l_orderkey`: a batch's keys span 512 KB of the
240 MB slot table, and the slots it finds 128 KB of each 60 MB payload
column) is read through a window of `WINDOW_ROWS` rows that the launch
picks from the batch's own indices: the smallest and the largest row
among the indices that count, a start clamped inside the table, and,
on the device, the choice: every such row inside the window, gather
from the window; else gather from the whole table as `take_rows` does.
Nothing but the indices in hand chooses, and a table no larger than
the window compiles no choice at all.  On a v5e (PERF.md section 6,
PR 33), 131,072 keys a launch: a gather through the window 0.237 ms
and its lane select 0.110; the join's whole probe launch over TPC-H's
orders (60 M slots, 15 M rows) with clustered keys 2.78 -> 0.73 ms
with one payload column and 7.54 -> 1.31 ms with three, with uniform
keys 1.78 -> 1.80 ms, and over a 1.5 M-row build with uniform keys
0.633 -> 0.658 ms: the choice costs the side that does not take the
window about 0.025 ms a launch.

**The mark by tile** (`set_rows_window`, the semi join's membership
table).  An element scatter costs a v5e ~6 ns an index whatever the
sort of the indices or the size of the target: 131,072 clustered keys a
launch into a 60 M-slot table took 0.81 ms through the window and 1.04
whole (PERF.md section 6).  Keys clustered as TPC-H's lineitem
is by `l_orderkey` keep each block of `MARK_BLOCK` consecutive entries
inside a few table rows, so the launch cuts its entries into blocks,
takes each block's smallest live row as its base, makes the block's
`[MARK_TILE_ROWS, LANES]` tile densely (entry `s` of the tile is set
where a live entry of the block lies `s` slots past the base: a compare
and an `any` over the block), and places the tiles from their bases
with one row scatter whose update is `max`, an OR: 4,096 rows of 128
lanes where the element scatter wrote 131,072 single slots, right
whatever the earlier marks and the tiles of neighbouring blocks that
share a row.  The launch goes by tile where every block's live rows
span at most `MARK_TILE_ROWS` and the window is taken (a table no
larger than the window is its own), and scatters as before where not:
the indices in hand choose, in the program.  On a v5e, 131,072
clustered keys a launch, 458 launches into a 60 M-slot table: 0.814 ->
0.120 ms a mark; shuffled keys take the scatter, 1.141 ms a mark on
either side (PERF.md section 6).

The truth table travels a bit a code, 32 to a word (`pack_bits`,
`take_bits`): a row then holds 4,096 codes, and a table of one row,
which is every dictionary up to that size (seven years of dates are
2,556), needs no gather at all: XLA reads the row in place and the
lookup is the lane select alone.  On a v5e, two lookups by 131,072
codes and a masked sum took 2.12 ms as element gathers, 0.55 ms from
bool or int8 rows, 0.59 ms from int32 rows, and from packed words
0.21 ms at 4,096 codes and 0.59 ms at 2^20 (PERF.md section 6, PR 31).
"""

from __future__ import annotations

import numpy as np

LANE_BITS = 7
LANES = 1 << LANE_BITS
_WORD_SHIFT = 5
WORD_BITS = 1 << _WORD_SHIFT
# rows of the window `take_rows_window` reads through: 1 MB of int32.
# 131,072 clustered indices, rows + lane select, on a v5e: 2.02 ms from
# a 240 MB table, 0.33 ms from a 1 MB window of it (PERF.md section 6,
# PR 28); inside the join's probe 0.35 ms, gather 0.237 + select 0.110
# (PR 33)
WINDOW_ROWS = 2_048
# entries of a block that `set_rows_window` marks as one tile, and the
# rows of the tile (module doc).  TPC-H's lineitem (1..7 lines an
# order, 8 of every 32 order keys) keeps a block of 128 lines' keys
# inside at most 3 rows of the membership table, and one of 256 inside
# 4; 4 rows leave blocks of 128 a row to spare.  131,072 clustered keys
# a mark on a v5e, in a first form of the program: blocks of 128 or 256
# with 4 rows 0.148 / 0.146 ms, 128 with 3 rows 0.360 (a compare 384
# wide), tiles as a one-hot contraction 0.172-0.193; the element
# scatter 0.814 (PERF.md section 6)
MARK_BLOCK = 128
MARK_TILE_ROWS = 4


def pad_rows(n: int) -> int:
    """`n` rounded up to whole rows of `LANES`."""
    return -(-n // LANES) * LANES


def take_rows(table, idx):
    """`table.reshape(-1)[idx]` of a `[rows, LANES]` table, as a
    gather of rows and a lane select.  `idx` is int32 and in range."""
    import jax.numpy as jnp
    from jax import lax

    rows = table[idx >> LANE_BITS]
    lane = lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    picked = jnp.where(lane == (idx & (LANES - 1))[:, None], rows,
                       jnp.zeros((), table.dtype))
    if table.dtype == jnp.bool_:
        return jnp.any(picked, axis=1)
    return jnp.sum(picked, axis=1, dtype=table.dtype)


def _window_start(rows: int, idx, live, window_rows: int):
    """(first row, taken) of the window of `window_rows` rows for the
    live indices of `idx` (module doc): from the smallest live row,
    clamped inside a table of `rows` rows, taken where the largest live
    row lies in it too."""
    import jax.numpy as jnp

    row = idx >> LANE_BITS
    low = jnp.min(jnp.where(live, row, rows))
    high = jnp.max(jnp.where(live, row, -1))
    start = jnp.clip(low, 0, rows - window_rows).astype(jnp.int32)
    return start, high - start < window_rows


def take_rows_window(tables, idx, live, window_rows: int = WINDOW_ROWS):
    """`take_rows(t, idx)` of every table of the pytree `tables`
    (`[rows, LANES]` each, one `rows` for all), read through a window
    of `window_rows` rows where the launch finds that the batch in
    hand fits one.  `live` (bool, `idx`'s shape) says which indices
    count: the window starts at the smallest live row, clamped so it
    ends inside the table, and is taken, on the device, when the
    largest live row lies in it too (no live index at all fits);
    otherwise every table is read whole, as `take_rows` reads it.  An
    index that is not live is pointed at the window's first entry and
    its result is the caller's to mask.  Tables of at most
    `window_rows` rows are read whole, by shape, with no conditional
    in the program.

    Returns `(values, took)`: the pytree of gathered arrays and a bool
    scalar, whether the window was taken."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    leaves = jax.tree.leaves(tables)
    rows = leaves[0].shape[0] if leaves else 0
    if rows <= window_rows:
        safe = jnp.where(live, idx, 0)
        return (jax.tree.map(lambda t: take_rows(t, safe), tables),
                jnp.zeros((), jnp.bool_))
    start, took = _window_start(rows, idx, live, window_rows)
    first = start << LANE_BITS
    safe = jnp.where(live, idx, first)

    # The windows are cut whatever the decision (1 MB a table) and held
    # outside the conditional: XLA otherwise moves the cut into the
    # branch, hands it the whole tables, and copies a table it had
    # prefetched back out of fast memory there (60 MB a launch).  Each
    # branch ends in a barrier too: without it the lane select, the
    # same in both, moves out behind the conditional and the gathered
    # rows and a 64 MB iota become its outputs (AOT for a v5e, PR 33).
    windows = lax.optimization_barrier(jax.tree.map(
        lambda t: lax.dynamic_slice(t, (start, jnp.int32(0)),
                                    (window_rows, LANES)), tables))

    def through_window(_, windows):
        return lax.optimization_barrier(
            jax.tree.map(lambda w: take_rows(w, safe - first), windows))

    def whole(tables, _):
        return lax.optimization_barrier(
            jax.tree.map(lambda t: take_rows(t, safe), tables))

    return lax.cond(took, through_window, whole, tables, windows), took


def _scatter_rows(table, idx, live, first_row):
    """`table` with entry `idx - first_row * LANES` set wherever `live`:
    one element scatter of True.  An index that is not live points past
    the end and is dropped."""
    import jax.numpy as jnp

    r = jnp.where(live, (idx >> LANE_BITS) - first_row, table.shape[0])
    return table.at[r, idx & (LANES - 1)].set(True, mode="drop")


def _mark_blocks(idx, live, rows: int):
    """`idx` and `live` cut into blocks of `MARK_BLOCK` (the last padded
    with entries that are not live), and each block's smallest and
    largest live row (`rows` and -1 where none is live)."""
    import jax.numpy as jnp

    pad = -idx.shape[0] % MARK_BLOCK
    if pad:
        idx = jnp.pad(idx, (0, pad))
        live = jnp.pad(live, (0, pad))
    idx, live = (a.reshape(-1, MARK_BLOCK) for a in (idx, live))
    row = idx >> LANE_BITS
    low = jnp.min(jnp.where(live, row, rows), axis=1)
    high = jnp.max(jnp.where(live, row, -1), axis=1)
    return idx, live, low, high


def _tile_rows(table, idx, live, low, first_row):
    """`table` with the blocks' entries set, a tile a block (module doc):
    `[MARK_TILE_ROWS, LANES]` bools made densely from the block's live
    entries, placed from its smallest live row `low` by a row scatter
    that ORs.  Every live entry of a block lies in its tile's rows; the
    tile of a block with none is placed past the end and dropped."""
    import jax.numpy as jnp

    off = jnp.where(live, idx - (low << LANE_BITS)[:, None], -1)
    slots = jnp.arange(MARK_TILE_ROWS * LANES, dtype=jnp.int32)
    tiles = jnp.any(off[:, :, None] == slots, axis=1).reshape(-1, LANES)
    at = ((low - first_row)[:, None]
          + jnp.arange(MARK_TILE_ROWS, dtype=jnp.int32)).reshape(-1)
    return table.at[at].max(tiles, mode="drop")


def set_rows_window(table, idx, live, window_rows: int):
    """`table` (bool `[rows, LANES]`) with entry `idx` set wherever
    `live`, and whether the launch marked it by tile (a bool scalar).
    By tile (module doc) where every block of `MARK_BLOCK` entries
    keeps its live entries inside `MARK_TILE_ROWS` rows, through a
    window of `window_rows` rows where the live indices fit one
    (`take_rows_window`'s rule); else one element scatter of True, as
    before, through the window where they fit it and into the whole
    table where not.  A table of at most `window_rows` rows is its own
    window, by shape.  Duplicate indices set the same value, whichever
    way.  Donate `table` to the launch: the update is then made in
    place, every branch included."""
    import jax.numpy as jnp
    from jax import lax

    rows = table.shape[0]
    bidx, blive, low, high = _mark_blocks(idx, live, rows)
    fits = jnp.all(high - low < MARK_TILE_ROWS)
    if rows <= window_rows:
        return lax.cond(
            fits, lambda t: _tile_rows(t, bidx, blive, low, 0),
            lambda t: _scatter_rows(t, idx, live, 0), table), fits
    start, took = _window_start(rows, idx, live, window_rows)
    at = (start, jnp.int32(0))
    # the window is cut outside the conditional and every branch ends
    # in a barrier, as `take_rows_window` does: the donated table is
    # then written in place whichever branch runs (AOT for a v5e)
    window = lax.optimization_barrier(
        lax.dynamic_slice(table, at, (window_rows, LANES)))

    def tiled(t, w):
        return lax.optimization_barrier(lax.dynamic_update_slice(
            t, _tile_rows(w, bidx, blive, low, start), at))

    def through_window(t, w):
        return lax.optimization_barrier(lax.dynamic_update_slice(
            t, _scatter_rows(w, idx, live, start), at))

    def whole(t, _):
        return lax.optimization_barrier(_scatter_rows(t, idx, live, 0))

    branch = jnp.where(took, jnp.where(fits, 0, 1), 2)
    return (lax.switch(branch, (tiled, through_window, whole), table, window),
            took & fits)


def pack_bits(bits: np.ndarray, capacity: int) -> np.ndarray:
    """Host side: `bool[n]` as `uint32[rows, LANES]`, entry `i` in bit
    `i % 32` of word `i // 32`, zero-filled up to whole rows that hold
    at least `capacity >= n` entries."""
    n_words = pad_rows(-(-capacity // WORD_BITS))
    padded = np.zeros(n_words * WORD_BITS, dtype=bool)
    padded[: len(bits)] = bits
    words = np.packbits(padded, bitorder="little").view("<u4")
    return words.reshape(-1, LANES)


def take_bits(words, idx):
    """Entry `idx` of a `pack_bits` table, as bool.  `idx` is int32
    and in range (`words.size * WORD_BITS` entries)."""
    import jax.numpy as jnp

    word = take_rows(words, idx >> _WORD_SHIFT)
    return ((word >> (idx & (WORD_BITS - 1)).astype(jnp.uint32)) & 1) != 0
