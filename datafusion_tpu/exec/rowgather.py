"""A lookup table read on the device as whole rows.

A table that a launch indexes by a batch of scattered positions stays
on the device as `[n / 128, 128]` and is read as a gather of rows with
the lane selected afterwards: 131,072 scattered int32 values cost a
v5e 0.37 ms that way from a 60 MB table against 1.15 ms as an element
gather, and an element gather costs 0.95-1.15 ms from a table of any
size, 16 KB included (PERF.md section 6, PR 28).  Reshaping a 1-D
table inside the launch would copy all of it in every launch, so the
owner of a table lays it out once, on the host or where it is built.

Two users: the join's device probe (`join/relation.py`: slot table and
build payload) and the ordered string-vs-literal compare
(`exec/expression.py`: the per-literal truth table over dictionary
codes, `AuxSpec("cmp_table")`).

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
