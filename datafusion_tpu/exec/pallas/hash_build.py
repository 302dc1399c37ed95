"""Pallas hash-join build kernel (dense-int keys, VMEM slot tiles).

The join's dense-int fast path direct-addresses its hash table: a
build-side key k occupies slot ``k - kmin``, so "build" means filling
two arrays over the K slots — the row index holding each key and how
many build rows share it (the probe needs the row to gather payload
from; the count decides whether the unique-key device probe is even
legal).  XLA lowers that as two serial scatters on TPU; this kernel
sweeps slot *tiles* instead:

    grid = (K/TILE_S, N/BLOCK_R)

Each step loads one BLOCK_R-row slice of (slot positions, liveness)
into VMEM, builds the one-hot membership of its rows against one
TILE_S slot tile, and reduces row-index max and row count into the
tile's accumulators — both living in VMEM across every row block of
the tile (last grid axis iterates innermost).  Dead rows (padding,
filtered, NULL keys) hit nothing.  `build_slot_table_numpy` is the
parity oracle / host fallback.
"""

from __future__ import annotations

import functools

import numpy as np

# One grid step holds a [BLOCK_R, TILE_S] int32 one-hot and two
# same-shaped temporaries in VMEM: 3 x 1 MiB, well inside Mosaic's
# default scoped budget on every TPU generation.  Both are multiples of
# the (8, 128) int32 tile.
TILE_S = 512
BLOCK_R = 512
# block index 0, spelled int32: under jax_enable_x64 a Python 0 in an
# index map traces as int64, which Mosaic refuses
_Z = np.int32(0)


def _kernel(pos_ref, live_ref, row_ref, cnt_ref, *, tile_s, block_r):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        row_ref[...] = jnp.full((1, tile_s), -1, jnp.int32)
        cnt_ref[...] = jnp.zeros((1, tile_s), jnp.int32)

    # rows ride the sublanes ([block_r, 1] columns), slots the lanes
    # ([1, tile_s] rows): every operand is 2-D, and the reductions over
    # axis 0 leave lane-dense [1, tile_s] accumulators
    pos = pos_ref[...]
    live = live_ref[...] != 0
    s0 = pl.program_id(0) * tile_s
    # absolute row index of each row in this block (the value the max
    # accumulates — the slot remembers WHICH build row holds its key)
    b0 = pl.program_id(1) * block_r
    rows = b0 + lax.broadcasted_iota(jnp.int32, (block_r, 1), 0)
    sidx = s0 + lax.broadcasted_iota(jnp.int32, (block_r, tile_s), 1)
    hit = (pos == sidx) & live
    row_cell = jnp.where(hit, rows, jnp.int32(-1))
    row_ref[...] = jnp.maximum(
        row_ref[...], jnp.max(row_cell, axis=0, keepdims=True)
    )
    # dtype pinned: under jax_enable_x64 an integer sum widens to
    # int64, which Mosaic does not lower
    cnt_ref[...] = cnt_ref[...] + jnp.sum(
        hit.astype(jnp.int32), axis=0, keepdims=True, dtype=jnp.int32
    )


@functools.lru_cache(maxsize=None)
def _build_call(n_pad: int, s_pad: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kern = functools.partial(_kernel, tile_s=TILE_S, block_r=BLOCK_R)
    return pl.pallas_call(
        kern,
        grid=(s_pad // TILE_S, n_pad // BLOCK_R),
        in_specs=[
            pl.BlockSpec((BLOCK_R, 1), lambda s, b: (b, _Z)),
            pl.BlockSpec((BLOCK_R, 1), lambda s, b: (b, _Z)),
        ],
        out_specs=[
            pl.BlockSpec((1, TILE_S), lambda s, b: (_Z, s)),
            pl.BlockSpec((1, TILE_S), lambda s, b: (_Z, s)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, s_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, s_pad), jnp.int32),
        ],
        interpret=interpret,
    )


def _pad_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def build_slot_table(pos, live, num_slots: int, interpret: bool = False):
    """Direct-address build: per slot in [0, num_slots), the max build
    row index whose key maps there (-1 = empty) and the number of build
    rows sharing it.  `pos` is int32 slot positions (key - kmin), `live`
    masks rows out.  Traceable — call under jit."""
    import jax.numpy as jnp

    n = pos.shape[0]
    n_pad = _pad_up(max(n, 1), BLOCK_R)
    s_pad = _pad_up(max(num_slots, 1), TILE_S)
    # 32-bit operands throughout (Mosaic has no 64-bit or 1-D bool
    # lanes): liveness travels as int32, padding rows are dead
    pos = jnp.pad(pos.astype(jnp.int32), (0, n_pad - n))
    live = jnp.pad(live.astype(jnp.int32), (0, n_pad - n))
    slot_row, slot_count = _build_call(n_pad, s_pad, interpret)(
        pos[:, None], live[:, None]
    )
    return slot_row[0, :num_slots], slot_count[0, :num_slots]


def build_slot_table_xla(pos, live, num_slots: int):
    """Stock-XLA scatter build with identical semantics (serial
    scatter on TPU): the path outside the kernel's engagement rule
    (exec/pallas/__init__.py)."""
    import jax.numpy as jnp

    n = pos.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    safe = jnp.where(live, pos, num_slots)  # dead rows land off-table
    slot_row = jnp.full(num_slots + 1, -1, jnp.int32).at[safe].max(
        jnp.where(live, rows, -1)
    )
    slot_count = jnp.zeros(num_slots + 1, jnp.int32).at[safe].add(
        live.astype(jnp.int32)
    )
    return slot_row[:num_slots], slot_count[:num_slots]


def build_slot_table_numpy(pos, live, num_slots: int):
    """Numpy parity oracle / host fallback for `build_slot_table`."""
    pos = np.asarray(pos)
    live = np.asarray(live, bool)
    sel = live & (pos >= 0) & (pos < num_slots)
    slot_row = np.full(num_slots, -1, np.int32)
    slot_count = np.zeros(num_slots, np.int32)
    rows = np.arange(pos.shape[0], dtype=np.int32)
    np.maximum.at(slot_row, pos[sel], rows[sel])
    np.add.at(slot_count, pos[sel], 1)
    return slot_row, slot_count
