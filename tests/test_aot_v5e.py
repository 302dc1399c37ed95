"""Programs of the main path compiled for a described TPU v5e by the XLA:TPU
compiler installed here (no chip attached: nothing runs).  The compiler
refuses, or crashes on, shapes the CPU backend takes: the semi join's
probe once selected its window of build keys by a 64-bit select-and-sum
that aborted XLA:TPU (PR 39), found by the chip before this test."""

import pytest


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.sharding import SingleDeviceSharding

    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no topology, no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("masked", [False, True])
def test_the_semi_probe_compiles_for_a_v5e(one_chip, masked):
    import jax
    import jax.numpy as jnp

    from datafusion_tpu.join.relation import _semi_probe_fn

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    rows = 131_072
    flags = shape(rows, jnp.bool_) if masked else None
    _semi_probe_fn().lower(shape(rows, jnp.int64), flags, flags,
                           shape(1_024, jnp.int64),
                           shape(1_024, jnp.bool_)).compile()


@pytest.mark.parametrize("masked", [False, True])
def test_the_semi_builds_live_rows_compile_for_a_v5e(one_chip, masked):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from datafusion_tpu.join.relation import _live_rows_fn

    def shape(dtype):
        return jax.ShapeDtypeStruct((1_024,), dtype, sharding=one_chip)

    _live_rows_fn().lower(shape(jnp.int64), np.int32(621), shape(jnp.bool_),
                          shape(jnp.bool_) if masked else None).compile()


def _lineitem_core():
    """The pipeline core of Q4's subquery predicate, `l_commitdate <
    l_receiptdate`, over (l_orderkey, l_commitdate, l_receiptdate)."""
    from datafusion_tpu.datatypes import DataType, Field, Schema
    from datafusion_tpu.exec.relation import _PipelineCore
    from datafusion_tpu.plan.expr import BinaryExpr, Column, Operator

    schema = Schema([Field(n, DataType.INT64, False) for n in (
        "l_orderkey", "l_commitdate", "l_receiptdate")])
    pred = BinaryExpr(Column(1), Operator.Lt, Column(2))
    return _PipelineCore.build(schema, pred, None, {}, {})


def _compiled_mark(one_chip, masked, rung):
    """The mark launch over a group of `rung` batches at TPC-H SF-10's
    size: 131,072 lineitem rows a batch into a table of 60 M one-byte
    slots, donated with the count of the batches by tile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from datafusion_tpu.join.relation import _semi_mark_fn

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = 131_072
    col = shape((rows,), jnp.int64)
    mask = shape((rows,), jnp.bool_) if masked else None
    entries = (((col, col, col), (None, None, None), mask),) * rung
    return _semi_mark_fn(_lineitem_core(), 0, (1, 2)).lower(
        shape(TABLE, jnp.bool_), shape((), jnp.int32), shape((), jnp.int64),
        shape((), jnp.int64), entries, np.zeros(rung, np.int32), (),
        ()).compile()


# the membership table of TPC-H SF-10's 60 M order keys
TABLE = (468_750, 128)


@pytest.mark.parametrize("masked", [False, True])
def test_the_semi_mark_compiles_for_a_v5e(one_chip, masked):
    """A group of one batch.  Every branch (by tile, scatter through the
    window, scatter into the whole table) writes the table in place: the
    program's temporaries stay far below the table's 60 MB."""
    memory = _compiled_mark(one_chip, masked, 1).memory_analysis()
    if memory is not None:
        assert memory.temp_size_in_bytes < TABLE[0] * TABLE[1] // 8


@pytest.mark.parametrize("masked", [False, True])
def test_a_group_of_256_marks_compiles_for_a_v5e(one_chip, masked):
    """The largest rung a scan's group takes (`fuse_group_max()`): the
    donated table is the program's output too (aliased, not copied),
    and the scan's steps update it in place: the program's peak is its
    arguments, the stacked entries (24 B a row, a byte more masked), one
    stacked 32-bit word being laid out as rows (4 B a row) and less than
    an eighth of the table besides, where a copy of the table a step, or
    one kept beside the carry, would add all 60 MB of it."""
    memory = _compiled_mark(one_chip, masked, 256).memory_analysis()
    if memory is not None:
        table = TABLE[0] * TABLE[1]
        stacked = 256 * 131_072 * (24 + masked)
        relaid = 256 * 131_072 * 4
        assert memory.alias_size_in_bytes >= table
        assert (memory.peak_memory_in_bytes - memory.argument_size_in_bytes
                < stacked + relaid + table // 8)


@pytest.mark.parametrize("masked", [False, True])
def test_the_semi_lookup_compiles_for_a_v5e(one_chip, masked):
    import jax
    import jax.numpy as jnp

    from datafusion_tpu.join.relation import _semi_lookup_fn

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = 131_072
    flags = shape((rows,), jnp.bool_) if masked else None
    _semi_lookup_fn().lower(
        shape((rows,), jnp.int64), flags, flags,
        shape((468_750, 128), jnp.bool_), shape((), jnp.int64),
        shape((), jnp.int64)).compile()
