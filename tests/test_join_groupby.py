"""GROUP BY numeric keys over a join's output, whose key columns exist
only on the device: the keyed aggregate (`exec/aggregate.py`,
`_KeyedAccumulator`) against a plain numpy reference on seeded data; the
join's int64 payload kept as 32-bit words; two builds in one query, the
second probed by a gathered column; `ORDER BY` a select-list alias; the
word-by-word sort (`exec/wordsort.py`) against `lax.sort`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from datafusion_tpu.exec import aggregate as agg_mod
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.exec.wordsort import key_words, lex_perm
from test_join import _counts, _delta, _mem_table

WIDE = 1 << 40


def _sort_key(row):
    return tuple((1, 0) if v is None else (0, v) for v in row)


def _rows(ctx, sql):
    return sorted(collect(ctx.sql(sql)).to_rows(), key=_sort_key)


def _tables(ctx, suffix, n_build=900, n_probe=6_000, batch_rows=512,
            null_build=False, seed=5):
    """`o<suffix>` (unique sparse int64 keys; payload columns: a small
    one, one past 32 bits with negatives, one nullable) and `l<suffix>`
    probing it with hits, misses and NULL keys, a float to sum and an
    int to filter by.  Returns the arrays."""
    rng = np.random.default_rng(seed)
    okey = np.sort(rng.choice(4 * n_build, n_build, replace=False)
                   ).astype(np.int64) + 3
    osmall = rng.integers(0, 40, n_build)
    owide = rng.integers(-3, 4, n_build) * WIDE + rng.integers(-9, 9, n_build)
    onull = rng.integers(0, 6, n_build)
    onull_ok = rng.random(n_build) > 0.2
    lkey = np.concatenate([rng.choice(okey, n_probe - 600),
                           rng.integers(0, 4 * n_build + 6, 600)])
    rng.shuffle(lkey)
    lkey_ok = rng.random(n_probe) > 0.03
    lval = np.round(rng.uniform(-50, 50, n_probe), 2)
    lsel = rng.integers(0, 100, n_probe)
    lneg = rng.integers(-4, 5, n_probe) * WIDE
    _mem_table(ctx, "o" + suffix, {
        "ok": okey, "osmall": osmall, "owide": owide,
        "onull": (onull, onull_ok)}, batch_rows=256)
    _mem_table(ctx, "l" + suffix, {
        "lk": (lkey, lkey_ok), "lval": lval, "lsel": lsel, "lneg": lneg},
        batch_rows=batch_rows)
    return dict(okey=okey, osmall=osmall, owide=owide, onull=onull,
                onull_ok=onull_ok, lkey=lkey, lkey_ok=lkey_ok, lval=lval,
                lsel=lsel, lneg=lneg)


def _joined(a, how="inner"):
    """The join written out row by row: a list of dicts, None for NULL."""
    row_of = {int(k): i for i, k in enumerate(a["okey"])}
    out = []
    for i in range(len(a["lkey"])):
        b = row_of.get(int(a["lkey"][i])) if a["lkey_ok"][i] else None
        if b is None and how == "inner":
            continue
        r = {"lk": int(a["lkey"][i]) if a["lkey_ok"][i] else None,
             "lval": float(a["lval"][i]), "lsel": int(a["lsel"][i]),
             "lneg": int(a["lneg"][i])}
        for name, col in (("ok", "okey"), ("osmall", "osmall"),
                          ("owide", "owide")):
            r[name] = None if b is None else int(a[col][b])
        r["onull"] = (None if b is None or not a["onull_ok"][b]
                      else int(a["onull"][b]))
        out.append(r)
    return out


def _grouped(rows, keys, keep=lambda r: True):
    """{key tuple: (count, sum of lval, min lsel, max lsel)} in numpy's
    float64, line by line."""
    groups: dict = {}
    for r in rows:
        if not keep(r):
            continue
        g = groups.setdefault(tuple(r[k] for k in keys), [0, 0.0, None, None])
        g[0] += 1
        g[1] += r["lval"]
        g[2] = r["lsel"] if g[2] is None else min(g[2], r["lsel"])
        g[3] = r["lsel"] if g[3] is None else max(g[3], r["lsel"])
    return groups


def _same(got, want_groups):
    want = sorted((k + (c, s, lo, hi) for k, (c, s, lo, hi)
                   in want_groups.items()), key=_sort_key)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        n = len(w) - 4
        assert g[:n] == w[:n] and g[n] == w[n] and g[n + 2:] == w[n + 2:]
        assert g[n + 1] == pytest.approx(w[n + 1], rel=1e-12, abs=1e-9)


AGGS = "COUNT(1), SUM(lval), MIN(lsel), MAX(lsel)"

# name -> (join, key columns, WHERE, predicate on a joined row)
KEY_CASES = {
    "one_key": ("JOIN", ["osmall"], "", lambda r: True),
    "two_keys": ("JOIN", ["osmall", "lsel"], "WHERE lsel < 30",
                 lambda r: r["lsel"] < 30),
    "three_keys": ("JOIN", ["ok", "osmall", "lsel"], "WHERE lsel >= 50",
                   lambda r: r["lsel"] >= 50),
    "past_32_bits_and_negative": ("JOIN", ["owide", "lneg"], "",
                                  lambda r: True),
    "nullable_key": ("JOIN", ["onull", "osmall"], "WHERE lsel > 10",
                     lambda r: r["lsel"] > 10),
    "left_outer_nulls": ("LEFT JOIN", ["ok", "onull"], "", lambda r: True),
    "left_outer_null_probe_key": ("LEFT JOIN", ["lk"], "WHERE lsel < 90",
                                  lambda r: r["lsel"] < 90),
    "nothing_kept": ("JOIN", ["osmall"], "WHERE lsel > 1000",
                     lambda r: False),
    "predicate_on_the_build_side": ("JOIN", ["lsel"], "WHERE owide < 0",
                                    lambda r: r["owide"] < 0),
}


@pytest.mark.parametrize("case", list(KEY_CASES))
def test_numeric_keys_over_join_output_against_numpy(case):
    join, keys, where, keep = KEY_CASES[case]
    ctx = ExecutionContext(batch_size=512, result_cache=False)
    a = _tables(ctx, "_k_" + case)
    sql = (f"SELECT {', '.join(keys)}, {AGGS} FROM l_k_{case} {join} "
           f"o_k_{case} ON l_k_{case}.lk = o_k_{case}.ok {where} "
           f"GROUP BY {', '.join(keys)}")
    s0 = _counts()
    got = _rows(ctx, sql)
    s1 = _counts()
    how = "inner" if join == "JOIN" else "left"
    want = _grouped(_joined(a, how), keys, keep)
    assert len(want) > 20 or case == "nothing_kept"
    _same(got, want)
    # made on the device: no key column pulled, nothing encoded on the host
    assert _delta(s0, s1, "aggregate.key_pull.bytes") == 0
    assert _delta(s0, s1, "aggregate.device_key.groups") == len(want)
    assert _delta(s0, s1, "aggregate.device_key.rows") == sum(
        c for c, *_ in want.values())
    assert _delta(s0, s1, "join.host_probe.rows") == 0
    assert _delta(s0, s1, "device.launches.agg.key_ids") >= 1


def test_a_batch_the_predicate_empties_and_groups_that_span_batches(
        monkeypatch):
    """`lsel` rises along the table, so `lsel < 20` empties most batches
    whole and the kept rows' groups recur in several."""
    monkeypatch.setattr(agg_mod._KeyedAccumulator, "_FLUSH_BATCHES", 4)
    ctx = ExecutionContext(batch_size=256, result_cache=False)
    a = _tables(ctx, "_span", batch_rows=256)
    order = np.argsort(a["lsel"], kind="stable")
    for name in ("lkey", "lkey_ok", "lval", "lsel", "lneg"):
        a[name] = a[name][order]
    _mem_table(ctx, "l_sorted", {"lk": (a["lkey"], a["lkey_ok"]),
                                 "lval": a["lval"], "lsel": a["lsel"],
                                 "lneg": a["lneg"]}, batch_rows=256)
    s0 = _counts()
    got = _rows(ctx, f"SELECT osmall, {AGGS} FROM l_sorted JOIN o_span "
                     "ON l_sorted.lk = o_span.ok WHERE lsel < 20 "
                     "GROUP BY osmall")
    s1 = _counts()
    want = _grouped(_joined(a), ["osmall"], lambda r: r["lsel"] < 20)
    _same(got, want)
    assert len(want) == 40 and min(c for c, *_ in want.values()) > 5
    probes = _delta(s0, s1, "device.launches.join.probe")
    assert probes == -(-6_000 // 256)
    # a batch group the predicate empties appends nothing
    assert _delta(s0, s1, "device.launches.agg.key_ids") == 6
    assert _delta(s0, s1, "device.launches.agg.group") == 2


@pytest.mark.parametrize("flush_batches,min_width,keys,reduces", [
    (2, 8, ["osmall"], 8), (2, 8, ["ok", "lsel"], 2),
    (32, 1024, ["ok", "lsel"], 1)])
def test_the_buffer_reduces_and_grows_as_it_fills(
        monkeypatch, flush_batches, min_width, keys, reduces):
    """Small appends into a buffer that fills many times over: every
    reduce folds rows into groups exactly (40 groups: the buffer stays
    small and reduces often; nearly a group a row: it grows), and at
    the defaults the one reduce is the last."""
    monkeypatch.setattr(agg_mod._KeyedAccumulator, "_FLUSH_BATCHES",
                        flush_batches)
    monkeypatch.setattr(agg_mod._KeyedAccumulator, "_MIN_WIDTH", min_width)
    ctx = ExecutionContext(batch_size=128, result_cache=False)
    sfx = f"_fill{flush_batches}{len(keys)}"
    a = _tables(ctx, sfx, batch_rows=128)
    s0 = _counts()
    got = _rows(ctx, f"SELECT {', '.join(keys)}, {AGGS} FROM l{sfx} "
                     f"JOIN o{sfx} ON l{sfx}.lk = o{sfx}.ok "
                     f"GROUP BY {', '.join(keys)}")
    s1 = _counts()
    want = _grouped(_joined(a), keys)
    _same(got, want)
    assert len(want) == 40 if keys == ["osmall"] else len(want) > 4_000
    assert _delta(s0, s1, "device.launches.agg.reduce") == reduces


def _every_row_a_group(ctx, suffix, n=20_000):
    """`o<suffix>` with `n` unique keys and `l<suffix>` hitting each
    once, out of order: no predicate, so every row is kept and every
    key is its own group."""
    rng = np.random.default_rng(23)
    okey = np.arange(n, dtype=np.int64) * 2 + 7
    lkey = rng.permutation(okey)
    lval = np.round(rng.uniform(-50, 50, n), 2)
    _mem_table(ctx, "o" + suffix, {"ok": okey, "oneg": -okey},
               batch_rows=4_096)
    _mem_table(ctx, "l" + suffix, {"lk": lkey, "lval": lval},
               batch_rows=1_024)
    return lkey, lval, (f"SELECT ok, oneg, COUNT(1), SUM(lval) FROM l{suffix} "
                        f"JOIN o{suffix} ON l{suffix}.lk = o{suffix}.ok "
                        "GROUP BY ok, oneg")


def test_every_row_kept_and_every_key_distinct():
    """The other end from Q3 (which keeps 0.5 % of its rows): 20,000
    rows make 20,000 groups.  The compaction takes each batch as it
    lies, the buffer grows as the groups do, and what the step read is
    counted: every row's mask, two int64 keys and the float summed."""
    ctx = ExecutionContext(batch_size=1_024, result_cache=False)
    lkey, lval, sql = _every_row_a_group(ctx, "_all")
    s0 = _counts()
    got = _rows(ctx, sql)
    s1 = _counts()
    order = np.argsort(lkey)
    assert [r[:3] for r in got] == [(int(k), -int(k), 1) for k in lkey[order]]
    assert [r[3] for r in got] == pytest.approx(list(lval[order]), abs=1e-12)
    assert _delta(s0, s1, "aggregate.device_key.groups") == 20_000
    assert _delta(s0, s1, "aggregate.device_key.rows") == 20_000
    assert _delta(s0, s1, "aggregate.device_key.offered") == 20_000
    assert _delta(s0, s1, "aggregate.device_key.input_bytes") == 20_000 * 24
    assert _delta(s0, s1, "aggregate.key_pull.bytes") == 0
    # 20 batches in one flush; its rows filled the first buffer whole
    assert _delta(s0, s1, "device.launches.agg.reduce") == 1


@pytest.mark.parametrize("room_mb,refused", [(8, False), (1, True)])
def test_the_keyed_buffer_grows_into_what_the_ledger_has_free(
        monkeypatch, room_mb, refused):
    """20,000 groups at 34 B a buffer row, asked for twice over (the
    buffer and the reduce's sorted copy): with 8 MB free the buffer is
    cut to fit and the answer is exact; with 1 MB free the query is
    refused before the buffer is made, and says so."""
    from datafusion_tpu.errors import ExecutionError
    from datafusion_tpu.obs.device import LEDGER

    monkeypatch.setattr(agg_mod._KeyedAccumulator, "_FLUSH_BATCHES", 4)
    ctx = ExecutionContext(batch_size=1_024, result_cache=False)
    lkey, _, sql = _every_row_a_group(ctx, f"_room{room_mb}")
    ctx.sql(sql.replace("GROUP BY", "WHERE ok < 0 GROUP BY"))  # plans
    collect(ctx.sql(f"SELECT COUNT(1) FROM l_room{room_mb} JOIN o_room{room_mb} "
                    f"ON l_room{room_mb}.lk = o_room{room_mb}.ok"))  # builds
    monkeypatch.setenv("DATAFUSION_TPU_HBM_BYTES",
                       str(LEDGER.live_bytes() + (room_mb << 20)))
    s0 = _counts()
    if refused:
        with pytest.raises(ExecutionError, match="bytes of device memory"):
            _rows(ctx, sql)
        return
    got = _rows(ctx, sql)
    s1 = _counts()
    assert [r[0] for r in got] == sorted(int(k) for k in lkey)
    assert _delta(s0, s1, "aggregate.device_key.groups") == 20_000
    # five flushes of 4,096 rows: the buffer found room for 32,768 rows
    # at a time, not the 131,072 it asks for first
    assert _delta(s0, s1, "device.launches.agg.reduce") > 1


def test_two_builds_in_one_query_the_second_probed_by_a_gathered_column():
    """Q3's shape at a small size: lineitem probes orders, the join's
    output probes customer by `o_custkey`, a gathered column; three
    numeric keys; the alias in ORDER BY, DESC, LIMIT."""
    rng = np.random.default_rng(17)
    n_ord, n_cust = 3_000, 300
    okey = (np.arange(n_ord) // 8) * 32 + np.arange(n_ord) % 8 + 1
    ocust = rng.integers(1, n_cust + 1, n_ord)
    odate = rng.integers(9_000, 9_400, n_ord)
    oprio = rng.integers(0, 2, n_ord)
    counts = rng.integers(1, 8, n_ord)
    lkey = np.repeat(okey, counts)
    lship = np.repeat(odate, counts) + rng.integers(1, 122, len(lkey))
    lprice = np.round(rng.uniform(900, 100_000, len(lkey)), 2)
    ldisc = rng.integers(0, 11, len(lkey)) / 100.0
    segs = ["AUTOMOBILE", "BUILDING", "FURNITURE"]
    cseg = rng.integers(0, 3, n_cust)
    ctx = ExecutionContext(batch_size=1024, result_cache=False)
    _mem_table(ctx, "lineitem3", {"l_orderkey": lkey, "l_shipdate": lship,
                                  "l_extendedprice": lprice,
                                  "l_discount": ldisc}, batch_rows=1024)
    _mem_table(ctx, "orders3", {"o_orderkey": okey, "o_custkey": ocust,
                                "o_orderdate": odate,
                                "o_shippriority": oprio}, batch_rows=1024)
    _mem_table(ctx, "customer3", {"c_custkey": np.arange(1, n_cust + 1),
                                  "c_mktsegment": [segs[i] for i in cseg]},
               batch_rows=1024)
    day = 9_200
    sql = ("SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)) AS "
           "revenue, o_orderdate, o_shippriority FROM lineitem3 "
           "JOIN orders3 ON lineitem3.l_orderkey = orders3.o_orderkey "
           "JOIN customer3 ON orders3.o_custkey = customer3.c_custkey "
           f"WHERE c_mktsegment = 'BUILDING' AND o_orderdate < {day} "
           f"AND l_shipdate > {day} GROUP BY l_orderkey, o_orderdate, "
           "o_shippriority ORDER BY revenue DESC, o_orderdate LIMIT 10")
    line_order = np.searchsorted(okey, lkey)
    keep = ((lship > day) & (odate[line_order] < day)
            & (cseg[ocust[line_order] - 1] == 1))
    revenue = np.bincount(line_order[keep],
                          weights=(lprice * (1 - ldisc))[keep],
                          minlength=n_ord)
    live = np.flatnonzero(np.bincount(line_order[keep], minlength=n_ord))
    first = live[np.lexsort((odate[live], -revenue[live]))][:10]
    want = [(int(okey[i]), int(odate[i]), int(oprio[i]), revenue[i])
            for i in first]
    assert len(live) > 100
    for query in range(2):
        s0 = _counts()
        got = collect(ctx.sql(sql)).to_rows()
        s1 = _counts()
        assert [g[:3] for g in got] == [w[:3] for w in want]
        assert [g[3] for g in got] == pytest.approx([w[3] for w in want],
                                                    rel=1e-12)
        assert _delta(s0, s1, "join.host_probe.rows") == 0
        assert _delta(s0, s1, "aggregate.key_pull.bytes") == 0
        assert _delta(s0, s1, "aggregate.device_key.groups") == len(live)
        probes = 2 * -(-len(lkey) // 1024)
        assert _delta(s0, s1, "device.launches.join.probe") == probes
        # the orders build's three payload columns, customer's one
        assert _delta(s0, s1, "join.probe.gathers") == probes // 2 * (3 + 1)
        if query == 0:
            assert _delta(s0, s1, "join.build.dense") == 2
            continue
        # the second query: both builds found again, every copy resident,
        # nothing of the keys or the answer's groups crosses the link
        assert _delta(s0, s1, "join.build.reuse") == 2
        assert _delta(s0, s1, "join.build.rows") == 0
        assert _delta(s0, s1, "h2d.resident_misses") == 0
        assert _delta(s0, s1, "h2d.bytes") == 0
        assert _delta(s0, s1, "d2h.bytes") < 2_048
        assert _delta(s0, s1, "device.launches.topk.final") == 1
        assert _delta(s0, s1, "device.launches.topk.gather") == 1


def test_a_float_key_over_join_output_is_encoded_on_the_host_and_counted():
    """The shape the device key step does not serve: its pull is
    counted, in `aggregate.key_pull.bytes` and in `d2h.bytes`."""
    ctx = ExecutionContext(batch_size=512, result_cache=False)
    a = _tables(ctx, "_fkey")
    s0 = _counts()
    got = _rows(ctx, "SELECT lval, COUNT(1) FROM l_fkey JOIN o_fkey "
                     "ON l_fkey.lk = o_fkey.ok GROUP BY lval")
    s1 = _counts()
    want: dict = {}
    for r in _joined(a):
        want[r["lval"]] = want.get(r["lval"], 0) + 1
    assert got == sorted(want.items())
    pulled = _delta(s0, s1, "aggregate.key_pull.bytes")
    assert pulled >= 8 * 6_000
    assert _delta(s0, s1, "d2h.bytes") >= pulled
    assert _delta(s0, s1, "aggregate.device_key.groups") == 0


def test_the_string_key_path_keeps_its_counters():
    """Q12's shape: two dictionary-coded keys over a join's output ride
    the aggregate's own launches (`_device_group_ids`): no key step, no
    pull, a launch a probe and the aggregate's."""
    rng = np.random.default_rng(23)
    okey = np.arange(2_000, dtype=np.int64) * 4 + 1
    prio = [("1-URGENT", "2-HIGH", "3-MEDIUM")[i]
            for i in rng.integers(0, 3, 2_000)]
    lkey = rng.choice(okey, 5_000)
    mode = [("MAIL", "SHIP", "RAIL", "AIR")[i]
            for i in rng.integers(0, 4, 5_000)]
    ctx = ExecutionContext(batch_size=512, result_cache=False)
    _mem_table(ctx, "o_str", {"ok": okey, "prio": prio}, batch_rows=512)
    _mem_table(ctx, "l_str", {"lk": lkey, "mode": mode}, batch_rows=512)
    sql = ("SELECT mode, prio, COUNT(1) FROM l_str JOIN o_str "
           "ON l_str.lk = o_str.ok WHERE mode = 'MAIL' OR mode = 'SHIP' "
           "GROUP BY mode, prio")
    collect(ctx.sql(sql))
    s0 = _counts()
    got = _rows(ctx, sql)
    s1 = _counts()
    want: dict = {}
    row_of = {int(k): i for i, k in enumerate(okey)}
    for k, m in zip(lkey.tolist(), mode):
        if m in ("MAIL", "SHIP"):
            key = (m, prio[row_of[k]])
            want[key] = want.get(key, 0) + 1
    assert got == sorted(k + (n,) for k, n in want.items()) and len(got) == 6
    launches = {k: _delta(s0, s1, k) for k in s1
                if k.startswith("device.launches")}
    assert {k: v for k, v in launches.items() if v} == {
        "device.launches": 11, "device.launches.join.probe": 10,
        "device.launches.agg.group": 1}
    for silent in ("aggregate.key_pull.bytes", "aggregate.device_key.groups",
                   "aggregate.device_key.rows", "h2d.resident_misses",
                   "h2d.bytes", "join.host_probe.rows"):
        assert _delta(s0, s1, silent) == 0, silent
    assert _delta(s0, s1, "d2h.bytes") < 1_024
    assert _delta(s0, s1, "join.build.reuse") == 1


@pytest.mark.parametrize("order,desc", [
    ("total", True), ("total", False), ("n", True)])
def test_order_by_a_select_list_alias(order, desc):
    """`SUM(x) AS total ... ORDER BY total DESC LIMIT k` plans as `ORDER
    BY SUM(x)` does, over a scan as over a join."""
    rng = np.random.default_rng(31)
    g = rng.integers(0, 50, 4_000)
    v = np.round(rng.uniform(0, 10, 4_000), 3)
    ctx = ExecutionContext(batch_size=1024, result_cache=False)
    _mem_table(ctx, "t_alias", {"g": g, "v": v}, batch_rows=1024)
    text = ("SELECT g, SUM(v) AS total, COUNT(1) AS n FROM t_alias "
            "GROUP BY g ORDER BY {} " + ("DESC" if desc else "") + ", g "
            "LIMIT 7")
    got = collect(ctx.sql(text.format(order))).to_rows()
    spelled = {"total": "SUM(v)", "n": "COUNT(1)"}[order]
    assert got == collect(ctx.sql(text.format(spelled))).to_rows()
    total = np.bincount(g, weights=v, minlength=50)
    n = np.bincount(g, minlength=50)
    by = total if order == "total" else n
    first = sorted(range(50), key=lambda i: (-by[i] if desc else by[i], i))[:7]
    assert [r[0] for r in got] == first
    assert [r[2] for r in got] == [int(n[i]) for i in first]


def test_an_alias_that_names_nothing_is_still_an_error():
    from datafusion_tpu.errors import InvalidColumnError

    ctx = ExecutionContext(result_cache=False)
    _mem_table(ctx, "t_bad", {"g": np.arange(4), "v": np.arange(4.0)})
    with pytest.raises(InvalidColumnError):
        ctx.sql("SELECT g, SUM(v) AS total FROM t_bad GROUP BY g "
                "ORDER BY revenue")


# -- the word-by-word sort ---------------------------------------------------

def _operands(n=3_000):
    rng = np.random.default_rng(41)
    f64 = np.concatenate([
        rng.standard_normal(n - 7) * 10.0 ** rng.integers(-200, 200, n - 7),
        [0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan, 1.0]])
    f32 = (rng.standard_normal(n) * 10.0 ** rng.integers(-20, 20, n)
           ).astype(np.float32)
    f32[:5] = [0.0, -0.0, np.inf, -np.inf, np.nan]
    return {
        "bool": rng.random(n) < 0.3,
        "int64": rng.integers(-3, 3, n) * WIDE + rng.integers(-5, 5, n),
        "uint64": rng.integers(0, 1 << 62, n).astype(np.uint64) * 4,
        "int32": rng.integers(-100, 100, n).astype(np.int32),
        "uint32": rng.integers(0, 1 << 32, n).astype(np.uint32),
        "int8": rng.integers(-100, 100, n).astype(np.int8),
        "float64": f64, "float32": f32,
    }


@pytest.mark.parametrize("names", [
    ["bool", "int64"], ["float64"], ["float64", "int64"], ["uint64", "int32"],
    ["int8", "bool", "float32"], ["uint32"], ["float32", "float64"],
    ["bool", "float64", "bool", "int64", "bool"]])
def test_word_sort_is_lax_sort(names):
    """The permutation of `lex_perm` over `key_words` is the stable
    multi-key `lax.sort`'s, NaNs, infinities and both zeros included."""
    ops = [_operands()[n] for n in names]
    n = len(ops[0])
    got = jax.jit(lambda *o: lex_perm(
        [w for x in o for w in key_words(x)]))(*ops)
    want = jax.jit(lambda *o: jax.lax.sort(
        o + (jnp.arange(n, dtype=jnp.int32),), num_keys=len(o),
        is_stable=True)[-1])(*ops)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_the_float64_words_a_tpu_traces_order_as_the_values(monkeypatch):
    """Off the CPU a float64 is sorted by the images of its (f32 hi,
    f32 lo) halves: the same order for values a float32 pair holds."""
    rng = np.random.default_rng(43)
    hi = np.round(rng.uniform(-1e6, 1e6, 4_000), 2).astype(np.float32)
    lo = (rng.standard_normal(4_000) * np.spacing(hi) / 4).astype(np.float32)
    x = np.concatenate([hi.astype(np.float64) + lo.astype(np.float64),
                        np.repeat(hi[:300].astype(np.float64), 2),
                        [0.0, -0.0, np.inf, -np.inf, np.nan, 1e-20, -1e-20]])
    want = jax.jit(lambda v: jax.lax.sort(
        (v, jnp.arange(len(x), dtype=jnp.int32)), num_keys=1,
        is_stable=True)[-1])(x)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    words = jax.jit(lambda v: tuple(key_words(v)))(x)
    assert len(words) == 2 and all(w.dtype == jnp.uint32 for w in words)
    got = jax.jit(lambda v: lex_perm(key_words(v)))(x)
    assert np.array_equal(np.asarray(got), np.asarray(want))


I32 = np.iinfo(np.int32)
I64 = np.iinfo(np.int64)


@pytest.mark.parametrize("lo,hi,n_words", [
    (I32.min, I32.max, 1),  # the whole of 32 bits, both ends met
    (I32.min, I32.max + 1, 2), (I32.min - 1, I32.max, 2),
    (I64.min, I64.max, 2), (0, 0, 1)])
def test_int64_payload_words(lo, hi, n_words):
    from datafusion_tpu.exec.rowgather import LANES, pad_rows, take_rows
    from datafusion_tpu.join.relation import _int64_of, _int64_words

    rng = np.random.default_rng(47)
    col = np.concatenate([
        rng.integers(lo, hi, 998, dtype=np.int64, endpoint=True), [lo, hi]])
    assert _int64_words(col.astype(np.int32)) is None
    assert _int64_words(col.astype(np.float64)) is None
    idx = np.concatenate([rng.integers(0, 1_000, 298), [998, 999]]
                         ).astype(np.int32)
    words = _int64_words(col)
    assert len(words) == n_words and all(w.itemsize == 4 for w in words)
    placed = tuple(
        jnp.asarray(np.pad(w, (0, pad_rows(1_000) - 1_000))
                    ).reshape(-1, LANES) for w in words)
    got = jax.jit(lambda words, i: _int64_of(
        tuple(take_rows(w, i) for w in words)))(placed, idx)
    assert got.dtype == jnp.int64
    assert np.array_equal(np.asarray(got), col[idx])


def test_an_empty_int64_payload_column_is_one_word():
    from datafusion_tpu.join.relation import _int64_words

    (word,) = _int64_words(np.zeros(0, np.int64))
    assert word.dtype == np.int32 and len(word) == 0
