"""`expr IN (SELECT ...)` as a semi join, and HAVING over an aggregate the
SELECT list does not name: the parser, the planner's placement of the
semi join, its plan text and wire form, the host index's and the device
probe's answers against a plain Python reference (duplicate build keys,
NULLs on either side, an empty build, a build of one key), and HAVING
pre-filtered by the aggregate itself, on the device where the groups
were folded there."""

import numpy as np
import pytest

from datafusion_tpu.errors import NotSupportedError
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.join import relation as join_rel
from datafusion_tpu.plan.logical import Join, LogicalPlan
from datafusion_tpu.sql import ast
from datafusion_tpu.sql.parser import parse_sql
from test_join import _counts, _delta, _mem_table


def _sorted_rows(rel):
    return sorted(collect(rel).to_rows(),
                  key=lambda r: tuple((v is None, v or 0) for v in r))


@pytest.fixture
def sctx():
    """`p` (probe: int keys with NULLs, a value) and `s` (build: keys
    with duplicates and NULLs, a weight)."""
    rng = np.random.default_rng(11)
    ctx = ExecutionContext(batch_size=256, result_cache=False)
    pk = rng.integers(0, 400, 1_000)
    pk_ok = rng.random(1_000) > 0.1
    sk = np.concatenate([rng.integers(0, 400, 60), [7, 7, 7, 9, 9]])
    sk_ok = np.concatenate([rng.random(60) > 0.2, [True] * 5])
    _mem_table(ctx, "p", {"pk": (pk, pk_ok),
                          "pv": np.arange(1_000, dtype=np.int64)},
               batch_rows=256)
    _mem_table(ctx, "s", {"sk": (sk, sk_ok),
                          "sw": rng.integers(0, 10, 65)}, batch_rows=32)
    ctx._data = dict(pk=pk, pk_ok=pk_ok, sk=sk, sk_ok=sk_ok)
    return ctx


# name -> (subquery WHERE, the build keys it keeps)
SEMI_CASES = {
    "duplicates_and_nulls": ("", lambda d: d["sk_ok"]),
    "empty_build": ("WHERE sw > 100", lambda d: np.zeros(len(d["sk"]), bool)),
    "one_key": ("WHERE sk = 7", lambda d: d["sk_ok"] & (d["sk"] == 7)),
}


@pytest.mark.parametrize("path", ["device", "host", "table"])
@pytest.mark.parametrize("case", list(SEMI_CASES))
def test_semi_join_against_python(sctx, monkeypatch, case, path):
    """`device`: the compare probe; `table`: past the compare probe's
    size, the membership table marked from `s`'s batches; `host`: past
    it, with the table refused too."""
    where, kept = SEMI_CASES[case]
    if path != "device":
        monkeypatch.setattr(join_rel, "_SEMI_DEVICE_MAX", -1)
    if path == "host":
        monkeypatch.setattr(join_rel, "_SEMI_SLOTS_PER_ROW", 0)
    d = sctx._data
    build = set(d["sk"][kept(d)].tolist())
    want = sorted((int(k), v) for v, (k, ok) in enumerate(
        zip(d["pk"], d["pk_ok"])) if ok and int(k) in build)
    s0 = _counts()
    got = _sorted_rows(sctx.sql(
        f"SELECT pk, pv FROM p WHERE pk IN (SELECT sk FROM s {where})"))
    s1 = _counts()
    # each probe row once, whatever the build's duplicates; NULL on
    # either side matches nothing
    assert got == want
    probe_tag = {"device": "device.launches.join.semi",
                 "table": "device.launches.join.semi_lookup"}
    for p, tag in probe_tag.items():
        assert _delta(s0, s1, tag) == (-(-1_000 // 256) if p == path else 0)
    # `s`'s three batches are one shape class: one group, one launch
    assert _delta(s0, s1, "device.launches.join.semi_mark") == (
        1 if path == "table" else 0)
    assert _delta(s0, s1, "join.semi.mark_batches") == (
        -(-65 // 32) if path == "table" else 0)
    if path == "host":
        assert _delta(s0, s1, "join.host_probe.rows") > 0
    else:
        assert _delta(s0, s1, "join.host_probe.rows") == 0
    assert _delta(s0, s1, "join.semi.probe_rows") == (
        0 if path == "host" else 1_000)


def test_the_semi_join_goes_under_the_inner_joins(sctx):
    """Through an inner join's ON equality, the IN column of the build
    side becomes the probe side's: the semi join filters the leftmost
    scan, and the other conjuncts stay a Selection above."""
    _mem_table(sctx, "d", {"dk": np.arange(400, dtype=np.int64),
                           "dn": np.arange(400, dtype=np.int64) % 5})
    plan = sctx._plan(parse_sql(
        "SELECT pv, dn FROM p JOIN d ON p.pk = d.dk "
        "WHERE dk IN (SELECT sk FROM s) AND dn < 3"))
    text = plan.pretty()
    assert "Join: type=semi, on=[#0=#0]" in text
    join = plan
    while not isinstance(join, Join):
        join = join.input
    assert join.join_type == "inner"
    assert isinstance(join.left, Join) and join.left.join_type == "semi"
    d = sctx._data
    build = set(d["sk"][d["sk_ok"]].tolist())
    want = sorted((v, int(k) % 5) for v, (k, ok) in enumerate(
        zip(d["pk"], d["pk_ok"])) if ok and int(k) in build
        and int(k) % 5 < 3)
    got = sorted(collect(sctx.sql(
        "SELECT pv, dn FROM p JOIN d ON p.pk = d.dk "
        "WHERE dk IN (SELECT sk FROM s) AND dn < 3")).to_rows())
    assert got == want


def test_explain_and_the_wire_form_of_a_semi_join(sctx):
    sql = "SELECT pk, pv FROM p WHERE pk IN (SELECT sk FROM s)"
    text = repr(sctx.sql("EXPLAIN " + sql))
    assert "Join: type=semi, on=[#0=#0]" in text
    plan = sctx._plan(parse_sql(sql))
    back = LogicalPlan.from_json_str(plan.to_json_str())
    assert back.pretty() == plan.pretty()
    assert '"join_type":"semi"' in plan.to_json_str()


@pytest.mark.parametrize("rules", ["cpu", "accelerator"])
@pytest.mark.parametrize("having", ["", "HAVING COUNT(*) > 1"])
def test_a_device_build_holds_only_its_groups(monkeypatch, having, rules):
    """`x IN (SELECT k FROM t GROUP BY k [HAVING ...])` whose subquery
    folds on the device (a wide key space of a table that keeps its
    batches): the keyed aggregate's answer is padded to a power of two
    with rows that read as key 0, valid; the semi build holds its groups
    only.  0 is on the probe side and not in t.  Under an accelerator's
    rules the Selection of HAVING runs on the host over the pre-filtered
    groups, and its mask goes up with the build."""
    from datafusion_tpu.exec import relation as exec_rel

    if rules == "accelerator":
        monkeypatch.setattr(exec_rel, "_is_accelerator", lambda d: True)
    rng = np.random.default_rng(5)
    k = rng.integers(1, 1 << 22, 3_000)
    k[:40] = k[40:80]  # groups of two
    uk, counts = np.unique(k, return_counts=True)
    keep = uk if not having else uk[counts > 1]
    assert len(keep) & (len(keep) - 1)  # padded
    pk = np.concatenate([np.zeros(7, np.int64), rng.choice(uk, 50),
                         keep[:20], rng.integers(1, 1 << 22, 100)])
    ctx = ExecutionContext(batch_size=4096, result_cache=False)
    _mem_table(ctx, "t", {"k": k}, batch_rows=4096)
    _mem_table(ctx, "p", {"pk": pk}, batch_rows=64)
    s0 = _counts()
    got = _sorted_rows(ctx.sql(
        f"SELECT pk FROM p WHERE pk IN (SELECT k FROM t GROUP BY k {having})"))
    s1 = _counts()
    assert got == sorted((int(x),) for x in pk if x in set(keep.tolist()))
    assert _delta(s0, s1, "aggregate.reduce.rows") >= len(k)
    assert _delta(s0, s1, "device.launches.join.semi") == -(-len(pk) // 64)
    assert _delta(s0, s1, "join.host_probe.rows") == 0
    assert _delta(s0, s1, "aggregate.having.kept") == (
        len(keep) if having else 0)


@pytest.mark.parametrize("sql, why", [
    ("SELECT pk FROM p WHERE pk IN (SELECT sk FROM s WHERE sk = pv)",
     "correlated"),
    ("SELECT pk FROM p WHERE pk NOT IN (SELECT sk FROM s)", "anti join"),
    ("SELECT pk FROM p WHERE pk IN (SELECT sk FROM s) OR pv = 1",
     "conjunct"),
])
def test_what_is_refused(sctx, sql, why):
    with pytest.raises(NotSupportedError, match=why):
        sctx.sql(sql)


def test_the_parser_reads_in_subquery():
    node = parse_sql("SELECT a FROM t WHERE a IN (SELECT b FROM u "
                     "GROUP BY b HAVING SUM(c) > 3) AND a > 1")
    left = node.selection.left
    assert isinstance(left, ast.SqlInSubquery) and not left.negated
    assert left.subquery.having is not None
    assert isinstance(parse_sql("SELECT a FROM t WHERE a NOT IN "
                                "(SELECT b FROM u)").selection,
                      ast.SqlInSubquery)


@pytest.mark.parametrize("resident_keys", ["wide", "narrow"])
def test_having_on_an_aggregate_outside_the_select_list(resident_keys):
    """`SELECT k ... GROUP BY k HAVING SUM(v) > x`: the sum is a hidden
    column.  Over a wide key space of a table that keeps its batches the
    groups are folded on the device and HAVING keeps its few there (only
    they leave); over a narrow one the host's encoder route pulls the
    groups and the Selection above filters them.  Both against numpy."""
    rng = np.random.default_rng(3)
    n = 40_000
    span = 1 << 22 if resident_keys == "wide" else 300
    k = np.sort(rng.integers(0, span, n))
    v = rng.integers(1, 50, n).astype(np.float64)
    ctx = ExecutionContext(batch_size=4096, result_cache=False)
    _mem_table(ctx, "t", {"k": k, "v": v}, batch_rows=4096)
    uk, inv = np.unique(k, return_inverse=True)
    sums = np.bincount(inv, weights=v)
    bar = float(np.sort(sums)[-40])
    want = sorted(int(x) for x in uk[sums > bar])
    s0 = _counts()
    got = sorted(r[0] for r in collect(ctx.sql(
        f"SELECT k FROM t GROUP BY k HAVING SUM(v) > {bar}")).to_rows())
    s1 = _counts()
    assert got == want and 0 < len(got) < 40
    if resident_keys == "wide":
        assert _delta(s0, s1, "aggregate.having.kept") == len(want)
        assert _delta(s0, s1, "aggregate.having.groups") == len(uk)
        assert _delta(s0, s1, "device.launches.agg.having") == 2
        # the groups' columns stayed on the device: a count and a
        # few rows came back, not the 10^4 groups
        assert _delta(s0, s1, "d2h.bytes") < 16 * 1024
    else:
        assert _delta(s0, s1, "aggregate.having.groups") == 0
        assert _delta(s0, s1, "device.launches.agg.having") == 0


def test_the_fold_is_right_on_keys_out_of_order():
    """The keyed fold's in-order path and its sorting path give the same
    groups: the same rows, clustered and shuffled, under HAVING."""
    rng = np.random.default_rng(9)
    n = 30_000
    k = np.sort(rng.integers(-(1 << 40), 1 << 40, n // 3)).repeat(3)
    v = rng.integers(1, 9, n).astype(np.float64)
    out = []
    for order in (np.arange(n), rng.permutation(n)):
        ctx = ExecutionContext(batch_size=2048, result_cache=False)
        _mem_table(ctx, "t", {"k": k[order], "v": v[order]}, batch_rows=2048)
        out.append(sorted(collect(ctx.sql(
            "SELECT k, SUM(v) FROM t GROUP BY k HAVING SUM(v) > 20")
        ).to_rows()))
    uk, inv = np.unique(k, return_inverse=True)
    sums = np.bincount(inv, weights=v)
    want = sorted((int(a), float(b)) for a, b in zip(uk, sums) if b > 20)
    assert out[0] == out[1] == want
