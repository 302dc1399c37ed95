"""The control of `correct`: the data sets' answers computed in float32, the
nearest precision below the float64 the configurations state, put in the
engine's place.  The oracle has to refuse them (`tests/tpubench/
test_tpubench_control.py` at a test's size); at a configuration's own size:

    python3 tests/tpubench/control_f32.py --config tpch_lineitem_sf10 --seeds 11,12,13

prints, a seed, the widest relative gap of the float32 answers to the oracle's
(the upper reading beside `check.RTOL`; PERF.md section 4).  Host numpy only:
it needs no chip and imports nothing of the engine.
"""

import argparse
import os
import sys

import numpy as np

F32 = np.float32


class Answer:
    """What an oracle's `check` reads of an engine result."""

    def __init__(self, columns: list):
        self.columns = columns

    def to_rows(self) -> list:
        return list(zip(*(c.tolist() for c in self.columns)))


def _group_sums(gid: np.ndarray, groups: np.ndarray, values: np.ndarray):
    """Per-group sums of `values` accumulated in float32, for the group
    ids in `groups` (sorted), each of which has rows."""
    order = np.argsort(gid, kind="stable")
    starts = np.searchsorted(gid[order], groups)
    return np.add.reduceat(values.astype(F32)[order], starts)


def tpch_lineitem(ds, tables: dict, oracle, template: str, params: dict) -> Answer:
    c = tables["lineitem"]
    plain = {n: (v[0] if isinstance(v, tuple) else v) for n, v in c.items()}
    qty, price, disc, tax = (plain[n].astype(F32) for n in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    if template == "q6":
        y = params["year"]
        days = ds.BASE_DATE + plain["l_shipdate"].astype("timedelta64[D]")
        d = params["discount_pct"]
        keep = ((days >= np.datetime64(f"{y}-01-01"))
                & (days < np.datetime64(f"{y + 1}-01-01"))
                & (np.rint(plain["l_discount"] * 100) >= d - 1)
                & (np.rint(plain["l_discount"] * 100) <= d + 1)
                & (plain["l_quantity"] < params["quantity"]))
        return Answer([np.array([(price * disc)[keep].sum(dtype=F32)], float)])
    cutoff = int((ds.END_DATE - np.timedelta64(params["delta"], "D")
                  - ds.BASE_DATE).astype(int))
    keep = plain["l_shipdate"] <= cutoff
    gid = (plain["l_returnflag"].astype(np.int64) * len(ds.STATUSES)
           + plain["l_linestatus"])[keep]
    groups = np.unique(gid)
    one = np.float32(1)
    disc_price = price * (one - disc)
    sums = [_group_sums(gid, groups, w[keep]) for w in (
        qty, price, disc_price, disc_price * (one + tax), disc)]
    n = np.bincount(gid)[groups]
    return Answer([
        np.array([ds.FLAGS[g // len(ds.STATUSES)] for g in groups], object),
        np.array([ds.STATUSES[g % len(ds.STATUSES)] for g in groups], object),
        *(s.astype(float) for s in sums[:4]),
        *((s / n.astype(F32)).astype(float) for s in (sums[0], sums[1], sums[4])),
        n])


def h2o_g1(ds, tables: dict, oracle, template: str, params: dict) -> Answer:
    """The oracle's own keys, as the engine would give them, with the
    aggregates of float columns summed in float32."""
    c = {n: (v[0] if isinstance(v, tuple) else v) for n, v in tables["x"].items()}
    keys, aggs = ds.QUESTIONS[template]
    want_keys, want_vals = oracle.answer(template)
    dom = oracle.dom
    gid = np.zeros(len(c["v1"]), np.int64)
    for k in keys:
        zero_based = c[k].astype(np.int64) - (0 if ds.KINDS[k] == "str" else 1)
        gid = gid * dom[k] + zero_based
    groups = np.zeros(len(want_keys[0]), np.int64)
    for k, col in zip(keys, want_keys):
        groups = groups * dom[k] + col
    order = np.argsort(groups)
    vals = []
    for (fn, col), want in zip(aggs, want_vals):
        if ds.KINDS[col] != "f64":
            vals.append(want)
            continue
        total = np.empty(len(groups), F32)
        total[order] = _group_sums(gid, groups[order], c[col])
        if fn == "mean":
            total = total / np.bincount(gid)[groups].astype(F32)
        vals.append(total.astype(float))
    width = {"id1": 3, "id2": 3, "id3": 10}
    out_keys = [np.array([f"id%0{width[k]}d" % (v + 1) for v in col])
                if ds.KINDS[k] == "str" else col + 1
                for k, col in zip(keys, want_keys)]
    return Answer(out_keys + vals)


CONTROLS = {"tpch_lineitem": tpch_lineitem, "h2o_g1": h2o_g1}


def widest_gap(spec, dataset: str, seed: int, rows: int, queries: list) -> dict:
    """{template: (what the oracle says of the float32 answer, its widest
    relative gap)} for (template, params) in `queries`."""
    from tpubench.check import Worst

    ds = spec.dataset(dataset)
    made = ds.generate(seed, rows, threads=min(8, os.cpu_count() or 1))
    out = {}
    for template, params in queries:
        worst = Worst()
        answer = CONTROLS[dataset](ds, made["tables"], made["oracle"],
                                   template, params)
        said = made["oracle"].check(template, params, answer, worst)
        out[template] = (said, worst.gap)
    return out


QUERIES = {
    "tpch_lineitem": [("q1", {"delta": 90}),
                      ("q6", {"year": 1995, "discount_pct": 4, "quantity": 25})],
    "h2o_g1": [("q1", {}), ("q2", {}), ("q3", {}), ("q5", {})],
}


def main(argv=None) -> int:
    from tpubench.spec import Spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rows", type=int, default=0,
                    help="default: the configuration's own")
    args = ap.parse_args(argv)
    spec = Spec()
    config = spec.config(args.config)
    for seed in map(int, args.seeds.split(",")):
        gaps = widest_gap(spec, config["dataset"], seed,
                          args.rows or config["rows"],
                          QUERIES[config["dataset"]])
        for template, (said, gap) in gaps.items():
            print(f"control_f32 {args.config} seed {seed} {template}: "
                  f"gap {gap:.3e} refused {said is not None}", flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    sys.exit(main())
