"""The load generator: parameters and schedules from the seed, the closed
loop's window rule, the open loop's due-time latency and lateness."""

import threading
import time

import numpy as np
import pytest

from tpubench import traffic

MIX = {
    "entry": "sql", "request": "query",
    "loop": {"kind": "closed", "clients": 2},
    "templates": [
        {"name": "a", "weight": 3, "params": {
            "year": {"dist": "uniform_int", "lo": 1993, "hi": 1997},
            "k": {"dist": "const", "value": 9}}},
        {"name": "b", "weight": 1, "params": {
            "rank": {"dist": "zipf_int", "lo": 1, "hi": 50, "s": 1.0}}},
    ],
}


def make_sql(template, params):
    return f"{template}:{sorted(params.items())}"


@pytest.fixture
def maker():
    return traffic.RequestMaker(MIX, make_sql)


@pytest.mark.parametrize("dist,allowed", [
    ({"dist": "const", "value": 90}, {90}),
    ({"dist": "uniform_int", "lo": 24, "hi": 25}, {24, 25}),
    ({"dist": "zipf_int", "lo": 60, "hi": 62, "s": 1.1}, {60, 61, 62}),
])
def test_draw_stays_in_its_domain_and_grid_lists_it(dist, allowed):
    rng = np.random.default_rng(0)
    seen = {traffic.draw(rng, dist) for _ in range(200)}
    assert seen == allowed == set(traffic.values_of(dist))


def test_zipf_favours_low_ranks():
    rng = np.random.default_rng(0)
    d = {"dist": "zipf_int", "lo": 1, "hi": 100, "s": 1.0}
    draws = [traffic.draw(rng, d) for _ in range(2000)]
    assert draws.count(1) > 5 * draws.count(10) > 0


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        traffic.draw(np.random.default_rng(0), {"dist": "normal"})


def test_requests_are_a_function_of_the_seed(maker):
    def stream(seed):
        rng = np.random.default_rng(seed)
        return [maker.request(rng, i, "c").queries[0].sql for i in range(50)]

    assert stream(1) == stream(1) != stream(2)
    names = [s.split(":")[0] for s in stream(1)]
    assert names.count("a") > names.count("b") > 0


def test_a_round_is_every_template_in_order():
    m = traffic.RequestMaker({**MIX, "request": "round"}, make_sql)
    req = m.request(np.random.default_rng(0), 0, "c")
    assert [q.template for q in req.queries] == ["a", "b"]


def test_grid_covers_every_value_of_the_named_parameters(maker):
    groups = maker.grid(["year"])
    assert all(len(g) == 1 for g in groups)
    reqs = [g[0] for g in groups]
    assert [r.queries[0].params.get("year") for r in reqs] == \
        [1993, 1994, 1995, 1996, 1997, None]  # template b has no `year`
    assert [r.queries[0].template for r in reqs] == ["a"] * 5 + ["b"]
    assert [len(g[0].queries) for g in maker.grid([])] == [1, 1]


@pytest.mark.parametrize("together", [2, 3])
def test_grid_groups_share_the_named_values_and_draw_the_rest(maker, together):
    groups = maker.grid(["year"], together)
    assert [len(g) for g in groups] == [together] * 6
    for g in groups[:5]:
        assert len({r.queries[0].params["year"] for r in g}) == 1
        assert len({r.client for r in g}) == together
    ranks = [r.queries[0].params["rank"] for r in groups[5]]
    assert all(1 <= k <= 50 for k in ranks)
    assert groups == maker.grid(["year"], together)  # from a fixed seed


def test_closed_loop_finishes_what_is_in_flight_and_counts_it(maker):
    def send(req):
        time.sleep(0.1)
        return [req.rid]

    win = traffic.run_closed(maker, send, seed=3, clients=2, seconds=0.25)
    # each client: requests start at 0, .1, .2 -> the third ends past .25
    assert len(win.outcomes) == 6
    assert all(o.error is None and o.results == [o.request.rid] for o in win.outcomes)
    assert win.t_close == max(o.end for o in win.outcomes)
    assert 0.29 < win.t_close - win.t_open < 0.6
    assert sorted(o.request.rid for o in win.outcomes) == list(range(6))
    assert {o.request.client for o in win.outcomes} == {"c0", "c1"}


def test_closed_loop_counts_a_raising_request_as_an_outcome(maker):
    def send(req):
        if req.rid == 1:
            raise RuntimeError("shed")
        return []

    win = traffic.run_closed(maker, send, seed=3, clients=1, seconds=1e9,
                             max_each=3)
    assert [type(o.error).__name__ if o.error else None
            for o in sorted(win.outcomes, key=lambda o: o.request.rid)] == \
        [None, "RuntimeError", None]


def test_closed_loop_clients_do_not_depend_on_each_other(maker):
    """Client 0's i-th request is the same whatever client 1 does."""
    def by_client(clients):
        win = traffic.run_closed(maker, lambda r: [], 7, clients, 1e9,
                                 max_each=4)
        return [o.request.queries[0].sql for o in
                sorted(win.outcomes, key=lambda o: o.request.rid)
                if o.request.client == "c0"]

    assert by_client(1)[:3] == by_client(2)[:3]


OPEN = {"kind": "open", "rate_per_s": 200, "arrivals": "poisson",
        "burst": {"every_s": 0.1, "size": 5},
        "tenants": {"count": 4, "zipf": 1.1}, "max_in_flight": 64}


def test_open_schedule_is_drawn_from_the_seed(maker):
    a = traffic.schedule(maker, OPEN, 1, 0.5)
    b = traffic.schedule(maker, OPEN, 1, 0.5)
    c = traffic.schedule(maker, OPEN, 2, 0.5)
    assert [(r.due, r.client, r.queries[0].sql) for r in a] == \
        [(r.due, r.client, r.queries[0].sql) for r in b]
    assert [r.due for r in a] != [r.due for r in c]
    due = np.array([r.due for r in a])
    assert np.all(np.diff(due) >= 0) and due.max() < 0.5
    assert 60 < len(a) < 180  # ~100 Poisson arrivals + 4 bursts of 5
    assert sum(np.isclose(due, 0.1)) == 5
    tenants = [r.client for r in a]
    assert set(tenants) <= {"t0", "t1", "t2", "t3"}
    assert tenants.count("t0") > tenants.count("t3")


def test_open_loop_times_from_the_due_time_and_reports_lateness(maker):
    """A server that stalls: one worker thread, each request 20 ms, 100
    requests a second due.  Latency counted from the due time grows with
    the queue; counted from the send it would stay at 20 ms."""
    gate = threading.Lock()

    def send(req):
        with gate:
            time.sleep(0.02)
        return []

    loop = {"kind": "open", "rate_per_s": 100, "arrivals": "uniform",
            "max_in_flight": 2}
    m = traffic.RequestMaker({**MIX, "loop": loop}, make_sql)
    win = traffic.run(m, send, seed=1, seconds=0.3)
    out = sorted(win.outcomes, key=lambda o: o.request.rid)
    assert len(out) == 29
    lat = [o.end - o.start for o in out]
    assert lat[0] < 0.05 and lat[-1] > 0.2  # the queue is charged
    assert [o.start for o in out] == [win.t_open + o.request.due for o in out]
    assert max(o.late_s for o in out) > 0.1  # the pool of 2 was the limit
    assert win.t_close == max(o.end for o in out)


def test_unknown_loop_kind_is_an_error(maker):
    m = traffic.RequestMaker({**MIX, "loop": {"kind": "spiral"}}, make_sql)
    with pytest.raises(ValueError):
        traffic.run(m, lambda r: [], 1, 0.1)
