"""The one general load generator: a traffic mix is a data file.

A mix (`traffic/<name>.json`) says which entry point takes the requests
(`entry`), what a request is (`request`: one `query` drawn by weight, or
one `round` of every template in order), how parameters are drawn
(`templates[].params`), and the loop:

- `{"kind": "closed", "clients": C}`: C client threads; each sends its
  next request when its last one returned.  A slow system gets less load.
- `{"kind": "open", "rate_per_s": R, "arrivals": "poisson" | "uniform",
  "burst": {"every_s": E, "size": B}, "tenants": {"count": T, "zipf": S},
  "max_in_flight": M}`: requests fall due on a schedule drawn from the
  seed whatever the system does; each is timed from when it was *due*, so
  a stall charges the requests queued behind it, and how late the
  generator itself ran is reported.

Everything is drawn from the seed before or outside the timed path: a
client's i-th request is the same in every run of that seed.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Query:
    template: str
    params: dict
    sql: str


@dataclass
class Request:
    rid: int
    client: str
    queries: list
    due: float = 0.0  # open loop: seconds after the window opens


@dataclass
class Outcome:
    request: Request
    start: float  # perf_counter: when sent (closed) or due (open)
    end: float
    results: list = None  # one engine result per query
    error: "BaseException | None" = None
    late_s: float = 0.0  # open loop: sent this long after it was due


@dataclass
class Window:
    outcomes: list = field(default_factory=list)
    t_open: float = 0.0
    t_close: float = 0.0  # the last completion counted


def draw(rng: np.random.Generator, dist: dict):
    """One value of a parameter distribution."""
    kind = dist["dist"]
    if kind == "const":
        return dist["value"]
    if kind == "uniform_int":
        return int(rng.integers(dist["lo"], dist["hi"] + 1))
    if kind == "zipf_int":  # rank r of lo..hi with weight r ** -s
        n = dist["hi"] - dist["lo"] + 1
        p = np.arange(1, n + 1, dtype=float) ** -dist["s"]
        return dist["lo"] + int(rng.choice(n, p=p / p.sum()))
    raise ValueError(f"unknown parameter distribution {kind!r}")


def values_of(dist: dict) -> list:
    """Every value a distribution can give (for the warm-up grid)."""
    if dist["dist"] == "const":
        return [dist["value"]]
    if dist["dist"] in ("uniform_int", "zipf_int"):
        return list(range(dist["lo"], dist["hi"] + 1))
    raise ValueError(f"unknown parameter distribution {dist['dist']!r}")


class RequestMaker:
    """Draws requests of one mix; `make_sql(template, params)` gives the
    query text (the data set binds, the template file formats)."""

    def __init__(self, mix: dict, make_sql):
        self.mix = mix
        self.make_sql = make_sql
        self.templates = mix["templates"]
        w = np.array([t.get("weight", 1) for t in self.templates], float)
        self.weights = w / w.sum()

    def _query(self, rng, t: dict, fixed: "dict | None" = None) -> Query:
        params = {k: draw(rng, d) for k, d in t.get("params", {}).items()}
        params.update(fixed or {})
        return Query(t["name"], params, self.make_sql(t["name"], params))

    def request(self, rng, rid: int, client: str, due: float = 0.0) -> Request:
        if self.mix.get("request", "query") == "round":
            queries = [self._query(rng, t) for t in self.templates]
        else:
            i = int(rng.choice(len(self.templates), p=self.weights))
            queries = [self._query(rng, self.templates[i])]
        return Request(rid, client, queries, due)

    def grid(self, names: list, together: int = 1) -> list:
        """For each template and each combination of the named
        parameters' values, a group of `together` single-query requests
        that share the combination (the other parameters drawn, so they
        differ): the literals that each compile a program of their own,
        and, sent together, the programs an entry point makes when it
        runs several such queries as one."""
        rng = np.random.default_rng(0)
        out = []
        for t in self.templates:
            combos = [{}]
            for n in names:
                if n in t.get("params", {}):
                    combos = [{**c, n: v} for c in combos
                              for v in values_of(t["params"][n])]
            for c in combos:
                out.append([Request(-1, f"warmup{i}", [self._query(rng, t, c)])
                            for i in range(together)])
        return out


def run_closed(maker: RequestMaker, send, seed: int, clients: int,
               seconds: float, max_each: int = 0) -> Window:
    """`clients` threads, each in its own loop until `seconds` have
    passed (or it has sent `max_each`); a request in flight at the
    deadline is finished and counted.  `send(request)` returns the
    results or raises."""
    win = Window()
    lock = threading.Lock()

    def client(ci: int):
        rng = np.random.default_rng([seed, 1, ci])
        i = 0
        while not max_each or i < max_each:
            req = maker.request(rng, ci + i * clients, f"c{ci}")
            i += 1
            t0 = time.perf_counter()
            out = Outcome(req, t0, t0)
            try:
                out.results = send(req)
            except Exception as e:  # noqa: BLE001 — counted in `failed`
                out.error = e
            out.end = time.perf_counter()
            with lock:
                win.outcomes.append(out)
            if out.end >= deadline:
                return

    threads = [threading.Thread(target=client, args=(ci,), name=f"client-{ci}")
               for ci in range(clients)]
    win.t_open = time.perf_counter()
    deadline = win.t_open + seconds
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    win.t_close = max((o.end for o in win.outcomes), default=win.t_open)
    return win


def schedule(maker: RequestMaker, loop: dict, seed: int,
             seconds: float) -> list:
    """The open loop's requests with their due times, from the seed."""
    rng = np.random.default_rng([seed, 2])
    rate = float(loop["rate_per_s"])
    n = max(int(rate * seconds * 1.5) + 16, 16)
    if loop.get("arrivals", "poisson") == "poisson":
        due = np.cumsum(rng.exponential(1.0 / rate, n))
    else:
        due = (np.arange(n) + 1) / rate
    due = due[due < seconds]
    burst = loop.get("burst")
    if burst:  # `size` extra requests due together, every `every_s`
        at = np.arange(burst["every_s"], seconds, burst["every_s"])
        due = np.sort(np.concatenate([due, np.repeat(at, burst["size"])]))
    tenants = loop.get("tenants", {"count": 1})
    t_dist = {"dist": "zipf_int", "lo": 0, "hi": tenants["count"] - 1,
              "s": tenants.get("zipf", 0.0)}
    out = []
    for i, d in enumerate(due):
        tenant = draw(rng, t_dist)
        out.append(maker.request(rng, i, f"t{tenant}", float(d)))
    return out


def run_open(maker: RequestMaker, send, seed: int, loop: dict,
             seconds: float) -> Window:
    """Send each scheduled request when it falls due, from a pool of
    `max_in_flight` threads; time it from its due time."""
    reqs = schedule(maker, loop, seed, seconds)
    win = Window()
    lock = threading.Lock()

    def one(req: Request, due_at: float):
        sent_at = time.perf_counter()
        out = Outcome(req, due_at, due_at, late_s=max(sent_at - due_at, 0.0))
        try:
            out.results = send(req)
        except Exception as e:  # noqa: BLE001 — counted in `failed`
            out.error = e
        out.end = time.perf_counter()
        with lock:
            win.outcomes.append(out)

    with ThreadPoolExecutor(max_workers=loop.get("max_in_flight", 128),
                            thread_name_prefix="open-loop") as pool:
        win.t_open = time.perf_counter()
        futures = []
        for req in reqs:
            due_at = win.t_open + req.due
            wait = due_at - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            futures.append(pool.submit(one, req, due_at))
        for f in futures:
            f.result()
    win.t_close = max((o.end for o in win.outcomes), default=win.t_open)
    return win


def run(maker: RequestMaker, send, seed: int, seconds: float) -> Window:
    loop = maker.mix["loop"]
    if loop["kind"] == "closed":
        return run_closed(maker, send, seed, loop["clients"], seconds)
    if loop["kind"] == "open":
        return run_open(maker, send, seed, loop, seconds)
    raise ValueError(f"unknown loop kind {loop['kind']!r}")
