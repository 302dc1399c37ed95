"""Operator drivers: of the rows a join probed in the window, the share
probed on the host, in per cent: 100 x `join.host_probe.rows` /
(`join.host_probe.rows` + `join.probe.rows`).  0 where every batch is
probed by a device launch; None where the window probed nothing, or on an
engine that times neither probe (`join.probe`, `join.host_probe`): there
`join.probe.rows` counts the host's rows too and says nothing."""


def read(run):
    if not ("join.probe" in run.timings or "join.host_probe" in run.timings):
        return None
    host = run.counts.get("join.host_probe.rows", 0)
    total = host + run.counts.get("join.probe.rows", 0)
    return 100 * host / total if total else None
