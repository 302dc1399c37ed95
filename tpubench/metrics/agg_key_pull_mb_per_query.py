"""D2H: the bytes of group-key columns born on the device that came back
to the host to be encoded (`aggregate.key_pull.bytes`, also counted in
`d2h.bytes`), MB per query.  0 where the device key step served every
batch; None in a cell whose aggregates take no key from the device
(neither `aggregate.device_key.groups` nor the pull's counter moved)."""


def read(run):
    pulled = run.counts.get("aggregate.key_pull.bytes", 0)
    if not (pulled or run.counts.get("aggregate.device_key.groups", 0)
            ) or not run.queries:
        return None
    return pulled / run.queries / 1e6
