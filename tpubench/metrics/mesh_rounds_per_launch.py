"""Mesh: rounds (one batch a shard) folded into one update launch:
`mesh.rounds` over the launches tagged `mesh.stacked` (one round) and
`mesh.multi` (several).  None where no round ran."""


def read(run):
    launches = (run.counts.get("device.launches.mesh.stacked", 0)
                + run.counts.get("device.launches.mesh.multi", 0))
    rounds = run.counts.get("mesh.rounds", 0)
    return rounds / launches if rounds and launches else None
