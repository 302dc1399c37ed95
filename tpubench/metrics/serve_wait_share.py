"""Serving: the share of the tickets' wall spent waiting, in per cent:
(`serve.path.queue_wait` + `serve.path.megabatch_window`) over
`serve.path.wall`, each summed over the window's tickets in
`Server._finish`.  None where the program has no such timers."""


def read(run):
    wall = run.timings.get("serve.path.wall", 0.0)
    if not run.queries or wall <= 0:
        return None
    waited = (run.timings.get("serve.path.queue_wait", 0.0)
              + run.timings.get("serve.path.megabatch_window", 0.0))
    return 100 * waited / wall
