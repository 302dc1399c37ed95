"""Serving: queries shed over queries submitted in the window."""


def read(run):
    if run.mix["entry"] != "serve":
        return None
    shed = run.counts.get("queries_shed", 0)
    total = shed + run.counts.get("queries_queued", 0)
    return 100 * shed / total if total else None
