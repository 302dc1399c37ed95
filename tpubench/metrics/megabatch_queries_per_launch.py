"""Serving: queries fused per megabatch launch; 0 where none was made."""


def read(run):
    launches = run.counts.get("serve.megabatch_launches", 0)
    if run.mix["entry"] != "serve":
        return None
    return run.counts.get("serve.megabatch_queries", 0) / launches if launches else 0.0
