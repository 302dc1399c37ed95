"""Compile: programs loaded from the persistent cache over set-up."""


def read(run):
    return run.setup["persistent_cache_hits"]
