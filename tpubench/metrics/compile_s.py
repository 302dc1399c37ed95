"""Compile: seconds in XLA backend compilation over set-up
(`/jax/core/compile/backend_compile_duration`)."""


def read(run):
    return run.setup["compile_s"]
