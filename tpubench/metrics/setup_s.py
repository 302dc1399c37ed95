"""Process start to the first timed request: data, residency, compile or
cache load, warm-up."""


def read(run):
    return run.setup["setup_s"]
