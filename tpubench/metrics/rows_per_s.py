"""Input rows of the tables scanned by completed requests over the
measured seconds (window opening to the last completion counted)."""


def read(run):
    return run.rows * run.queries / run.measured_s if run.queries else None
