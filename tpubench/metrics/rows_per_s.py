"""Input rows of the tables scanned by completed requests (for each query,
the rows of the tables its text names) over the measured seconds (window
opening to the last completion counted)."""


def read(run):
    return run.rows_scanned / run.measured_s if run.queries else None
