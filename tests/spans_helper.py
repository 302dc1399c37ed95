"""Reads the engine's `dftpu.*` spans back from a JAX profile, for the
tests of the tracing seam (`utils/metrics.py`)."""

import glob
import os
from collections import namedtuple

Span = namedtuple("Span", "name thread start end stats")


def host_spans(trace_dir: str, prefix: str = "dftpu.") -> list:
    """Every event of the host plane whose name starts with `prefix`, as
    (name, thread, start ns, end ns, stats); `thread` numbers the plane's
    lines, one a thread."""
    import jax

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    (plane,) = [p for p in profile.planes if p.name == "/host:CPU"]
    return [
        Span(e.name, thread, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats))
        for thread, line in enumerate(plane.lines)
        for e in line.events
        if e.name.startswith(prefix)
    ]
