"""Host time per lane chunk in the dead-time rebuild and the statistics
fold (layer: sweep host pipeline), read from the profiler's Python tracer
events."""

FUNCTIONS = (
    ("fleetsim.py", "_dead_time"),
    ("fleetstats.py", "stats_from_outputs"),
    ("fleetstats.py", "merge"),
)


def read(ctx):
    s = ctx["trace"].host_seconds(FUNCTIONS)
    return None if s is None else 1e3 * s / ctx["chunks"]
