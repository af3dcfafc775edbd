"""Host time per lane chunk in the functions that walk the whole row
table (layer: sweep host pipeline): its conversion to the replay's state,
the check that the integer state can replay it, and the reboot bound that
decides the fast path, read from the profiler's Python tracer events.  On
a stacked ``PlanSet`` table they walk every candidate's padded rows."""

FUNCTIONS = (
    ("fleetsim.py", "_rows_as"),
    ("fleetsim.py", "_int_rows_fit"),
    ("fleetsim.py", "_reboot_upper_bound"),
)


def read(ctx):
    s = ctx["trace"].host_seconds(FUNCTIONS)
    return None if s is None else 1e3 * s / ctx["chunks"]
