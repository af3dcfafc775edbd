"""Host time per lane chunk in the Philox samplers that draw the lanes'
inputs, and the prefix sums of their traces (layer: sweep host
pipeline), read from the profiler's Python tracer events."""

FUNCTIONS = (
    ("failures.py", "initial_charge_fraction_stream"),
    ("failures.py", "harvest_jitter_stream"),
    ("failures.py", "reboot_recharge_times_stream"),
    ("failures.py", "charge_capacity_jitter_stream"),
    ("failures.py", "recharge_trace_cumulative"),
    ("failures.py", "charge_trace_cumulative"),
)


def read(ctx):
    s = ctx["trace"].host_seconds(FUNCTIONS)
    return None if s is None else 1e3 * s / ctx["chunks"]
