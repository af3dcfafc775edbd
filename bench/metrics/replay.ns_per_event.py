"""Device busy time per simulated lane-event (layer: replay kernel).

A lane-event is one plan row or one charge of one lane, counted by the
driver from the plan's row count and the window's reboots, never from the
program's loop, so the number reads the same work whatever implements
the replay.  Busy time is summed over the chips."""


def read(ctx):
    tr = ctx["trace"]
    busy, events = tr.busy_total_s(), ctx["lane_events"]
    if not tr.chips or busy <= 0 or not events:
        return None
    return busy / events * 1e9
