"""The replay's share of the device's memory-bandwidth roofline (layer:
replay kernel).

The replay does no matrix work, so bandwidth bounds it: the least time is
the bytes the simulated work must read (lane-events times the row's
semantic fields at 4 bytes each, counted by ``bench.events``, whatever
width the program packs them in) over the HBM bandwidth of
``bench/peaks.json``; the share is that over the device busy time."""


def read(ctx):
    tr = ctx["trace"]
    busy = tr.busy_total_s()
    if not tr.chips or busy <= 0 or not ctx["lane_events"]:
        return None
    need = ctx["lane_events"] * ctx["bytes_per_event"]
    return 100.0 * need / (busy * ctx["peaks"]()["hbm_bytes_per_s"])
