"""Share of the replay's executed lane-event slots that held an active
event (layer: replay kernel): the window's ``FleetStats.replay_events``
over ``replay_event_slots``.  The batched event loop runs every lane of a
batch until its slowest lane is done, so the rest of the slots are lanes
that sat masked, waiting for that lane or rounding out its last trip.
None where the program does not count them."""


def read(ctx):
    counts = ctx.get("counts") or {}
    events = counts.get("replay_events")
    slots = counts.get("replay_event_slots")
    if not events or not slots:
        return None
    return 100.0 * events / slots
