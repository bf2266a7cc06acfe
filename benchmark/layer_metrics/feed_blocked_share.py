"""Share of the window's wall in which the feed's producer held a finished
batch and the ring had no room for it (span ``dls.feed/ring_full``): sum of
``input_blocked_s`` over sum of ``lap_s``. It is the feed's headroom: near
zero, the feed sets the pace or is about to."""


def read(ctx):
    wall = sum(e.get("lap_s", 0.0) for e in ctx["laps"])
    if not wall or any("input_blocked_s" not in e for e in ctx["laps"]):
        return None
    return 100.0 * sum(e["input_blocked_s"] for e in ctx["laps"]) / wall
