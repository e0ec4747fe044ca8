"""Mean of the program's own ``RescaleTimings.restore`` over the window's
rescales: moving the state onto the new chips."""


def read(rec):
    ts = [e["timings"]["restore"] for e in rec.events if e["kind"] == "rescale"]
    return sum(ts) / len(ts) if ts else None
