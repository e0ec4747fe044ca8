"""Mean of the program's own ``RescaleTimings.restart`` over the window's
rescales: a warm mesh cache hit, or a re-jit where the cache was lost."""


def read(rec):
    ts = [e["timings"]["restart"] for e in rec.events if e["kind"] == "rescale"]
    return sum(ts) / len(ts) if ts else None
