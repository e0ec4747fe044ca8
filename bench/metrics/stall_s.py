"""Seconds the job trains nothing per elastic event: the mean over the
window's events of the wall time of each event's call."""


def read(rec):
    if not rec.events:
        return None
    return sum(e["seconds"] for e in rec.events) / len(rec.events)
