"""90th percentile (nearest rank) of the wall time of the window's steps."""
import math


def read(rec):
    if not rec.step_s:
        return None
    s = sorted(rec.step_s)
    return s[math.ceil(0.9 * len(s)) - 1]
