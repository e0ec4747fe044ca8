"""Share of the window in which the chips the job held ran no operation,
from the profiler trace (1 - busy chip-seconds / held chip-seconds)."""


def read(rec):
    if rec.trace is None or not rec.trace.busy_s:
        return None
    return 100.0 * rec.trace.idle_share
