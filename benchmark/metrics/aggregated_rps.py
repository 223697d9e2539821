"""Reports of the jobs that finished inside the window, over the time
from the window's start to the last of those finishes (host clock)."""


def read(rec):
    reports, seconds = rec.aggregated()
    return reports / seconds if seconds > 0 else None
