"""95th percentile of the window's uploads, each timed from the moment
it was due (host clock). An upload not answered 201 counts as slower
than any answered one: it takes the window's length, or the slowest
answered upload if that was slower."""

import math


def read(rec):
    if not rec.uploads:
        return None
    answered = [r[1] for r in rec.uploads if r is not None and r[0] == 201]
    missing = max([rec.window_s] + answered)
    lat = sorted(answered + [missing] * (len(rec.uploads) - len(answered)))
    return lat[math.ceil(0.95 * len(lat)) - 1] * 1e3
