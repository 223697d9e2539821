"""Share of the traced stretch in which no operation ran on the device:
1 minus the union of device-op intervals over the stretch (profiler
trace)."""


def read(rec):
    if rec.trace is None or rec.trace.window_s <= 0:
        return None
    return (1.0 - rec.trace.busy_s / rec.trace.window_s) * 100.0
