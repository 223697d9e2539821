"""Device time of the prepare programs (leader init and helper init,
from the trace's executed programs) per report: each program's mean
device time per dispatch over the traced stretch, divided by the
reports a dispatch of it carried (janus_engine_rows_total over
janus_engine_dispatches_total in the window), summed over the two."""

PROGRAMS = ("leader_init", "helper_init")


def read(rec):
    if rec.trace is None:
        return None
    total_ns = 0.0
    for prog in PROGRAMS:
        spans = [e - s for name, s, e in rec.trace.modules if name == prog]
        dispatches = rec.counter("janus_engine_dispatches_total", op=prog)
        if not spans or not dispatches:
            return None
        rows_per_dispatch = rec.counter("janus_engine_rows_total", op=prog) / dispatches
        total_ns += sum(spans) / len(spans) / rows_per_dispatch
    return total_ns * 1e-3
