"""Host-to-device bytes per report, over the whole served run (window
and drain) so that every byte and every report of a job are counted
together: janus_engine_hd_bytes_total{direction="h2d"} over the reports
of all jobs. A count that repeats exactly for a given program."""


def read(rec):
    b = rec.counter("janus_engine_hd_bytes_total", "start", "drained", direction="h2d")
    n = rec.reports_total()
    return b / n if n and b else None
