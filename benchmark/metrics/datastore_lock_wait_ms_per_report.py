"""Time both aggregators' datastore transactions waited for the write
lock over the window (connect plus `BEGIN IMMEDIATE` until it returns:
janus_database_transaction_phase_seconds{phase="lock_wait"}, every tx),
per report aggregated in it (the numerator of aggregated_rps). None
where the program records no transaction phases."""


def read(rec):
    n, s = rec.histogram("janus_database_transaction_phase_seconds", phase="lock_wait")
    reports, _ = rec.aggregated()
    return s / reports * 1e3 if n and reports else None
