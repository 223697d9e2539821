"""Datastore transaction time of both aggregators over the window
(janus_database_transaction_duration_seconds), per report aggregated in
it (the numerator of aggregated_rps)."""


def read(rec):
    _, s = rec.histogram("janus_database_transaction_duration_seconds")
    reports, _ = rec.aggregated()
    return s / reports * 1e3 if reports else None
