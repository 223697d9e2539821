"""Mean time the helper takes for one aggregation job's init request
over the window, from janus_http_request_duration_seconds{route="aggregate_init"}."""


def read(rec):
    n, s = rec.histogram("janus_http_request_duration_seconds", route="aggregate_init")
    return s / n * 1e3 if n else None
