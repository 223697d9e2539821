"""Mean server time of the leader's upload route over the window, from
janus_http_request_duration_seconds{route="upload"}."""


def read(rec):
    n, s = rec.histogram("janus_http_request_duration_seconds", route="upload")
    return s / n * 1e3 if n else None
