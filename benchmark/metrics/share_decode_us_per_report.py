"""The leader's single staging pass over a job's stored shares, per
report, over the window: janus_leader_share_decode_seconds (one
observation a Prio3 init job: decrypt each share into the limb columns,
then the range check) over the reports through the pass,
janus_leader_shares_decoded_total (both results). None where either is
missing, as in a program without the pass."""


def read(rec):
    n, s = rec.histogram("janus_leader_share_decode_seconds")
    reports = rec.counter("janus_leader_shares_decoded_total")
    return s / reports * 1e6 if n and reports else None
