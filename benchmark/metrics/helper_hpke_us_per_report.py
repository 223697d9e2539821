"""The helper's HPKE open of the leader's report shares per report over
the window: janus_aggregate_init_stage_seconds{stage="hpke_stage"} over
the rows the helper's init program carried,
janus_engine_rows_total{op="helper_init"}. None where either is
missing."""


def read(rec):
    n, s = rec.histogram("janus_aggregate_init_stage_seconds", stage="hpke_stage")
    rows = rec.counter("janus_engine_rows_total", op="helper_init")
    return s / rows * 1e6 if n and rows else None
