"""Time one job step waits in the leader's step pipeline over the
window: for each stage of janus_step_pipeline_queue_wait_seconds (the
read, device, http and commit queues and the staging window), the mean
wait, summed over the stages. None where the program records no
queue waits."""

STAGES = ("read", "staging", "device", "http", "commit")


def read(rec):
    total_s = 0.0
    seen = False
    for stage in STAGES:
        n, s = rec.histogram("janus_step_pipeline_queue_wait_seconds", stage=stage)
        if n:
            total_s += s / n
            seen = True
    return total_s * 1e3 if seen else None
