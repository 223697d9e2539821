"""Mean time one upload group commit waited for the leader's write lock
over the window: janus_database_transaction_phase_seconds at
tx="upload_batch", phase="lock_wait", sum over count. None where no
group commit ran or the program records no transaction phases."""


def read(rec):
    n, s = rec.histogram(
        "janus_database_transaction_phase_seconds", tx="upload_batch", phase="lock_wait"
    )
    return s / n * 1e3 if n else None
