"""Seconds from process start to the window's start (host clock):
boot, warm-up (compiles in a checkout's first run), making the
corpus, storing the backlog and cutting its jobs."""


def read(rec):
    return rec.setup_s
