"""Set-up time: process start to the start of the window (corpus, ingest,
engine build, compile and warm-up)."""


def read(r):
    return r.setup_s
