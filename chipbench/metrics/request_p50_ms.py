"""Median latency of all requests sent in the window, due time to last
byte of the answer, in milliseconds."""


def read(run):
    return run.latency_ms(0.50)
