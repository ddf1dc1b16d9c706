"""Share of the traced window in which no op ran on the device, in
percent: 100 * (1 - busy_s / window_s)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
