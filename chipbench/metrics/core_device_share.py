"""Share of the traced window's device busy time that the core
extraction programs took, in percent: their device seconds over
``busy_s``.  Reads nothing where the trace holds no core program."""

MARK = "core"


def read(run):
    if run.trace is None:
        return None
    core = sum(s for name, s in run.trace.program_s.items() if MARK in name)
    if core <= 0:
        return None
    return 100.0 * core / run.trace.busy_s
