"""Mean wall time of one driver solve call, pad through fetch, in ms: the
change in the pipeline registry's ``deppy_solve_seconds`` sum over its
count (the server registry's family of that name is per request and
includes the scheduler's wait)."""


def read(run):
    h = run.pipeline.get("deppy_solve_seconds")
    if not h or not h["count"]:
        return None
    return 1000.0 * h["sum"] / h["count"]
