"""Mean seconds a request waited in the scheduler queue, in ms: the
change in ``deppy_request_queue_wait_seconds``' sum over its count."""


def read(run):
    h = run.server.get("deppy_request_queue_wait_seconds")
    if not h or not h["count"]:
        return None
    return 1000.0 * h["sum"] / h["count"]
