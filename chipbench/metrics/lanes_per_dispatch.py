"""Problems per coalesced scheduler dispatch over the window: the change
in ``deppy_sched_coalesced_batch_size``'s sum over its count."""


def read(run):
    h = run.server.get("deppy_sched_coalesced_batch_size")
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"]
