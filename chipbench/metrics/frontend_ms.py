"""Mean host time a request spent in the HTTP front end, in ms: the
``service.parse``, ``sched.encode`` and ``service.render`` span seconds
in the window over the window's ``service.request`` spans.  Reads
nothing where the program has none of these spans."""

SPANS = ("service.parse", "sched.encode", "service.render")


def read(run):
    requests = run.spans.get("service.request", [0, 0.0])[0]
    seen = [run.spans[s][1] for s in SPANS if s in run.spans]
    if not requests or not seen:
        return None
    return 1000.0 * sum(seen) / requests
