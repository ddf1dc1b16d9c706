"""Cluster states of ``deppy-sat-bench``, in the service's wire form.

deppy's ``BenchmarkSolve`` instance (pkg/sat/bench_test.go:10-64),
written out: ``length`` variables named by their index; each is
Mandatory with probability ``pMandatory``, has one Dependency on
1..nDependency-1 other variables with probability ``pDependency``, and
1..nConflict-1 Conflict constraints on other variables with probability
``pConflict``.  The draws are taken in the source's order; Python's
``random`` stands in for Go's ``math/rand``, so the streams differ and
the distribution is the same.
"""


def state(rng, label, cfg):
    """One problem drawn from ``rng``; ``label`` is unused: the source
    names every variable by its index."""
    n = cfg["length"]

    def other(i):
        y = i
        while y == i:
            y = rng.randrange(n)
        return y

    out = []
    for i in range(n):
        cons = []
        if rng.random() < cfg["pMandatory"]:
            cons.append({"type": "mandatory"})
        if rng.random() < cfg["pDependency"]:
            k = rng.randrange(1, cfg["nDependency"])
            cons.append({"type": "dependency",
                         "ids": [str(other(i)) for _ in range(k)]})
        if rng.random() < cfg["pConflict"]:
            for _ in range(rng.randrange(1, cfg["nConflict"])):
                cons.append({"type": "conflict", "id": str(other(i))})
        var = {"id": str(i)}
        if cons:
            var["constraints"] = cons
        out.append(var)
    return {"variables": out}
