"""Engine sub-iterations per query: ``DiffuseStats.local_iters``,
summed over the window's queries, over the number of queries."""


def read(r):
    n = r.counters.get("queries", 0)
    return r.counters["query_local_iters"] / n if n else None
