"""Traffic generators, driven by a mix's parameters and a seed.

The same seed always gives the same queries in the same order.
"""

from __future__ import annotations


def query_schedule(programs, keys, count: int):
    """Closed-loop queries: for each key in turn, each program from it;
    ``refresh`` once every pair has been asked, so that no query is served
    from the session's cache."""
    n = len(programs)
    return [(programs[i % n], keys[(i // n) % len(keys)],
             i >= n * len(keys)) for i in range(count)]
