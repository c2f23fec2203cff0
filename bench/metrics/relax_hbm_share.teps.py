"""Share (%) of the chip's peak HBM bandwidth that the bytes the query
sweeps need (``bench/lib/work.py``) make over the device's busy time
inside the ``bench.query`` spans."""

from bench.lib.measure import busy_in
from bench.lib.work import hbm_share_pct


def read(r):
    if r.trace is None:
        return None
    c = r.counters
    return hbm_share_pct(c.get("query_local_iters", 0), c["live_edges"],
                         c["vertices"], busy_in(r.trace, "bench.query") * 1e-9,
                         r.peaks["hbm_bytes_per_s"])
