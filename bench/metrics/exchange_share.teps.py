"""Share (%) of the devices' busy time inside the ``bench.query`` spans
that the collectives between chips take (``bench/lib/exchange.py``),
averaged over the devices: the all_to_all exchange, the quiescence
psums and the waits at the barrier they hold."""

from bench.lib.exchange import collective_share_pct


def read(r):
    return collective_share_pct(r.trace, "bench.query")
