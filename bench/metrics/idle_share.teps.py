"""Device idle share (%) of the query cell's traced window: one minus
the union of device-operation intervals over the window."""

from bench.lib.measure import idle_share_pct


def read(r):
    return idle_share_pct(r.trace)
