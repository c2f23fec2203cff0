"""The exchange between chips in a device trace: the self time of the
collective operations (``all-to-all``, ``all-gather``, ``all-reduce``,
``collective-permute``, with their ``-start``/``-done`` halves; ``_`` in
place of ``-`` as the chip's trace names them) inside
host spans, as a share of the device's busy time there.  A device that
waits at the barrier for a slower one waits inside a collective, so
load imbalance between chips shows here too."""

from __future__ import annotations

import re

from bench.lib.measure import Trace, covered, self_times, short_name, union

# HLO text writes ``all-to-all.48``; the chip's trace writes the same op
# ``all_to_all.48``
COLLECTIVE = re.compile(
    r"(all[-_]to[-_]all|all[-_]gather|all[-_]reduce|collective[-_]permute)"
    r"([-_]start|[-_]done)?(\.[\d.]+)?$")


def is_collective(op: str) -> bool:
    """Whether a trace op name (``%all-to-all.3 = ...`` or bare) is one
    of the collectives."""
    return COLLECTIVE.match(short_name(op)) is not None


def collective_share_pct(tr: Trace | None,
                         span_name: str = "bench.query") -> float | None:
    """Collective self time ÷ busy time inside the spans named
    ``span_name``, per device, averaged over the devices that were busy
    there, in %; None without a trace, a device or such a span."""
    if tr is None or not tr.ops:
        return None
    lo, hi = tr.window
    spans = union([(a, b) for n, a, b in tr.spans if n == span_name], lo, hi)
    if not spans:
        return None
    shares = []
    for dev in tr.ops:
        inside = [e for e in dev
                  if any(a <= e[1] and e[2] <= b for a, b in spans)]
        busy = sum(covered(union([(a, b) for _, a, b in inside], lo, hi),
                           a, b) for a, b in spans)
        if busy <= 0:
            continue
        coll = sum(t for name, t in self_times(inside) if is_collective(name))
        shares.append(100.0 * coll / busy)
    return sum(shares) / len(shares) if shares else None
