"""The bytes a relaxation sweep needs, from the graph's sizes alone.

Per sub-iteration of a dense sweep, each live directed edge reads its
source, destination and weight and its source's state (4 x 4 B), and
each vertex's state is read and written once more (2 x 4 B).  Padding,
staged delta blocks and layout bytes do not count, so the number reads
the same work whatever implementation does it.
"""

from __future__ import annotations

BYTES_PER_EDGE = 16
BYTES_PER_VERTEX = 8


def sweep_bytes(live_edges: int, vertices: int) -> int:
    """Bytes one dense sub-iteration needs over the whole graph."""
    return BYTES_PER_EDGE * int(live_edges) + BYTES_PER_VERTEX * int(vertices)


def hbm_share_pct(sub_iterations: int, live_edges: int, vertices: int,
                  busy_s: float, peak_bytes_per_s: float) -> float | None:
    """Share (%) of the peak HBM bandwidth that the needed bytes of
    ``sub_iterations`` sweeps make over ``busy_s`` of device time."""
    if busy_s <= 0 or sub_iterations <= 0:
        return None
    need = sub_iterations * sweep_bytes(live_edges, vertices)
    return 100.0 * need / busy_s / peak_bytes_per_s
