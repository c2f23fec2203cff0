"""Plain host references and the comparisons that decide ``correct``.

scipy's Dijkstra and breadth-first search on the host edge list the
benchmark keeps: they import nothing of the program.
``sssp_lowp`` is the control: the same shortest paths computed in the
next precision below the configuration's float32, bfloat16, by
Bellman-Ford sweeps that round every weight and every sum.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import breadth_first_order, dijkstra


def csr(src, dst, w, n):
    return csr_matrix((np.asarray(w, np.float64), (src, dst)), shape=(n, n))


def ref_sssp(g, root: int) -> np.ndarray:
    return dijkstra(g, directed=True, indices=root)


def ref_bfs(g, root: int) -> np.ndarray:
    """Hop counts from scipy's BFS tree (pointer jumping up the
    predecessor array); unreached vertices are +inf."""
    order, pred = breadth_first_order(g, root, directed=True,
                                      return_predecessors=True)
    ptr = np.where(pred >= 0, pred, -1).astype(np.int64)
    hop = (ptr >= 0).astype(np.int64)
    while (ptr >= 0).any():
        m = ptr >= 0
        up = ptr[m]
        new_hop = hop.copy()
        new_ptr = ptr.copy()
        new_hop[m] = hop[m] + hop[up]
        new_ptr[m] = ptr[up]
        hop, ptr = new_hop, new_ptr
    depth = np.full(g.shape[0], np.inf)
    depth[order] = hop[order]
    return depth


def sssp_lowp(src, dst, w, n, root: int) -> np.ndarray:
    """The control: Bellman-Ford in bfloat16 (weights and every partial
    sum rounded), as a program that stored distances in bfloat16 would
    compute them."""
    import ml_dtypes

    bf = ml_dtypes.bfloat16
    wb = np.asarray(w, np.float32).astype(bf).astype(np.float32)
    dist = np.full(n, np.inf, np.float32)
    dist[root] = 0.0
    while True:
        cand = (dist[src] + wb).astype(bf).astype(np.float32)
        new = dist.copy()
        np.minimum.at(new, dst, cand)
        if np.array_equal(new, dist):
            return dist.astype(np.float64)
        dist = new


# -- the numbers compared ----------------------------------------------------

def sssp_gap(got, ref) -> tuple[float, int]:
    """(widest relative gap over vertices the reference reaches, count of
    vertices whose reachability differs)."""
    got = np.asarray(got, np.float64)
    fin = np.isfinite(ref)
    reach = int(np.count_nonzero(np.isfinite(got) != fin))
    pos = fin & (ref > 0) & np.isfinite(got)
    gap = (float(np.max(np.abs(got[pos] - ref[pos]) / ref[pos]))
           if pos.any() else 0.0)
    root_off = fin & (ref == 0) & (got != 0)
    return gap, reach + int(np.count_nonzero(root_off))


def exact_mismatch(got, ref) -> int:
    """Vertices whose value differs (hop counts; inf equals inf)."""
    return int(np.count_nonzero(np.asarray(got, np.float64) != ref))

