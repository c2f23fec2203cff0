"""The graph, its search keys and labelings, and the query schedule:
deterministic per seed."""

import numpy as np
import pytest

from bench.lib import graph as G
from bench.lib.traffic import query_schedule

GEN = {"scale": 10, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19}
SEED = 2**31 + 7


@pytest.fixture(scope="module")
def g():
    return G.graph500(GEN, SEED)


def test_graph_is_symmetric_deduplicated_and_seeded(g):
    key = g.src.astype(np.int64) * g.n + g.dst
    rev = g.dst.astype(np.int64) * g.n + g.src
    assert np.all(np.diff(key) > 0) and not np.any(g.src == g.dst)
    order = np.argsort(rev)
    assert np.array_equal(rev[order], key)
    assert np.array_equal(g.w[order], g.w)          # one weight per edge
    assert g.w.min() > 0 and g.w.max() <= 1
    again = G.graph500(GEN, SEED)
    assert np.array_equal(again.src, g.src) and np.array_equal(again.w, g.w)
    other = G.graph500(GEN, SEED + 1)
    assert other.src.size != g.src.size or not np.array_equal(other.src,
                                                              g.src)


def test_search_keys(g):
    keys, warm = G.search_keys(g, 64, SEED)
    deg = np.bincount(g.src, minlength=g.n)
    assert len(set(keys) | {warm}) == 65 and all(deg[k] > 0 for k in keys)
    assert G.search_keys(g, 64, SEED) == (keys, warm)


def test_query_schedule_alternates_and_refreshes():
    plan = query_schedule(["bfs", "sssp"], [10, 11, 12], 8)
    assert [p for p, _, _ in plan] == ["bfs", "sssp"] * 4
    assert [k for _, k, _ in plan] == [10, 10, 11, 11, 12, 12, 10, 10]
    assert [r for _, _, r in plan] == [False] * 6 + [True] * 2


def test_relabeling_keeps_each_cell_and_the_graph(g):
    """Another seed, the same graph: ids move only within their cell's
    run of ids, edges and weights map over one to one."""
    h, lab = G.relabel_within_cells(g, 4, SEED)
    q = g.n // 4
    assert np.array_equal(np.sort(lab), np.arange(g.n))
    assert np.array_equal(lab // q, np.arange(g.n) // q)
    want = np.sort(lab[g.src].astype(np.int64) * g.n + lab[g.dst])
    assert np.array_equal(h.src.astype(np.int64) * g.n + h.dst, want)
    assert np.array_equal(np.sort(h.w), np.sort(g.w))
    again, lab2 = G.relabel_within_cells(g, 4, SEED)
    assert np.array_equal(lab, lab2)
    assert not np.array_equal(lab, G.relabel_within_cells(g, 4, 1)[1])
