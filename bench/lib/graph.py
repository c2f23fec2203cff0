"""The benchmark's graphs, made from a seed.

A copy of the Graph500 Kronecker generator (spec section 3: initiator
a=.57, b=.19, c=.19, edge factor 16, vertex labels permuted), kept here so
that the yardstick cannot move with the program.  Unlike the program's
copy, this one gives each undirected edge one weight for both of its
directions, as the Graph500 SSSP kernel's undirected graph has.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Graph(NamedTuple):
    src: np.ndarray      # int32 [E] directed edges, both directions, sorted
    dst: np.ndarray      # int32 [E]
    w: np.ndarray        # float32 [E], w(u, v) == w(v, u)
    n: int               # vertex ids 0..n-1


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), stream]))


def kronecker_pairs(rng, scale: int, count: int, a: float, b: float,
                    c: float):
    """``count`` Kronecker edge draws (unpermuted labels), int64."""
    src = np.zeros(count, np.int64)
    dst = np.zeros(count, np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    for i in range(scale):
        r1 = rng.random(count)
        r2 = rng.random(count)
        src_bit = r1 > ab
        dst_bit = np.where(src_bit, r2 > c_norm, r2 > a_norm)
        src |= src_bit.astype(np.int64) << i
        dst |= dst_bit.astype(np.int64) << i
    return src, dst


def draw_weights(rng, count: int) -> np.ndarray:
    """Uniform weights on (0, 1] in float32: the spec's [0, 1) mirrored,
    so that no edge weighs 0 (a sparse reference would drop it)."""
    return (np.float32(1.0) - rng.random(count, dtype=np.float32))


def graph500(gen: dict, seed: int) -> Graph:
    """Build the configuration's graph: ``gen`` holds scale, edge_factor
    and the initiator a, b, c."""
    scale = int(gen["scale"])
    n = 1 << scale
    rng = rng_for(seed, 0)
    s, d = kronecker_pairs(rng, scale, n * int(gen["edge_factor"]),
                           gen["a"], gen["b"], gen["c"])
    perm = rng.permutation(n).astype(np.int64)
    s, d = perm[s], perm[d]
    keep = s != d
    lo = np.minimum(s[keep], d[keep])
    hi = np.maximum(s[keep], d[keep])
    und = np.unique(lo * n + hi)                 # undirected, u < v
    w_und = draw_weights(rng, und.size)
    u, v = und // n, und % n
    key = np.concatenate([u * n + v, v * n + u])
    w = np.concatenate([w_und, w_und])
    order = np.argsort(key)
    key, w = key[order], w[order]
    return Graph((key // n).astype(np.int32), (key % n).astype(np.int32),
                 w, n)


def relabel_within_cells(g: Graph, cells: int, seed: int):
    """The same graph under another labeling: ``--seed`` permutes the
    vertex ids within each run of ``ceil(n / cells)`` ids, where ``cells``
    is the configuration's ``relabel_blocks``, the block partition of the
    deployment's compute cells.  Every seed then gives the same blocks the
    same vertices and edges, so that the work of a run does not change
    with the seed; only the order of ids, slots and edges in each block
    does.  Returns the graph and the map old id -> new id."""
    q = -(-g.n // cells)
    rng = rng_for(seed, 3)
    lab = np.concatenate([lo + rng.permutation(min(q, g.n - lo))
                          for lo in range(0, g.n, q)]).astype(np.int64)
    src, dst = lab[g.src], lab[g.dst]
    order = np.lexsort((dst, src))
    return Graph(src[order].astype(np.int32), dst[order].astype(np.int32),
                 g.w[order], g.n), lab


def search_keys(g: Graph, count: int, seed: int):
    """Graph500 search keys: ``count`` distinct vertices of degree >= 1,
    drawn from the seed; plus one more such vertex for warming up."""
    rng = rng_for(seed, 1)
    cand = np.flatnonzero(np.bincount(g.src, minlength=g.n) > 0)
    pick = rng.choice(cand, size=count + 1, replace=False)
    return [int(x) for x in pick[:count]], int(pick[count])


def undirected_edges_reached(g_src: np.ndarray, reached: np.ndarray) -> int:
    """Graph500 traversed edges: undirected input edges with an endpoint
    reached from the root.  The graph is symmetric, so both endpoints of
    such an edge are reached and each counts twice among directed
    edges."""
    return int(np.count_nonzero(reached[g_src])) // 2
