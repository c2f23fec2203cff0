"""End-to-end behaviour of the paper's system: diffusive computation."""

import numpy as np
import pytest

from repro.core import (
    bfs,
    build,
    connected_components,
    personalized_pagerank,
    sssp,
)
from repro.core.diffuse import (_sg_as_dict, diffuse, initial_state,
                                make_spmd_diffuse)
from repro.core.event import build_adjacency, event_sssp
from repro.core.generators import GENERATORS, make_graph_family
from repro.core.programs import sssp_program
from repro.core.dynamic import (
    NameServer,
    edge_add,
    incremental_sssp,
    peek,
    vertex_add,
    vertex_delete,
)

FAMILIES = list(GENERATORS)


def _dist_close(a, b, atol=1e-4):
    a = np.where(np.isinf(a), 1e30, a)
    b = np.where(np.isinf(b), 1e30, b)
    return np.allclose(a, b, atol=atol)


@pytest.mark.parametrize("family", FAMILIES)
def test_sssp_matches_event_oracle_all_families(family):
    src, dst, w, n = make_graph_family(family, 150, seed=2)
    dist_ev, ev = event_sssp(build_adjacency(src, dst, w, n), n, 0)
    part = build(src, dst, n, w, n_cells=4)
    res = sssp(part, 0)
    assert _dist_close(res.values, np.array(dist_ev))
    assert ev.ds_terminated and not ev.ds_was_premature


@pytest.mark.parametrize("strategy", ["block", "hash", "locality"])
def test_partition_strategies_same_fixed_point(strategy):
    src, dst, w, n = make_graph_family("scale_free", 200, seed=1)
    ref = sssp(build(src, dst, n, w, n_cells=1), 0).values
    got = sssp(build(src, dst, n, w, n_cells=8, strategy=strategy), 0).values
    assert _dist_close(got, ref)


def test_parent_tree_is_consistent():
    src, dst, w, n = make_graph_family("erdos_renyi", 150, seed=3)
    part = build(src, dst, n, w, n_cells=4)
    res = sssp(part, 0)
    dist, parent = res.values, res.extra["parent"]
    wmap = {}
    for s, d, x in zip(src, dst, w):
        key = (int(s), int(d))
        wmap[key] = min(wmap.get(key, np.inf), float(x))
    for v in range(n):
        if np.isfinite(dist[v]) and v != 0:
            p = int(parent[v])
            assert p >= 0
            assert np.isclose(dist[v], dist[p] + wmap[(p, v)], atol=1e-4)


def test_async_beats_or_matches_bsp_rounds():
    src, dst, w, n = make_graph_family("small_world", 300, seed=4)
    part = build(src, dst, n, w, n_cells=8)
    r_async = sssp(part, 0, max_local_iters=64)
    r_bsp = sssp(part, 0, max_local_iters=1)
    assert int(r_async.stats.rounds) <= int(r_bsp.stats.rounds)
    assert _dist_close(r_async.values, r_bsp.values)


def test_operons_sent_equals_delivered():
    src, dst, w, n = make_graph_family("graph500", 256, seed=5)
    res = sssp(build(src, dst, n, w, n_cells=4), 0)
    assert int(res.stats.operons_sent) == int(res.stats.operons_delivered)


def test_actions_normalized_at_least_one_edge_visit():
    src, dst, w, n = make_graph_family("erdos_renyi", 100, seed=6)
    res = sssp(build(src, dst, n, w, n_cells=2), 0)
    n_reachable_edges = sum(
        1 for s in src if np.isfinite(res.values[int(s)])
    )
    assert int(res.stats.actions) >= n_reachable_edges > 0


def test_bfs_and_cc_and_ppr():
    src, dst, w, n = make_graph_family("powerlaw_cluster", 150, seed=7)
    part = build(src, dst, n, w, n_cells=4)
    lv = bfs(part, 0).values
    dist_ev, _ = event_sssp(
        build_adjacency(src, dst, np.ones_like(w), n), n, 0
    )
    assert _dist_close(lv, np.array(dist_ev))
    cc = connected_components(part).values
    reach = np.isfinite(lv)
    assert len(set(cc[reach])) == 1
    ppr = personalized_pagerank(part, 0, eps=1e-6)
    assert 0.9 < ppr.values.sum() <= 1.0 + 1e-3


def test_ds_termination_fires_exactly_at_quiescence():
    src, dst, w, n = make_graph_family("small_world", 100, seed=8)
    for schedule in ("lifo", "fifo"):
        _, st = event_sssp(build_adjacency(src, dst, w, n), n, 0, schedule)
        assert st.ds_terminated
        assert not st.ds_was_premature
        assert st.acks == st.actions   # one ack per diffusion message


def test_spmd_engine_matches_logical_engine():
    from repro.launch.mesh import make_mesh

    src, dst, w, n = make_graph_family("erdos_renyi", 120, seed=9)
    part = build(src, dst, n, w, n_cells=1)
    mesh = make_mesh((1,), ("cells",))
    prog = sssp_program(3)
    fn = make_spmd_diffuse(mesh, prog, part.sg, axis_name="cells")
    vs, st = fn(_sg_as_dict(part.sg), *initial_state(part.sg, prog))
    ref = sssp(part, 3)
    got = np.asarray(part.to_global_layout(vs["dist"]))[: part.n_real]
    assert _dist_close(got, ref.values)


def test_dynamic_graph_primitives_and_incremental_sssp():
    """Dynamic-graph round trip through the session API, with the legacy
    ``incremental_sssp`` wrapper checked for agreement along the way."""
    from repro.core import DiffusionSession

    src, dst, w, n = make_graph_family("erdos_renyi", 120, seed=10)
    sess = DiffusionSession.from_edges(src, dst, n, w, n_cells=4,
                                       edge_slack=0.3, node_slack=0.1)
    sess.query("sssp", source=0)

    # legacy path on an identical twin (same partition, same updates)
    part = build(src, dst, n, w, n_cells=4, edge_slack=0.3, node_slack=0.1)
    ns = NameServer(part)
    vstate, _ = diffuse(part, sssp_program(0))

    rng = np.random.default_rng(1)
    live = np.stack([src, dst], 1)
    deletes = [tuple(map(int, live[i]))
               for i in rng.choice(len(src), 4, replace=False)]
    inserts = [(int(rng.integers(0, n)), int(rng.integers(0, n)),
                float(1 + rng.random() * 5)) for _ in range(4)]

    for u, v in deletes:
        sess.delete_edge(u, v)
    for u, v, x in inserts:
        sess.add_edge(u, v, x)
    sess.commit()
    got = sess.query("sssp", source=0).values[:n]

    part, vstate, _ = incremental_sssp(part, ns, vstate, 0,
                                       inserts=inserts, deletes=deletes)
    legacy = np.asarray(part.to_global_layout(vstate["dist"]))[: part.n_real]
    assert _dist_close(got, legacy)

    edges = {}
    for s, d, x in zip(src, dst, w):
        edges[(int(s), int(d))] = float(x)
    for u, v in deletes:
        edges.pop((u, v), None)
    for u, v, x in inserts:
        edges[(u, v)] = x
    s2 = np.array([e[0] for e in edges])
    d2 = np.array([e[1] for e in edges])
    w2 = np.array(list(edges.values()))
    dist_ev, _ = event_sssp(build_adjacency(s2, d2, w2, n), n, 0)
    assert _dist_close(got, np.array(dist_ev))

    # vertex primitives through the session + raw-primitive parity
    gid = sess.add_vertex(shard=1)
    sess.add_edge(0, gid, 2.5)
    sess.commit()
    assert np.isfinite(sess.query("sssp", source=0).values[gid])
    pk = sess.peek(0, source=0)
    assert np.isfinite(np.asarray(pk)).sum() > 0

    sg, gid2 = vertex_add(part.sg, ns, shard=1)
    sg = edge_add(sg, ns, 0, gid2, 2.5)
    part.sg = sg
    vstate, _ = diffuse(part, sssp_program(0))
    s_, l_ = ns.resolve(gid2)
    assert np.isfinite(float(vstate["dist"][s_, l_]))
    pk = peek(part.sg, vstate["dist"], ns, 0)
    assert np.isfinite(np.asarray(pk)).sum() > 0
    part.sg = vertex_delete(part.sg, ns, gid2)
    vstate, _ = diffuse(part, sssp_program(0))
    assert np.isinf(float(vstate["dist"][s_, l_]))

    sess.delete_vertex(gid)
    sess.commit()
    assert np.isinf(sess.query("sssp", source=0).values[gid])


def test_global_pagerank_matches_power_iteration():
    from repro.core import pagerank

    src, dst, w, n = make_graph_family("scale_free", 200, seed=11)
    part = build(src, dst, n, w, n_cells=4)
    res = pagerank(part, alpha=0.15, eps=1e-8)
    # power iteration reference: p <- alpha*u + (1-alpha) W^T p
    deg = np.bincount(src, minlength=n).astype(np.float64)
    deg = np.maximum(deg, 1)
    p = np.full(n, 1.0 / n)
    u = np.full(n, 1.0 / n)
    for _ in range(200):
        spread = np.zeros(n)
        np.add.at(spread, dst, p[src] / deg[src])
        p = 0.15 * u + 0.85 * spread
    got = res.values / max(res.values.sum(), 1e-12)
    ref = p / p.sum()
    assert np.max(np.abs(got - ref)) < 5e-3, np.max(np.abs(got - ref))


def test_delta_stepping_gate_reduces_actions_to_near_ideal():
    """Beyond-paper: priority-gated diffusion (delta-stepping buckets)
    reaches the paper's ideal Actions Normalized ~= 1.0."""
    from repro.core.diffuse import diffuse as _diffuse
    from repro.core.programs import sssp_program as _sssp

    src, dst, w, n = make_graph_family("scale_free", 600, seed=12)
    part = build(src, dst, n, w, n_cells=4, strategy="locality")
    ref, _ = event_sssp(build_adjacency(src, dst, w, n), n, 0)

    vs0, st0 = _diffuse(part, _sssp(0))
    vs1, st1 = _diffuse(part, _sssp(0), delta=2.0)
    for vs in (vs0, vs1):
        got = np.asarray(part.to_global_layout(vs["dist"]))[: part.n_real]
        assert _dist_close(got, np.array(ref))
    assert int(st1.actions) < int(st0.actions)
    assert float(st1.actions) / len(src) < 1.25   # near-ideal work
