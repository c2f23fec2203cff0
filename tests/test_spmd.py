"""The spmd engine through ``DiffusionSession`` on four virtual devices:
answers against scipy and the event oracle, one compile per program
structure, and the graph kept one cell per device across commits.

One subprocess (the device count is fixed when JAX starts) runs every
check on a seeded Graph500 graph at scale 10 with random weights, and
prints what the tests below assert on.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from scipy.sparse.csgraph import connected_components

    from bench.lib import graph as G
    from bench.lib import refs
    from repro.core.session import DiffusionSession

    COMPILE = "/jax/core/compile/backend_compile_duration"
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(ev) if ev == COMPILE else None)

    def kron(scale):
        return G.graph500({"scale": scale, "edge_factor": 16, "a": .57,
                           "b": .19, "c": .19}, 20220218)

    def misplaced(sess):
        arrays = dict(sess.sg.state_dict(), **sess._spmd_views(True))
        return sorted(k for k, a in arrays.items()
                      if not a.sharding.is_equivalent_to(NamedSharding(
                          sess._mesh, P() if k == "replica_members"
                          else P("cells")), a.ndim))

    def answers(sess, g, keys, src, dst, w):
        ref_g = refs.csr(src, dst, w, g.n)
        out = {"sssp_gap": 0.0, "sssp_reach": 0, "bfs_mismatch": 0}
        for k in keys:
            s = sess.query("sssp", source=k, refresh=True).values[:g.n]
            gap, reach = refs.sssp_gap(s, refs.ref_sssp(ref_g, k))
            out["sssp_gap"] = max(out["sssp_gap"], gap)
            out["sssp_reach"] += reach
            b = sess.query("bfs", source=k, refresh=True).values[:g.n]
            out["bfs_mismatch"] += refs.exact_mismatch(
                b, refs.ref_bfs(ref_g, k))
        cc = sess.query("cc", refresh=True).values[:g.n]
        n_cc, label = connected_components(ref_g, directed=True,
                                           connection="weak")
        out["cc_same_partition"] = bool(
            len(np.unique(cc)) == n_cc
            and all(len(np.unique(label[cc == c])) == 1
                    for c in np.unique(cc)))
        return out

    out = {}
    g = kron(10)
    sess = DiffusionSession.from_edges(g.src, g.dst, g.n, g.w,
                                       engine="spmd", edge_slack=0.05)
    out["devices"] = sorted(d.id for d in sess._mesh.devices.flat)
    out["misplaced_built"] = misplaced(sess)
    deg = np.bincount(g.src, minlength=g.n)
    keys = [int(k) for k in np.flatnonzero(deg > 0)[[3, 50, 200]]]
    out["before"] = answers(sess, g, keys[:2], g.src, g.dst, g.w)

    n0 = len(compiles)
    sess.query("sssp", source=keys[2])                    # a new source
    sess.query("sssp", source=keys[2], refresh=True)      # the same again
    sess.query("bfs", source=keys[0], refresh=True)
    out["repeat_compiles"] = len(compiles) - n0
    state = sess.vertex_state("sssp", source=keys[2])
    out["state_devices"] = sorted({d.id for a in state.values()
                                   for d in a.sharding.device_set})

    u, v = int(g.src[5]), int(g.dst[5])
    sess.delete_edge(u, v).delete_edge(v, u)
    sess.add_edge(keys[0], keys[2], 0.01).add_edge(keys[2], keys[0], 0.01)
    info = sess.commit()
    out["repairs"] = sorted({s for s, _ in info.repairs.values()})
    out["misplaced_committed"] = misplaced(sess)
    src, dst, w = sess.edge_list()
    out["after"] = answers(sess, g, keys[:2], src, dst, w)

    # the event oracle is message-at-a-time on the host: a scale-6 graph
    small = kron(6)
    sm = DiffusionSession.from_edges(small.src, small.dst, small.n, small.w,
                                     engine="spmd")
    root = int(small.src[0])
    out["event"] = {}
    for name, kw in (("sssp", {"source": root}), ("bfs", {"source": root}),
                     ("cc", {})):
        a = sm.query(name, **kw).values[:small.n]
        b = np.asarray(sm.query(name, engine="event", **kw).values)[:small.n]
        fin = np.isfinite(b)
        out["event"][name] = bool(
            np.array_equal(np.isfinite(a), fin)
            and np.allclose(a[fin], b[fin], rtol=1e-6, atol=0))
    print("SPMD_RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [x for x in out.stdout.splitlines()
            if x.startswith("SPMD_RESULT ")]
    assert line, out.stdout[-2000:] + out.stderr[-4000:]
    return json.loads(line[0].split(" ", 1)[1])


@pytest.mark.parametrize("when", ["before", "after"])
def test_spmd_answers_equal_scipy(result, when):
    """bfs hop counts and cc components exactly, sssp to float32
    rounding, before and after a commit with deletes and inserts."""
    got = result[when]
    assert got["sssp_gap"] < 1e-5 and got["sssp_reach"] == 0, got
    assert got["bfs_mismatch"] == 0, got
    assert got["cc_same_partition"], got


def test_spmd_answers_equal_the_event_oracle(result):
    assert result["event"] == {"sssp": True, "bfs": True, "cc": True}


def test_second_query_compiles_nothing(result):
    """A new source, the same source again and the other program: the
    session's jitted shard_map and the eager init compile no program."""
    assert result["repeat_compiles"] == 0


def test_engine_arrays_sharded_one_cell_per_device(result):
    assert result["devices"] == [0, 1, 2, 3]
    assert result["misplaced_built"] == []
    assert result["state_devices"] == [0, 1, 2, 3]


def test_placement_holds_after_a_commit(result):
    assert set(result["repairs"]) >= {"parents", "component"}
    assert result["misplaced_committed"] == []
