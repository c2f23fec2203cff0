"""Closed loop of single queries: one caller, the next query sent when
the last has returned its values to the host.

The mix names the programs, taken in turn, each from the next of the
first ``keys_per_cycle`` Graph500 search keys, in an order drawn from the
seed (``query_schedule``); the window closes after the cycle over those
keys in which its time runs out.  Correct: every query of the window
against scipy on the host edge list, after the window.
"""

from __future__ import annotations

import time

import numpy as np

from bench.lib import graph as G
from bench.lib import refs
from bench.lib.traffic import query_schedule

REFS = {"sssp": refs.ref_sssp, "bfs": refs.ref_bfs}


def setup(ctx):
    with ctx.phase("graph"):
        g, lab, g0 = ctx.graph()
        keys0, warm = G.search_keys(g0, int(ctx.cfg["search_keys"]),
                                    int(ctx.cfg["graph_seed"]))
        # each run answers the same keys, in an order of its own
        k = int(ctx.mix["keys_per_cycle"])
        keys = [int(lab[keys0[i]])
                for i in G.rng_for(ctx.seed, 4).permutation(k)]
        warm = int(lab[warm])
    with ctx.phase("session"):
        sess = ctx.session(g)
    with ctx.phase("warm-up"):
        # the same programs at the same shapes, from a vertex with no edge
        # where there is one, so that warming up costs a round, not a query
        lone = np.flatnonzero(np.bincount(g.src, minlength=g.n) == 0)
        root = int(lone[0]) if lone.size else warm
        for prog in ctx.mix["programs"]:
            sess.query(prog, source=root)
    return {"g": g, "keys": keys, "sess": sess}


def run(ctx, st):
    sess, spans = st["sess"], ctx.spans
    # far more than a window can hold: the loop stops at the deadline
    plan = query_schedule(ctx.mix["programs"], st["keys"], 100_000)
    done = []
    cycle = len(ctx.mix["programs"]) * len(st["keys"])
    ctx.begin_window()
    deadline = ctx.t0 + ctx.seconds
    for i, (prog, key, refresh) in enumerate(plan):
        # the window holds whole cycles over the keys, so that every run
        # does the same work
        if i % cycle == 0 and time.perf_counter() >= deadline:
            break
        with spans("bench.query"):
            res = sess.query(prog, source=key, refresh=refresh)
        with spans("bench.result"):
            stats = res.stats
            done.append((prog, key, np.asarray(res.values),
                         int(stats.local_iters), bool(stats.converged)))
    ctx.end_window()
    st["done"] = done
    # where the window's wall time went, query by query, on an earlier line
    walls = [(n, t1 - t0) for n, t0, t1 in spans.done]
    asked = [w for n, w in walls if n == "bench.query"]
    ctx.note("window queries: " + " ".join(
        f"{d[0]}:{w}" for d, w in zip(done, asked))
        + f" result_s={sum(w for n, w in walls if n == 'bench.result')}"
        + f" between_s={ctx.t1 - ctx.t0 - sum(w for _, w in walls)}")


def release(ctx, st):
    st.pop("sess").close()


def check(ctx, st):
    g, done = st["g"], st["done"]
    ref_g = refs.csr(g.src, g.dst, g.w, g.n)
    gap, reach, bfs_bad, unconverged, failed, edges = 0.0, 0, 0, 0, 0, 0
    limits = ctx.cfg["limits"]
    for prog, key, values, _, conv in done:
        ref = REFS[prog](ref_g, key)
        edges += G.undirected_edges_reached(g.src, np.isfinite(ref))
        if prog == "sssp":
            q_gap, q_reach = refs.sssp_gap(values, ref)
            gap, reach = max(gap, q_gap), reach + q_reach
            bad = q_gap > limits["sssp_rel_gap"] or q_reach
        else:
            q_bad = refs.exact_mismatch(values, ref)
            bfs_bad += q_bad
            bad = q_bad > 0
        unconverged += not conv
        failed += bool(bad) or not conv
    iters = sum(d[3] for d in done)
    return {
        "attempted": len(done),
        "failed": failed,
        "checks": {
            "sssp_rel_gap": (gap, limits["sssp_rel_gap"]),
            "sssp_reach_mismatch": (reach, 0),
            "bfs_hop_mismatch": (bfs_bad, 0),
            "unconverged": (unconverged, 0),
        },
        "e2e": {"query_teps": edges / (ctx.t1 - ctx.t0)} if done else {},
        "counters": {
            "queries": len(done),
            "query_local_iters": iters,
            "live_edges": int(g.src.size),
            "vertices": g.n,
        },
    }
