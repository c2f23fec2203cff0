#!/usr/bin/env python3
"""Bring-up smoke run of ``DiffusionSession`` on a TPU.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --chips 4      # four chips: the spmd phase only

One process, no subprocesses.  It refuses to run unless JAX's first device
is a TPU, and prints as its last line exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
Every check raises on failure, so any failed phase exits non-zero.

One chip, through the session's public entry points:

1. ingest   — Graph500 RMAT (a=.57, b=.19, c=.19, edge factor 16) at
   ``--scale``, partitioned over 4 cells of the ``sharded`` engine;
2. queries  — sssp, bfs (from a seeded root) and cc against scipy's
   Dijkstra, BFS and connected components;
3. update   — one seeded batch of edge inserts and deletes, ``commit()``,
   the repaired sssp/bfs/cc re-checked on the updated edge list;
4. durable  — ``save()``, a second (insert-only) batch,
   ``DiffusionSession.open()``: every cached result bitwise equal to the
   live session's;
5. pallas   — sssp on ``backend="pallas"`` bitwise equal to ``xla``;
6. sum      — a ppr query on the recovered, still dirty session (sum
   programs compact first), then the compacted pull and push views
   against the host edge list, and the ppr against a session built
   afresh from the same edges.  The sum query comes last because every
   later commit would repeat its compaction and dense scan sweeps (tens
   of seconds each at scale 20); the fresh build waits for the recovered
   session to close, so one graph layout is on the device at a time.

Four chips (``--chips 4``): the same graph on ``engine="spmd"``, one cell
per device — sssp, cc, sssp from a new source (which must compile
nothing), one commit — bitwise against ``engine="sharded"`` with 4 cells
on device 0; each phase prints every device's peak bytes.

The persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when
that is set, else ``.jax_cache/`` next to this file.  Phase times are
single cold runs, not benchmark numbers; compile time is kept apart.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# free edge slots per cell, as a fraction of its edges: the commit
# batches' inserts land in them
EDGE_SLACK = 0.01
# undirected inserts (and as many deletes) in the first commit batch, each
# staged in both directions; graphs below scale 14 take 2^scale / 16
BATCH_PAIRS = 1024
# l1 distance allowed between two ppr runs over the same edges
PPR_L1_TOL = 1e-5

# jax.monitoring events that make up a compile: trace, lower, backend
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeError(RuntimeError):
    """A smoke check failed."""


def check(what: str, ok, detail: str = "") -> None:
    if not ok:
        raise SmokeError(f"check {what} FAILED {detail}".rstrip())
    print(f"check {what}: ok {detail}".rstrip(), flush=True)


class Clock:
    """Per-phase wall time, with compile time (from jax.monitoring) and
    the device's peak bytes reported beside it (every device's, where
    there are several)."""

    def __init__(self, jax):
        self._jax = jax
        self._compile = 0.0
        self.compiles = 0       # backend compiles (not traces, lowerings)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self._compile += duration
        if event == _COMPILE_EVENTS[-1]:
            self.compiles += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0 = time.perf_counter(), self._compile
        print(f"phase {name}: start", flush=True)
        yield
        wall = time.perf_counter() - t0
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", "n/a")
                 for d in self._jax.devices()]
        each = f" peak_bytes_by_device={peaks}" if len(peaks) > 1 else ""
        print(f"phase {name}: wall_s={wall} compile_s={self._compile - c0} "
              f"peak_bytes_in_use={peaks[0]}{each}", flush=True)


# ---------------------------------------------------------------------------
# plain host references (scipy.sparse.csgraph)
# ---------------------------------------------------------------------------

def _csr(src, dst, w, n):
    from scipy.sparse import csr_matrix
    return csr_matrix((w.astype(np.float64), (src, dst)), shape=(n, n))


def ref_sssp(g, root):
    from scipy.sparse.csgraph import dijkstra
    return dijkstra(g, directed=True, indices=root)


def ref_bfs(g, root):
    """Hop counts from scipy's BFS tree (pointer jumping up the
    predecessor array); unreached vertices are +inf."""
    from scipy.sparse.csgraph import breadth_first_order
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


def ref_cc(g):
    from scipy.sparse.csgraph import connected_components
    return connected_components(g, directed=True, connection="weak")[1]


def same_partition(a, b) -> bool:
    """Two labelings define the same partition iff the label pairs
    form a bijection."""
    pairs = np.unique(np.stack([a, b]).astype(np.int64), axis=1)
    return (pairs.shape[1] == np.unique(a).size == np.unique(b).size)


def check_sssp(tag, res, ref):
    got = np.asarray(res.values, np.float64)
    inf_ok = np.array_equal(np.isinf(got), np.isinf(ref))
    fin = np.isfinite(ref)
    err = (float(np.max(np.abs(got[fin] - ref[fin]) / np.maximum(ref[fin], 1)))
           if fin.any() else 0.0)
    check(f"{tag} sssp == dijkstra", inf_ok and err <= 1e-5 and
          bool(res.stats.converged),
          f"(reached={int(fin.sum())} max_rel_err={err})")


def check_bfs(tag, res, ref):
    got = np.asarray(res.values, np.float64)
    check(f"{tag} bfs == scipy bfs", np.array_equal(got, ref) and
          bool(res.stats.converged),
          f"(reached={int(np.isfinite(ref).sum())} "
          f"depth={int(ref[np.isfinite(ref)].max())})")


def check_cc(tag, res, ref):
    got = np.asarray(res.values)
    check(f"{tag} cc == scipy components", same_partition(got, ref) and
          bool(res.stats.converged),
          f"(components={np.unique(ref).size})")


def edge_multiset(src, dst, w, n):
    """(src*n + dst, weight) pairs in key order (the smoke's edge lists
    hold each (src, dst) once, so the key alone fixes the order)."""
    key = src.astype(np.int64) * n + dst
    order = np.argsort(key)
    return key[order], w[order]


def check_layout(tag, sg, src, dst, w, n):
    """Both edge views of ``sg`` sorted (pull by destination key, push by
    source) and holding exactly the host edge list (src, dst, w)."""
    gid = np.asarray(sg.gid)
    rows = np.arange(sg.n_shards)[:, None]
    want = edge_multiset(src, dst, w, n)
    pull, push = sg.csr_view(), sg.push_view()
    for view, order_key, src_local, dst_gid, weight in (
            ("pull", pull["csr_key"], pull["csr_src"], pull["csr_dst_gid"],
             pull["csr_weight"]),
            ("push", push["push_src"], push["push_src"],
             push["push_dst_gid"], push["push_weight"])):
        order_key = np.asarray(order_key)
        live = order_key >= 0
        # live positions form a sorted prefix of each row
        prefix = np.all(np.cumsum(~live, axis=1)[live] == 0)
        ordered = all(np.all(np.diff(k[k >= 0]) >= 0) for k in order_key)
        got = edge_multiset(gid[rows, np.maximum(np.asarray(src_local), 0)]
                            [live], np.asarray(dst_gid)[live],
                            np.asarray(weight)[live], n)
        same = (got[0].size == want[0].size
                and np.array_equal(got[0], want[0])
                and np.array_equal(got[1], want[1]))
        check(f"{tag} {view} view == edge list", prefix and ordered and same,
              f"(live={int(live.sum())} reference={want[0].size})")


# ---------------------------------------------------------------------------
# graph + update batches
# ---------------------------------------------------------------------------

def build_graph(scale: int, seed: int):
    from repro.core.generators import make_graph_family
    src, dst, w, n = make_graph_family("graph500", 1 << scale, seed=seed)
    return src, dst, w, n


def make_batch(rng, src, dst, w, n, n_dels: int, n_ins: int):
    """``n_dels`` undirected deletes of live edges and ``n_ins``
    undirected inserts of absent ones, each staged in both directions so
    the graph stays symmetric (cc = weak components).  Returns the ops
    and the updated host edge list."""
    keys = src.astype(np.int64) * n + dst
    und = np.flatnonzero(src < dst)
    d = und[rng.choice(und.size, n_dels, replace=False)]
    dels = np.stack([src[d], dst[d]], axis=1).astype(np.int64)
    ins = []
    taken = set()
    skeys = np.sort(keys)
    while len(ins) < n_ins:
        u, v = (int(x) for x in rng.integers(0, n, 2))
        a, b = min(u, v), max(u, v)
        k = a * n + b
        i = np.searchsorted(skeys, k)
        if a == b or (a, b) in taken or (i < skeys.size and skeys[i] == k):
            continue
        taken.add((a, b))
        ins.append((a, b, float(np.float32(1.0 + 7.0 * rng.random()))))
    ins_a = np.array(ins, dtype=np.float64).reshape(-1, 3)
    iu, iv = ins_a[:, 0].astype(np.int64), ins_a[:, 1].astype(np.int64)
    iw = ins_a[:, 2].astype(np.float32)
    gone = np.concatenate([dels[:, 0] * n + dels[:, 1],
                           dels[:, 1] * n + dels[:, 0]])
    keep = ~np.isin(keys, gone)
    src2 = np.concatenate([src[keep], iu, iv]).astype(np.int32)
    dst2 = np.concatenate([dst[keep], iv, iu]).astype(np.int32)
    w2 = np.concatenate([w[keep], iw, iw]).astype(np.float32)
    return (dels, (iu, iv, iw)), (src2, dst2, w2)


def stage(sess, ops):
    dels, (iu, iv, iw) = ops
    for u, v in dels:
        sess.delete_edge(int(u), int(v))
        sess.delete_edge(int(v), int(u))
    for u, v, wt in zip(iu, iv, iw):
        sess.add_edge(int(u), int(v), float(wt))
        sess.add_edge(int(v), int(u), float(wt))


def batch_pairs(scale: int) -> int:
    return min(BATCH_PAIRS, (1 << scale) // 16)


def pick_root(rng, src, n):
    deg = np.bincount(src, minlength=n)
    return int(rng.choice(np.flatnonzero(deg > 0)))


def ppr_close(a, b):
    """Two ppr runs over the same edges take the same push steps, so they
    agree to float32 rounding: the l1 distance of their ranks (and of
    their leftover residuals) must stay below ``PPR_L1_TOL``, far below
    the rank mass itself (at least the root's teleport share, 0.15)."""
    def l1(x, y):
        return float(np.abs(np.asarray(x, np.float64)
                            - np.asarray(y, np.float64)).sum())
    d_rank = l1(a.values, b.values)
    d_res = l1(a.extra["residual"], b.extra["residual"])
    mass = float(np.sum(a.values, dtype=np.float64))
    return (mass > 100 * PPR_L1_TOL and max(d_rank, d_res) <= PPR_L1_TOL,
            f"(rank_mass={mass} l1_rank={d_rank} l1_residual={d_res} "
            f"tol={PPR_L1_TOL})")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

QUERIES = ("sssp", "bfs", "cc")


def timed(label: str, fn, *args, **kwargs):
    """Run one step and print its wall time (results come back as host
    arrays, so the device work is inside the window)."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    print(f"step {label}: wall_s={time.perf_counter() - t0}", flush=True)
    return out


def query(sess, name, **kw):
    res = timed(f"query {name}", sess.query, name, **kw)
    st = res.stats
    print(f"stats {name}: rounds={int(st.rounds)} "
          f"local_iters={int(st.local_iters)} actions={int(st.actions)}",
          flush=True)
    return res


def commit(sess):
    info = timed("commit", sess.commit)
    for key, (strategy, st) in info.repairs.items():
        if st is not None:
            print(f"stats repair {key[0]} ({strategy}): rounds="
                  f"{int(st.rounds)} local_iters={int(st.local_iters)} "
                  f"push_iters={int(st.push_iters)}", flush=True)
    return info


def run_one_chip(args, clock) -> None:
    from repro.core.session import DiffusionSession

    rng = np.random.default_rng(args.seed)
    snap = os.path.join(args.out, "snapshot")
    shutil.rmtree(snap, ignore_errors=True)
    pairs = batch_pairs(args.scale)
    common = dict(n_cells=4, edge_slack=EDGE_SLACK,
                  backend="xla", on_budget="raise")

    with clock.phase("1-ingest"):
        src, dst, w, n = timed("generate", build_graph, args.scale,
                               args.seed)
        sess = timed("from_edges", DiffusionSession.from_edges, src, dst, n,
                     w, engine="sharded", **common)
        live = int(sess.sg.n_edges())
        check("ingest live edges", live == src.size,
              f"(scale={args.scale} vertices={n} live_edges={live})")
        print(f"layout_bytes={sess.sg.layout_bytes()}", flush=True)
    root = pick_root(rng, src, n)
    # every sub-iteration of a sum-combine query is a dense scan sweep of
    # the whole stream (seconds at scale 20); eps=1e-2 keeps ppr to a few
    seed_kw = {"sssp": {"source": root}, "bfs": {"source": root},
               "cc": {}, "ppr": {"source": root, "eps": 1e-2}}

    with clock.phase("2-queries"):
        g = timed("reference graph", _csr, src, dst, w, n)
        check_sssp("pre-commit", query(sess, "sssp", source=root),
                   timed("dijkstra", ref_sssp, g, root))
        check_bfs("pre-commit", query(sess, "bfs", source=root),
                  timed("scipy bfs", ref_bfs, g, root))
        check_cc("pre-commit", query(sess, "cc"),
                 timed("scipy components", ref_cc, g))
        del g

    with clock.phase("3-update"):
        ops1, (src1, dst1, w1) = make_batch(rng, src, dst, w, n, pairs,
                                            pairs)
        del src, dst, w
        timed("stage", stage, sess, ops1)
        info = commit(sess)
        check("commit applied", info.applied.n_ops == 4 * pairs,
              f"(ops={info.applied.n_ops} repairs="
              f"{sorted((k[0], s) for k, (s, _) in info.repairs.items())})")
        live = int(sess.sg.n_edges())
        check("post-commit live edges", live == src1.size,
              f"(live_edges={live} reference={src1.size})")
        g = timed("reference graph", _csr, src1, dst1, w1, n)
        check_sssp("post-commit", query(sess, "sssp", source=root),
                   timed("dijkstra", ref_sssp, g, root))
        check_bfs("post-commit", query(sess, "bfs", source=root),
                  timed("scipy bfs", ref_bfs, g, root))
        check_cc("post-commit", query(sess, "cc"),
                 timed("scipy components", ref_cc, g))
        del g

    with clock.phase("4-durable"):
        timed("save", sess.save, snap)
        ops2, (src2, dst2, w2) = make_batch(rng, src1, dst1, w1, n, 0,
                                            max(pairs // 16, 1))
        del src1, dst1, w1
        stage(sess, ops2)
        commit(sess)
        live_res = {name: query(sess, name, **seed_kw[name])
                    for name in QUERIES}
        sess.close()
        del sess
        gc.collect()
        rec = timed("open", DiffusionSession.open, snap)
        for name in QUERIES:
            a, b = live_res[name], rec.query(name, **seed_kw[name])
            same = np.array_equal(np.asarray(a.values),
                                  np.asarray(b.values), equal_nan=True)
            same &= all(np.array_equal(np.asarray(v), np.asarray(b.extra[k]),
                                       equal_nan=True)
                        for k, v in a.extra.items())
            check(f"open() == live {name}", same)
        del live_res

    with clock.phase("5-pallas"):
        rx = rec.query("sssp", source=root)
        rp = query(rec, "sssp", source=root, backend="pallas")
        check("pallas sssp == xla sssp",
              np.array_equal(np.asarray(rx.values), np.asarray(rp.values))
              and np.array_equal(rx.extra["parent"], rp.extra["parent"])
              and bool(rp.stats.converged))
        del rx, rp

    with clock.phase("6-sum"):
        dirty = int(np.asarray(rec.sg.delta_count).sum()
                    + np.asarray(rec.sg.tomb_count).sum())
        ppr = query(rec, "ppr", **seed_kw["ppr"])
        clean = int(np.asarray(rec.sg.delta_count).sum()
                    + np.asarray(rec.sg.tomb_count).sum())
        check("ppr compacted the dirty graph", dirty > 0 and clean == 0
              and bool(ppr.stats.converged),
              f"(staged+tombstoned before={dirty})")
        timed("layout check", check_layout, "compacted", rec.sg, src2,
              dst2, w2, n)
        rec.close()
        del rec
        gc.collect()
        shutil.rmtree(snap, ignore_errors=True)
        fresh = timed("from_edges", DiffusionSession.from_edges, src2, dst2,
                      n, w2, engine="sharded", **common)
        ok, detail = ppr_close(ppr, query(fresh, "ppr", **seed_kw["ppr"]))
        check("ppr == fresh build ppr", ok, detail)


def run_four_chips(args, clock) -> None:
    import jax

    from repro.core.graph import from_edges
    from repro.core.partition import Partitioned, partition
    from repro.core.session import DiffusionSession

    rng = np.random.default_rng(args.seed)
    with clock.phase("spmd-ingest"):
        src, dst, w, n = build_graph(args.scale, args.seed)
        root = pick_root(rng, src, n)
        root2 = pick_root(rng, src, n)
        pairs = batch_pairs(args.scale)
        ops, _ = make_batch(rng, src, dst, w, n, pairs, pairs)
        part = partition(from_edges(src, dst, n, w,
                                    edge_slack=EDGE_SLACK), 4)
        del src, dst, w

    def run(engine):
        # each session gets its own Partitioned around the shared initial
        # layout: commit() replaces a session's part.sg, never mutates it
        sess = DiffusionSession(
            Partitioned(part.sg, part.owner, part.local, n_real=part.n_real,
                        replica=part.replica),
            engine=engine, backend="xla", on_budget="raise")
        out = [sess.query("sssp", source=root), sess.query("cc")]
        devs = {d for name, kw in (("sssp", {"source": root}), ("cc", {}))
                for a in sess.vertex_state(name, **kw).values()
                for d in a.sharding.device_set}
        # a query from a new source compiles nothing on either engine
        c0, t0 = clock.compiles, time.perf_counter()
        out.append(sess.query("sssp", source=root2))
        print(f"{engine}: second sssp source: wall_s="
              f"{time.perf_counter() - t0} compiles={clock.compiles - c0}",
              flush=True)
        check(f"{engine} query from a new source compiles nothing",
              clock.compiles == c0, f"(compiles={clock.compiles - c0})")
        stage(sess, ops)
        sess.commit()
        out += [sess.query("sssp", source=root), sess.query("cc")]
        del sess
        gc.collect()
        return out, devs

    with clock.phase("spmd-4-devices"):
        spmd, devs = run("spmd")
        check("spmd state spread over 4 devices",
              len(devs) == 4 and devs == set(jax.devices()[:4]),
              f"(devices={sorted(d.id for d in devs)})")
    with clock.phase("sharded-device-0"):
        sharded, devs0 = run("sharded")
        check("sharded state on device 0", devs0 == {jax.devices()[0]})
    for tag, a, b in zip(("sssp", "cc", "sssp from a new source",
                          "sssp after commit", "cc after commit"), spmd,
                         sharded):
        check(f"spmd == sharded {tag}",
              np.array_equal(np.asarray(a.values), np.asarray(b.values))
              and bool(a.stats.converged) and bool(b.stats.converged))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=20,
                    help="Graph500 scale (2^scale vertices)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(HERE, "artifacts",
                                                  "chip_smoke"),
                    help="scratch directory for the snapshot")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (first device is "
              f"{devices[0].platform!r}); refusing to run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(HERE, ".jax_cache"))
    sys.path.insert(0, os.path.join(HERE, "src"))
    import repro.core.session  # noqa: F401 — fail before any phase if absent

    os.makedirs(args.out, exist_ok=True)
    print(f"device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)} "
          f"jax={jax.__version__}", flush=True)
    clock = Clock(jax)
    if args.chips == 4:
        run_four_chips(args, clock)
    else:
        run_one_chip(args, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
