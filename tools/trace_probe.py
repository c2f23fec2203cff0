#!/usr/bin/env python3
"""Read the program's spans, scopes and host reads on a query cell's
graph, on the chip, and print what share of the device time each
``diffuse.*`` scope takes, on each device.

    python3 tools/trace_probe.py [--config graph500-s16] [--scale N]
                                 [--seed N] [--keys 3] [--out F]

It builds the session of ``bench/configs/<config>.json`` (the graph
from its ``graph_seed``, ids relabeled by ``--seed``, on the engine it
names: ``graph500-s18-spmd`` runs one cell per chip on four), warms bfs
and sssp up, then runs bfs and sssp from ``--keys`` search keys twice:
untraced, with per-stage host seconds, host reads, compiles, the
``repro.place`` stages and the ``/repro/exchange_bytes`` counter from a
``jax.monitoring`` listener, and under the profiler, each query inside a
``bench.query`` span as the benchmark's loop makes them.

The chip's ``XLA Ops`` events carry no op metadata, so a device op's
scope comes from the compiled HLO of the diffusion (``_run_rounds``, or
the spmd engine's ``spmd_rounds``): each instruction's
``op_name`` holds the ``jax.named_scope`` path, and its innermost
``diffuse.*`` name is the op's scope.  The module is compiled afresh with
the persistent cache off (an executable loaded from the cache prints no
metadata).  The compiler leaves some fusions without metadata (the
segment-combine scatters); such a fusion takes the commonest scope of
the instructions in the computations it calls, nested fusions included.
Device ops outside that module (eager init, per-call gathers) count as
``unscoped``.  On several devices a collective's self time is the
device's share of the exchange, waits at the barrier included.

It prints, and writes as JSON to ``--out``: the share of device self
time per scope inside ``repro.query``, over all devices and per device;
each device's peak memory; per query the device-idle time
inside each ``repro.query.*`` stage; relax time per sub-iteration,
per program split by the step of the sweep each op belongs to (source
read, emit, scan, run-end gathers, delta segment: the function of
``kernels/edge_relax/ref.py`` in the op's source frames, or for a
gather whose frames the compiler dropped, its output size), the
``/repro/source_gathers`` count of each program,
exchange time per round, host reads per query; the top ops as
``<scope>/<hlo name>``; and the longest idle gaps of the traced window,
each named by the innermost ``bench.*`` or ``repro.*`` span it overlaps.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.lib import graph as G  # noqa: E402
from bench.lib import measure as M  # noqa: E402
from repro.core import trace  # noqa: E402
from repro.core.diffuse import _run_rounds, initial_state  # noqa: E402
from bench.lib.exchange import is_collective  # noqa: E402
from repro.core.programs import PROGRAMS  # noqa: E402
from repro.core.session import DiffusionSession  # noqa: E402

PROGS = ("bfs", "sssp")
MODULES = ("jit__run_rounds", "jit_spmd_rounds")
COMPILE_EVENT = M.COMPILE_EVENT
_SCOPE_RE = re.compile("|".join(re.escape(s) for s in trace.SCOPES))


def scope_of(op_name):
    found = _SCOPE_RE.findall(op_name or "")
    return found[-1] if found else "unscoped"


def load_json(path):
    with open(path) as f:
        return json.load(f)


def build(config: str, scale: int | None, seed: int, n_keys: int):
    cfg = load_json(os.path.join(ROOT, "bench/configs", f"{config}.json"))
    if scale is not None:
        cfg["generator"]["scale"] = scale
    g0 = G.graph500(cfg["generator"], int(cfg["graph_seed"]))
    g, lab = G.relabel_within_cells(g0, int(cfg["relabel_blocks"]), seed)
    keys0, warm = G.search_keys(g0, int(cfg["search_keys"]),
                                int(cfg["graph_seed"]))
    keys = [int(lab[k]) for k in keys0[:2 * n_keys]]
    sess = DiffusionSession.from_edges(g.src, g.dst, g.n, g.w,
                                       engine=cfg["engine"],
                                       edge_slack=float(cfg["edge_slack"]))
    lone = np.flatnonzero(np.bincount(g.src, minlength=g.n) == 0)
    root = int(lone[0]) if lone.size else int(lab[warm])
    for p in PROGS:
        sess.query(p, source=root)
    return sess, keys


def run_queries(sess, plan, events, annotate: bool):
    rows = []
    for p, k in plan:
        events.clear()
        t0 = time.perf_counter()
        if annotate:
            with jax.profiler.TraceAnnotation("bench.query"):
                r = sess.query(p, source=k, refresh=True)
        else:
            r = sess.query(p, source=k, refresh=True)
        rows.append({"prog": p, "wall": time.perf_counter() - t0,
                     "stages": [e for e in events if e[0] == "dur"],
                     "reads": sum(e[0] == "read" for e in events),
                     "compiles": sum(e[0] == "compile" for e in events),
                     "exchange_bytes": sum(e[2] for e in events
                                           if e[0] == "exchange"),
                     "rounds": int(r.stats.rounds),
                     "local_iters": int(r.stats.local_iters)})
    return rows


# the step of one relax sweep an op belongs to, by the function of
# kernels/edge_relax/ref.py in its source frames (first match wins)
PARTS = (("gather_runs", "run_end"), ("delta_tables", "delta"),
         ("merge_tables", "delta"), ("_block_scan", "scan"),
         ("stream_scan", "scan"), ("gather_sources", "source_read"),
         ("emit_messages", "emit"))


def frame_parts(txt: str) -> dict:
    """Stack frame id -> sweep part, from the module's ``FunctionNames``,
    ``FileLocations`` and ``StackFrames`` tables: the first of
    :data:`PARTS` among the functions on the frame's chain, else
    ``other``.  The table prints a frame's parent one above its id (0:
    the outermost frame has none)."""
    tables, cur = collections.defaultdict(dict), None
    for line in txt.splitlines():
        if line in ("FunctionNames", "FileLocations", "StackFrames"):
            cur = line
            continue
        m = re.match(r"^(\d+) (.*)$", line) if cur else None
        if not m:
            cur = None if not line.strip() else cur
            continue
        tables[cur][int(m.group(1))] = m.group(2)
    fn = {k: v.strip('"') for k, v in tables["FunctionNames"].items()}
    loc_fn = {k: fn.get(int(re.search(r"function_name_id=(\d+)",
                                      v).group(1)))
              for k, v in tables["FileLocations"].items()}
    frames = {k: (int(re.search(r"file_location_id=(\d+)", v).group(1)),
                  int(re.search(r"parent_frame_id=(\d+)", v).group(1)))
              for k, v in tables["StackFrames"].items()}
    out = {}
    for f in frames:
        names, seen, cur_f = set(), set(), f
        while cur_f in frames and cur_f not in seen:
            seen.add(cur_f)
            loc, parent = frames[cur_f]
            names.add(loc_fn.get(loc))
            cur_f = parent - 1
        out[f] = next((part for name, part in PARTS if name in names),
                      "other")
    return out


def gather_sizes(sg, cells: int) -> dict:
    """Output elements of a module's gathers -> sweep part, for gathers
    whose source frames the compiler dropped: a source read fills the
    ``cells`` edge streams, a run-end gather each cell's key space, and
    the delta segment's its slice of the streams."""
    w = sg.csr_perm.shape[-1]
    sizes = {cells * w: "source_read",
             cells * sg.n_shards * sg.n_per_shard: "run_end"}
    if sg.delta_width:
        sizes.setdefault(cells * sg.delta_width, "delta")
    return sizes


def hlo_scopes(sess, prog_name: str, key: int) -> tuple:
    """:func:`hlo_maps` of the diffusion module of one program."""
    prog = PROGRAMS[prog_name].factory(source=key)
    v0, a0 = initial_state(sess.sg, prog)
    if sess.engine == "spmd":
        fn = next(f for k, f in sess._spmd_fns.items() if k[0] == prog)
        txt = fn.lower(sess._spmd_views(sess.sweep != "pull"), v0,
                       a0).compile().as_text()
    else:
        txt = _run_rounds.lower(sess.sg, prog, v0, a0, sess.max_local_iters,
                                sess.max_rounds, None, sess.backend,
                                sess.sweep).compile().as_text()
    cells = 1 if sess.engine == "spmd" else sess.sg.n_shards
    return hlo_maps(txt, gather_sizes(sess.sg, cells))


def _elements(line: str) -> int | None:
    """Element count of an instruction's (first) result shape."""
    m = re.search(r" = \(?[a-z]+\d*\[([\d,]*)\]", line)
    if not m:
        return None
    return int(np.prod([int(d) for d in m.group(1).split(",") if d]))


def hlo_maps(txt: str, sizes: dict) -> tuple:
    """HLO instruction name -> scope of the diffusion, and -> the sweep
    part (:data:`PARTS`) of a relax op: a fusion takes the part of the
    gathers or scatters it holds, else the commonest.  A gather, or a
    fusion holding one, that no frame names takes its part from its
    output size (``sizes``, :func:`gather_sizes`); a scatter's is the
    delta segment's, the only scatters of a dense pull sweep."""
    parts = frame_parts(txt)
    comp_of, op_of, calls, part_of, size_of = {}, {}, {}, {}, {}
    comp_ops = collections.defaultdict(list)
    comp_parts = collections.defaultdict(list)
    cur = None
    for line in txt.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = head.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if not m:
            continue
        name = m.group(1)
        on = re.search(r'op_name="([^"]*)"', line)
        op_of[name] = on.group(1) if on else None
        comp_of[name] = cur
        sf = re.search(r"stack_frame_id=(\d+)", line)
        part_of[name] = parts.get(int(sf.group(1))) if sf else None
        opcode = re.search(r" = [^ ]+ ([\w\-]+)\(", line)
        size_of[name] = _elements(line)
        comp_parts[cur].append((opcode.group(1) if opcode else "",
                                part_of[name]))
        c = re.search(r"calls=%([\w.\-]+)", line)
        if c:
            calls[name] = c.group(1)
        comp_ops[cur].append(op_of[name])
    comp_calls = collections.defaultdict(list)
    for name, c in calls.items():
        comp_calls[comp_of[name]].append(c)

    def comp_scopes(c, seen):
        if c in seen:
            return collections.Counter()
        seen.add(c)
        cnt = collections.Counter(scope_of(x) for x in comp_ops[c] if x)
        for sub in comp_calls[c]:
            cnt.update(comp_scopes(sub, seen))
        return cnt

    def inner_parts(c, seen):
        if c in seen:
            return []
        seen.add(c)
        out = list(comp_parts[c])
        for sub in comp_calls[c]:
            out += inner_parts(sub, seen)
        return out

    smap, pmap = {}, {}
    for name, on in op_of.items():
        s = scope_of(on)
        if s == "unscoped" and name in calls:
            cnt = comp_scopes(calls[name], set())
            cnt.pop("unscoped", None)
            if cnt:
                s = cnt.most_common(1)[0][0]
        smap[name] = s
        inner = inner_parts(calls[name], set()) if name in calls else []
        inner.append((name.split(".")[0], part_of[name]))
        ops = {op for op, _ in inner}
        moves = [q for op, q in inner if q in ("source_read", "run_end",
                                               "delta")
                 and op.startswith(("gather", "scatter"))]
        known = collections.Counter(q for _, q in inner if q)
        if moves:
            pmap[name] = moves[0]
        elif any(op.startswith("scatter") for op in ops):
            pmap[name] = "delta"
        elif "gather" in ops and size_of[name] in sizes:
            pmap[name] = sizes[size_of[name]]
        else:
            pmap[name] = (known.most_common(1)[0][0] if known
                          else "other")
    return smap, pmap


def read_events(log_dir: str):
    """(device ops, diffusion module events, host spans) of the trace;
    ops and modules per device plane, in plane order."""
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    ops, mods, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            d_ops, d_mods = [], []
            for line in plane.lines:
                ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
                if line.name == "XLA Ops":
                    d_ops += ev
                elif line.name == "XLA Modules":
                    d_mods += [m for m in ev if m[0].startswith(MODULES)]
            if d_ops:
                ops.append(d_ops)
                mods.append(d_mods)
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(("bench.", "repro."))]
    spans.sort(key=lambda s: s[1])
    return ops, mods, spans


def reduce_trace(ops, mods, spans, plan, hlo_maps):
    """Per device: (scope self ns, op self ns, per-query rows)."""
    return [reduce_device(o, m, spans, plan, hlo_maps)
            for o, m in zip(ops, mods)]


def reduce_device(ops, mods, spans, plan, hlo_maps):
    queries = [s for s in spans if s[0] == trace.QUERY]
    scope_ns, by_op, rows = (collections.Counter(), collections.Counter(),
                             [])
    for (_, q0, q1), (p, _) in zip(queries, plan):
        inside = [e for e in ops if e[1] >= q0 and e[2] <= q1]
        rr = [m for m in mods if m[1] >= q0 and m[2] <= q1]
        in_rr = [e for e in inside
                 if any(m[1] <= e[1] and e[2] <= m[2] for m in rr)]
        rest = [e for e in inside if e not in in_rr]
        q_scope, q_part = collections.Counter(), collections.Counter()
        smap, pmap = hlo_maps[p]
        for name, t in M.self_times(in_rr):
            sn = M.short_name(name)
            s = smap.get(sn, "unscoped")
            q_scope[s] += t
            if s == trace.SCOPE_RELAX:
                q_part[pmap.get(sn, "other")] += t
            q_scope["collectives"] += t if is_collective(sn) else 0
            by_op[f"{s}/{sn}"] += t
        for name, t in M.self_times(rest):
            q_scope["unscoped"] += t
            by_op[f"unscoped/{M.short_name(name)}"] += t
        merged = M.union([(a, b) for _, a, b in inside], q0, q1)
        busy = M.covered(merged, q0, q1)
        idle = collections.Counter()
        for sn, s0, s1 in spans:
            if sn.startswith(trace.QUERY + ".") and s0 >= q0 and s1 <= q1:
                idle[sn] += (s1 - s0) - M.covered(merged, s0, s1)
        rows.append({"prog": p, "span_ms": (q1 - q0) * 1e-6,
                     "busy_ms": busy * 1e-6,
                     "idle_ms": (q1 - q0 - busy) * 1e-6,
                     "idle_ms_by_stage": {k: v * 1e-6
                                          for k, v in idle.items()},
                     "self_ms_by_scope": {k: v * 1e-6
                                          for k, v in q_scope.items()},
                     "relax_ms_by_part": {k: v * 1e-6
                                          for k, v in q_part.items()}})
        scope_ns.update(q_scope)
    return scope_ns, by_op, rows


def sweep_parts(per_dev, traced, plan) -> dict:
    """Per program: relax device time per sub-iteration by sweep part
    (:data:`PARTS`), in ms, averaged over the devices."""
    out = {}
    for prog in PROGS:
        idx = [i for i, (p, _) in enumerate(plan) if p == prog]
        iters = sum(traced[i]["local_iters"] for i in idx)
        tot = collections.Counter()
        for _, _, rows in per_dev:
            for i in idx:
                tot.update(rows[i]["relax_ms_by_part"])
        if iters:
            out[prog] = {k: v / len(per_dev) / iters
                         for k, v in sorted(tot.items())}
            out[prog]["total"] = sum(tot.values()) / len(per_dev) / iters
    return out


def shares(scope_ns):
    total = sum(v for k, v in scope_ns.items() if k != "collectives")
    return {k: 100 * v / total for k, v in scope_ns.items()} if total else {}


def innermost_gaps(ops, spans, top: int = 10):
    win = [s for s in spans if s[0] == "bench.window"][-1]
    merged = M.union([(a, b) for _, a, b in ops], win[1], win[2])
    edges = [win[1]] + [x for ab in merged for x in ab] + [win[2]]
    gaps = sorted([(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a],
                  key=lambda x: x[0] - x[1])[:top]
    named = []
    for a, b in gaps:
        best = None
        for n, s0, s1 in spans:
            if n == "bench.window" or min(b, s1) <= max(a, s0):
                continue
            if best is None or (s1 - s0) < (best[2] - best[1]):
                best = (n, s0, s1)
        named.append([best[0] if best else "host", (b - a) * 1e-9])
    return named


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="graph500-s16")
    ap.add_argument("--scale", type=int, default=None,
                    help="override the configuration's scale")
    ap.add_argument("--seed", type=int, default=2147483999)
    ap.add_argument("--keys", type=int, default=3)
    ap.add_argument("--out", default="artifacts/trace_probe.json")
    args = ap.parse_args()

    events, source_gathers = [], {}

    def on_scalar(ev, v, **kw):
        if ev == trace.HOST_READ_BYTES:
            events.append(("read", kw.get("site"), v))
        elif ev == trace.EXCHANGE_BYTES:
            events.append(("exchange", None, v))
        elif ev == trace.SOURCE_GATHERS:
            source_gathers[kw.get("program")] = v

    def on_duration(ev, d, **_):
        if ev.startswith(trace.EVENT_PREFIX + trace.QUERY):
            events.append(("dur", ev[len(trace.EVENT_PREFIX):], d))
        elif ev == trace.EVENT_PREFIX + trace.PLACE:
            events.append(("place", None, d))
        elif ev == COMPILE_EVENT:
            events.append(("compile", None, d))

    jax.monitoring.register_scalar_listener(on_scalar)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    t0 = time.perf_counter()
    sess, keys = build(args.config, args.scale, args.seed, args.keys)
    setup = {"seconds": time.perf_counter() - t0,
             "place_s": [e[2] for e in events if e[0] == "place"],
             "compiles": sum(e[0] == "compile" for e in events)}

    plan_untraced = [(p, k) for k in keys[args.keys:] for p in PROGS]
    plan = [(p, k) for k in keys[:args.keys] for p in PROGS]
    out = {"config": args.config, "engine": sess.engine, "seed": args.seed,
           "keys": keys, "setup": setup,
           "untraced": run_queries(sess, plan_untraced, events, False)}

    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        out["traced"] = run_queries(sess, plan, events, True)
    jax.profiler.stop_trace()

    jax.config.update("jax_enable_compilation_cache", False)
    hlo_maps = {p: hlo_scopes(sess, p, keys[0]) for p in PROGS}
    ops, mods, spans = read_events(log_dir)
    if not ops:
        raise SystemExit("the trace holds no device ops")
    per_dev = reduce_trace(ops, mods, spans, plan, hlo_maps)
    scope_ns, by_op = collections.Counter(), collections.Counter()
    for s_ns, o_ns, _ in per_dev:
        scope_ns.update(s_ns)
        by_op.update(o_ns)
    rows = per_dev[0][2]

    traced = out["traced"]
    n_dev = len(per_dev)
    out.update({
        "devices": n_dev,
        "memory_peak_bytes": [(d.memory_stats() or {}).get(
            "peak_bytes_in_use") for d in jax.devices()],
        "scope_share_pct": shares(scope_ns),
        "scope_share_pct_by_device": [shares(s) for s, _, _ in per_dev],
        "compiles_per_query": sum(q["compiles"] for q in traced)
        / len(traced),
        "exchange_bytes_per_query": sum(q["exchange_bytes"] for q in traced)
        / len(traced),
        "sweep_ms": scope_ns[trace.SCOPE_RELAX] * 1e-6 / n_dev
        / sum(q["local_iters"] for q in traced),
        "exchange_ms_per_round": scope_ns[trace.SCOPE_EXCHANGE] * 1e-6
        / n_dev / sum(q["rounds"] for q in traced),
        "query_host_idle_ms": sum(q["idle_ms"] for q in rows) / len(rows),
        "source_gathers": source_gathers,
        "sweep_ms_by_part": sweep_parts(per_dev, traced, plan),
        "host_reads_per_query": sum(q["reads"] for q in traced)
        / len(traced),
        "top_ops": [[k, v * 1e-9 / n_dev]
                    for k, v in by_op.most_common(20)],
        "idle_gaps": innermost_gaps(ops[0], spans),
        "per_query": rows,
    })
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=str)
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("per_query", "untraced", "traced")},
                     default=str))
    for q in rows:
        print(json.dumps(q))


if __name__ == "__main__":
    main()
