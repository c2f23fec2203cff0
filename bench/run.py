#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``) and its
traffic mix (``bench/traffic/<mix>.json``) are found by name from
``BENCHMARK.json``; the mix names the loop (``bench/loops/<loop>.py``)
that drives the session, and each per-layer metric has a reader of its
own (``bench/metrics/<metric>.py``).  The run refuses to start unless
JAX's first device is a TPU and there are as many as the cell asks for.

It builds the deployment from ``--seed``, warms up (set-up), measures for
``--seconds``, checks what the window produced against plain references,
and prints as its last line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics from a profiled window), ``device``,
with ``--trace 1`` a ``breakdown``, and last the ``checks``, each number
compared beside its limit (also the last lines of standard error).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str):
    """(BENCHMARK.json, the cell, its configuration, its mix) by name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = load_json(os.path.join(HERE, "configs", f"{cell['config']}.json"))
    mix = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    return bench, cell, cfg, mix


def cell_metrics(bench: dict, cell: str):
    """The cell's end-to-end metrics, and its per-layer metrics."""
    def has(m):
        return "workloads" not in m or cell in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if has(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if cell in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in names)]
    return e2e, layer


class Context:
    """What a loop gets: the deployment, the mix, the seed, the window,
    the spans, and a session built as the deployment fixes it."""

    def __init__(self, jax, cfg, mix, seed, seconds, trace, scratch,
                 compiles):
        from bench.lib.measure import Spans

        self.jax, self.cfg, self.mix = jax, cfg, mix
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.scratch, self.compiles = scratch, compiles
        self.spans = Spans()
        self.t0 = self.t1 = None
        self._window_span = None
        self.trace_dir = None

    def graph(self):
        """The deployment's graph from its own seed, relabeled by
        ``--seed`` within each of its ``relabel_blocks``: (graph, old id
        -> new id, the graph as generated)."""
        from bench.lib.graph import graph500, relabel_within_cells

        g0 = graph500(self.cfg["generator"], int(self.cfg["graph_seed"]))
        g, lab = relabel_within_cells(g0, int(self.cfg["relabel_blocks"]),
                                      self.seed)
        return g, lab, g0

    def session(self, g):
        """The system under test: the session with the engine, edge slack
        and durability the deployment fixes, every other setting its
        default."""
        from repro.core.session import DiffusionSession

        kw = {"engine": self.cfg["engine"],
              "edge_slack": float(self.cfg["edge_slack"])}
        if self.cfg.get("durability"):
            kw["journal_fsync"] = self.cfg["durability"]["journal_fsync"]
        return DiffusionSession.from_edges(g.src, g.dst, g.n, g.w, **kw)

    def begin_window(self) -> None:
        if self.trace:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.trace_dir = os.path.join(self.scratch, "trace")
            self.jax.profiler.start_trace(self.trace_dir,
                                          profiler_options=opts)
            self.spans.annotate = True
            self._window_span = self.jax.profiler.TraceAnnotation(
                "bench.window")
            self._window_span.__enter__()
        self.t0 = time.perf_counter()

    def end_window(self) -> None:
        self.t1 = time.perf_counter()
        if self.trace:
            self._window_span.__exit__(None, None, None)
            self.spans.annotate = False
            self.jax.profiler.stop_trace()

    @contextlib.contextmanager
    def phase(self, name: str):
        """Note a set-up step's wall time and what it compiled."""
        t0 = time.perf_counter()
        yield
        t1 = time.perf_counter()
        n, hits, secs = self.compiles.between(t0, t1)
        self.note(f"setup {name}: wall_s={t1 - t0} compiles={n} "
                  f"cache_loads={hits} compile_s={secs}")

    def note(self, line: str) -> None:
        print(line, file=sys.stderr, flush=True)


class Readings:
    """What a per-layer metric's reader gets."""

    def __init__(self, counters, trace, peaks):
        self.counters, self.trace, self.peaks = counters, trace, peaks


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    bench, cell, cfg, mix = load_cell(args.workload)
    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform!r} "
              f"device(s): refusing to run", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    result = execute(jax, bench, cell, cfg, mix, args)
    print(json.dumps(result), flush=True)
    return 0


def execute(jax, bench, cell, cfg, mix, args) -> dict:
    """Set up, measure, check: the whole run after the look for a chip."""
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.lib import measure

    peaks_all = load_json(os.path.join(HERE, "peaks.json"))
    dev = jax.devices()[0]
    if args.trace and dev.device_kind not in peaks_all:
        raise SystemExit(f"bench: no peaks for device kind "
                         f"{dev.device_kind!r} in bench/peaks.json")
    loop = load_module(os.path.join(HERE, "loops", f"{mix['loop']}.py"),
                       f"bench_loop_{mix['loop']}")
    compiles = measure.CompileCounter(jax)
    scratch = tempfile.mkdtemp(prefix="bench-")
    try:
        ctx = Context(jax, cfg, mix, args.seed, args.seconds, args.trace,
                      scratch, compiles)
        st = loop.setup(ctx)
        loop.run(ctx, st)
        setup_s = ctx.t0 - T_START
        n_comp, n_load, comp_s = compiles.between(ctx.t0, ctx.t1)
        ctx.note(f"window: seconds={ctx.t1 - ctx.t0} compiles={n_comp} "
                 f"cache_loads={n_load} compile_s={comp_s} "
                 f"setup_s={setup_s}")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices())
        trace = None
        if args.trace:
            trace = measure.read_trace(ctx.trace_dir)
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        loop.release(ctx, st)
        gc.collect()
        out = loop.check(ctx, st)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    e2e_defs, layer_defs = cell_metrics(bench, cell["name"])
    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result = {}
    if args.trace:
        busy = measure.busy_ns(trace) * 1e-9
        device["busy_s"] = busy
        device["window_s"] = (trace.window[1] - trace.window[0]) * 1e-9
        read = Readings(out["counters"], trace, peaks_all[dev.device_kind])
        for m in layer_defs:
            reader = load_module(os.path.join(HERE, "metrics",
                                              f"{m['name']}.py"),
                                 f"bench_metric_{m['name']}")
            value = reader.read(read)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": measure.top_ops(trace),
                               "idle_gaps": measure.idle_gaps(trace)}
    else:
        values = dict(out["e2e"], setup_s=setup_s)
        for m in e2e_defs:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in out["checks"].items()}
    correct = (out["attempted"] > 0 and out["failed"] == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    for k, c in checks.items():
        print(f"check {k}: value={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    return {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device,
            **result, "checks": checks}


if __name__ == "__main__":
    sys.exit(main())
