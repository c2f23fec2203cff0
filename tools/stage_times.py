#!/usr/bin/env python3
"""Run a script with a listener on the program's host stages, and print
each query's host seconds per stage on standard error when it exits.

    python3 tools/stage_times.py [--out STAGES.json] -- bench/run.py --workload ...

The listener takes the ``jax.monitoring`` events of ``repro.core.trace``:
the duration event ``/repro/<stage>`` of every stage, and the
``/repro/host_read_bytes`` event of every counted host read.  Each
``repro.query`` event closes one query; the stages and reads recorded
since the previous one belong to it.  An untraced run's slow query is so
pinned to a stage: ``budget`` is the host waiting for the device, the
others are host work.  ``--out`` also writes the raw events, each with
its ``time.perf_counter()`` stamp.
"""

from __future__ import annotations

import argparse
import atexit
import json
import os
import runpy
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro.core import trace  # noqa: E402


def per_query(events):
    """[{stage: seconds, "reads": n, "end": t}] in the order the queries
    ended."""
    out, cur = [], {"reads": 0}
    for kind, name, t, value in events:
        if kind == "read":
            cur["reads"] += 1
        elif name == trace.QUERY:
            out.append(dict(cur, total=value, end=t))
            cur = {"reads": 0}
        elif name.startswith(trace.QUERY + "."):
            stage = name[len(trace.QUERY) + 1:]
            cur[stage] = cur.get(stage, 0.0) + value
    return out


def main():
    argv = sys.argv[1:]
    if "--" not in argv:
        raise SystemExit(__doc__)
    cut = argv.index("--")
    ap = argparse.ArgumentParser()
    ap.add_argument("--out")
    args = ap.parse_args(argv[:cut])
    script, script_args = argv[cut + 1], argv[cut + 2:]

    events = []
    prefix = trace.EVENT_PREFIX

    def on_dur(event, secs, **_):
        if event.startswith(prefix):
            events.append(("dur", event[len(prefix):], time.perf_counter(),
                           secs))

    def on_scalar(event, value, **kw):
        if event == trace.HOST_READ_BYTES:
            events.append(("read", kw.get("site"), time.perf_counter(),
                           value))

    jax.monitoring.register_event_duration_secs_listener(on_dur)
    jax.monitoring.register_scalar_listener(on_scalar)

    def report():
        for i, q in enumerate(per_query(events)):
            parts = " ".join(f"{k}={q[k]:.6f}" for k in
                             ("total", "prepare", "dispatch", "budget",
                              "result") if k in q)
            print(f"stages query {i}: {parts} reads={q['reads']}",
                  file=sys.stderr)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(events, f)

    atexit.register(report)
    sys.argv = [script] + script_args
    runpy.run_path(script, run_name="__main__")


if __name__ == "__main__":
    main()
