#!/usr/bin/env python3
"""The control for ``correct``, read at the cell's own size: the
reference put in the program's place, in the precision below the
configuration's.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--count 4]

For each seed it rebuilds the cell's graph and the first ``--count``
shortest-path queries that its window sends, computes them in bfloat16
(``refs.sssp_lowp``) and prints the widest relative gap to the float64
reference beside the cell's limit: the readings that the limit is set
from.  Whether the harness's ``correct`` comes out false with these
answers in the program's place is a test
(``bench/tests/test_bench_query_cell.py``).  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]

from bench.lib import graph as G  # noqa: E402
from bench.lib import refs  # noqa: E402
from bench.run import load_cell  # noqa: E402


def graphs_and_roots(cfg: dict, mix: dict, seed: int, count: int):
    """(src, dst, w, n, root) of the first ``count`` shortest-path
    queries the query cell's window answers, labeled as ``seed`` labels
    them."""
    gseed = int(cfg["graph_seed"])
    g0 = G.graph500(cfg["generator"], gseed)
    g, lab = G.relabel_within_cells(g0, int(cfg["relabel_blocks"]), seed)
    keys, _ = G.search_keys(g0, int(cfg["search_keys"]), gseed)
    keys = [int(lab[k]) for k in keys[:int(mix["keys_per_cycle"])]]
    return [(g.src, g.dst, g.w, g.n, r) for r in keys][:count]


def control_gap(cfg: dict, mix: dict, seed: int, count: int) -> float:
    """Widest relative gap of the bfloat16 control over the queries."""
    gap = 0.0
    for src, dst, w, n, root in graphs_and_roots(cfg, mix, seed, count):
        ref = refs.ref_sssp(refs.csr(src, dst, w, n), root)
        low = refs.sssp_lowp(src, dst, w, n, root)
        gap = max(gap, refs.sssp_gap(low, ref)[0])
    return gap


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--count", type=int, default=4,
                    help="shortest-path queries per seed")
    args = ap.parse_args(argv)
    _, _, cfg, mix = load_cell(args.workload)
    limit = cfg["limits"]["sssp_rel_gap"]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"seed": seed,
                          "sssp_rel_gap": control_gap(cfg, mix, seed,
                                                      args.count),
                          "limit": limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
