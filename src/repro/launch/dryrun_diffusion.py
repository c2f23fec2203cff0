import os
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=512"
)
# ^ first lines, before any jax import (see dryrun.py)

"""Dry-run of the PAPER'S OWN workload at production scale: diffusive SSSP
on a Graph500-class RMAT graph, one compute cell per chip.

    python -m repro.launch.dryrun_diffusion --scale 26 [--multi-pod]

Lowers + compiles the shard_map SPMD diffusion engine (local relaxation
while-loops with device-dependent trip counts, all_to_all operon exchange,
psum termination detection) for 256 cells (one pod) or 512 (two pods,
'cells' spanning the pod axis), with ShapeDtypeStruct graph shards — no
allocation.  Proves the paper's execution model lowers to a coherent
collective schedule on real hardware meshes.
"""

import argparse   # noqa: E402
import json       # noqa: E402
import types      # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.diffuse import make_spmd_diffuse  # noqa: E402
from repro.core.programs import sssp_program      # noqa: E402
from repro.launch.mesh import make_mesh           # noqa: E402


def build_specs(scale: int, n_cells: int, edge_factor: int = 16,
                with_push: bool = False):
    from repro.core.graph import DEFAULT_EDGE_BLOCK

    n = 1 << scale
    e = n * edge_factor * 2          # symmetrized
    np_ = n // n_cells
    ep = e // n_cells
    eb = -(-ep // DEFAULT_EDGE_BLOCK) * DEFAULT_EDGE_BLOCK   # CSR padding
    S = n_cells
    i32 = jnp.int32
    # the engine-facing view (diffuse._sg_as_dict): vertex block + the
    # destination-sorted pull streams (ShardedGraph.csr_view) with their
    # run layout over the n flat destination keys, and — for
    # push/auto sweeps only, mirroring _sg_as_dict — the source-sorted
    # push streams (ShardedGraph.push_view)
    specs = {
        "node_ok": jax.ShapeDtypeStruct((S, np_), jnp.bool_),
        "gid": jax.ShapeDtypeStruct((S, np_), i32),
        "out_degree": jax.ShapeDtypeStruct((S, np_), i32),
        "csr_key": jax.ShapeDtypeStruct((S, eb), i32),
        "csr_skey": jax.ShapeDtypeStruct((S, eb), i32),
        "csr_src": jax.ShapeDtypeStruct((S, eb), i32),
        "csr_src_gid": jax.ShapeDtypeStruct((S, eb), i32),
        "csr_weight": jax.ShapeDtypeStruct((S, eb), jnp.float32),
        "csr_dst_gid": jax.ShapeDtypeStruct((S, eb), i32),
        "csr_run_end": jax.ShapeDtypeStruct((S, n), i32),
    }
    if with_push:
        specs.update({
            "push_src": jax.ShapeDtypeStruct((S, eb), i32),
            "push_key": jax.ShapeDtypeStruct((S, eb), i32),
            "push_weight": jax.ShapeDtypeStruct((S, eb), jnp.float32),
            "push_dst_gid": jax.ShapeDtypeStruct((S, eb), i32),
            "push_pos": jax.ShapeDtypeStruct((S, eb), i32),
        })
    return specs, np_, ep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=26)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--max-local-iters", type=int, default=64)
    ap.add_argument("--sweep", default="pull",
                    choices=("pull", "push", "auto"),
                    help="sweep direction of the relaxation step "
                         "(DESIGN.md §2.8)")
    args = ap.parse_args()

    n_cells = 512 if args.multi_pod else 256
    mesh = make_mesh((n_cells,), ("cells",))
    sgd, np_, ep = build_specs(args.scale, n_cells,
                               with_push=args.sweep != "pull")
    print(f"[diffusion dry-run] RMAT scale={args.scale}: "
          f"{1 << args.scale:,} vertices, {n_cells} cells, "
          f"{np_:,} vertices + {ep:,} edges per cell")

    prog = sssp_program(0, track_parents=False)
    fn = make_spmd_diffuse(mesh, prog, sgd, axis_name="cells",
                           max_local_iters=args.max_local_iters,
                           sweep=args.sweep)
    # the initial state as the session passes it: [S, Np] per field
    vstate0, active0 = jax.eval_shape(lambda d: prog.init(
        types.SimpleNamespace(gid=d["gid"], node_ok=d["node_ok"],
                              out_degree=d["out_degree"])), sgd)
    lowered = fn.lower(sgd, vstate0, active0)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    print("memory_analysis:",
          {k: int(getattr(ma, k + "_size_in_bytes", 0))
           for k in ("argument", "output", "temp")})
    try:
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        "../../.."))
        from benchmarks.hlo_analysis import analyze_hlo
        h = analyze_hlo(compiled.as_text())
        print(f"collective bytes/round-program: "
              f"{h['collective_bytes']/1e6:.1f} MB/device; "
              f"dynamic whiles (diffusion rounds + local relaxation): "
              f"{h['dynamic_whiles']}")
        coll = {k: v["count"] for k, v in h["collectives"].items()
                if v["count"]}
        print("collective schedule:", coll)
        out = {
            "scale": args.scale, "n_cells": n_cells,
            "per_cell_vertices": np_, "per_cell_edges": ep,
            "collectives": h["collectives"],
            "dynamic_whiles": h["dynamic_whiles"],
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        }
        art = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                           "../../..", "artifacts"))
        os.makedirs(art, exist_ok=True)
        tag = f"diffusion_sssp_s{args.scale}_{n_cells}cells"
        with open(os.path.join(art, tag + ".json"), "w") as f:
            json.dump(out, f, indent=1)
    except Exception as exc:
        print("analysis skipped:", exc)
    print("diffusion dry-run OK")


if __name__ == "__main__":
    main()
