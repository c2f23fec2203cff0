"""Relaxation backends + direction-optimizing sweeps — the engine's
gather→emit→segment-combine step as a pluggable interface.

The diffusive engines (logical sharded and SPMD shard_map — diffuse.py) run
the same bulk-asynchronous while-loop structure; what differs per backend is
only how one cell turns its vertex block + edge stream into the combined
per-destination message table:

* ``"xla"``     — the segmented scan over the destination-sorted stream
  for every program, read out with one gather per destination at its
  run end (no scatter over the sorted region); the default and the
  production path.
* ``"pallas"``  — the fused ``kernels/edge_relax`` block kernel: emit +
  dense-rank in-block combine per 128-edge block (interpret mode
  off-TPU, so CI exercises the same code path).  Min/max programs only
  on a TPU: sum programs raise ``NotImplementedError`` there.

Orthogonally, ``sweep`` picks the *direction* (DESIGN.md §2.8):

* ``"pull"`` — the dense sweep over the whole destination-sorted stream
  (every edge visited, inactive senders masked); O(E) per sub-iteration.
* ``"push"`` — the frontier-compacted sweep over the source-sorted push
  stream: only the blocks holding an active sender's out-edges are
  gathered, so a sparse round costs O(frontier-adjacent edges).  The
  compaction capacity is bucketed to a power-of-two ladder
  (:func:`push_caps`) and selected *per sub-iteration* by the engine via
  ``lax.switch`` — every bucket traces once, none recompiles at runtime.
* ``"auto"``  — per-sub-iteration direction selector: push while the
  measured active-block count stays under ``push_threshold * n_blocks``,
  dense pull otherwise (the direction-optimizing rule of Beamer-style
  BFS, generalized to every program).

All sweep × backend combinations return bitwise-identical tables (see
kernels/edge_relax), so both knobs are pure execution choices — every
future perf kernel (delta-bucketed relaxation, rhizome splitting of heavy
vertices) slots in as another entry here without touching engine or
program code.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from . import trace
from .msg import block_any

__all__ = [
    "RELAX_BACKENDS",
    "RELAX_SWEEPS",
    "DEFAULT_PUSH_THRESHOLD",
    "make_relax",
    "push_caps",
    "active_push_blocks",
    "select_bucket",
]

# the one registry of relaxation backends; kernels/edge_relax re-exports it
RELAX_BACKENDS = ("xla", "pallas")

# sweep directions understood by make_relax / the engines / the session
RELAX_SWEEPS = ("pull", "push", "auto")

# auto picks push while active blocks <= threshold * total blocks
DEFAULT_PUSH_THRESHOLD = 0.5


def push_caps(n_blocks: int) -> tuple:
    """The power-of-two compaction-bucket ladder for a cell with
    ``n_blocks`` push blocks: (1, 2, 4, ..., n_blocks).  Static shapes —
    each bucket is traced exactly once into its ``lax.switch`` branch, so
    a frontier of any size runs without recompiling."""
    caps = []
    c = 1
    while c < n_blocks:
        caps.append(c)
        c *= 2
    caps.append(n_blocks)
    return tuple(caps)


def active_push_blocks(senders, push_src, block_e: int):
    """Per-cell count of push blocks touched by the sending frontier.

    ``senders`` is [..., Np] bool (optionally with a lane axis at -2 —
    lanes OR into one shared active set); ``push_src`` is the matching
    [..., Eb] source-sorted stream.  Cheap elementwise work (one bool
    gather + a block-any); the engines run it every sub-iteration to
    drive :func:`select_bucket`.
    """
    if senders.ndim == push_src.ndim + 1:        # laned: OR over lanes
        senders = senders.any(axis=-2)
    ok = push_src >= 0
    act = jnp.take_along_axis(senders, jnp.clip(push_src, 0), axis=-1) & ok
    return jnp.sum(block_any(act, block_e), axis=-1, dtype=jnp.int32)


def select_bucket(n_active_blocks, n_blocks: int, sweep: str,
                  push_threshold: float = DEFAULT_PUSH_THRESHOLD):
    """Pick the per-sub-iteration direction: a compaction-bucket index
    into :func:`push_caps` (push), or ``len(push_caps(n_blocks))`` (the
    dense pull branch).

    ``n_active_blocks`` may carry leading axes (per-cell counts); the
    bucket is shared across cells — ``lax.switch`` under the logical
    engine's shard vmap only stays a true conditional while its index is
    unbatched — so the max count picks it, guaranteeing no cell's
    frontier overflows its bucket.
    """
    caps = push_caps(n_blocks)
    count = jnp.max(n_active_blocks).astype(jnp.int32)
    if sweep == "pull":
        return jnp.int32(len(caps))
    k = jnp.searchsorted(jnp.asarray(caps, jnp.int32), count, side="left")
    k = jnp.minimum(k, len(caps) - 1).astype(jnp.int32)
    if sweep == "push":
        return k
    dense = count > jnp.int32(max(1, int(push_threshold * n_blocks)))
    return jnp.where(dense, jnp.int32(len(caps)), k)


def make_relax(prog, n_shards: int, n_per_shard: int, block_e: int,
               backend: str = "xla", sweep: str = "pull",
               push_threshold: float = DEFAULT_PUSH_THRESHOLD,
               delta_e: int = 0) -> Callable:
    """Build the per-cell relaxation step for ``prog`` on ``backend``.

    The returned function maps one cell's (vstate [Np] pytree, senders
    [Np] bool, sg_s dict with the ``csr_*``/``push_*`` sorted streams and
    optionally the ``csr_run_end`` run layout,
    and — for push/auto sweeps — the scalar ``bucket`` chosen by
    :func:`select_bucket`) to

        table [S, Np]  combined messages per destination (identity = none)
        cnt   [S, Np]  int32 sending-edge count per destination
        pay   [S, Np]  int32 argbest payload, or None

    over the flat destination key space — row ``my_shard`` is the local
    inbox, the other rows are outbox contributions.  vmap it over cells in
    the logical engine (keep ``bucket`` unbatched); call it per device
    under shard_map in SPMD.

    For a laned program (``prog.lanes = L`` — see
    :func:`~.programs.make_laned`) the cell's vstate leaves/senders are
    [L, Np] and the kernel broadcasts the whole sweep over lanes against
    one shared edge stream; outputs become [S, L, Np].

    ``sweep="pull"`` reproduces the dense sweep exactly (``bucket`` is
    ignored); ``"push"``/``"auto"`` stage one ``lax.switch`` over the
    compaction ladder + the dense branch, dispatching on ``bucket`` at
    runtime with zero recompiles.  Every branch returns the same table
    bitwise (tests/test_sweep.py), so the direction is invisible to
    programs.

    ``delta_e`` (static) is the width of the graph's staged delta
    segment (``ShardedGraph.delta_width``, DESIGN.md §2.9): the scan
    paths scan only the sorted region and fold the staged blocks in
    through a scatter; 0 = delta-free streams.

    The per-edge source reads follow the program
    (:meth:`~.programs.VertexProgram.source_reads`): a program that
    reads the source gid takes it from the ``csr_src_gid`` stream of
    ``sg_s``, and the sender flag rides in a non-negative state word
    where the program has one.  Building the step records the E-long
    source gathers of one dense sweep as the scalar event
    :data:`~.trace.SOURCE_GATHERS`, tagged with the program's name.
    """
    if backend not in RELAX_BACKENDS:
        raise ValueError(
            f"backend must be one of {RELAX_BACKENDS}, got {backend!r}")
    if sweep not in RELAX_SWEEPS:
        raise ValueError(
            f"sweep must be one of {RELAX_SWEEPS}, got {sweep!r}")
    # deferred import: kernels ←→ core import cycles resolve at call time
    from ..kernels.edge_relax.ops import edge_relax, edge_relax_push

    n_keys = n_shards * n_per_shard
    interpret = backend == "pallas" and jax.default_backend() != "tpu"
    if backend == "pallas" and not interpret and prog.combine == "sum":
        raise NotImplementedError(
            "backend='pallas' has no TPU kernel for sum-combine programs: "
            "the scan kernel (kernels/edge_relax.edge_relax_scan) needs a "
            "1-D gather Mosaic does not lower and holds a cell's whole "
            "edge stream in VMEM; use backend='xla'")

    def _shape(table, cnt, pay):
        if prog.lanes:
            # [L, n_keys] -> [S, L, Np]: destination shard leads so row
            # my_shard is still the local inbox
            shp = (-1, n_shards, n_per_shard)
            table = jnp.swapaxes(table.reshape(shp), 0, 1)
            cnt = jnp.swapaxes(cnt.reshape(shp), 0, 1)
            pay = (jnp.swapaxes(pay.reshape(shp), 0, 1)
                   if pay is not None else None)
        else:
            table = table.reshape(n_shards, n_per_shard)
            cnt = cnt.reshape(n_shards, n_per_shard)
            pay = (pay.reshape(n_shards, n_per_shard)
                   if pay is not None else None)
        return table, cnt, pay

    reads = prog.source_reads()
    if reads.gathers is not None:
        jax.monitoring.record_scalar(trace.SOURCE_GATHERS, reads.gathers,
                                     program=prog.name)

    def _dense(vstate, senders, sg_s):
        return edge_relax(
            prog, vstate, senders, sg_s["gid"],
            sg_s["csr_key"], sg_s["csr_src"], sg_s["csr_weight"],
            sg_s["csr_dst_gid"],
            n_keys=n_keys, block_e=block_e, backend=backend,
            interpret=interpret, skey=sg_s.get("csr_skey"),
            delta_e=delta_e, run_end=sg_s.get("csr_run_end"),
            src_gid=sg_s["csr_src_gid"] if reads.gid else None,
        )

    if sweep == "pull":
        def relax(vstate, senders, sg_s, bucket=None):
            del bucket
            return _shape(*_dense(vstate, senders, sg_s))
        return relax

    def _push(vstate, senders, sg_s, cap: int):
        sg_push = {k: sg_s[k] for k in ("push_src", "push_key",
                                        "push_weight", "push_dst_gid",
                                        "push_pos")}
        return edge_relax_push(
            prog, vstate, senders, sg_s["gid"], sg_push, sg_s["csr_key"],
            n_keys=n_keys, block_e=block_e, cap=cap, backend=backend,
            interpret=interpret, skey=sg_s.get("csr_skey"),
            delta_e=delta_e, run_end=sg_s.get("csr_run_end"),
        )

    def relax(vstate, senders, sg_s, bucket=None):
        if bucket is None:
            raise ValueError(
                f"sweep={sweep!r} relaxation needs the per-iteration "
                "bucket from select_bucket(); only sweep='pull' runs "
                "without one")
        nb = sg_s["push_src"].shape[-1] // block_e
        caps = push_caps(nb)
        branches = [
            (lambda c: lambda args: _push(*args, cap=c))(cap)
            for cap in caps
        ]
        branches.append(lambda args: _dense(*args))
        out = lax.switch(jnp.clip(bucket, 0, len(caps)), branches,
                         (vstate, senders, sg_s))
        return _shape(*out)

    return relax
