"""Public wrapper for the edge_relax kernels: backend dispatch + the
shared phase-2 combines.

The contract both backends satisfy: given one cell's vertex block and its
destination-sorted edge streams, return the combined per-destination
message table over the flat key space ``dst_shard * Np + dst_local``:

    table [n_keys] msg_dtype   combined messages (identity where none)
    cnt   [n_keys] int32       number of sending edges per destination
    pay   [n_keys] int32|None  argbest payload (selection monoids only)

Lane-stacked inputs (``senders`` [L, Np] — multi-query lanes) broadcast
the sweep over lanes and return [L, n_keys] tables.

Dispatch: on ``xla`` every dense sweep takes the segmented-scan path
(``ref.stream_scan`` — fixed tree order, lane- and block-independent, so
lanes are bitwise-equal to solo queries; over the destination-sorted
stream it needs no scatter, and each destination's table entry is one
gather at its run end).  On ``pallas`` sum programs take the scan kernel
and min/max the fused blocked kernel (order-free monoids agree across
all paths).  Phase 2 — the run-end gather (scan) / cross-block scatter
(blocked) — is XLA code shared verbatim by both backends, so the two are
bitwise-identical — asserted program-by-program in tests/test_session
and per-lane in tests/test_lanes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.msg import identity_for, run_ends
from ...core.relax import RELAX_BACKENDS
from .kernel import edge_relax_blocks, edge_relax_push_blocks, edge_relax_scan
from .ref import (
    compact_push_blocks,
    delta_tables,
    edge_messages,
    edge_relax_push_flat,
    edge_relax_push_stream,
    edge_relax_stream,
    gather_runs,
    merge_tables,
)

__all__ = ["edge_relax", "edge_relax_push", "RELAX_BACKENDS"]


def _combine_blocks(part, cnt, uniq, pay, n_keys: int, combine: str,
                    msg_dtype):
    """Phase 2: scatter the per-block partial tables into the flat key
    space — O(blocks * block_e) rows, shared by both backends."""
    ident = identity_for(combine, msg_dtype)
    ids = jnp.where(uniq < 0, n_keys, uniq)
    p = part
    table = jnp.full((n_keys + 1,), ident, msg_dtype)
    if combine == "min":
        table = table.at[ids].min(p)
    elif combine == "max":
        table = table.at[ids].max(p)
    else:
        table = table.at[ids].add(p)
    cnt_t = jnp.zeros((n_keys + 1,), jnp.int32).at[ids].add(cnt)
    pay_t = None
    if pay is not None:
        # winners: block partials equal to the globally combined value
        win = jnp.where(p == table[ids], pay, -1)
        pay_t = jnp.full((n_keys + 1,), -1, jnp.int32).at[ids].max(win)
        pay_t = pay_t[:n_keys]
    return table[:n_keys], cnt_t[:n_keys], pay_t


def edge_relax_push(prog, vstate, senders, gid, sg_push, csr_key,
                    n_keys: int, block_e: int, cap: int,
                    backend: str = "xla", interpret: bool = False,
                    skey=None, delta_e: int = 0, run_end=None):
    """Frontier-compacted push sweep of one cell — the sparse twin of
    :func:`edge_relax`, same (table, cnt, pay) contract.

    ``sg_push`` holds the source-sorted streams (``ShardedGraph.
    push_view``); ``cap`` is the static compaction bucket (power-of-two
    ladder, see relax.py) and must bound the cell's true active-block
    count.  Dispatch mirrors the dense sweep: sum programs and all laned
    runs scatter their compacted messages back into the dense stream
    layout and run the shared scan (``ref.edge_relax_push_stream`` —
    bitwise-equal to the dense scan for the order-sensitive monoid, on
    either backend); single-query min/max takes the unsorted segment path
    on ``xla`` and the block kernel over the gathered blocks on ``pallas``
    (order-free monoids agree across all paths).  Phase 2 is the same
    shared XLA code as the dense sweep.

    A graph with a staged delta segment (``delta_e`` trailing stream
    positions, DESIGN.md §2.9) needs no special push handling on the
    flat/blocked paths — a delta block is active exactly when one of its
    staged edges' sources sends, so compaction covers it like any sorted
    block; the stream path forwards ``skey``/``delta_e``/``run_end`` so
    its dense reconstruction scans only the sorted region.
    """
    if backend not in RELAX_BACKENDS:
        raise ValueError(
            f"backend must be one of {RELAX_BACKENDS}, got {backend!r}")
    laned = senders.ndim == 2

    if prog.combine == "sum" or laned:
        return edge_relax_push_stream(prog, vstate, senders, gid, sg_push,
                                      csr_key, n_keys, block_e, cap,
                                      skey=skey, delta_e=delta_e,
                                      run_end=run_end)
    if backend == "xla":
        return edge_relax_push_flat(prog, vstate, senders, gid, sg_push,
                                    n_keys, block_e, cap)
    idx, _ = compact_push_blocks(senders, sg_push["push_src"], block_e, cap)
    part, cnt, uniq, pay = edge_relax_push_blocks(
        prog, vstate, senders, gid, sg_push, idx, block_e,
        interpret=interpret)
    return _combine_blocks(part, cnt, uniq, pay, n_keys, prog.combine,
                           prog.msg_dtype)


def edge_relax(prog, vstate, senders, gid, key, src, weight, dst_gid,
               n_keys: int, block_e: int, backend: str = "xla",
               interpret: bool = False, skey=None, delta_e: int = 0,
               run_end=None, src_gid=None):
    """One relaxation sweep of one cell; see module docstring for the
    returned (table, cnt, pay) contract.

    Multi-query lanes: when ``senders`` is [L, Np] (vstate leaves [L, Np])
    the sweep broadcasts over the lane axis against the *same* edge stream
    — the kernel's gather/emit/combine runs per lane under one batched
    dispatch — and the outputs gain a leading lane axis [L, n_keys].

    Incremental streams (DESIGN.md §2.9): ``key`` is the live-masked
    destination key (tombstones read ``-1`` and never send) and ``skey``
    the structural sorted key; ``delta_e`` trailing positions are the
    staged delta segment.  The blocked path consumes tombstones and delta
    blocks through its ordinary masking/scatter handling; the scan paths
    scan the sorted region against ``skey`` and fold the (unsorted) delta
    segment in through the shared :func:`~.ref.delta_tables` scatter.
    ``run_end`` is the run layout of ``skey``'s sorted region
    (:func:`~repro.core.msg.run_ends`), computed once per dispatch by the
    engines; computed per call when omitted.  ``src_gid`` is the
    structural ``csr_src_gid`` stream for programs that read the source
    gid (:func:`~.ref.gather_sources`); the scan kernel, which runs off
    the TPU only, reads ``gid`` itself."""
    if backend not in RELAX_BACKENDS:
        raise ValueError(
            f"backend must be one of {RELAX_BACKENDS}, got {backend!r}")
    if skey is None:
        skey = key
    laned = senders.ndim == 2      # [L, Np] lane-stacked vertex block

    # Every program takes the segmented-scan path on xla, and sum
    # programs on both backends: its fixed tree order is independent of
    # block boundaries and lane count, which is what makes a lane's sum
    # bitwise-equal to the same query run solo (order-free min/max match
    # every other path bitwise anyway).
    if backend == "xla":
        return edge_relax_stream(prog, vstate, senders, gid, key, src,
                                 weight, dst_gid, n_keys, skey=skey,
                                 delta_e=delta_e, run_end=run_end,
                                 src_gid=src_gid)
    if prog.combine == "sum":
        es = key.shape[-1] - delta_e
        if run_end is None:
            run_end = run_ends(skey[:es], n_keys)
        scan1 = lambda vs, sd: edge_relax_scan(
            prog, vs, sd, gid, key[:es], src[:es], weight[:es],
            dst_gid[:es], skey=skey[:es], interpret=interpret)
        scanned = (jax.vmap(scan1)(vstate, senders) if laned
                   else scan1(vstate, senders))
        out = gather_runs(scanned, run_end, prog.monoid, prog.msg_dtype)
        if delta_e:
            # delta tail: shared XLA phase (message bodies + scatter),
            # merged by the monoid — same code the XLA path runs
            cand, send, pay = edge_messages(
                prog, vstate, senders, gid, key[es:], src[es:],
                weight[es:], dst_gid[es:],
                None if src_gid is None else src_gid[es:])
            out = merge_tables(prog, out, delta_tables(
                prog, cand, send, pay, key[es:], n_keys))
        return out

    if laned:                      # pallas min/max: lane-batched kernel
        return jax.vmap(
            lambda vs, sd: edge_relax(
                prog, vs, sd, gid, key, src, weight, dst_gid,
                n_keys=n_keys, block_e=block_e, backend=backend,
                interpret=interpret, skey=skey, delta_e=delta_e,
                src_gid=src_gid,
            )
        )(vstate, senders)
    part, cnt, uniq, pay = edge_relax_blocks(
        prog, vstate, senders, gid, key, src, weight, dst_gid, block_e,
        interpret=interpret, src_gid=src_gid)
    return _combine_blocks(part, cnt, uniq, pay, n_keys, prog.combine,
                           prog.msg_dtype)
