"""Pallas TPU program-parametric edge relaxation — the paper's memory-driven
hot loop as one kernel.

Two kernels share the engine's relaxation step (gather ``vstate[src]`` →
``prog.emit`` → segment-combine by destination), generalized to every
registered combine :class:`~repro.core.monoid.Monoid` and the payload
path:

* :func:`edge_relax_blocks` — the blocked dense-rank kernel (min/max
  single-query path; per 128-edge block, grid-parallel), also the body of
  the frontier-compacted push sweep (:func:`edge_relax_push_blocks`);
* :func:`edge_relax_scan` — the segmented-scan kernel (sum programs and
  multi-query lanes; whole stream resident, ``ref.stream_scan`` body
  executed verbatim for bitwise parity with the XLA path).  Off-TPU
  only: Mosaic has no 1-D gather from VMEM and the stream does not fit
  VMEM at deployment size, so ``relax.make_relax`` refuses it on a TPU.

Blocked-kernel anatomy:

* the per-edge source reads (``vstate[src]`` and the sender flag,
  packed into one word where the program allows, :func:`~.ref.
  gather_sources`) are XLA gathers ahead of the kernel — Mosaic lowers
  only 2-D gathers within a tile, not a gather from a whole vertex block
  held in VMEM; the source gid arrives as the ``csr_src_gid`` stream;
* edges arrive in the graph's **blocked-CSR layout** (sorted by
  ``(dst_shard, dst_local)``, ``-1``-padded to a block multiple — see
  ``ShardedGraph.with_csr``), so each block's combine is the dense-rank
  one-hot reduction shared with ``segment_reduce`` (MXU-shaped for sum);
* ``prog.emit`` / ``prog.payload`` are traced *into* the kernel body, so any
  registered vertex program (SSSP / BFS / CC / PPR / PageRank) runs on this
  path unchanged;
* every stream, inputs and outputs alike, is one ``[1, E]`` row tiled in
  ``(1, block_e)`` blocks: a block whose leading dim equals the array's
  satisfies the TPU (8, 128) tiling rule.

Phase 2 (cross-block combine of the tiny per-block partial tables) is XLA
code shared with the reference — see ops.py.  The per-block math itself
lives in ref.py (:func:`~.ref.block_combine`) and is executed verbatim here,
which is what makes the two backends bitwise-interchangeable.

Off-TPU (CI) the kernels run under ``pl.pallas_call(..., interpret=True)``
— same ops, no Mosaic lowering — so the bitwise backend-equivalence tests
run everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import (
    block_combine,
    edge_messages,
    emit_messages,
    gather_sources,
    push_gather,
    stream_scan,
)

__all__ = ["edge_relax_blocks", "edge_relax_scan", "edge_relax_push_blocks"]


def _kernel(*refs, prog, treedef, n_leaves: int, block_e: int):
    srefs = refs[:n_leaves]
    send_ref, sgid_ref, key_ref, w_ref, dstg_ref = (
        refs[n_leaves:n_leaves + 5]
    )
    outs = refs[n_leaves + 5:]
    src_state = jax.tree_util.tree_unflatten(
        treedef, [r[0] for r in srefs]      # [block_e] gathered reads
    )
    cand, send, pay = emit_messages(
        prog, src_state, send_ref[0] != 0, sgid_ref[0], key_ref[0],
        w_ref[0], dstg_ref[0],
    )
    part, cnt, uniq, pay_part = block_combine(
        cand, send, key_ref[0], pay, prog.combine, block_e
    )
    outs[0][0] = part
    outs[1][0] = cnt
    outs[2][0] = uniq
    if pay_part is not None:
        outs[3][0] = pay_part


def _scan_kernel(*refs, prog, treedef, n_leaves: int):
    vrefs = refs[:n_leaves]
    senders_ref, gid_ref, key_ref, skey_ref, src_ref, w_ref, dstg_ref = (
        refs[n_leaves:n_leaves + 7]
    )
    outs = refs[n_leaves + 7:]
    vstate = jax.tree_util.tree_unflatten(
        treedef, [r[0] for r in vrefs]
    )
    cand, send, pay = edge_messages(
        prog, vstate, senders_ref[0], gid_ref[0], key_ref[0], src_ref[0],
        w_ref[0], dstg_ref[0],
    )
    v, c, p = stream_scan(prog.monoid, cand, send, skey_ref[0], pay)
    outs[0][0] = v
    outs[1][0] = c
    if p is not None:
        outs[2][0] = p


def edge_relax_scan(prog, vstate, senders, gid, key, src, weight, dst_gid,
                    skey=None, interpret: bool = False):
    """Pallas scan kernel: the whole destination-sorted stream resident in
    VMEM, combined by the segmented associative scan (``ref.stream_scan``
    executed verbatim — bitwise parity with the XLA scan path by
    construction).  The canonical ``backend="pallas"`` path for sum
    programs, whose per-destination accumulation must not depend on block
    boundaries or lane count.

    ``key`` is the live-masked destination key (send masking; tombstones
    read ``-1``) and ``skey`` the structural sorted key driving the
    scan's run layout (defaults to ``key``); the caller slices off the
    staged delta segment first — it is combined outside the kernel by
    the shared scatter pass (ops.py).

    Returns the scanned (value, count[, payload]) streams, each [E]; feed
    to ``ref.gather_runs`` for the run-end gather (shared XLA phase 2).
    """
    if skey is None:
        skey = key
    leaves, treedef = jax.tree_util.tree_flatten(vstate)
    np_ = gid.shape[0]
    e = key.shape[0]

    whole = lambda n: pl.BlockSpec((1, n), lambda: (0, 0))
    n_out = 3 if prog.with_payload else 2
    out_dtypes = [prog.msg_dtype, jnp.int32, jnp.int32][:n_out]
    outs = pl.pallas_call(
        functools.partial(_scan_kernel, prog=prog, treedef=treedef,
                          n_leaves=len(leaves)),
        in_specs=(
            [whole(np_) for _ in leaves]
            + [whole(np_), whole(np_)]          # senders, gid
            + [whole(e) for _ in range(5)]      # key, skey, src, w, dst_gid
        ),
        out_specs=[whole(e) for _ in range(n_out)],
        out_shape=[jax.ShapeDtypeStruct((1, e), dt) for dt in out_dtypes],
        interpret=interpret,
    )(
        *[leaf[None] for leaf in leaves],
        senders[None], gid[None],
        key[None], skey[None], src[None], weight[None], dst_gid[None],
    )
    v, c = outs[0][0], outs[1][0]
    p = outs[2][0] if prog.with_payload else None
    return v, c, p


def edge_relax_push_blocks(prog, vstate, senders, gid, sg_push, idx,
                           block_e: int, interpret: bool = False):
    """Frontier-compacted Pallas sweep: per-block partial tables for the
    ``cap = len(idx)`` active blocks of the *source-sorted* push stream.

    ``idx`` is the compacted active-block list
    (:func:`~.ref.compact_push_blocks`; fill slots carry ``nb``).  The
    shared :func:`~.ref.push_gather` pulls only those blocks out of the
    push streams (``sg_push``, ``ShardedGraph.push_view``) and reads
    their fill positions as dead (key ``-1``), so fills contribute no
    message and no count; the dense block kernel then runs over the
    ``cap`` gathered blocks.

    Push blocks are not destination-sorted, so a destination may occupy
    several dense ranks within one block; the shared phase-2 scatter
    merges them (order-free min/max), keeping push bitwise-equal to the
    dense paths.  Returns (part, cnt, uniq[, pay]) each [cap * block_e].
    """
    g, _ = push_gather(sg_push, idx, block_e)
    return edge_relax_blocks(prog, vstate, senders, gid, g["key"], g["src"],
                             g["weight"], g["dst_gid"], block_e,
                             interpret=interpret)


def edge_relax_blocks(prog, vstate, senders, gid, key, src, weight, dst_gid,
                      block_e: int, interpret: bool = False, src_gid=None):
    """Per-block partial tables for one relaxation sweep of one cell.

    Inputs are this cell's vertex block ([Np] vstate leaves, ``senders``,
    ``gid``) and its destination-sorted edge streams ([Eb], Eb a multiple
    of ``block_e``; ``src_gid`` the optional ``csr_src_gid`` stream).
    Returns (part, cnt, uniq[, pay]) each [Eb]: block
    i's partial table occupies ``[i * block_e, (i + 1) * block_e)`` —
    feed to ``ops._combine_blocks`` for the cross-block phase.
    """
    e = key.shape[0]
    assert e % block_e == 0, "pad the stream via ShardedGraph.with_csr"
    src_state, src_send, src_gid = gather_sources(prog, vstate, senders,
                                                  gid, src, src_gid)
    leaves, treedef = jax.tree_util.tree_flatten(src_state)
    n_in = len(leaves) + 5
    stream = pl.BlockSpec((1, block_e), lambda i: (0, i))

    n_out = 4 if prog.with_payload else 3
    out_dtypes = [prog.msg_dtype, jnp.int32, jnp.int32, jnp.int32][:n_out]
    outs = pl.pallas_call(
        functools.partial(_kernel, prog=prog, treedef=treedef,
                          n_leaves=len(leaves), block_e=block_e),
        grid=(e // block_e,),
        in_specs=[stream] * n_in,
        out_specs=[stream] * n_out,
        out_shape=[jax.ShapeDtypeStruct((1, e), dt) for dt in out_dtypes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(
        *[leaf[None] for leaf in leaves],
        src_send.astype(jnp.int32)[None], src_gid[None],
        key[None], weight[None], dst_gid[None],
    )
    part, cnt, uniq = (o[0] for o in outs[:3])
    pay = outs[3][0] if prog.with_payload else None
    return part, cnt, uniq, pay
