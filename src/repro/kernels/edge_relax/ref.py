"""Shared math + XLA reference paths for the edge_relax kernels.

Two *single-source-of-truth* bodies keep the backends bitwise-identical:

* ``stream_scan`` — the segmented scan over the globally
  destination-sorted stream.  The Pallas scan kernel
  (:func:`~.kernel.edge_relax_scan`) and the XLA scan path
  (:func:`edge_relax_stream`) execute exactly this function, and its
  fixed tree order depends only on the stream length — never on lane
  count or block boundaries — which is what lets the engine promise that
  a query lane reproduces the same query run solo bit-for-bit, even for
  the order-sensitive sum monoid.  The dense pull path of every program
  on ``xla``: it reads the sorted region with slices, elementwise ops
  and one gather per destination at its run end — no scatter.
* ``block_combine`` — the blocked dense-rank segment combine executed
  verbatim by the blocked Pallas kernel
  (:func:`~.kernel.edge_relax_blocks`).

``flat_combine`` is the unsorted segment combine (scatters by
destination) of the frontier-compacted push sweep, whose gathered blocks
are not destination-sorted.  Min/max over a set is association-free, so
flat, blocked, and scan all agree bitwise by construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...core.msg import block_any, identity_for, run_ends, segment_combine

__all__ = [
    "edge_messages",
    "gather_sources",
    "emit_messages",
    "block_combine",
    "flat_combine",
    "stream_scan",
    "gather_runs",
    "delta_tables",
    "merge_tables",
    "stream_combine",
    "edge_relax_stream",
    "compact_push_blocks",
    "push_gather",
    "edge_relax_push_flat",
    "edge_relax_push_stream",
]


_SIGN = np.int32(np.iinfo(np.int32).min)     # the sign bit of a word


def gather_sources(prog, vstate, senders, gid, src, src_gid=None):
    """Per-edge reads of the source vertex: (state leaves, sender flag,
    gid), each [..., E].  ``vstate`` leaves and ``senders`` may carry
    leading lane axes, which broadcast through.

    Where the program has a ``flag_leaf`` (:meth:`~repro.core.programs.
    VertexProgram.source_reads`: a signed 32-bit field declared
    non-negative, so its sign bit is always clear) the sender flag rides
    in that bit: one vertex-length pass sets it, one gather reads word
    and flag together, and clearing the bit again returns the field's
    bits exactly.  ``src_gid``, when given, is the structural stream
    ``csr_src_gid`` (``gid[src]`` at every position), read instead of
    gathering ``gid``."""
    flag = prog.source_reads().flag_leaf
    if flag is None:
        src_state = jax.tree_util.tree_map(lambda a: a[..., src], vstate)
        src_send = senders[..., src]
    else:
        word = vstate[flag]
        bits = lax.bitcast_convert_type(word, jnp.int32)
        packed = jnp.where(senders, bits | _SIGN, bits)[..., src]
        src_send = packed < 0
        src_state = {k: v[..., src] for k, v in vstate.items() if k != flag}
        src_state[flag] = lax.bitcast_convert_type(packed & ~_SIGN,
                                                   word.dtype)
    if src_gid is None:
        src_gid = gid[src]
    return src_state, src_send, src_gid


def emit_messages(prog, src_state, src_send, src_gid, key, weight, dst_gid):
    """Emit along the edge stream from already-gathered source reads
    (:func:`gather_sources`): run the program's ``emit``, and mask
    non-sending / dead edges to the combine identity (the *monoid's*
    identity — custom monoids may differ from their scatter class's, and
    the scan path folds padding through the custom ``op``, where only a
    true identity is absorbing).  Elementwise, so it runs identically
    inside the Pallas block kernel and in the XLA paths.

    Returns (cand [..., E] msg_dtype, send [..., E] bool,
    pay [..., E] int32 | None).
    """
    valid = key >= 0
    send = src_send & valid
    msg = prog.emit(src_state, weight, src_gid, dst_gid)
    ident = prog.monoid.identity(prog.msg_dtype)
    cand = jnp.where(send, msg, ident).astype(prog.msg_dtype)
    pay = None
    if prog.with_payload:
        pay = prog.payload(src_state, src_gid).astype(jnp.int32)
        pay = jnp.where(send, pay, -1)
    return cand, send, pay


def edge_messages(prog, vstate, senders, gid, key, src, weight, dst_gid,
                  src_gid=None):
    """Gather + emit along the destination-sorted edge stream — the one
    message body of every path (dense, push, scan, delta).  ``senders``
    and vstate leaves may carry leading lane axes; ``src_gid`` is the
    optional ``csr_src_gid`` stream (:func:`gather_sources`).  Returns
    (cand, send, pay) as :func:`emit_messages`."""
    return emit_messages(
        prog, *gather_sources(prog, vstate, senders, gid, src, src_gid),
        key, weight, dst_gid)


def flat_combine(cand, send, pay, ids, n_keys: int, combine: str):
    """Phase 2 of the unsorted segment path: scatter-combine the
    candidate messages by destination id (``n_keys`` = drop row), count
    senders, and ride the argbest payload with the segment-max-over-
    winners tie-break — the same rule as :func:`stream_scan`'s payload
    fold, so the compacted push sweep stays bitwise-equal to the dense
    pull sweep for payload programs."""
    table = segment_combine(cand, ids, n_keys + 1, combine,
                            indices_are_sorted=False)
    cnt = segment_combine(send.astype(jnp.int32), ids, n_keys + 1, "sum")
    pay_t = None
    if pay is not None:
        win = send & (cand == table[ids])
        pay_t = segment_combine(jnp.where(win, pay, -1), ids, n_keys + 1,
                                "max")
        pay_t = jnp.where(cnt[:n_keys] > 0, pay_t[:n_keys], -1)
    return table[:n_keys], cnt[:n_keys], pay_t


def block_combine(cand, send, key, pay, combine: str, block_e: int):
    """One block of the dense-rank segment combine (see module docstring).

    ``key`` is sorted within the block with ``-1`` padding trailing, so
    each destination's messages form a contiguous run; ``rank`` densely
    numbers the runs and the combine reduces over a one-hot [E, W] mask
    (the same trick as segment_reduce — on TPU the sum case is MXU food).

    Returns (part [Be], cnt [Be] int32, uniq [Be] int32, pay_part | None).
    """
    # Edges run down the sublanes of [Be, Be] masks and runs across the
    # lanes.  The neighbour shift and the run ranks are mask reductions,
    # and a column is a transposed row broadcast: Mosaic lowers no
    # concatenate, cumsum or [Be] -> [Be, 1] reshape on a TPU.
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_e), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_e), 1)

    def col(a):
        return jnp.broadcast_to(a.reshape(1, block_e), (block_e, block_e)).T

    valid = key >= 0
    prev = jnp.max(jnp.where(rows == lanes - 1, col(key), -2), axis=0)
    new_seg = (key != prev) & valid
    rank = jnp.sum(jnp.where(rows <= lanes, col(new_seg.astype(jnp.int32)),
                             0), axis=0) - 1
    rank = jnp.where(valid, rank, -1)

    onehot = col(rank) == lanes                # [Be, W]; rank -1 = none
    c = col(cand)
    ident = identity_for(combine, cand.dtype)
    if combine == "min":
        part = jnp.min(jnp.where(onehot, c, ident), axis=0)
    elif combine == "max":
        part = jnp.max(jnp.where(onehot, c, ident), axis=0)
    elif combine == "sum":
        part = jnp.sum(jnp.where(onehot, c, jnp.zeros((), cand.dtype)),
                       axis=0)
    else:  # pragma: no cover
        raise ValueError(f"unknown combine {combine!r}")
    send_c = col(send.astype(jnp.int32))
    cnt = jnp.sum(jnp.where(onehot, send_c, 0), axis=0)
    uniq = jnp.max(jnp.where(onehot, col(key), -1), axis=0)
    pay_part = None
    if pay is not None:
        win = onehot & (send_c != 0) & (c == part.reshape(1, block_e))
        pay_part = jnp.max(jnp.where(win, col(pay), -1), axis=0)
    return part, cnt, uniq, pay_part


# Width of one level of :func:`_block_scan`.  128 = the TPU lane width.
SCAN_BLOCK = 128


def _block_scan(comb, elems):
    """Inclusive scan of ``elems`` (a tuple of equal-shape [..., E] arrays)
    under the associative ``comb``, in a fixed tree order that depends
    only on E.

    Each level scans blocks of ``SCAN_BLOCK`` consecutive elements by
    log-step shifts (Hillis-Steele, masked at block starts), scans the
    block totals recursively, and folds each block's carry in from the
    left.  The block totals are read with one gather of E/128 indices;
    the carries go back out to E positions as a broadcast of the E/128
    block prefixes over their 128 lanes — no E-long gather, so a level
    costs elementwise work only.  ``lax.associative_scan``'s odd/even
    recursion builds one strided shape per level, and the TPU compiler's
    time on it grows about 4x per doubling of E (104 s at E = 2^20, 409 s
    at 2^21, v5e target); reshaping a [S, E] stream to [S, E/128, 128]
    blocks costs about as much again (~100 s at E = 2^24.3 per cell), so
    the elements are never blocked: only the carries are, in the
    direction that broadcasts.
    """
    e = elems[0].shape[-1]
    b = SCAN_BLOCK
    lane = jnp.arange(e, dtype=jnp.int32) % b
    d = 1
    while d < min(b, e):
        # each element folds in the one d to its left, within its block;
        # the pad (a shift, not a concatenate of two slices) keeps a level
        # one fusion per array on the TPU: half the compile time
        pad = [(0, 0)] * (elems[0].ndim - 1) + [(d, 0)]
        right = comb(tuple(jnp.pad(a[..., :-d], pad) for a in elems), elems)
        elems = tuple(jnp.where(lane >= d, r, a)
                      for a, r in zip(elems, right))
        d *= 2
    if e <= b:
        return elems
    nb = -(-e // b)
    last = jnp.minimum(jnp.arange(nb, dtype=jnp.int32) * b + (b - 1), e - 1)
    tot = _block_scan(comb, tuple(jnp.take(a, last, axis=-1) for a in elems))

    def expand(t):
        # the carry into block j >= 1 is the inclusive scan of blocks < j
        # (block 0's carry is masked off below)
        t = jnp.concatenate([t[..., :1], t[..., :-1]], axis=-1)
        t = jnp.broadcast_to(t[..., None], t.shape + (b,))
        return t.reshape(t.shape[:-2] + (nb * b,))[..., :e]

    blk = jnp.arange(e, dtype=jnp.int32) // b
    carry = tuple(expand(t) for t in tot)
    folded = comb(carry, elems)
    return tuple(jnp.where(blk >= 1, f, a) for f, a in zip(folded, elems))


def stream_scan(monoid, cand, send, key, pay):
    """Segmented inclusive scan over the destination-sorted edge stream.

    The whole per-shard stream is globally sorted by destination key
    (``ShardedGraph.build_csr``), so every destination's messages form
    one contiguous run.  A segmented scan (:func:`_block_scan`) combines
    each run left-to-right in a *fixed tree order* determined only by the
    stream length — never by the lane count — which is what makes a
    lane's sum bitwise-identical to the same query run solo, and lets
    lanes batch as pure elementwise ops (no scatters: a vmapped scatter
    is ~30x slower on CPU).

    Carries (combined value, sending count, winning payload) per element;
    ``scanned[..., e]`` holds the run-prefix combine up to e.  Shared
    verbatim by the XLA path and the Pallas scan kernel (bitwise parity
    by construction).

    ``cand``/``send`` are [..., E] (leading lane axes broadcast), ``key``
    [E], ``pay`` [..., E] int32 or None.
    """
    prev = jnp.concatenate([jnp.full((1,), -2, key.dtype), key[:-1]])
    start = jnp.broadcast_to(key != prev, cand.shape)
    cnt = jnp.broadcast_to(send, cand.shape).astype(jnp.int32)

    if pay is None:
        def comb(a, b):
            va, ca, sa = a
            vb, cb, sb = b
            return (jnp.where(sb, vb, monoid.elem(va, vb)),
                    jnp.where(sb, cb, ca + cb),
                    sa | sb)
        v, c, _ = _block_scan(comb, (cand, cnt, start))
        return v, c, None

    pay = jnp.broadcast_to(pay, cand.shape)

    def comb(a, b):
        va, ca, pa, sa = a
        vb, cb, pb, sb = b
        v = jnp.where(sb, vb, monoid.elem(va, vb))
        c = jnp.where(sb, cb, ca + cb)
        # winner's payload rides along; ties keep the max payload —
        # the same rule as the flat path's segment-max over winners
        bw = monoid.improves(vb, va)
        aw = monoid.improves(va, vb)
        p = jnp.where(sb, pb,
                      jnp.where(bw, pb,
                                jnp.where(aw, pa, jnp.maximum(pa, pb))))
        return v, c, p, sa | sb

    v, c, p, _ = _block_scan(comb, (cand, cnt, pay, start))
    return v, c, p


def gather_runs(scanned, run_end, monoid, msg_dtype):
    """Phase 2 of the scan path: read each destination's run total.

    ``run_end`` [n_keys] is the sorted region's run layout
    (:func:`~repro.core.msg.run_ends`: destination k's last position, or
    ``-1``), so the read is one pure gather per destination (no
    scatter), lane-batched for free.  Shared XLA code for both backends.
    """
    v, c, p = scanned
    ok = run_end >= 0
    li = jnp.maximum(run_end, 0)
    ident = monoid.identity(msg_dtype)
    table = jnp.where(ok, jnp.take(v, li, axis=-1), ident)
    cnt = jnp.where(ok, jnp.take(c, li, axis=-1), 0)
    pay = None
    if p is not None:
        pay = jnp.where(ok & (cnt > 0), jnp.take(p, li, axis=-1), -1)
    return table, cnt, pay


def delta_tables(prog, cand, send, pay, key, n_keys: int):
    """Combine a staged **delta segment** (DESIGN.md §2.9) into a flat
    key-space table: the appended delta blocks are unsorted, so they take
    a shared-index scatter by destination instead of the scan — the same
    scatter-class semantics as :func:`flat_combine`, batched over leading
    lane axes for free because the index vector is shared across lanes.
    Order-free (min/max) monoids stay bitwise-equal to a full rebuild
    that would have sorted these edges into their runs; sum reassociates
    (which is why the engines compact before sum-combine programs).

    ``cand``/``send``/``pay`` are [..., D] message streams from
    :func:`edge_messages` over the delta slice, ``key`` [D] its
    destination ids (``-1`` = free/tombstoned, dropped).
    """
    ids = jnp.where(key >= 0, key, n_keys).astype(jnp.int32)
    lane = cand.shape[:-1]
    ident = prog.monoid.identity(prog.msg_dtype)
    table = jnp.full(lane + (n_keys + 1,), ident, prog.msg_dtype)
    if prog.combine == "min":
        table = table.at[..., ids].min(cand)
    elif prog.combine == "max":
        table = table.at[..., ids].max(cand)
    else:
        table = table.at[..., ids].add(cand)     # non-senders hold +0
    sendb = jnp.broadcast_to(send, cand.shape)
    cnt = jnp.zeros(lane + (n_keys + 1,), jnp.int32).at[..., ids].add(
        sendb.astype(jnp.int32))
    pay_t = None
    if pay is not None:
        payb = jnp.broadcast_to(pay, cand.shape)
        win = sendb & (cand == table[..., ids])
        pay_t = jnp.full(lane + (n_keys + 1,), -1, jnp.int32).at[
            ..., ids].max(jnp.where(win, payb, -1))
        pay_t = jnp.where(cnt[..., :n_keys] > 0, pay_t[..., :n_keys], -1)
    return table[..., :n_keys], cnt[..., :n_keys], pay_t


def merge_tables(prog, a, b):
    """Monoid-merge two (table, cnt, pay) triples over the same key space
    — how the sorted region's scan output absorbs the delta segment's
    scatter output.  The payload rule is the shared tie-break (max over
    winners), so argbest programs stay bitwise-equal to the single-pass
    combines."""
    t1, c1, p1 = a
    t2, c2, p2 = b
    table = prog.monoid.elem(t1, t2)
    cnt = c1 + c2
    pay = None
    if p1 is not None:
        pay = jnp.maximum(jnp.where((t1 == table) & (c1 > 0), p1, -1),
                          jnp.where((t2 == table) & (c2 > 0), p2, -1))
    return table, cnt, pay


def stream_combine(prog, cand, send, pay, key, skey, n_keys: int,
                   delta_e: int, run_end=None):
    """The one home of the sorted-region/delta-segment split: segmented
    scan + run-end gather over ``[..., :es]`` against the structural
    ``skey``, with the staged delta segment (``delta_e`` trailing
    positions, unsorted) folded in through :func:`delta_tables` and
    merged by the monoid.  Every full-width message-stream consumer
    (dense scan path, push-sweep reconstruction) calls this, so the
    'incremental == rebuild bitwise' contract cannot drift between
    backends or sweeps.

    ``run_end`` is ``run_ends(skey[:es], n_keys)``; the engines compute
    it once per dispatch (``csr_run_end``), and it is computed here when
    not given.
    """
    es = key.shape[-1] - delta_e
    if run_end is None:
        run_end = run_ends(skey[:es], n_keys)
    sl = lambda a: None if a is None else a[..., :es]
    scanned = stream_scan(prog.monoid, cand[..., :es], send[..., :es],
                          skey[:es], sl(pay))
    out = gather_runs(scanned, run_end, prog.monoid, prog.msg_dtype)
    if delta_e:
        dl = lambda a: None if a is None else a[..., es:]
        out = merge_tables(prog, out, delta_tables(
            prog, cand[..., es:], send[..., es:], dl(pay), key[es:],
            n_keys))
    return out


def edge_relax_stream(prog, vstate, senders, gid, key, src, weight, dst_gid,
                      n_keys: int, skey=None, delta_e: int = 0,
                      run_end=None, src_gid=None):
    """Scan-based relaxation sweep (XLA): gather → emit → segmented scan
    over the sorted region → run-end gather, plus the shared-index
    scatter over the staged delta segment (``delta_e`` trailing
    positions) merged in by the monoid (:func:`stream_combine`).
    Handles single ([Np] leaves) and laned ([L, Np]) vertex blocks
    uniformly; the dense pull path of every program on ``xla``.

    ``key`` is the live-masked destination key (tombstones ``-1``) used
    for send masking; ``skey`` the structural sorted key driving the
    run layout (defaults to ``key`` — identical on delta-free graphs);
    ``run_end`` its precomputed run ends (:func:`stream_combine`);
    ``src_gid`` the optional ``csr_src_gid`` stream.

    Returns (table [..., n_keys], cnt, pay | None).
    """
    if skey is None:
        skey = key
    cand, send, pay = edge_messages(prog, vstate, senders, gid, key, src,
                                    weight, dst_gid, src_gid)
    return stream_combine(prog, cand, send, pay, key, skey, n_keys,
                          delta_e, run_end)


# --------------------------------------------------------------------------
# push (frontier-compacted) sweep — work proportional to the active
# frontier's out-edge blocks instead of the whole stream (DESIGN.md §2.8)
# --------------------------------------------------------------------------

def compact_push_blocks(senders_any, push_src, block_e: int, cap: int):
    """Compact the frontier's out-edge blocks of one cell to ``cap`` slots.

    The push stream is source-sorted (``ShardedGraph.build_push_csr``), so
    a sender's out-edges are contiguous and a block is *active* iff any of
    its edges' sources is a sender.  Active block indices compact to the
    front in ascending order (each lands at its rank among the active
    blocks); fill slots
    carry ``nb`` (one past the last block).  ``cap`` must bound the true
    active count — the direction selector (relax.py) guarantees it by
    picking the bucket from the measured count.

    Returns (idx [cap] int32, valid [cap] bool).
    """
    nb = push_src.shape[0] // block_e
    ok = push_src >= 0
    act = senders_any[jnp.clip(push_src, 0)] & ok            # [Eb]
    blk = block_any(act, block_e)                            # [nb]
    # rank-scatter compaction (no sort: a sort of nb keys costs the TPU
    # compiler ~20 s per bucket of the push ladder at deployment size)
    rank = jnp.cumsum(blk, dtype=jnp.int32) - 1
    idx = jnp.full((cap,), nb, jnp.int32).at[
        jnp.where(blk, rank, cap)].set(
        jnp.arange(nb, dtype=jnp.int32), mode="drop")
    return idx, idx < nb


def push_gather(sg_push, idx, block_e: int):
    """Gather the compacted blocks' edge streams ([cap] block indices ->
    [cap * block_e] element streams).  Fill blocks (``idx == nb``) clamp
    to the last block and are neutralized by the returned ``valid`` mask.
    """
    nb = sg_push["push_src"].shape[0] // block_e
    # flat [cap * block_e] positions, built without a [cap, block_e]
    # reshape (a TPU relayout that compiles slowly at large caps)
    j = jnp.arange(idx.shape[0] * block_e, dtype=jnp.int32)
    blk = jnp.take(idx, j // block_e)
    pos = jnp.clip(blk, 0, nb - 1) * block_e + j % block_e
    g = lambda a: jnp.take(a, pos, axis=-1)
    src = g(sg_push["push_src"])
    valid = (blk < nb) & (src >= 0)
    return {
        "src": src,
        # key carries the validity (-1 on dead positions AND fill-block
        # positions), so the shared message bodies' ``key >= 0`` mask
        # covers compaction fills with no extra plumbing
        "key": jnp.where(valid, g(sg_push["push_key"]), -1),
        "weight": g(sg_push["push_weight"]),
        "dst_gid": g(sg_push["push_dst_gid"]),
        "pos": g(sg_push["push_pos"]),
    }, valid


def edge_relax_push_flat(prog, vstate, senders, gid, sg_push, n_keys: int,
                         block_e: int, cap: int):
    """Frontier-compacted push sweep, single-query min/max (order-free):
    compact -> gather -> emit -> unsorted segment-combine by destination.

    The sending-edge multiset is exactly the dense sweep's (inactive
    blocks hold no senders by construction) and min/max are
    association-free, so the table/cnt/payload triple is bitwise-equal
    to the dense :func:`edge_relax_stream` (the same :func:`edge_messages`
    body, and :func:`flat_combine`'s tie-break is the scan's) while
    touching O(cap * block_e) edges.
    """
    idx, _ = compact_push_blocks(senders, sg_push["push_src"], block_e, cap)
    g, _ = push_gather(sg_push, idx, block_e)
    cand, send, pay = edge_messages(prog, vstate, senders, gid, g["key"],
                                    g["src"], g["weight"], g["dst_gid"])
    ids = jnp.where(send, g["key"], n_keys)
    return flat_combine(cand, send, pay, ids, n_keys, prog.combine)


def edge_relax_push_stream(prog, vstate, senders, gid, sg_push, csr_key,
                           n_keys: int, block_e: int, cap: int, skey=None,
                           delta_e: int = 0, run_end=None):
    """Frontier-compacted push sweep for sum programs and all laned runs:
    compact -> gather -> emit -> scatter the messages back into the dense
    destination-sorted stream layout (via ``push_pos``) -> the shared
    :func:`stream_scan` + :func:`gather_runs` over the sorted region and
    :func:`delta_tables` over the staged delta segment (a staged edge's
    ``push_pos`` is its own delta position, so its message lands exactly
    where the dense sweep would emit it).

    Reconstructing the dense stream (identity everywhere no gathered edge
    sends — exactly what the dense sweep holds there) keeps the scan's
    fixed tree order, so the order-sensitive sum monoid and every laned
    run stay bitwise-equal to the dense path; only the gather/emit work
    shrinks to the frontier's blocks.  Laned ``senders`` [L, Np] share one
    OR-ed active set (one gather serves every lane).
    """
    if skey is None:
        skey = csr_key
    senders_any = senders if senders.ndim == 1 else senders.any(axis=0)
    idx, _ = compact_push_blocks(senders_any, sg_push["push_src"], block_e,
                                 cap)
    g, valid = push_gather(sg_push, idx, block_e)
    cand, send, pay = edge_messages(prog, vstate, senders, gid, g["key"],
                                      g["src"], g["weight"], g["dst_gid"])
    e = csr_key.shape[0]
    dpos = jnp.where(valid, g["pos"], e)               # fills dropped
    ident = prog.monoid.identity(prog.msg_dtype)
    lane = cand.shape[:-1]
    scat = lambda full, v: full.at[..., dpos].set(v, mode="drop")
    cand_full = scat(jnp.full(lane + (e,), ident, prog.msg_dtype), cand)
    send_full = scat(jnp.zeros(lane + (e,), bool),
                     jnp.broadcast_to(send, cand.shape))
    pay_full = None
    if pay is not None:
        pay_full = scat(jnp.full(lane + (e,), -1, jnp.int32),
                        jnp.broadcast_to(pay, cand.shape))
    return stream_combine(prog, cand_full, send_full, pay_full, csr_key,
                          skey, n_keys, delta_e, run_end)

