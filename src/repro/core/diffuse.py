"""Bulk-asynchronous diffusive execution engine.

TPU-native realization of the paper's diffusive computation (DESIGN.md §2):

* Each **compute cell** (= logical shard / mesh device) owns a vertex block
  and the out-edges of those vertices.
* Inside a *round*, every cell runs **local relaxation sub-iterations to
  local quiescence** — unordered, data-driven work exactly like the paper's
  asynchronous diffusion, but vectorized.  Cross-cell messages ("operons")
  accumulate into per-destination **outboxes**, coalesced with the program's
  combine :class:`~.monoid.Monoid` (min for SSSP — duplicate relaxations
  merge in the mailbox, the TPU analogue of the paper's many-small-messages
  traffic).
* The relaxation step itself (gather ``vstate[src]`` → ``prog.emit`` →
  segment-combine by destination) is delegated to a pluggable backend
  (``backend="xla" | "pallas"`` — see relax.py): both consume the graph's
  destination-sorted blocked-CSR edge stream and return the same combined
  per-destination message table bit for bit, so the engine's while-loop
  structure is backend-independent.
* At the round boundary the outboxes are exchanged (``all_to_all`` on a real
  mesh; an axis-reduce in the single-device logical engine) and receivers run
  the program's predicate to decide whether to (re)activate — Code Listing
  1's ``if v.distance >= distance``.
* Termination = global quiescence: no vertex active and no operon in flight
  (the paper's §V.A step 6), detected by counting — see termination.py.

**Multi-query lanes** (DESIGN.md §2.7): a program built by
:func:`~.programs.make_laned` carries ``lanes=L`` and lane-stacked vertex
state (per shard: [L, Np] leaves).  The engine then broadcasts the whole
gather→emit→combine over lanes — one edge sweep serves L queries — with
outboxes gaining a lane axis and quiescence tracked per lane: a converged
lane is masked out of message generation while the slowest lanes finish.
Because emit/receive are identical across lanes and extra (quiescent)
rounds are bitwise no-ops, each lane reproduces its single-query fixed
point exactly.

``max_local_iters=1`` degenerates the engine to classic BSP; larger values
give the paper's asynchronous behaviour.  The benchmark suite uses this knob
to reproduce the paper's async-vs-BSP comparison.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .graph import DEFAULT_EDGE_BLOCK, ShardedGraph
from .msg import run_ends
from .partition import Partitioned
from .programs import VertexProgram
from .relax import (
    DEFAULT_PUSH_THRESHOLD,
    active_push_blocks,
    make_relax,
    push_caps,
    select_bucket,
)
from .termination import quiescent
from .trace import (
    SCOPE_APPLY,
    SCOPE_CONTROL,
    SCOPE_EXCHANGE,
    SCOPE_RELAX,
    to_host,
)

__all__ = [
    "diffuse",
    "diffuse_from",
    "exact_streams_for",
    "initial_state",
    "DiffuseStats",
    "FRONTIER_LOG_CAP",
    "diffuse_spmd_step",
    "make_spmd_diffuse",
    "spmd_exchange_bytes",
    "cell_sharding",
    "engine_views",
    "on_mesh",
    "logical_view",
]

# Per-round introspection buffers (frontier size, chosen direction) record
# the first FRONTIER_LOG_CAP rounds; later rounds overwrite the last slot.
FRONTIER_LOG_CAP = 512


class DiffuseStats(NamedTuple):
    rounds: jnp.ndarray            # global exchange rounds
    local_iters: jnp.ndarray       # total local sub-iterations (all cells)
    actions: jnp.ndarray           # edge-messages emitted (paper's "actions")
    remote_actions: jnp.ndarray    # actions crossing a cell boundary
    operons_sent: jnp.ndarray      # coalesced cross-cell mailbox entries sent
    operons_delivered: jnp.ndarray # ... and delivered (DS invariant: equal)
    max_frontier: jnp.ndarray      # introspection: peak active count
    push_iters: jnp.ndarray        # local sub-iterations swept via push
    frontier_log: jnp.ndarray      # [FRONTIER_LOG_CAP] active count per
                                   #   round (-1 = round never ran)
    dir_log: jnp.ndarray           # [FRONTIER_LOG_CAP] direction chosen at
                                   #   round start: 1 push, 0 pull, -1 n/a
    converged: jnp.ndarray         # bool: True = real quiescence (empty
                                   #   frontier + empty mailboxes), False =
                                   #   the max_rounds budget cut the loop
                                   #   at a non-fixed point


def _stats0() -> DiffuseStats:
    z = jnp.zeros((), jnp.int32)
    log = jnp.full((FRONTIER_LOG_CAP,), -1, jnp.int32)
    return DiffuseStats(z, z, z, z, z, z, z, z, log, log,
                        jnp.zeros((), bool))


def _gate(prog, vstate, active, threshold):
    """Delta-stepping-style priority gate: only vertices whose priority is
    within the current bucket fire (beyond-paper optimization; None
    threshold or priority-less programs = the paper's ungated diffusion).
    Laned runs carry a per-lane threshold [L, 1] that broadcasts."""
    if prog.priority is None or threshold is None:
        return active
    return active & (prog.priority(vstate) <= threshold)


def _local_iter_shard(prog: VertexProgram, np_, s_, my_shard, sg_s, st, relax,
                      threshold=None, lane_live=None, bucket=None,
                      member_full=None):
    """One local relaxation sub-iteration, per-shard view (vmapped over S).

    The gather→emit→segment-combine step is delegated to ``relax`` (built by
    :func:`repro.core.relax.make_relax`): it maps this cell's vertex block +
    destination-sorted CSR edge stream to the combined [S, Np] message table
    ([S, L, Np] for laned programs).  Row ``my_shard`` is applied as the
    local inbox inside this sub-iteration; the other rows merge into the
    cross-cell outbox.  ``lane_live`` masks converged lanes out of message
    generation.

    ``member_full`` ([S, Np] bool, or None) marks hub-replica member slots
    (DESIGN.md §2.12).  Messages destined for a member slot are *never*
    delivered mid-round — even from the slot's own cell — but are held in
    the outbox for the round-boundary replica merge, so every member of a
    group applies the identical merged message exactly once per round and
    the members stay state-mirrored.
    """
    (vstate, active, outbox, outbox_has, outbox_pay) = st
    monoid = prog.monoid
    ident = monoid.identity(prog.msg_dtype)

    with jax.named_scope(SCOPE_CONTROL):
        senders = _gate(prog, vstate, active, threshold)
        if lane_live is not None:
            senders = senders & lane_live[:, None]
    with jax.named_scope(SCOPE_RELAX):
        table, cnt, pay = relax(vstate, senders, sg_s, bucket)
    with jax.named_scope(SCOPE_APPLY):
        mine = (jnp.arange(s_, dtype=jnp.int32) == my_shard).reshape(
            (s_,) + (1,) * (table.ndim - 1))
        if member_full is None:
            keep_local = mine
        else:
            member_dst = member_full.reshape(
                (s_,) + (1,) * (table.ndim - 2) + (np_,))
            keep_local = mine & ~member_dst

        inbox = jnp.take(table, my_shard, axis=0)
        has_local = jnp.take(cnt, my_shard, axis=0) > 0
        pay_in = (jnp.take(pay, my_shard, axis=0) if prog.with_payload
                  else None)
        if member_full is not None:
            member_row = jnp.take(member_full, my_shard, axis=0)   # [Np]
            has_local = has_local & ~member_row
            inbox = jnp.where(member_row, ident, inbox)
            if prog.with_payload:
                pay_in = jnp.where(member_row, -1, pay_in)

        contrib = jnp.where(keep_local, ident, table)
        contrib_has = (cnt > 0) & ~keep_local
        if prog.with_payload:
            pay_contrib = jnp.where(keep_local, -1, pay)
            take_new = contrib_has & monoid.improves(contrib, outbox)
            outbox_pay = jnp.where(take_new, pay_contrib, outbox_pay)
        outbox = monoid.merge(outbox, contrib, contrib_has)
        outbox_has = outbox_has | contrib_has

        vstate = prog.on_send(vstate, senders)
        vstate, activated = prog.receive(
            vstate, inbox, has_local, pay_in, sg_s["node_ok"]
        )
        activated = activated | (active & ~senders)   # withheld stay active

    with jax.named_scope(SCOPE_CONTROL):
        n_send = jnp.sum(cnt)                      # sending edges (actions)
        counts = {
            "actions": n_send,
            "remote": n_send - jnp.sum(jnp.where(keep_local, cnt, 0)),
        }
    return (vstate, activated, outbox, outbox_has, outbox_pay), counts


def _sg_as_dict(sg: ShardedGraph, with_push: bool = False,
                src_gid: bool = True):
    """ShardedGraph -> the engine-facing array dict: the per-cell vertex
    block (``node_ok``/``gid``/``out_degree``) plus the destination-sorted
    pull streams the relax backends consume (``csr_key`` live-masked,
    ``csr_skey`` structural — see DESIGN.md §2.9 — and ``csr_run_end``,
    the sorted region's run layout, :func:`~.msg.run_ends`: computed here
    once per dispatch, not in every sweep; ``csr_src_gid`` unless
    ``src_gid`` is False, as the logical engine passes for programs
    that do not read the source gid) — and, when
    ``with_push`` (any sweep that can compact), the source-sorted push
    streams too (built on demand for graphs with invalidated views).
    The unsorted edge arrays always stay out, and the push streams stay
    out of pull sweeps for the same reason — the engine never reads
    them, and under shard_map they would be real per-device inputs
    inflating edge-stream transfer/residency."""
    if sg.csr_perm is None or (with_push and sg.push_perm is None):
        sg = sg.with_csr()
    d = {
        "node_ok": sg.node_ok,
        "gid": sg.gid,
        "out_degree": sg.out_degree,
    }
    d.update(_pull_streams(sg))
    if not src_gid:
        del d["csr_src_gid"]
    if with_push:
        d.update(sg.push_view())
    if sg.replica_members is not None:
        d["replica_members"] = sg.replica_members
    return d


def _pull_streams(sg: ShardedGraph) -> dict:
    """:meth:`~.graph.ShardedGraph.csr_view` plus its run layout."""
    d = sg.csr_view()
    es = d["csr_skey"].shape[-1] - sg.delta_width
    n_keys = sg.n_shards * sg.n_per_shard
    d["csr_run_end"] = jax.vmap(lambda k: run_ends(k, n_keys))(
        d["csr_skey"][:, :es])
    return d


@partial(jax.jit, static_argnames=("direction",))
def _streams(sg: ShardedGraph, direction: str) -> dict:
    """The computed half of :func:`_sg_as_dict`, as one program: the
    gathered pull streams and run layout, or the gathered push streams."""
    if direction == "push":
        d = sg.push_view()
        return {k: d[k] for k in ("push_key", "push_weight", "push_dst_gid")}
    d = _pull_streams(sg)
    del d["csr_skey"]
    return d


def engine_views(sg: ShardedGraph, pull: bool = True,
                 push: bool = False) -> dict:
    """:func:`_sg_as_dict` of a graph with both views, for callers that
    keep it between dispatches (the session's spmd cells): the pull half
    (vertex block, pull streams with ``csr_src_gid``, run layout, replica
    table) and/or the push half.  The gathers and the run layout run as
    one compiled program (eagerly, the layout's binary search would
    dispatch op by op); the fields ``_sg_as_dict`` passes through
    unchanged are the graph's own arrays here, not copies."""
    if sg.csr_perm is None or sg.push_perm is None:
        raise ValueError("engine_views needs both views; call with_csr()")
    d = {}
    if pull:
        d.update(node_ok=sg.node_ok, gid=sg.gid, out_degree=sg.out_degree)
        d.update(_streams(sg, "pull"), csr_skey=sg.csr_key)
        if sg.replica_members is not None:
            d["replica_members"] = sg.replica_members
    if push:
        d.update(_streams(sg, "push"), push_src=sg.push_src,
                 push_pos=sg.push_pos)
    return d


# --------------------------------------------------------------------------
# Hub replicas ("rhizomes", DESIGN.md §2.12): engine-side merge machinery.
# All members of a split hub mirror one vertex state; the engines enforce it
# by (a) suppressing mid-round delivery at member slots (_local_iter_shard),
# (b) merging member partials through the monoid once per round at the
# exchange and re-broadcasting the merged message to every member, and
# (c) re-broadcasting vstate/active from the primary at diffusion entry so
# adopted/repaired states (which only touch primaries) re-mirror for free.
# --------------------------------------------------------------------------

def _replica_maps(rmem, S: int, Np: int):
    """[G, Rmax] flat member keys -> (member_mask [S, Np] bool marking every
    member slot, rsrc [S*Np] int32 mapping each slot to its group primary's
    flat key — identity outside groups)."""
    tot = S * Np
    valid = rmem >= 0
    tgt = jnp.where(valid, rmem, tot)
    member_mask = jnp.zeros((tot,), bool).at[tgt].set(True, mode="drop")
    prim = jnp.broadcast_to(rmem[:, :1], rmem.shape).astype(jnp.int32)
    rsrc = jnp.arange(tot, dtype=jnp.int32).at[tgt].set(prim, mode="drop")
    return member_mask.reshape(S, Np), rsrc


def _broadcast_from_primary(tree, rsrc, S: int, Np: int):
    """Copy each group primary's value over all its member slots (identity
    elsewhere); leaves are [S, (L,), Np]."""
    def bcast(x):
        lead = x.shape[1:-1]
        flat = jnp.moveaxis(x, 0, -2).reshape(lead + (S * Np,))
        flat = flat[..., rsrc]
        return jnp.moveaxis(flat.reshape(lead + (S, Np)), -2, 0)
    return jax.tree_util.tree_map(bcast, tree)


def _merge_replicas(monoid, with_payload: bool, ident, rmem, S: int, Np: int,
                    inbox, has, pay):
    """Round-boundary replica merge on per-destination-reduced inboxes
    ([S, (L,), Np]): gather each group's member entries in fixed member
    order, fold them through the monoid (``reduce_rows`` — the same fixed
    tree order as the exchange reduce, so sum programs stay deterministic),
    and scatter the merged message back to *all* member slots.  Runs
    identically in the logical engine and (on all_gather'ed rows) in the
    SPMD engine, so both produce bit-identical merges."""
    tot = S * Np
    lead = inbox.shape[1:-1]
    R = rmem.shape[1]

    def flat(x):
        return jnp.moveaxis(x, 0, -2).reshape(lead + (tot,))

    def unflat(x):
        return jnp.moveaxis(x.reshape(lead + (S, Np)), -2, 0)

    fi, fh = flat(inbox), flat(has)
    valid = rmem >= 0                              # [G, R]
    idx = jnp.clip(rmem, 0)
    vals = fi[..., idx]                            # [..., G, R]
    hm = fh[..., idx] & valid
    # invalid members gather garbage through the clip — force to identity
    vals = jnp.where(hm, vals, ident)
    vr = jnp.moveaxis(vals, -1, 0)                 # [R, ..., G]
    hr = jnp.moveaxis(hm, -1, 0)
    merged = monoid.reduce_rows(vr, hr, axis=0)    # [..., G]
    has_g = jnp.any(hr, axis=0)
    tgt = jnp.where(valid, rmem, tot)
    fi = fi.at[..., tgt].set(
        jnp.broadcast_to(merged[..., None], merged.shape + (R,)),
        mode="drop")
    fh = fh.at[..., tgt].set(
        jnp.broadcast_to(has_g[..., None], has_g.shape + (R,)),
        mode="drop")
    out_pay = None
    if with_payload:
        fp = flat(pay)
        pr = jnp.moveaxis(fp[..., idx], -1, 0)     # [R, ..., G]
        best = monoid.argbest(vr, axis=0)          # [..., G]
        pay_g = jnp.take_along_axis(pr, best[None], axis=0)[0]
        fp = fp.at[..., tgt].set(
            jnp.broadcast_to(pay_g[..., None], pay_g.shape + (R,)),
            mode="drop")
        out_pay = unflat(fp)
    return unflat(fi), unflat(fh), out_pay


def logical_view(sg: ShardedGraph):
    """The program-init view of a (possibly hub-split) graph: ``node_ok``
    counts each hub once (False at non-primary member slots) and
    ``out_degree`` carries the *group-total* degree at every member slot,
    so degree-normalized emits (PPR / PageRank) divide by the hub's real
    out-degree.  Unsplit graphs pass through unchanged; the engine's
    entry broadcast then mirrors the primary's init state over members."""
    if sg.replica_members is None:
        return sg
    import types as _types

    S, Np = sg.n_shards, sg.n_per_shard
    tot = S * Np
    rmem = sg.replica_members
    nonprim = jnp.where(rmem[:, 1:] >= 0, rmem[:, 1:], tot)
    node_ok = sg.node_ok & ~(
        jnp.zeros((tot,), bool).at[nonprim].set(True, mode="drop")
        .reshape(S, Np))
    valid = rmem >= 0
    flatdeg = sg.out_degree.reshape(tot)
    share = jnp.where(valid, flatdeg[jnp.clip(rmem, 0)], 0)
    total = share.sum(axis=1)                      # [G]
    deg = flatdeg.at[jnp.where(valid, rmem, tot)].set(
        jnp.broadcast_to(total[:, None], rmem.shape).astype(flatdeg.dtype),
        mode="drop").reshape(S, Np)
    return _types.SimpleNamespace(gid=sg.gid, node_ok=node_ok,
                                  out_degree=deg)


@partial(jax.jit, static_argnames=("prog", "max_local_iters", "max_rounds",
                                   "delta", "backend", "sweep",
                                   "push_threshold"))
def _run_rounds(sg: ShardedGraph, prog: VertexProgram, vstate0, active0,
                max_local_iters: int, max_rounds: int, delta=None,
                backend: str = "xla", sweep: str = "pull",
                push_threshold: float = DEFAULT_PUSH_THRESHOLD):
    S, Np = sg.n_shards, sg.n_per_shard
    L = prog.lanes
    lane = (L,) if L else ()
    if sg.csr_perm is None or (sweep != "pull" and sg.push_perm is None):
        sg = sg.with_csr()          # invalidated views: rebuild in-trace
    sgd = _sg_as_dict(sg, with_push=sweep != "pull",
                      src_gid=prog.source_reads().gid)
    # the [G, Rmax] member table rides outside the per-shard vmap below
    rmem = sgd.pop("replica_members", None)
    if rmem is not None:
        member_mask, rsrc = _replica_maps(rmem, S, Np)
        # entry broadcast: callers (init, adopt, commit-repair splices)
        # only maintain primary slots — mirror them over the members
        vstate0 = _broadcast_from_primary(vstate0, rsrc, S, Np)
        active0 = _broadcast_from_primary(active0, rsrc, S, Np)
    else:
        member_mask = None
    relax = make_relax(prog, S, Np, sg.csr_block, backend, sweep,
                       push_threshold, delta_e=sg.delta_width)
    nb = sgd["csr_key"].shape[-1] // sg.csr_block
    n_caps = len(push_caps(nb))
    monoid = prog.monoid
    ident = monoid.identity(prog.msg_dtype)

    outbox0 = jnp.full((S, S) + lane + (Np,), ident, prog.msg_dtype)
    has0 = jnp.zeros((S, S) + lane + (Np,), bool)
    pay0 = (jnp.full((S, S) + lane + (Np,), -1, jnp.int32)
            if prog.with_payload else None)

    stats0 = _stats0()

    shard_ids = jnp.arange(S, dtype=jnp.int32)
    use_gate = delta is not None and prog.priority is not None

    def _bucket_of(vstate, active, thr, lane_live):
        """The direction selector: gated sending frontier -> per-cell
        active push-block counts -> shared bucket index (see relax.py)."""
        gated = jax.vmap(lambda vs, a: _gate(prog, vs, a, thr))(vstate,
                                                                active)
        if lane_live is not None:
            gated = gated & lane_live[None, :, None]
        counts = active_push_blocks(gated, sgd["push_src"], sg.csr_block)
        return select_bucket(counts, nb, sweep, push_threshold)

    @jax.named_scope(SCOPE_CONTROL)
    def round_cond(c):
        st, stats = c
        _, active, _, outbox_has, _ = st
        return (~quiescent(jnp.sum(active.astype(jnp.int32)),
                           jnp.sum(outbox_has.astype(jnp.int32)))) & (
            stats.rounds < max_rounds
        )

    def round_body(c):
        st, stats = c
        with jax.named_scope(SCOPE_CONTROL):
            if use_gate:
                # bucket threshold: min active priority + delta, per
                # round — computed per lane so a gated laned run
                # reproduces each single-query bucket sequence exactly
                prio = jax.vmap(prog.priority)(st[0])
                masked = jnp.where(st[1], prio, jnp.inf)
                if L:
                    thr = jnp.min(masked, axis=(0, masked.ndim - 1))[
                        :, None] + delta
                else:
                    thr = jnp.min(masked) + delta
            else:
                thr = jnp.inf
            # per-lane quiescence: converged lanes stop generating messages
            lane_live = (jnp.any(st[1], axis=(0, st[1].ndim - 1)) if L
                         else None)

            # round-start introspection: frontier size here; the direction
            # is logged by the first local sub-iteration from the bucket it
            # actually dispatches (the frontier may grow mid-round; only
            # the opening choice is logged — push_iters counts the rest)
            li = jnp.minimum(stats.rounds, FRONTIER_LOG_CAP - 1)
            stats = stats._replace(
                frontier_log=stats.frontier_log.at[li].set(
                    jnp.sum(st[1].astype(jnp.int32))),
            )

        @jax.named_scope(SCOPE_CONTROL)
        def local_cond(c2):
            st2, stats2, liters = c2
            gated = jax.vmap(lambda vs, a: _gate(prog, vs, a,
                                                 thr if use_gate else None))(
                st2[0], st2[1])
            return jnp.any(gated) & (liters < max_local_iters)

        def local_body(c2):
            st2, stats2, liters = c2
            with jax.named_scope(SCOPE_CONTROL):
                if sweep != "pull":
                    bucket = _bucket_of(st2[0], st2[1],
                                        thr if use_gate else None, lane_live)
                    is_push = jnp.where(bucket < n_caps, 1, 0).astype(
                        jnp.int32)
                else:
                    bucket, is_push = None, jnp.zeros((), jnp.int32)
            local_iter = jax.vmap(
                lambda i, g, s: _local_iter_shard(
                    prog, Np, S, i, g, s, relax,
                    thr if use_gate else None, lane_live, bucket,
                    member_full=member_mask,
                ),
                in_axes=(0, 0, 0),
            )
            st2, counts = local_iter(shard_ids, sgd, st2)
            with jax.named_scope(SCOPE_CONTROL):
                stats2 = stats2._replace(
                    local_iters=stats2.local_iters + 1,
                    actions=stats2.actions + jnp.sum(counts["actions"]),
                    remote_actions=stats2.remote_actions
                    + jnp.sum(counts["remote"]),
                    max_frontier=jnp.maximum(
                        stats2.max_frontier,
                        jnp.sum(st2[1].astype(jnp.int32))),
                    push_iters=stats2.push_iters + is_push,
                    dir_log=stats2.dir_log.at[li].set(
                        jnp.where(liters == 0, is_push, stats2.dir_log[li])),
                )
                liters = liters + 1
            return st2, stats2, liters

        st, stats, _ = lax.while_loop(
            local_cond, local_body, (st, stats, jnp.zeros((), jnp.int32))
        )
        vstate, active, outbox, outbox_has, outbox_pay = st
        # ---- exchange: deliver every outbox to its destination cell ----
        with jax.named_scope(SCOPE_EXCHANGE):
            n_ops = jnp.sum(outbox_has.astype(jnp.int32))
            inbox_all = monoid.reduce_rows(outbox, outbox_has, axis=0)
            has_all = outbox_has.any(axis=0)
            pay_all = None
            if prog.with_payload:
                src_idx = monoid.argbest(outbox, axis=0)
                pay_all = jnp.take_along_axis(outbox_pay, src_idx[None],
                                              axis=0)[0]
            if rmem is not None:
                # replica merge, folded into the exchange: member partials
                # combine through the monoid and the merged message lands
                # on every member slot before receive
                inbox_all, has_all, pay_all = _merge_replicas(
                    monoid, prog.with_payload, ident, rmem, S, Np,
                    inbox_all, has_all, pay_all)
            recv = jax.vmap(
                lambda vs, ib, hs, pl, nok: prog.receive(vs, ib, hs, pl, nok)
            )
            vstate, activated = recv(vstate, inbox_all, has_all, pay_all,
                                     sgd["node_ok"])
            active = active | activated
            outbox = jnp.full_like(outbox, ident)
            outbox_has = jnp.zeros_like(outbox_has)
            if prog.with_payload:
                outbox_pay = jnp.full_like(outbox_pay, -1)
        with jax.named_scope(SCOPE_CONTROL):
            stats = stats._replace(
                rounds=stats.rounds + 1,
                operons_sent=stats.operons_sent + n_ops,
                operons_delivered=stats.operons_delivered + n_ops,
                max_frontier=jnp.maximum(
                    stats.max_frontier, jnp.sum(active.astype(jnp.int32))
                ),
            )
        return (vstate, active, outbox, outbox_has, outbox_pay), stats

    st0 = (vstate0, active0, outbox0, has0, pay0)
    (st, stats) = lax.while_loop(round_cond, round_body, (st0, stats0))
    # budget watchdog: the loop exits on quiescence OR rounds == max_rounds;
    # re-evaluating the predicate on the final state tells the two apart
    _, active_f, _, outbox_has_f, _ = st
    with jax.named_scope(SCOPE_CONTROL):
        stats = stats._replace(converged=quiescent(
            jnp.sum(active_f.astype(jnp.int32)),
            jnp.sum(outbox_has_f.astype(jnp.int32))))
    return st[0], stats


def exact_streams_for(sg: ShardedGraph, prog: VertexProgram) -> ShardedGraph:
    """Compact a dirty graph before a **sum-combine** diffusion.

    Min/max fixed points consume tombstones and staged delta blocks
    bitwise-identically to a full rebuild (order-free monoids), but a
    floating sum reassociates when the staged edges' contributions fold
    in through the delta scatter instead of their sorted run positions —
    so sum programs compact first, keeping the "incremental == rebuild,
    bitwise" contract across the whole program matrix.  Cheap host check
    of the per-cell counters; a traced graph skips it (the delta path is
    still exact-to-tolerance) and an already-clean graph pays nothing.
    Callers that own the graph (the session) persist the compacted copy
    so the sort is paid once per dirty epoch, not per query.
    """
    if (prog.combine != "sum" or sg.csr_perm is None
            or sg.delta_count is None):
        return sg
    if isinstance(sg.delta_count, jax.core.Tracer):
        return sg
    # intentional O(cells) policy read: device_get (not int()/.item()) so
    # a warm query stays legal under jax.transfer_guard("disallow")
    dc = to_host(sg.delta_count, "exact_streams")
    tc = to_host(sg.tomb_count, "exact_streams")
    dirty = (dc.max(initial=0) + tc.max(initial=0)) > 0
    return sg.with_csr() if dirty else sg


def initial_state(sg: ShardedGraph, prog: VertexProgram):
    """The program's ``(vstate, active)`` on ``sg``, computed eagerly.

    Init runs concretely (not traced), so its per-query scalar constants
    (e.g. the source id) upload h2d here; that O(1) upload is legal under
    the sanitizer, whose contract guards d2h syncs and retraces — the d2h
    direction of any ambient guard stays in force."""
    with jax.transfer_guard_host_to_device("allow"):
        return prog.init(logical_view(sg))


def diffuse(
    part: Partitioned | ShardedGraph,
    prog: VertexProgram,
    max_local_iters: int = 64,
    max_rounds: int = 10_000,
    delta=None,
    backend: str = "xla",
    sweep: str = "pull",
    push_threshold: float = DEFAULT_PUSH_THRESHOLD,
):
    """Run a diffusive computation to quiescence.

    Returns (vertex_state pytree in [S, Np] layout — [S, L, Np] for laned
    programs — and DiffuseStats).  Equivalent of the paper's
    ``hpx_diffuse`` (Code Listing 3): the program carries
    vertex_func/predicate; the terminator is the engine's built-in
    counting quiescence detector.  ``backend`` selects the relaxation
    kernel and ``sweep`` the direction — dense pull, frontier-compacted
    push, or the per-sub-iteration ``"auto"`` selector (see relax.py);
    every choice reaches the same fixed point bitwise.

    The initial ``(vstate, active)`` is computed *eagerly* and enters
    the jitted fixed-point loop as traced arrays: combined with
    :class:`~.programs.VertexProgram`'s init-excluding structural
    equality, every query that differs only in its init parameters
    (``sssp(source=k)`` for any k of the same graph shape) reuses one
    ``_run_rounds`` compilation — zero retraces across sources.
    """
    sg = part.sg if isinstance(part, Partitioned) else part
    sg = exact_streams_for(sg, prog)
    vstate0, active0 = initial_state(sg, prog)
    return _run_rounds(sg, prog, vstate0, active0, max_local_iters,
                       max_rounds, delta, backend, sweep, push_threshold)


def diffuse_from(
    part: Partitioned | ShardedGraph,
    prog: VertexProgram,
    vstate,
    active,
    max_local_iters: int = 64,
    max_rounds: int = 10_000,
    delta=None,
    backend: str = "xla",
    sweep: str = "pull",
    push_threshold: float = DEFAULT_PUSH_THRESHOLD,
):
    """Resume / continue a diffusion from an explicit (state, frontier).

    Used by the dynamic-graph repair path (incremental SSSP) — the paper's
    point that diffusive computations restart from *within* the data rather
    than from a central coordinator.  ``delta`` applies the same
    delta-stepping priority gate as :func:`diffuse`, so a gated query's
    incremental repair runs gated too.  Repairs resume from a *tiny*
    frontier, which is exactly where ``sweep="push"`` turns the O(E)
    per-round sweep into O(frontier-adjacent edges) — the session's
    repair path defaults to it."""
    sg = part.sg if isinstance(part, Partitioned) else part
    sg = exact_streams_for(sg, prog)
    return _run_rounds(sg, prog, vstate, active, max_local_iters, max_rounds,
                       delta, backend, sweep, push_threshold)


# --------------------------------------------------------------------------
# SPMD device engine: one compute cell per mesh device, shard_map + all_to_all
# --------------------------------------------------------------------------

def diffuse_spmd_step(prog: VertexProgram, axis_name: str, n_shards: int,
                      n_per_shard: int, max_local_iters: int, max_rounds: int,
                      block_e: int = DEFAULT_EDGE_BLOCK,
                      backend: str = "xla", sweep: str = "pull",
                      push_threshold: float = DEFAULT_PUSH_THRESHOLD,
                      delta_e: int = 0):
    """Build the per-device diffusion function for use inside shard_map.

    The returned fn takes per-device blocks of the ShardedGraph arrays
    (leading dim 1 = this device's shard, including the ``csr_*``/
    ``push_*`` sorted edge streams) and of the initial ``(vstate,
    active)`` — computed outside, like :func:`initial_state` for the
    logical engine, so the function holds no query parameter — and runs
    rounds of (local relax -> all_to_all operon exchange -> receive)
    until a psum'd quiescence check fires.  The local while_loop has
    device-dependent trip count — cells
    genuinely run ahead of each other between exchanges.  The relaxation
    step dispatches to the same ``backend``/``sweep`` implementations as
    the logical engine; the direction selector runs *per device* on the
    local frontier (no collective — the sweep branches contain none), so
    a cell with a dense frontier pulls while its sparse neighbours push.
    Laned programs carry their lane axis through the all_to_all unchanged.
    """
    S, Np = n_shards, n_per_shard
    L = prog.lanes
    lane = (L,) if L else ()
    relax = make_relax(prog, S, Np, block_e, backend, sweep, push_threshold,
                       delta_e=delta_e)
    monoid = prog.monoid
    ident_f = lambda: monoid.identity(prog.msg_dtype)

    def spmd_rounds(sgd, vstate0, active0):
        my_shard = lax.axis_index(axis_name).astype(jnp.int32)
        sgd = dict(sgd)
        # replicated [G, Rmax] member table (P() spec — no device axis)
        rmem = sgd.pop("replica_members", None)
        sg_s = {k: v[0] for k, v in sgd.items()}
        vstate = jax.tree_util.tree_map(lambda a: a[0], vstate0)
        active = active0[0]

        member_mask = None
        if rmem is not None:
            member_mask, rsrc = _replica_maps(rmem, S, Np)
            # entry broadcast: callers only maintain primary slots — mirror
            # them over the members (members may live on other devices —
            # gather, map, re-slice)
            def _bcast_row(x):
                full = _broadcast_from_primary(
                    lax.all_gather(x, axis_name), rsrc, S, Np)
                return jnp.take(full, my_shard, axis=0)

            vstate = jax.tree_util.tree_map(_bcast_row, vstate)
            active = _bcast_row(active)
        outbox = jnp.full((S,) + lane + (Np,), ident_f(), prog.msg_dtype)
        outbox_has = jnp.zeros((S,) + lane + (Np,), bool)
        outbox_pay = (jnp.full((S,) + lane + (Np,), -1, jnp.int32)
                      if prog.with_payload else None)
        stats = _stats0()
        nb = sg_s["csr_key"].shape[-1] // block_e
        n_caps = len(push_caps(nb))

        def _bucket_of(act):
            counts = active_push_blocks(act, sg_s["push_src"], block_e)
            return select_bucket(counts, nb, sweep, push_threshold)

        @jax.named_scope(SCOPE_CONTROL)
        def round_cond(c):
            _, _, global_live, stats = c
            return (global_live > 0) & (stats.rounds < max_rounds)

        def round_body(c):
            st, _, global_live, stats = c
            with jax.named_scope(SCOPE_CONTROL):
                if L:
                    # per-lane global quiescence: psum'd lane frontiers
                    # mask converged lanes out of message generation
                    lane_live = lax.psum(
                        jnp.sum(st[1].astype(jnp.int32), axis=-1), axis_name
                    ) > 0
                else:
                    lane_live = None

                # round-start introspection: the psum'd frontier is
                # already in hand (replicated); the direction is this
                # device's opening pick, logged by the first local
                # sub-iteration and pmax'd into the log at the end
                li = jnp.minimum(stats.rounds, FRONTIER_LOG_CAP - 1)
                stats = stats._replace(
                    frontier_log=stats.frontier_log.at[li].set(
                        global_live.astype(jnp.int32)),
                )

            @jax.named_scope(SCOPE_CONTROL)
            def local_cond(c2):
                st2, stats2, liters = c2
                return jnp.any(st2[1]) & (liters < max_local_iters)

            def local_body(c2):
                st2, stats2, liters = c2
                with jax.named_scope(SCOPE_CONTROL):
                    if sweep != "pull":
                        act = (st2[1] if lane_live is None
                               else st2[1] & lane_live[:, None])
                        bucket = _bucket_of(act)
                        is_push = jnp.where(bucket < n_caps, 1, 0).astype(
                            jnp.int32)
                    else:
                        bucket, is_push = None, jnp.zeros((), jnp.int32)
                st2, counts = _local_iter_shard(prog, Np, S, my_shard, sg_s,
                                                st2, relax, None, lane_live,
                                                bucket,
                                                member_full=member_mask)
                with jax.named_scope(SCOPE_CONTROL):
                    stats2 = stats2._replace(
                        local_iters=stats2.local_iters + 1,
                        actions=stats2.actions + counts["actions"],
                        remote_actions=stats2.remote_actions
                        + counts["remote"],
                        push_iters=stats2.push_iters + is_push,
                        dir_log=stats2.dir_log.at[li].set(
                            jnp.where(liters == 0, is_push,
                                      stats2.dir_log[li])),
                    )
                    liters = liters + 1
                return st2, stats2, liters

            st, stats, _ = lax.while_loop(
                local_cond, local_body, (st, stats, jnp.zeros((), jnp.int32))
            )
            vstate, active, outbox, outbox_has, outbox_pay = st
            with jax.named_scope(SCOPE_EXCHANGE):
                n_ops = jnp.sum(outbox_has.astype(jnp.int32))
                # exchange: row t of my outbox goes to device t
                rec = lax.all_to_all(outbox, axis_name, split_axis=0,
                                     concat_axis=0, tiled=True)
                rec_has = lax.all_to_all(
                    outbox_has.astype(jnp.int8), axis_name, split_axis=0,
                    concat_axis=0, tiled=True,
                ) > 0
                inbox = monoid.reduce_rows(rec, rec_has, axis=0)
                has = rec_has.any(axis=0)
                pay = None
                if prog.with_payload:
                    rec_pay = lax.all_to_all(outbox_pay, axis_name,
                                             split_axis=0, concat_axis=0,
                                             tiled=True)
                    idx = monoid.argbest(rec, axis=0)
                    pay = jnp.take_along_axis(rec_pay, idx[None], axis=0)[0]
                    outbox_pay = jnp.full_like(outbox_pay, -1)
                if rmem is not None:
                    # replica merge on the gathered [S, ...] rows — the
                    # exact computation the logical engine runs, then
                    # re-slice this device's row, so both engines merge
                    # bit-identically
                    ib = lax.all_gather(inbox, axis_name)
                    hs = lax.all_gather(has, axis_name)
                    pa = (lax.all_gather(pay, axis_name)
                          if prog.with_payload else None)
                    ib, hs, pa = _merge_replicas(
                        monoid, prog.with_payload, ident_f(), rmem, S, Np,
                        ib, hs, pa)
                    inbox = jnp.take(ib, my_shard, axis=0)
                    has = jnp.take(hs, my_shard, axis=0)
                    if prog.with_payload:
                        pay = jnp.take(pa, my_shard, axis=0)
                vstate, activated = prog.receive(vstate, inbox, has, pay,
                                                 sg_s["node_ok"])
                active = active | activated
                outbox = jnp.full((S,) + lane + (Np,), ident_f(),
                                  prog.msg_dtype)
                outbox_has = jnp.zeros((S,) + lane + (Np,), bool)
            with jax.named_scope(SCOPE_CONTROL):
                live = lax.psum(jnp.sum(active.astype(jnp.int32)), axis_name)
                delivered = lax.psum(n_ops, axis_name)
                stats = stats._replace(
                    rounds=stats.rounds + 1,
                    operons_sent=stats.operons_sent + n_ops,
                    operons_delivered=stats.operons_delivered + delivered,
                )
            return ((vstate, active, outbox, outbox_has, outbox_pay), None,
                    live, stats)

        with jax.named_scope(SCOPE_CONTROL):
            live0 = lax.psum(jnp.sum(active.astype(jnp.int32)), axis_name)
        st0 = (vstate, active, outbox, outbox_has, outbox_pay)
        st, _, live_f, stats = lax.while_loop(
            round_cond, round_body, (st0, None, live0, stats)
        )
        vfinal = jax.tree_util.tree_map(lambda a: a[None], st[0])
        with jax.named_scope(SCOPE_CONTROL):
            stats = stats._replace(
                # live_f is already a psum — replicated, so every device
                # reports the same budget-vs-quiescence verdict
                converged=(live_f == 0),
                actions=lax.psum(stats.actions, axis_name),
                remote_actions=lax.psum(stats.remote_actions, axis_name),
                operons_sent=lax.psum(stats.operons_sent, axis_name),
                local_iters=lax.pmax(stats.local_iters, axis_name),
                max_frontier=lax.pmax(stats.max_frontier, axis_name),
                push_iters=lax.pmax(stats.push_iters, axis_name),
                dir_log=lax.pmax(stats.dir_log, axis_name),
            )
        return vfinal, stats

    return spmd_rounds


def cell_sharding(mesh, key: str, axis_name: str = "cells"):
    """Where an engine-facing array lives on the mesh: one cell per
    device (``P(axis_name)`` on the leading axis), except the [G, Rmax]
    replica member table, which every device holds whole."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P() if key == "replica_members"
                         else P(axis_name))


def on_mesh(mesh, arrays: dict, axis_name: str = "cells") -> dict:
    """``device_put`` a dict of [S, ...] arrays (a graph's
    :meth:`~.graph.ShardedGraph.state_dict`, its engine-facing views, or
    a vertex state) one cell per device, by :func:`cell_sharding`.  An
    array already placed so is returned as it is.  An array of another
    platform (a graph built on the host's CPU device) is cut into cells
    in host memory: ``device_put`` would slice it on this platform's
    default device, which would then hold the whole array."""
    platform = mesh.devices.flat[0].platform

    def host_or_self(v):
        if isinstance(v, jax.Array) and any(
                d.platform != platform for d in v.sharding.device_set):
            return np.asarray(v)
        return v

    return {k: jax.device_put(host_or_self(v),
                              cell_sharding(mesh, k, axis_name))
            for k, v in arrays.items()}


def spmd_exchange_bytes(prog: VertexProgram, n_shards: int,
                        n_per_shard: int) -> int:
    """Bytes one round's ``all_to_all`` exchange of the spmd engine sends
    between devices, summed over the devices: each device sends its
    outbox rows for the other ``n_shards - 1`` cells, ``n_per_shard``
    slots per row (and lane), each slot a value, a one-byte has flag and
    an int32 payload for payload programs.  From shapes alone."""
    slot = (jnp.dtype(prog.msg_dtype).itemsize + 1
            + (4 if prog.with_payload else 0))
    return (n_shards * (n_shards - 1) * n_per_shard * (prog.lanes or 1)
            * slot)


def make_spmd_diffuse(mesh, prog: VertexProgram, sg_template,
                      axis_name: str = "cells", max_local_iters: int = 64,
                      max_rounds: int = 10_000, backend: str = "xla",
                      block_e: int | None = None, sweep: str = "pull",
                      push_threshold: float = DEFAULT_PUSH_THRESHOLD,
                      delta_blocks: int | None = None):
    """Wrap the per-device engine in shard_map over ``axis_name``, jitted.

    ``sg_template`` may be a ShardedGraph or a dict of (ShapeDtypeStruct)
    arrays matching :func:`_sg_as_dict` — the latter is what the session
    (its placed views) and the dry-run pass; dict templates must carry
    the ``csr_*`` (and for push/auto sweeps the ``push_*``) stream
    fields, padded to a multiple of ``block_e`` (pass it when the streams
    were built with a non-default :meth:`ShardedGraph.with_csr` block).
    ``delta_blocks`` is the staged-delta capacity baked into the streams
    (taken from a ShardedGraph template automatically; dict templates
    default to 0 = delta-free).

    Returns a jitted function ``(sgd, vstate0, active0) -> (vertex_state,
    stats)``: the graph dict, the initial state from
    :func:`initial_state` and the vertex state out all in [S, Np] layout
    ([S, L, Np] for laned programs), one cell per device.  The program's
    ``init`` never enters the trace, so one compile serves every query
    of the same program structure (any source) at the same shapes.
    """
    from jax.sharding import PartitionSpec as P

    if isinstance(sg_template, ShardedGraph):
        if sg_template.csr_perm is None or (
                sweep != "pull" and sg_template.push_perm is None):
            sg_template = sg_template.with_csr()
        sgd_t = _sg_as_dict(sg_template, with_push=sweep != "pull")
        block_e = block_e or sg_template.csr_block
        if delta_blocks is None:
            delta_blocks = max(sg_template.delta_blocks, 0)
    else:
        sgd_t = dict(sg_template)
        block_e = block_e or DEFAULT_EDGE_BLOCK
    delta_blocks = delta_blocks or 0
    if sgd_t["csr_key"].shape[-1] % block_e:
        raise ValueError(
            f"csr streams of width {sgd_t['csr_key'].shape[-1]} are not a "
            f"multiple of block_e={block_e}; pass the block the template "
            f"was padded with")
    S = sgd_t["gid"].shape[0]
    Np = sgd_t["gid"].shape[1]

    spmd_rounds = diffuse_spmd_step(
        prog, axis_name, S, Np, max_local_iters, max_rounds,
        block_e=block_e, backend=backend, sweep=sweep,
        push_threshold=push_threshold, delta_e=delta_blocks * block_e,
    )
    # graph arrays and vertex state shard one cell per device; the
    # [G, Rmax] replica member table (when present) is replicated — every
    # device needs every group
    cells = P(axis_name)
    in_specs = ({k: cell_sharding(mesh, k, axis_name).spec for k in sgd_t},
                cells, cells)
    out_specs = (cells, DiffuseStats(*[P()] * len(DiffuseStats._fields)))
    return jax.jit(jax.shard_map(
        spmd_rounds,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    ))
