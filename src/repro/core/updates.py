"""Batched graph mutation — the paper's seven primitives, vectorized and
**device-resident** (DESIGN.md §2.9).

:class:`UpdateBatch` collects vertex/edge add/delete/touch operations and
applies them to a :class:`~repro.core.graph.ShardedGraph` with **one
compiled program per batch shape**: the whole apply — slot matching,
cumsum-based free-slot allocation, field scatters, and the incremental
CSR patching (tombstones + staged delta blocks) — runs as a single
:func:`jax.jit` (``apply_updates``) over op arrays padded to a
power-of-two size ladder, so repeated batch shapes never recompile and
the steady-state commit does **zero device->host transfers**: commit
cost is O(batch) scatters, not the O(E log E) stream re-sort the eager
``with_csr`` rebuild pays.  Group order matches the sequential
primitives in ``dynamic.py``:

    vertex adds -> edge deletes -> vertex deletes -> edge adds -> touches

Semantics notes (mirroring the sequential primitives):

* edge deletes remove the first matching live slot per occurrence — a
  batch deleting the same (u, v) pair twice removes two parallel edges;
* edge adds fill the lowest free slots of the source's cell, in order
  (device-side: the rank-th free slot found by a cumsum over the free
  mask — no host readback of the edge stream);
* vertex deletes drop the vertex's out-edges and mask + degree-fix its
  in-edges across all cells;
* id allocation happens eagerly at ``add_vertex`` time (through the
  NameServer), so new ids are usable by later ops in the same batch.

Compaction policy: staging falls back to the eager ``with_csr`` rebuild
when a cell's delta segment would overflow, or when its tombstones
exceed ``TOMBSTONE_COMPACT_FRACTION`` of its edge slots — amortizing the
sort over many O(batch) commits.  The policy check reads only the [S]
counters (O(cells) scalars, not the edge stream).
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import TOMBSTONE_COMPACT_FRACTION

__all__ = ["UpdateBatch", "AppliedUpdates", "apply_updates"]


class AppliedUpdates(NamedTuple):
    """What a batch did — consumed by the session's incremental repair."""

    vertex_adds: tuple        # ((gid, shard, local), ...)
    vertex_deletes: tuple     # (gid, ...)
    edge_adds: tuple          # ((u, v, w), ...)
    edge_deletes: tuple       # ((u, v), ...)
    touched: tuple            # (gid, ...)

    @property
    def has_deletes(self) -> bool:
        return bool(self.vertex_deletes or self.edge_deletes)

    @property
    def n_ops(self) -> int:
        return (len(self.vertex_adds) + len(self.vertex_deletes)
                + len(self.edge_adds) + len(self.edge_deletes)
                + len(self.touched))


def _pow2(n: int) -> int:
    """Pad a group size up the power-of-two ladder (0 stays 0), so a
    stream of similarly-sized batches reuses one compiled apply."""
    return 1 << (n - 1).bit_length() if n else 0


def _pad(a: np.ndarray, k: int, fill) -> jnp.ndarray:
    out = np.full((k,), fill, a.dtype)
    out[: a.shape[0]] = a
    return jnp.asarray(out)


# edge-delete ops matched against their cell's edge slots per lax.map
# step: memory is DELETE_MATCH_BATCH x (edge slots per cell)
DELETE_MATCH_BATCH = 8


@partial(jax.jit, static_argnames=("stage",))
def apply_updates(sg, ops: dict, stage: bool):
    """The whole batched apply as one compiled program.

    ``ops`` holds the padded op-group arrays (any group may be absent);
    padding rows carry out-of-range indices so every scatter drops them.
    ``stage`` (static) selects incremental CSR patching — tombstones for
    the delete groups, staged delta entries for the add group; False
    leaves the views untouched for a caller-side eager rebuild.

    Returns ``(sg, del_ok, add_ok)``: which edge-delete ops matched a
    live edge (phantom deletes are no-ops) and which edge adds found a
    free slot (False => the cell's edge memory is full and the caller
    must reject the batch).
    """
    np_ = sg.n_per_shard
    ep = sg.edges_per_shard
    i32 = jnp.int32

    if "va_s" in ops:
        s, l, g = ops["va_s"], ops["va_l"], ops["va_g"]
        sg = dataclasses.replace(
            sg,
            node_ok=sg.node_ok.at[s, l].set(True, mode="drop"),
            gid=sg.gid.at[s, l].set(g, mode="drop"),
            out_degree=sg.out_degree.at[s, l].set(0, mode="drop"),
        )

    del_ok = None
    if "ed_su" in ops:
        su, lu, vg, occ = (ops["ed_su"], ops["ed_lu"], ops["ed_vg"],
                           ops["ed_occ"])

        def match_one(op):
            s, l, v, o = op
            match = ((sg.src_local[s] == l) & (sg.dst_gid[s] == v)
                     & sg.edge_ok[s])                       # [Ep]
            # the occ-th occurrence of a pair takes the occ-th matching
            # slot (first-match semantics): the slot where the running
            # match count reaches occ+1 — a cumsum + argmax, not a
            # per-row argsort (whose O(Ep log Ep) per op would rival the
            # full rebuild this path replaces)
            hit = match & (jnp.cumsum(match, dtype=i32) == o + 1)
            slot = jnp.argmax(hit).astype(i32)
            return slot, hit[slot]

        # a few ops at a time: all K rows at once is a [K, Ep] table
        # (64 GB for 2,048 deletes at Graph500 scale 20)
        slot, del_ok = jax.lax.map(match_one, (su, lu, vg, occ),
                                   batch_size=DELETE_MATCH_BATCH)
        # non-matching rows would land on an arbitrary live slot and race
        # with real deletes at the same index (duplicate scatter indices
        # with conflicting values are unordered in XLA) — route them out
        # of bounds instead, where scatter drops them.
        slot = jnp.where(del_ok, slot, ep)
        sg = dataclasses.replace(
            sg,
            edge_ok=sg.edge_ok.at[su, slot].set(False, mode="drop"),
            out_degree=sg.out_degree.at[su, lu].add(
                -del_ok.astype(i32), mode="drop"),
        )
        if stage:
            sg = sg.with_edge_tombstones(su, slot, del_ok)

    if "vd_s" in ops:
        s, l = ops["vd_s"], ops["vd_l"]
        dv = jnp.zeros((sg.n_shards, np_), bool).at[s, l].set(
            True, mode="drop")
        dead_out = sg.edge_ok & jnp.take_along_axis(dv, sg.src_local,
                                                    axis=1)
        dead_in = sg.edge_ok & dv[sg.dst_shard, sg.dst_local]
        deg = jax.vmap(
            lambda d, sl, m: d.at[sl].add(-m.astype(i32))
        )(sg.out_degree, sg.src_local, dead_in & ~dead_out)
        sg = dataclasses.replace(
            sg,
            edge_ok=sg.edge_ok & ~dead_out & ~dead_in,
            node_ok=sg.node_ok.at[s, l].set(False, mode="drop"),
            out_degree=deg.at[s, l].set(0, mode="drop"),
        )
        if stage:
            sg = sg.with_slot_tombstones(dead_out | dead_in)

    add_ok = None
    if "ea_su" in ops:
        su, lu, sv, lv, vg, w, rank = (
            ops["ea_su"], ops["ea_lu"], ops["ea_sv"], ops["ea_lv"],
            ops["ea_vg"], ops["ea_w"], ops["ea_rank"])
        valid = rank >= 0
        # lowest free slots per cell, in arrival order: the op's rank
        # among its cell's adds picks the rank-th free slot — located by
        # a per-cell searchsorted over the free-mask cumsum (a [S, K]
        # table, not a [K, Ep] gather), all device-side (the old path
        # pulled the whole edge_ok stream to the host every batch)
        free_cum = jnp.cumsum((~sg.edge_ok).astype(i32), axis=1)  # [S, Ep]
        targets = jnp.arange(1, su.shape[0] + 1, dtype=i32)
        slot_tab = jax.vmap(
            lambda c: jnp.searchsorted(c, targets).astype(i32)
        )(free_cum)                                               # [S, K]
        slot = slot_tab[su, jnp.clip(rank, 0)]
        have = free_cum[su, -1] > rank
        add_ok = have | ~valid
        ok = valid & have
        slot = jnp.where(ok, slot, ep)
        sg = dataclasses.replace(
            sg,
            src_local=sg.src_local.at[su, slot].set(lu, mode="drop"),
            dst_shard=sg.dst_shard.at[su, slot].set(sv, mode="drop"),
            dst_local=sg.dst_local.at[su, slot].set(lv, mode="drop"),
            dst_gid=sg.dst_gid.at[su, slot].set(vg, mode="drop"),
            weight=sg.weight.at[su, slot].set(w, mode="drop"),
            edge_ok=sg.edge_ok.at[su, slot].set(True, mode="drop"),
            out_degree=sg.out_degree.at[su, lu].add(
                ok.astype(i32), mode="drop"),
        )
        if stage:
            sg = sg.with_staged_edges(su, slot, lu, sv * np_ + lv, rank,
                                      ok)
    return sg, del_ok, add_ok


class UpdateBatch:
    """Collect mutations; apply them as one compiled scatter program.

    Build one through :meth:`repro.core.session.DiffusionSession.update`
    (the session then repairs its cached programs on ``commit()``), or
    standalone with a :class:`~repro.core.dynamic.NameServer`.
    """

    def __init__(self, ns):
        self.ns = ns
        self._vadds: list[tuple[int, int, int]] = []
        self._vdels: list[int] = []
        self._eadds: list[tuple[int, int, float]] = []
        self._edels: list[tuple[int, int]] = []
        self._touch: list[int] = []

    def __len__(self) -> int:
        return (len(self._vadds) + len(self._vdels) + len(self._eadds)
                + len(self._edels) + len(self._touch))

    # -- the seven primitives (peek is a read; see session.peek) ----------

    def add_vertex(self, shard: int | None = None) -> int:
        """Reserve a vertex slot (eager id allocation); returns the gid."""
        if shard is None:
            shard = self.ns.best_shard()
        gid, s, l = self.ns.allocate(shard)
        self._vadds.append((gid, s, l))
        return gid

    def delete_vertex(self, gid: int):
        self._vdels.append(int(gid))
        return self

    def touch_vertex(self, gid: int):
        """Re-activate ``gid`` at the next commit (the relax seed)."""
        self._touch.append(int(gid))
        return self

    def add_edge(self, u: int, v: int, w: float = 1.0):
        self._eadds.append((int(u), int(v), float(w)))
        return self

    def delete_edge(self, u: int, v: int):
        self._edels.append((int(u), int(v)))
        return self

    def touch_edge(self, u: int):
        """Re-emit on all of u's out-edges at the next commit."""
        return self.touch_vertex(u)

    # -- host-side packing -------------------------------------------------

    def _pack_ops(self, sg) -> tuple[dict, dict]:
        """Resolve gids and pack each op group into padded device arrays
        (power-of-two ladder; padding rows scatter out of range).
        Returns ``(ops, per_cell)`` — the second holds host-side per-cell
        add/delete counts for the compaction policy, so the policy never
        reads the freshly uploaded device arrays back."""
        ns = self.ns
        np_ = sg.n_per_shard
        n_shards = sg.n_shards
        ops: dict = {}
        per_cell = {"adds": np.zeros(n_shards, np.int64),
                    "dels": np.zeros(n_shards, np.int64)}

        if self._vadds:
            k = _pow2(len(self._vadds))
            g, s, l = (np.array([t[i] for t in self._vadds], np.int32)
                       for i in (0, 1, 2))
            ops["va_s"] = _pad(s, k, 0)
            ops["va_l"] = _pad(l, k, np_)        # pad -> dropped
            ops["va_g"] = _pad(g, k, 0)

        if self._edels:
            k = _pow2(len(self._edels))
            n = len(self._edels)
            su = np.empty(n, np.int32)
            lu = np.empty(n, np.int32)
            vg = np.empty(n, np.int32)
            occ = np.empty(n, np.int32)   # occurrence index per (u, v)
            seen: Counter = Counter()
            for j, (u, v) in enumerate(self._edels):
                # split sources: probe the member cell the rank hash
                # stored this (u, v) edge in (build and add used it too)
                su[j], lu[j] = ns.route_edge(u, v)
                vg[j] = v
                occ[j] = seen[(u, v)]
                seen[(u, v)] += 1
            ops["ed_su"] = _pad(su, k, 0)
            ops["ed_lu"] = _pad(lu, k, np_)      # pad matches no src_local
            ops["ed_vg"] = _pad(vg, k, -1)       # ... and no dst_gid
            ops["ed_occ"] = _pad(occ, k, 0)
            per_cell["dels"] = np.bincount(su, minlength=n_shards)

        if self._vdels:
            # a split hub dies at ALL member slots (out-edges are stored
            # across members), so expand each gid to its member pairs
            pairs: list[tuple[int, int]] = []
            for gid in self._vdels:
                pairs.extend(ns.members_of(gid) or [ns.resolve(gid)])
            k = _pow2(len(pairs))
            s = np.array([p[0] for p in pairs], np.int32)
            l = np.array([p[1] for p in pairs], np.int32)
            ops["vd_s"] = _pad(s, k, 0)
            ops["vd_l"] = _pad(l, k, np_)        # pad -> dropped

        if self._eadds:
            k = _pow2(len(self._eadds))
            n = len(self._eadds)
            su = np.empty(n, np.int32)
            lu = np.empty(n, np.int32)
            sv = np.empty(n, np.int32)
            lv = np.empty(n, np.int32)
            vg = np.empty(n, np.int32)
            w = np.empty(n, np.float32)
            rank = np.empty(n, np.int32)         # index among cell's adds
            cell_rank: Counter = Counter()       # must NOT shadow per_cell
            for j, (u, v, wj) in enumerate(self._eadds):
                # split endpoints route by the rank hash (same slots the
                # partition-time build picks — incremental == rebuild)
                su[j], lu[j] = ns.route_edge(u, v)
                sv[j], lv[j] = ns.route_target(v, u)
                vg[j], w[j] = v, wj
                rank[j] = cell_rank[int(su[j])]
                cell_rank[int(su[j])] += 1
            ops["ea_su"] = _pad(su, k, 0)
            ops["ea_lu"] = _pad(lu, k, np_)      # pad -> degree add drops
            ops["ea_sv"] = _pad(sv, k, 0)
            ops["ea_lv"] = _pad(lv, k, 0)
            ops["ea_vg"] = _pad(vg, k, 0)
            ops["ea_w"] = _pad(w, k, 0.0)
            ops["ea_rank"] = _pad(rank, k, -1)   # -1 marks padding
            per_cell["adds"] = np.bincount(su, minlength=n_shards)
        return ops, per_cell

    # -- vectorized apply --------------------------------------------------

    def apply(self, sg, incremental: bool | None = None) -> tuple:
        """Apply every collected op; returns (new sg, AppliedUpdates).

        ``incremental=None`` (default) patches the CSR views in place
        (tombstones + staged delta blocks) when the graph carries them
        and the compaction policy allows, falling back to the eager
        ``with_csr`` rebuild otherwise; ``False`` forces the eager
        rebuild (the pre-incremental behaviour, kept for benchmarking
        and as an escape hatch)."""
        topo = bool(self._edels or self._vdels or self._eadds)
        stage = incremental is not False and topo and (
            sg.csr_perm is not None and sg.delta_count is not None
            and sg.delta_width > 0)
        ops, per_cell = self._pack_ops(sg)   # one resolve pass for both
        if stage:
            # compaction / capacity policy: O(cells) counter reads only
            # (per-cell op counts were tallied host-side while packing)
            dc = np.asarray(jax.device_get(sg.delta_count), np.int64)
            tc = np.asarray(jax.device_get(sg.tomb_count), np.int64)
            overflow = np.any(dc + per_cell["adds"] > sg.delta_width)
            crowded = np.any(
                tc + per_cell["dels"]
                > TOMBSTONE_COMPACT_FRACTION * sg.edges_per_shard)
            if (overflow or crowded) and np.any(dc + tc):
                # accumulated dirt tripped the policy: fold it out with
                # the merge compaction (views are consistent here) and
                # retry staging into the fresh delta segment — only a
                # batch too big for an *empty* segment forces the eager
                # full rebuild below
                sg = sg.with_csr()
                overflow = np.any(per_cell["adds"] > sg.delta_width)
                crowded = np.any(
                    per_cell["dels"]
                    > TOMBSTONE_COMPACT_FRACTION * sg.edges_per_shard)
            if overflow or crowded:
                stage = False
        if incremental is True and topo and not stage:
            raise ValueError(
                "incremental apply requested but the graph carries no "
                "delta-capable CSR views (call with_csr()) or the "
                "compaction policy demands a rebuild")
        new_sg, del_ok, add_ok = apply_updates(sg, ops, stage=stage)
        if add_ok is not None:
            bad = np.flatnonzero(~np.asarray(jax.device_get(add_ok)))
            if bad.size:
                j = int(bad[0])
                cell = self.ns.resolve(self._eadds[j][0])[0]
                raise RuntimeError(
                    f"compute cell {cell} has no free edge slots "
                    f"(batched edge_add #{j})"
                )
        if topo and not stage:
            # eager rebuild (compaction): apply_updates(stage=False)
            # mutated topology without patching the views, so drop them
            # first — the merge compaction must never read stale streams
            new_sg = new_sg.invalidate_csr().with_csr()
        elif stage and self._vdels:
            # vertex deletes tombstone a data-dependent number of edges
            # (every in/out edge of the victim) that the pre-apply
            # crowding bound cannot count; re-check the committed
            # counters (O(cells) scalars) so density never exceeds the
            # policy bound for longer than this one batch
            tc2 = np.asarray(jax.device_get(new_sg.tomb_count), np.int64)
            if np.any(tc2 > TOMBSTONE_COMPACT_FRACTION
                      * sg.edges_per_shard):
                new_sg = new_sg.with_csr()

        # NameServer slot release happens only after every group applied
        # cleanly: if edge adds raise (cell full), the graph is unchanged
        # and the whole batch can be retried or amended without the name
        # server having drifted from the graph.
        for gid in self._vdels:
            self.ns.release(gid)

        # edge_deletes records only ops that removed a live edge, so a
        # phantom delete is a no-op for downstream incremental repair
        # (deleting (source, source) must not invalidate the SSSP tree —
        # the source is self-parented as a sentinel).
        if del_ok is not None:
            ok_host = np.asarray(jax.device_get(del_ok))
            deleted = tuple(e for j, e in enumerate(self._edels)
                            if ok_host[j])
        else:
            deleted = ()
        applied = AppliedUpdates(
            vertex_adds=tuple(self._vadds),
            vertex_deletes=tuple(self._vdels),
            edge_adds=tuple(self._eadds),
            edge_deletes=deleted,
            touched=tuple(self._touch),
        )
        self._vadds, self._vdels = [], []
        self._eadds, self._edels, self._touch = [], [], []
        return new_sg, applied
