"""Graph containers for the diffusive-computation engine.

Two containers:

* :class:`Graph` — a flat edge-list graph with *capacity slots* so that the
  paper's dynamic primitives (edge/vertex add/delete) are O(1) functional
  updates that never change array shapes (no recompilation).
* :class:`ShardedGraph` — the graph partitioned over "compute cells" (the
  paper's CCs = mesh devices / logical shards).  Every array carries a leading
  shard axis ``S``; vertices live on exactly one shard and edges live with the
  shard that owns their *source* vertex (messages flow src -> dst, so the
  emitting side holds the edge, mirroring the paper's "computation moves to
  where the data lives").
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Graph", "ShardedGraph", "from_edges", "DEFAULT_EDGE_BLOCK",
           "DELTA_BLOCK_FRACTION", "TOMBSTONE_COMPACT_FRACTION"]

# Edge-block width of the blocked-CSR view.  128 matches the TPU lane width
# (and segment_reduce's dense-rank tile); the Pallas edge_relax kernel and
# its XLA reference both combine within blocks of exactly this many edges.
DEFAULT_EDGE_BLOCK = 128

# Delta-segment policy (DESIGN.md §2.9).  A rebuild reserves staged delta
# blocks for this fraction of the sorted stream (>= 1 block), and the
# session/update layer triggers a compacting rebuild once tombstones
# exceed the same fraction of a cell's edge slots — so the incremental
# views' extra sweep cost is bounded at ~25% while commits stay O(batch).
DELTA_BLOCK_FRACTION = 0.25
TOMBSTONE_COMPACT_FRACTION = 0.25

# with_csr() compaction switches from the full re-argsort to the staged
# merge (sorted-prefix compact + delta-only sort + searchsorted merge) once
# the per-cell stream is at least this wide: below it the merge's extra
# elementwise passes cost more than the sort they avoid.
MERGE_COMPACT_MIN_WIDTH = 4096


def default_delta_blocks(edges_per_shard: int, block: int) -> int:
    """Staged-delta capacity (in blocks) reserved by a rebuild."""
    nb = -(-edges_per_shard // block)
    return max(1, int(nb * DELTA_BLOCK_FRACTION))


def build_csr(dst_shard, dst_local, edge_ok, n_shards: int, n_per_shard: int,
              block: int):
    """Destination-sorted blocked-CSR permutation of per-shard edge slots.

    Sort key per live edge is the flat destination ``dst_shard * Np +
    dst_local`` (so one combine pass produces the whole [S, Np] message
    table); dead/padding slots sort last.  Returns

    * ``perm``  [S, Eb] int32 — sorted position -> original edge slot,
    * ``key``   [S, Eb] int32 — sorted destination key, ``-1`` on dead and
      padding positions (always trailing),

    with ``Eb`` = edge capacity rounded up to a multiple of ``block`` so
    every kernel block is fully resident.  Pure jnp — safe inside jit and
    cheap enough to rerun on every topology change.
    """
    ep = dst_shard.shape[-1]
    eb = -(-ep // block) * block
    sentinel = n_shards * n_per_shard
    key = jnp.where(edge_ok, dst_shard * n_per_shard + dst_local, sentinel)
    perm = jnp.argsort(key, axis=-1, stable=True).astype(jnp.int32)
    skey = jnp.take_along_axis(key, perm, axis=-1)
    skey = jnp.where(skey >= sentinel, -1, skey).astype(jnp.int32)
    pad = eb - ep
    if pad:
        perm = jnp.pad(perm, ((0, 0), (0, pad)))
        skey = jnp.pad(skey, ((0, 0), (0, pad)), constant_values=-1)
    return perm, skey


def build_push_csr(src_local, edge_ok, csr_perm, n_per_shard: int,
                   block: int):
    """Source-sorted blocked-CSR permutation — the "push" twin of
    :func:`build_csr`.

    Sort key per live edge is the *source* local index, so every vertex's
    out-edges form one contiguous run and a frontier's out-edge blocks
    can be gathered without touching the rest of the stream; dead/padding
    slots sort last.  Returns

    * ``perm``  [S, Eb] int32 — push position -> original edge slot,
    * ``src``   [S, Eb] int32 — sorted source local index, ``-1`` on dead
      and padding positions (always trailing),
    * ``pos``   [S, Eb] int32 — the same edge's position in the
      *destination-sorted* stream of ``csr_perm`` (``-1`` on dead/pad) —
      what lets a push sweep scatter its messages back into the dense
      stream layout so the sum monoid's fixed scan order is preserved
      bit for bit.

    ``csr_perm`` is the matching destination-sorted permutation from
    :func:`build_csr` (only its first ``Ep`` columns — the real argsort —
    are read).  Pure jnp, same cost class as the pull sort.
    """
    s_, ep = src_local.shape
    eb = -(-ep // block) * block
    key = jnp.where(edge_ok, src_local, n_per_shard)
    perm = jnp.argsort(key, axis=-1, stable=True).astype(jnp.int32)
    ssrc = jnp.take_along_axis(key, perm, axis=-1)
    ssrc = jnp.where(ssrc >= n_per_shard, -1, ssrc).astype(jnp.int32)
    # invert the destination sort: edge slot -> dense stream position
    rows = jnp.arange(s_, dtype=jnp.int32)[:, None]
    inv = jnp.zeros((s_, ep), jnp.int32).at[rows, csr_perm[:, :ep]].set(
        jnp.broadcast_to(jnp.arange(ep, dtype=jnp.int32), (s_, ep))
    )
    pos = jnp.take_along_axis(inv, perm, axis=-1)
    pos = jnp.where(ssrc >= 0, pos, -1)
    pad = eb - ep
    if pad:
        perm = jnp.pad(perm, ((0, 0), (0, pad)))
        ssrc = jnp.pad(ssrc, ((0, 0), (0, pad)), constant_values=-1)
        pos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1)
    return perm, ssrc, pos


@partial(jax.jit, static_argnames=("sw", "dwid", "ep"))
def _merge_compact_views(csr_key, csr_perm, csr_live, push_src, push_perm,  # analysis: allow(int64): traced under enable_x64 by _merge_compact — the with-block is at the call site
                         edge_ok, *, sw: int, dwid: int, ep: int):
    """Jitted body of :meth:`ShardedGraph._merge_compact` — one fused
    program per (S, width) shape, so the merge's many elementwise passes
    don't pay eager dispatch at scale.  Must be traced under ``enable_x64``
    (the composites are int64); all outputs are int32.

    XLA:CPU executes 2-D index scatters slowly, so every scatter here is a
    flattened 1-D scatter, and the (key, slot) pair is carried as the single
    int64 composite ``key * (ep + 1) + slot`` — exactly invertible by
    divmod — so each view needs only 4 scatters total."""
    s_ = edge_ok.shape[0]
    w = sw + dwid
    i32 = jnp.int32
    idx_a = jnp.arange(sw, dtype=i32)[None, :]
    idx_b = jnp.arange(dwid, dtype=i32)[None, :]
    idx_w = jnp.arange(w, dtype=i32)[None, :]
    row_off_w = (jnp.arange(s_, dtype=i32) * w)[:, None]
    row_off_ep = (jnp.arange(s_, dtype=i32) * ep)[:, None]
    oob_w = s_ * w
    oob_ep = s_ * ep
    dead_m = ~edge_ok
    slot_ids = jnp.broadcast_to(jnp.arange(ep, dtype=i32), (s_, ep))
    dead_rank = jnp.cumsum(dead_m, axis=1).astype(i32) - 1
    n_dead = jnp.sum(dead_m, axis=1).astype(i32)

    def flat_set(dest, pos, valid, row_off, oob, vals):
        """dest[s, pos] = vals where valid, via a flattened 1-D scatter."""
        flat = jnp.where(valid, pos + row_off, oob)
        return dest.reshape(-1).at[flat.reshape(-1)].set(
            vals.reshape(-1), mode="drop").reshape(dest.shape)

    def compact_merge(key, perm, live, dead_val):
        """One view's (key, perm, live-mask) -> merged (key, perm,
        [S, ep] slot -> new position inverse)."""
        comp_base = jnp.asarray(ep + 1, jnp.int64)
        big = jnp.asarray(1 << 60, jnp.int64)
        live_a = live[:, :sw] & (key[:, :sw] != dead_val)
        comp_src = jnp.where(
            live_a,
            key[:, :sw].astype(jnp.int64) * comp_base + perm[:, :sw],
            big)
        pos_a0 = jnp.cumsum(live_a, axis=1).astype(i32) - 1
        comp_a = flat_set(jnp.full((s_, sw), big, jnp.int64),
                          pos_a0, live_a,
                          (jnp.arange(s_, dtype=i32) * sw)[:, None],
                          s_ * sw, comp_src)
        n_a = jnp.sum(live_a, axis=1).astype(i32)

        live_b = live[:, sw:] & (key[:, sw:] != dead_val)
        comp_src_b = jnp.where(
            live_b,
            key[:, sw:].astype(jnp.int64) * comp_base + perm[:, sw:],
            big)
        comp_b = jnp.sort(comp_src_b, axis=1)
        n_b = jnp.sum(live_b, axis=1).astype(i32)

        ins_a = jax.vmap(jnp.searchsorted)(comp_b, comp_a).astype(i32)
        ins_b = jax.vmap(jnp.searchsorted)(comp_a, comp_b).astype(i32)
        pos_a = idx_a + ins_a
        pos_b = idx_b + ins_b

        merged = jnp.full((s_, w), big, jnp.int64)
        merged = flat_set(merged, pos_a, idx_a < n_a[:, None],
                          row_off_w, oob_w, comp_a)
        merged = flat_set(merged, pos_b, idx_b < n_b[:, None],
                          row_off_w, oob_w, comp_b)
        n_live = n_a + n_b
        live_pos = idx_w < n_live[:, None]
        new_key = jnp.where(
            live_pos, (merged // comp_base).astype(i32), dead_val)
        new_perm = jnp.where(
            live_pos, (merged % comp_base).astype(i32), 0)
        # dead slots tail the live region in ascending slot order — the
        # stable argsort's tie-break on the shared sentinel key
        dead_pos = (n_live[:, None] + dead_rank).astype(i32)
        new_perm = flat_set(new_perm, dead_pos, dead_m, row_off_w, oob_w,
                            slot_ids)
        # positions [0, n_live + n_dead) hold each slot id exactly once,
        # so the inverse is one scatter of position keyed by slot
        occupied = idx_w < (n_live + n_dead)[:, None]
        inv = flat_set(jnp.zeros((s_, ep), i32),
                       jnp.where(occupied, new_perm, ep),
                       occupied, row_off_ep, oob_ep,
                       jnp.broadcast_to(idx_w, (s_, w)))
        return new_key, new_perm, inv

    key, perm, inv = compact_merge(csr_key, csr_perm, csr_live, -1)
    psrc, pperm, pinv = compact_merge(push_src, push_perm, push_src >= 0, -1)
    ppos = jnp.where(
        psrc >= 0,
        jnp.take_along_axis(
            inv, jnp.clip(pperm, 0, ep - 1).astype(i32), axis=-1),
        -1)
    return key, perm, inv, psrc, pperm, pinv, ppos


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["src", "dst", "weight", "edge_ok", "node_ok"],
    meta_fields=["n_nodes"],
)
@dataclasses.dataclass(frozen=True)
class Graph:
    """Flat directed edge-list graph with capacity slots.

    ``src/dst/weight`` have length = edge *capacity*; slots with
    ``edge_ok == False`` are free (their src/dst are 0 and must be masked).
    Undirected graphs are stored with both directions materialized.
    """

    src: jnp.ndarray       # [Ecap] int32
    dst: jnp.ndarray       # [Ecap] int32
    weight: jnp.ndarray    # [Ecap] float32
    edge_ok: jnp.ndarray   # [Ecap] bool
    node_ok: jnp.ndarray   # [Ncap] bool
    n_nodes: int           # static vertex capacity

    @property
    def edge_capacity(self) -> int:
        return int(self.src.shape[0])

    def n_edges(self) -> jnp.ndarray:
        """Dynamic count of live edges."""
        return jnp.sum(self.edge_ok.astype(jnp.int32))

    def degrees(self) -> jnp.ndarray:
        """Out-degree per vertex (live edges only)."""
        return jax.ops.segment_sum(
            self.edge_ok.astype(jnp.int32), self.src, num_segments=self.n_nodes
        )


def from_edges(
    src: np.ndarray,
    dst: np.ndarray,
    n_nodes: int,
    weight: np.ndarray | None = None,
    edge_slack: float = 0.0,
    node_slack: float = 0.0,
) -> Graph:
    """Build a :class:`Graph` from host edge arrays, with optional slack
    capacity for dynamic updates (fraction of initial size)."""
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    e = src.shape[0]
    if weight is None:
        weight = np.ones(e, np.float32)
    weight = np.asarray(weight, np.float32)
    ecap = e + int(np.ceil(e * edge_slack))
    ncap = n_nodes + int(np.ceil(n_nodes * node_slack))
    pad = ecap - e
    return Graph(
        src=jnp.asarray(np.concatenate([src, np.zeros(pad, np.int32)])),
        dst=jnp.asarray(np.concatenate([dst, np.zeros(pad, np.int32)])),
        weight=jnp.asarray(np.concatenate([weight, np.zeros(pad, np.float32)])),
        edge_ok=jnp.asarray(
            np.concatenate([np.ones(e, bool), np.zeros(pad, bool)])
        ),
        node_ok=jnp.asarray(
            np.concatenate([np.ones(n_nodes, bool), np.zeros(ncap - n_nodes, bool)])
        ),
        n_nodes=ncap,
    )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "src_local",
        "dst_shard",
        "dst_local",
        "dst_gid",
        "weight",
        "edge_ok",
        "node_ok",
        "gid",
        "out_degree",
        "csr_perm",
        "csr_key",
        "csr_live",
        "csr_inv",
        "push_perm",
        "push_src",
        "push_pos",
        "push_inv",
        "delta_count",
        "tomb_count",
        "replica_of",
        "replica_group",
        "replica_members",
    ],
    meta_fields=["n_shards", "n_per_shard", "n_nodes", "csr_block",
                 "delta_blocks"],
)
@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """Graph partitioned over S compute cells.

    Every data array has leading shard axis ``S``.  Edge slots are padded per
    shard to the max shard edge count; ``edge_ok`` masks padding and deleted
    edges.  ``gid`` maps (shard, local) -> original vertex id; ``dst_gid`` is
    the global id of each edge's destination (used for payload messages such
    as parent pointers).

    ``csr_perm``/``csr_key`` are the destination-sorted "pull" blocked-CSR
    view (:func:`build_csr`): the per-shard edge stream sorted by
    ``(dst_shard, dst_local)`` and padded to a ``csr_block`` multiple —
    the layout the dense relaxation kernels assume.
    ``push_perm``/``push_src``/``push_pos`` are its source-sorted "push"
    twin (:func:`build_push_csr`): the same edges sorted by source local
    index, so an active frontier's out-edges live in a few contiguous
    blocks that a sparse sweep can gather without streaming the rest
    (DESIGN.md §2.8).

    **Delta-segment incremental maintenance (DESIGN.md §2.9):** both views
    carry ``delta_blocks`` staged blocks *appended after* the sorted
    stream, so topology changes never pay the O(E log E) re-sort:

    * deletes become in-place **tombstones** — ``csr_live`` drops to
      False at the edge's dense position (the structural ``csr_key`` is
      kept so the scan paths' run layout stays sorted) and ``push_src``
      drops to ``-1`` at its push position (:meth:`with_edge_tombstones`
      / :meth:`with_slot_tombstones`);
    * adds land at the next free **staged delta** position of their
      cell's delta segment, identically in both views
      (:meth:`with_staged_edges`; ``delta_count`` is the per-cell
      cursor), which the relaxation kernels consume as extra
      frontier-activated blocks;
    * ``csr_inv``/``push_inv`` map an edge slot back to its stream
      positions so a delete is an O(1) scatter;
    * a full :meth:`with_csr` rebuild ("compaction") folds tombstones
      out and delta edges into sorted position; the update layer
      triggers it when a cell's delta segment overflows or its
      ``tomb_count`` passes ``TOMBSTONE_COMPACT_FRACTION`` of its slots.

    Both views are built at partition time and patched together by
    ``UpdateBatch.apply`` and the sequential per-edge primitives;
    :meth:`invalidate_csr` remains the escape hatch that drops *both*
    views (the engines then rebuild lazily at the next diffusion), so
    ``csr_view()``/``push_view()`` raise on a graph invalidated that way
    until ``with_csr()`` is called.
    """

    src_local: jnp.ndarray   # [S, Ep] int32 — local index of the edge source
    dst_shard: jnp.ndarray   # [S, Ep] int32 — owner shard of the destination
    dst_local: jnp.ndarray   # [S, Ep] int32 — local index at the owner shard
    dst_gid: jnp.ndarray     # [S, Ep] int32 — global id of the destination
    weight: jnp.ndarray      # [S, Ep] float32
    edge_ok: jnp.ndarray     # [S, Ep] bool
    node_ok: jnp.ndarray     # [S, Np] bool
    gid: jnp.ndarray         # [S, Np] int32 — global id of each local vertex
    out_degree: jnp.ndarray  # [S, Np] int32 — live out-degree
    n_shards: int
    n_per_shard: int
    n_nodes: int             # number of real (unpadded) vertices
    csr_perm: jnp.ndarray | None = None  # [S, W] int32 stream pos -> slot
    csr_key: jnp.ndarray | None = None   # [S, W] int32 structural dst key|-1
    csr_live: jnp.ndarray | None = None  # [S, W] bool live (not tombstone)
    csr_inv: jnp.ndarray | None = None   # [S, Ep] int32 slot -> dense pos
    push_perm: jnp.ndarray | None = None  # [S, W] int32 push pos -> slot
    push_src: jnp.ndarray | None = None   # [S, W] int32 sorted src | -1
    push_pos: jnp.ndarray | None = None   # [S, W] int32 dense pos | -1
    push_inv: jnp.ndarray | None = None   # [S, Ep] int32 slot -> push pos
    delta_count: jnp.ndarray | None = None  # [S] int32 staged adds per cell
    tomb_count: jnp.ndarray | None = None   # [S] int32 tombstones per cell
    # Hub-replica ("rhizome") maps, None on unsplit graphs (DESIGN.md
    # §2.12).  A split hub occupies one *member* slot per assigned cell;
    # member 0 is the primary slot the NameServer resolves.
    replica_of: jnp.ndarray | None = None      # [S, Np] int32 hub gid at
                                               #   non-primary member slots,
                                               #   -1 elsewhere
    replica_group: jnp.ndarray | None = None   # [S, Np] int32 group index at
                                               #   every member slot, -1 else
    replica_members: jnp.ndarray | None = None  # [G, Rmax] int32 flat member
                                                #   keys (s*Np + l), member 0
                                                #   = primary, -1 = pad
    csr_block: int = DEFAULT_EDGE_BLOCK
    delta_blocks: int = -1               # staged blocks; -1 = policy default

    @property
    def edges_per_shard(self) -> int:
        return int(self.src_local.shape[1])

    # -- snapshot serialization (session durability, DESIGN.md §2.13) ------

    _META_FIELDS = ("n_shards", "n_per_shard", "n_nodes", "csr_block",
                    "delta_blocks")

    def state_dict(self) -> dict:
        """Every non-None data array by field name — the snapshot leaves.

        Both CSR views, the delta/tombstone counters, and the replica
        maps are included verbatim, so a restored graph is bitwise-equal
        *including* its incremental view state (dirty segments and all)."""
        out = {}
        for f in dataclasses.fields(self):
            if f.name in self._META_FIELDS:
                continue
            v = getattr(self, f.name)
            if v is not None:
                out[f.name] = v
        return out

    def meta_dict(self) -> dict:
        """The static geometry, JSON-ready (snapshot manifest metadata)."""
        return {name: int(getattr(self, name)) for name in self._META_FIELDS}

    @classmethod
    def from_state(cls, arrays: dict, meta: dict) -> "ShardedGraph":
        """Rebuild from :meth:`state_dict` arrays + :meth:`meta_dict`.

        ``arrays`` values may be numpy (fresh off a checkpoint) — they
        are uploaded with their saved dtypes; absent optional fields
        restore as None."""
        kw = dict(meta)
        for f in dataclasses.fields(cls):
            if f.name in cls._META_FIELDS:
                continue
            if f.name in arrays:
                kw[f.name] = jnp.asarray(arrays[f.name])
        return cls(**kw)

    @property
    def sorted_width(self) -> int:
        """Width of the *sorted* region of both views (Eb): edge capacity
        rounded up to a ``csr_block`` multiple.  The staged delta region
        occupies ``[sorted_width, sorted_width + delta_width)``."""
        return -(-self.edges_per_shard // self.csr_block) * self.csr_block

    @property
    def delta_width(self) -> int:
        """Per-cell staged-delta capacity in edge slots."""
        return max(self.delta_blocks, 0) * self.csr_block

    def with_csr(self, block: int | None = None,
                 delta_blocks: int | None = None) -> "ShardedGraph":
        """Rebuild ("compact") both blocked-CSR views from the current
        topology: tombstones fold out, staged delta edges land in sorted
        position, and a fresh (empty) delta segment of ``delta_blocks``
        staged blocks is appended to each view.

        When the graph already carries consistent views (every mutation
        patched them — the tombstone/delta invariant) and the geometry is
        unchanged, the rebuild is a *merge* (DESIGN.md §2.10): the live
        sorted prefix is already in (key, slot) order, so compaction is a
        rank/compact pass plus a sort of only the small delta segment and
        a two-way ``searchsorted`` merge — bitwise-identical output to
        the full stable argsort at a fraction of its cost.  Graphs whose
        views were dropped (:meth:`invalidate_csr`) take the full-sort
        path, which is also the only path reachable in-trace."""
        block = block or self.csr_block
        if delta_blocks is None:
            delta_blocks = self.delta_blocks
        if delta_blocks < 0:
            delta_blocks = default_delta_blocks(self.edges_per_shard, block)
        if (self.csr_perm is not None and self.delta_count is not None
                and not isinstance(self.delta_count, jax.core.Tracer)
                and block == self.csr_block
                and delta_blocks == self.delta_blocks):
            # every mutation path either patches the views and bumps a
            # counter, or drops the views entirely — so zero counters on
            # present views means they are already exactly what a rebuild
            # would produce.  Host policy read via device_get (not an
            # implicit bool()) so it stays legal under
            # jax.transfer_guard("disallow"); a traced graph skips the
            # shortcut and takes the trace-safe full-sort path below.
            dc = jax.device_get(self.delta_count)  # analysis: allow(host-sync): per-compaction policy counters, guard-legal
            tc = (jax.device_get(self.tomb_count)  # analysis: allow(host-sync): per-compaction policy counters, guard-legal
                  if self.tomb_count is not None else None)
            if not dc.any() and (tc is None or not tc.any()):  # analysis: allow(host-sync): counters already host-side (device_get above)
                return self
            if self.sorted_width >= MERGE_COMPACT_MIN_WIDTH:
                return self._merge_compact()
        s_, ep = self.src_local.shape
        perm, key = build_csr(self.dst_shard, self.dst_local, self.edge_ok,
                              self.n_shards, self.n_per_shard, block)
        pperm, psrc, ppos = build_push_csr(
            self.src_local, self.edge_ok, perm, self.n_per_shard, block)
        dw = delta_blocks * block
        if dw:
            pad = ((0, 0), (0, dw))
            perm = jnp.pad(perm, pad)
            key = jnp.pad(key, pad, constant_values=-1)
            pperm = jnp.pad(pperm, pad)
            psrc = jnp.pad(psrc, pad, constant_values=-1)
            ppos = jnp.pad(ppos, pad, constant_values=-1)
        # slot -> stream position inverses (O(batch) delete tombstoning);
        # only live slots' entries are ever read — the first ep stream
        # positions hold the real argsort, so scattering through them
        # covers every slot
        rows = jnp.arange(s_, dtype=jnp.int32)[:, None]
        pos = jnp.broadcast_to(jnp.arange(ep, dtype=jnp.int32), (s_, ep))
        inv = jnp.zeros((s_, ep), jnp.int32).at[rows, perm[:, :ep]].set(pos)
        pinv = jnp.zeros((s_, ep), jnp.int32).at[rows, pperm[:, :ep]].set(pos)
        zero = jnp.zeros((s_,), jnp.int32)
        return dataclasses.replace(
            self, csr_perm=perm, csr_key=key, csr_live=key >= 0,
            csr_inv=inv, push_perm=pperm, push_src=psrc, push_pos=ppos,
            push_inv=pinv, delta_count=zero, tomb_count=zero,
            csr_block=block, delta_blocks=delta_blocks,
        )

    def _merge_compact(self) -> "ShardedGraph":
        """Compact both views by merging instead of re-sorting.

        The sorted region's live entries are already in ascending
        ``(key, slot)`` composite order — exactly the order a stable
        argsort of the full key stream would produce (its tie-break *is*
        slot order, and slots are unique per cell) — so folding the
        tombstones out is a cumsum/scatter compact, only the delta
        segment (<= ``delta_width`` entries) is sorted, and the two
        ascending streams meet through a pair of vmapped
        ``searchsorted`` calls.  Dead slots fill the tail in ascending
        slot order, reproducing the full rebuild bit for bit.  Pure jnp
        and shape-static."""
        # the (key, slot) composites need 64-bit ints at scale; every
        # *stored* array stays int32/bool — only jitted intermediates are
        # wide, so the x64 flag never leaks outside this call
        with jax.enable_x64():
            key, perm, inv, psrc, pperm, pinv, ppos = _merge_compact_views(
                self.csr_key, self.csr_perm, self.csr_live,
                self.push_src, self.push_perm, self.edge_ok,
                sw=self.sorted_width, dwid=self.delta_width,
                ep=self.edges_per_shard)
        zero = jnp.zeros((self.src_local.shape[0],), jnp.int32)
        return dataclasses.replace(
            self, csr_perm=perm, csr_key=key, csr_live=key >= 0,
            csr_inv=inv, push_perm=pperm, push_src=psrc, push_pos=ppos,
            push_inv=pinv, delta_count=zero, tomb_count=zero,
        )

    def layout_bytes(self) -> dict:
        """Host-side accounting of the device layout's byte footprint.

        ``edge_stream`` is the per-slot edge fields, ``csr_views`` both
        blocked-CSR views (and their inverses/counters), ``node`` the
        vertex-slot arrays.  ``live_edge_bytes`` is the floor: live
        edges x bytes-per-edge-slot — the degree-aware capacity model
        keeps ``edge_stream`` within ~2x of it even on skewed families
        (DESIGN.md §2.10)."""
        def nbytes(*arrays):
            return int(sum(a.size * a.dtype.itemsize
                           for a in arrays if a is not None))

        edge_stream = nbytes(self.src_local, self.dst_shard, self.dst_local,
                             self.dst_gid, self.weight, self.edge_ok)
        slot_bytes = edge_stream // max(1, self.n_shards
                                        * self.edges_per_shard)
        live_edges = int(jnp.sum(self.edge_ok))
        return {
            "edge_stream": edge_stream,
            "csr_views": nbytes(self.csr_perm, self.csr_key, self.csr_live,
                                self.csr_inv, self.push_perm, self.push_src,
                                self.push_pos, self.push_inv,
                                self.delta_count, self.tomb_count),
            "node": nbytes(self.node_ok, self.gid, self.out_degree),
            "live_edges": live_edges,
            "live_edge_bytes": live_edges * slot_bytes,
            "total": edge_stream + nbytes(
                self.csr_perm, self.csr_key, self.csr_live, self.csr_inv,
                self.push_perm, self.push_src, self.push_pos, self.push_inv,
                self.delta_count, self.tomb_count, self.node_ok, self.gid,
                self.out_degree),
        }

    def invalidate_csr(self) -> "ShardedGraph":
        """Drop both CSR views without paying the re-sorts — the escape
        hatch for callers that batch many mutations outside the
        tombstone/delta patch path.  The rebuild happens in-trace on a
        local copy — an invalidated graph re-sorts on *every* diffusion
        until the caller persists it with :meth:`with_csr`; the batched
        ``UpdateBatch.apply`` and the per-edge primitives instead patch
        the views in place (tombstones + staged deltas) so mutated
        graphs never carry that recurring cost.  Pull and push views are
        always dropped together — a graph can never carry one stale
        view."""
        return dataclasses.replace(self, csr_perm=None, csr_key=None,
                                   csr_live=None, csr_inv=None,
                                   push_perm=None, push_src=None,
                                   push_pos=None, push_inv=None,
                                   delta_count=None, tomb_count=None)

    # -- incremental view maintenance (DESIGN.md §2.9) --------------------

    def with_edge_tombstones(self, shard, slot, ok) -> "ShardedGraph":
        """Tombstone K edges at ``(shard, slot)`` (``ok`` masks no-ops) in
        both views: O(K) scatters through the slot->position inverses.
        The dense position keeps its structural ``csr_key`` (the scan
        paths' run layout stays sorted) and drops ``csr_live``; the push
        position drops ``push_src`` to ``-1`` (its own validity
        sentinel)."""
        ep = self.edges_per_shard
        w = self.csr_key.shape[-1]
        sl = jnp.clip(slot, 0, ep - 1)
        dpos = jnp.where(ok, self.csr_inv[shard, sl], w)
        ppos = jnp.where(ok, self.push_inv[shard, sl], w)
        return dataclasses.replace(
            self,
            csr_live=self.csr_live.at[shard, dpos].set(False, mode="drop"),
            push_src=self.push_src.at[shard, ppos].set(-1, mode="drop"),
            tomb_count=self.tomb_count.at[shard].add(
                ok.astype(jnp.int32), mode="drop"),
        )

    def with_slot_tombstones(self, dead) -> "ShardedGraph":
        """Tombstone every edge slot in the ``dead`` [S, Ep] mask (the
        vertex-delete path, where the doomed set is discovered as a
        mask): one O(E) elementwise pass over both views, no sort."""
        at_dense = jnp.take_along_axis(
            dead, jnp.clip(self.csr_perm, 0, self.edges_per_shard - 1),
            axis=-1)
        newly = self.csr_live & at_dense
        at_push = jnp.take_along_axis(
            dead, jnp.clip(self.push_perm, 0, self.edges_per_shard - 1),
            axis=-1) & (self.push_src >= 0)
        return dataclasses.replace(
            self,
            csr_live=self.csr_live & ~at_dense,
            push_src=jnp.where(at_push, -1, self.push_src),
            tomb_count=self.tomb_count
            + jnp.sum(newly, axis=-1).astype(jnp.int32),
        )

    def with_staged_edges(self, shard, slot, src_local, dst_key, rank,
                          ok) -> "ShardedGraph":
        """Stage K freshly-written edges (``(shard, slot)`` already hold
        their fields) into the delta segment of both views: position =
        ``sorted_width + delta_count[shard] + rank`` (``rank`` = the
        op's index among this batch's adds to the same cell).  O(K)
        scatters; the caller must have checked capacity
        (``delta_count + adds-per-cell <= delta_width``)."""
        es = self.sorted_width
        w = self.csr_key.shape[-1]
        ep = self.edges_per_shard
        dpos = jnp.where(ok, es + self.delta_count[shard] + rank, w)
        islot = jnp.where(ok, slot, ep)
        i32 = jnp.int32
        return dataclasses.replace(
            self,
            csr_perm=self.csr_perm.at[shard, dpos].set(
                slot.astype(i32), mode="drop"),
            csr_key=self.csr_key.at[shard, dpos].set(
                dst_key.astype(i32), mode="drop"),
            csr_live=self.csr_live.at[shard, dpos].set(True, mode="drop"),
            csr_inv=self.csr_inv.at[shard, islot].set(
                dpos.astype(i32), mode="drop"),
            push_perm=self.push_perm.at[shard, dpos].set(
                slot.astype(i32), mode="drop"),
            push_src=self.push_src.at[shard, dpos].set(
                src_local.astype(i32), mode="drop"),
            push_pos=self.push_pos.at[shard, dpos].set(
                dpos.astype(i32), mode="drop"),
            push_inv=self.push_inv.at[shard, islot].set(
                dpos.astype(i32), mode="drop"),
            delta_count=self.delta_count.at[shard].add(
                ok.astype(i32), mode="drop"),
        )

    def csr_view(self) -> dict:
        """The destination-sorted edge streams the relax backends consume.

        [S, W] gathers of the edge fields through ``csr_perm`` (W =
        sorted region + staged delta segment); positions with
        ``csr_key == -1`` (dead / padding / tombstoned / free delta)
        carry garbage and must be masked by the key.  ``csr_key`` here is
        the *live-masked* key (tombstones read ``-1``); ``csr_skey``
        keeps the structural sorted key so the scan paths'
        ``searchsorted`` run layout survives tombstoning (the delta
        segment of ``csr_skey`` is unsorted — the kernels consume it
        through a separate scatter pass, never the scan).  ``csr_src_gid``
        is the source vertex's gid at each position, ``gid[csr_src]``
        per cell: structural, so a sweep reads it as a stream instead of
        gathering ``gid`` (the engines keep it only for programs that
        read the source gid, :meth:`~.programs.VertexProgram.
        source_reads`).
        """
        if self.csr_perm is None:
            raise ValueError("ShardedGraph has no CSR view; call with_csr()")
        take = lambda a: jnp.take_along_axis(a, self.csr_perm, axis=-1)
        src = take(self.src_local)
        return {
            "csr_key": jnp.where(self.csr_live, self.csr_key, -1),
            "csr_skey": self.csr_key,
            "csr_src": src,
            "csr_src_gid": jax.vmap(lambda g, s: g[s])(self.gid, src),
            "csr_weight": take(self.weight),
            "csr_dst_gid": take(self.dst_gid),
        }

    def push_view(self) -> dict:
        """The source-sorted edge streams the push sweep consumes.

        [S, W] gathers of the edge fields through ``push_perm``;
        positions with ``push_src == -1`` (dead / padding / tombstoned)
        carry garbage and must be masked.  ``push_pos`` maps each push
        position back to its slot in the destination-sorted stream of
        :meth:`csr_view` (staged delta edges map to their own delta
        position — the two views stage identically).
        """
        if self.push_perm is None:
            raise ValueError("ShardedGraph has no push view; call with_csr()")
        take = lambda a: jnp.take_along_axis(a, self.push_perm, axis=-1)
        key = take(self.dst_shard) * self.n_per_shard + take(self.dst_local)
        return {
            "push_src": self.push_src,
            "push_key": jnp.where(self.push_src >= 0, key, -1),
            "push_weight": take(self.weight),
            "push_dst_gid": take(self.dst_gid),
            "push_pos": self.push_pos,
        }

    def n_edges(self) -> jnp.ndarray:
        # int32 accumulator on purpose: without enable_x64 a jnp.int64
        # cast silently degrades to 32-bit anyway, and edge-slot counts
        # fit int32 at every scale this layout can hold in memory
        return jnp.sum(self.edge_ok.astype(jnp.int32))

    def scatter_from_global(self, values: jnp.ndarray, owner, local, fill=0):
        """Map a [n_nodes] global array to [S, Np] shard layout."""
        out = jnp.full((self.n_shards, self.n_per_shard), fill, values.dtype)
        return out.at[owner, local].set(values)

    def gather_to_global(self, values: jnp.ndarray, owner, local):
        """Map a [S, Np] shard-layout array back to [n_nodes] global order."""
        return values[owner, local]
