"""DiffusionSession — one message-driven front door for the whole system.

The paper's thesis is that static queries, graph mutation, and incremental
recomputation belong to **one** programming model (diffusive computation),
not three code paths.  The session realizes that (DESIGN.md §2.4):

* it owns the :class:`ShardedGraph`, the :class:`NameServer` (the paper's
  hardware name server), and cached per-program vertex state;
* **queries** go through one interface — ``session.query("sssp",
  source=0)`` — for every registered program (SSSP / BFS / CC / PPR /
  PageRank / triangle counting), on any execution backend
  (``engine="sharded" | "event" | "spmd"``);
* **mutations** accumulate in an :class:`UpdateBatch` (the seven
  primitives of §VI, batched) and land with ``session.commit()``, which
  applies them as **one compiled, device-resident scatter program** that
  patches the blocked-CSR views in place (tombstones + staged delta
  blocks — O(batch), no stream re-sort; DESIGN.md §2.9) and then
  *repairs* every cached program by re-diffusing only the affected
  frontier — the generic form of the paper's dynamic-graph processing.
  ``max_cache_entries=`` bounds the query cache with LRU eviction for
  long-running streaming sessions.

Repair strategies (per registered program, picked to reproduce the
from-scratch fixed point exactly):

* ``parents``   — shortest-path trees: deleted tree edges invalidate
  their downstream subtree via parent-pointer chasing through the global
  namespace, then every still-finite vertex re-emits once (SSSP).
* ``component`` — label diffusions: deletes reset every vertex of the
  affected components to its init label; all live vertices re-emit (CC).
* ``restart``   — residual-push programs (PPR / PageRank): their
  finite-eps fixed point is push-order-dependent, so only a fresh
  diffusion reproduces the from-scratch bits; insert-only traffic on
  monotone programs still takes the warm frontier path.

Engine matrix (DESIGN.md §2.5): ``sharded`` is the bulk-asynchronous
logical engine (default, any program); ``spmd`` shard_maps one compute
cell per mesh device (any program, needs >= n_cells devices; a session
built with ``engine="spmd"`` keeps every cell's arrays on its own
device, placed again after each commit or compaction); ``event``
is the message-at-a-time host oracle with real Dijkstra–Scholten
termination — a generic interpreter runs any registered program, with
handwritten fast oracles for SSSP/BFS.

Programs are declarative, user-registrable specs (programs.py, DESIGN.md
§2.7); ``query`` accepts registry names, ``@diffusive`` handles, bound
queries, or raw lowered programs, and a pluralized lane param
(``sources=[...]``) fans out into multi-query lanes of one diffusion
with per-lane cache entries.

Orthogonally, ``backend="xla" | "pallas"`` (DESIGN.md §2.6) picks the
relaxation-kernel implementation inside the sharded/spmd engines, and
``sweep="pull" | "push" | "auto"`` (DESIGN.md §2.8) the sweep direction —
dense destination-sorted pull, frontier-compacted source-sorted push, or
the per-round direction selector.  Every combination produces
bitwise-identical fixed points, so both are pure execution choices;
commit()-time repairs resume from tiny frontiers and therefore default
to the push sweep.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import warnings
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import chaos
from . import trace
from .diffuse import (
    DiffuseStats,
    diffuse,
    diffuse_from,
    engine_views,
    exact_streams_for,
    initial_state,
    logical_view,
    make_spmd_diffuse,
    on_mesh,
    spmd_exchange_bytes,
)
from .dynamic import NameServer, _invalidate_subtrees
from .graph import ShardedGraph, from_edges
from .journal import OpRecord, UpdateJournal
from .partition import Partitioned, ReplicaInfo, partition
from .relax import RELAX_BACKENDS, RELAX_SWEEPS
from .programs import (
    PROGRAMS,
    BoundQuery,
    ProgramHandle,
    ProgramSpec,
    VertexProgram,
    freeze_kwargs,
    make_laned,
    register_program,
)
from .updates import AppliedUpdates, UpdateBatch

__all__ = [
    "DiffusionSession",
    "ProgramSpec",
    "Result",
    "register_program",
    "PROGRAMS",
    "ConvergenceError",
    "ConvergenceWarning",
    "ValidationError",
    "JournalReplayError",
]

ENGINES = ("sharded", "event", "spmd")
ON_BUDGET = ("raise", "warn", "partial")

SNAPSHOT_FORMAT = 1
_JOURNAL_FILE = "journal.bin"


def _json_np(o):
    """json.dumps fallback: numpy scalars in cached query kwargs."""
    if isinstance(o, np.generic):
        return o.item()
    raise TypeError(f"not JSON-serializable: {type(o).__name__}")


def _host_build():
    """Build arrays on the host's CPU device when the process has one
    besides its accelerators, so that no accelerator holds what is built
    there; a no-op otherwise."""
    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


class ConvergenceError(RuntimeError):
    """A diffusion hit its max_rounds budget before quiescence
    (``on_budget="raise"``)."""


class ConvergenceWarning(UserWarning):
    """Budget-exhaustion warning (``on_budget="warn"``, the default)."""


class ValidationError(RuntimeError):
    """A query result violated its program's Field schema (``validate=``):
    NaN in a float field, or a value outside the field's domain."""


class JournalReplayError(RuntimeError):
    """Journal replay diverged from the snapshot (e.g. a replayed vertex
    allocation produced a different id) — the store is inconsistent."""


class Result(NamedTuple):
    values: np.ndarray          # per-vertex result in global vertex order
    stats: Any                  # DiffuseStats | EventStats | None (cached)
    extra: dict


def _event_sssp(session, source: int = 0, unit_weights: bool = False,
                **_):
    from .event import build_adjacency, event_sssp

    src, dst, w = session.edge_list()
    if unit_weights:
        w = np.ones_like(w)
    n = session.n_ids
    dist, st = event_sssp(build_adjacency(src, dst, w, n), n, source)
    return np.array(dist), st


def _run_triangles(session, engine=None, **kwargs):
    from .triangles import triangle_count_bitset

    src, dst, _ = session.edge_list()
    count = int(triangle_count_bitset(src, dst, session.n_ids))
    return Result(values=np.array(count), stats=None,
                  extra={"triangles": count})


# The diffusive programs register themselves in programs.py via the
# @diffusive decorator; here we attach the session-level extras the
# decorator cannot know about — the host event-engine oracles and the
# non-diffusive custom queries.
PROGRAMS["sssp"] = PROGRAMS["sssp"]._replace(event_fn=_event_sssp)
PROGRAMS["bfs"] = PROGRAMS["bfs"]._replace(
    event_fn=lambda session, **kw: _event_sssp(session, unit_weights=True,
                                               **kw))
register_program(ProgramSpec(
    "triangles", None, "", run_fn=_run_triangles,
))


@dataclasses.dataclass
class _Entry:
    """One cached (program, kwargs) fixed point."""

    spec: ProgramSpec
    prog: VertexProgram | None
    value_key: str
    kwargs: dict
    vstate: Any
    stats: Any
    engine: str
    backend: str = "xla"
    delta: float | None = None   # delta-stepping gate, kept across repairs
    sweep: str | None = None     # explicit sweep knob; None = defaulted
                                 #   (queries use the session's, repairs
                                 #   default to the push sweep)
    raw: Any = None              # run_fn programs (triangles): the cached
                                 #   Result itself; repaired by recount


class CommitInfo(NamedTuple):
    applied: AppliedUpdates
    repairs: dict               # query key -> (strategy, stats)


class DiffusionSession:
    """Stateful front door: build once, query / mutate / commit forever."""

    def __init__(self, part: Partitioned, ns: NameServer | None = None,
                 engine: str = "sharded", backend: str = "xla",
                 sweep: str = "pull", max_local_iters: int = 64,
                 max_rounds: int = 10_000,
                 max_cache_entries: int | None = None,
                 on_budget: str = "warn", validate: bool = False,
                 journal_fsync: str = "always", snapshot_keep: int = 3):
        if engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, "
                             f"got {engine!r}")
        if backend not in RELAX_BACKENDS:
            raise ValueError(f"backend must be one of {RELAX_BACKENDS}, "
                             f"got {backend!r}")
        if sweep not in RELAX_SWEEPS:
            raise ValueError(f"sweep must be one of {RELAX_SWEEPS}, "
                             f"got {sweep!r}")
        if max_cache_entries is not None and max_cache_entries < 1:
            raise ValueError("max_cache_entries must be >= 1 (or None "
                             "for an unbounded cache)")
        if on_budget not in ON_BUDGET:
            raise ValueError(f"on_budget must be one of {ON_BUDGET}, "
                             f"got {on_budget!r}")
        self.part = part
        self._ns = ns                # lazily built: queries don't need one
        self.engine = engine
        self.backend = backend
        self.sweep = sweep
        self.max_local_iters = max_local_iters
        self.max_rounds = max_rounds
        # LRU query cache: a long-running streaming session sees an
        # unbounded stream of (program, source, backend, sweep) variants;
        # max_cache_entries bounds the retained fixed points — an evicted
        # entry simply recomputes on its next query and is no longer
        # repaired by commit().  Insertion order doubles as recency
        # (hits reinsert).
        self.max_cache_entries = max_cache_entries
        # convergence watchdog (DESIGN.md §2.13): what to do when a
        # diffusion exhausts max_rounds before quiescence, and whether to
        # schema-check results against each program's Field domains
        self.on_budget = on_budget
        self.validate = validate
        self._cache: dict[tuple, _Entry] = {}
        self._pending: UpdateBatch | None = None
        self._spmd_fns: dict = {}
        # spmd engine: one mesh per session, and the engine-facing views
        # of ``_views_of`` (a graph version) kept on it between dispatches
        self._mesh = None
        self._views: dict | None = None
        self._views_of: ShardedGraph | None = None
        # durability (DESIGN.md §2.13): armed by save()/open()
        self._dur_dir: str | None = None
        self._ckpt = None                       # CheckpointManager
        self._journal: UpdateJournal | None = None
        self._journal_fsync = journal_fsync
        self._snapshot_keep = snapshot_keep
        if engine == "spmd":
            self._spmd_mesh()
            self._set_graph(part.sg)

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def from_edges(cls, src, dst, n_nodes: int, weight=None,
                   n_cells: int = 4, strategy: str = "block",
                   edge_slack: float = 0.0, node_slack: float = 0.0,
                   engine: str = "sharded",
                   replica_threshold: int | str | None = None,
                   **kw) -> "DiffusionSession":
        """Build + partition a graph over n_cells compute cells.

        ``edge_slack`` / ``node_slack`` reserve free capacity slots per
        cell for the dynamic primitives (paper §VI).
        ``replica_threshold`` enables skew-aware hub splitting
        (rhizomes, DESIGN.md §2.12): ``"auto"`` or an int degree bound.

        With ``engine="spmd"`` the graph is built on the host's CPU device
        and then placed one cell per accelerator, so no accelerator ever
        holds the whole graph."""
        with _host_build() if engine == "spmd" else contextlib.nullcontext():
            g = from_edges(src, dst, n_nodes, weight,
                           edge_slack=edge_slack, node_slack=node_slack)
            part = partition(g, n_cells, strategy=strategy,
                             replica_threshold=replica_threshold)
        return cls(part, engine=engine, **kw)

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    @property
    def sg(self):
        return self.part.sg

    @property
    def ns(self) -> NameServer:
        """The global namespace (built on first mutation/resolution)."""
        if self._ns is None:
            self._ns = NameServer(self.part)
        return self._ns

    @property
    def n_cells(self) -> int:
        return self.sg.n_shards

    @property
    def n_ids(self) -> int:
        """Size of the global id space (capacity + dynamically added)."""
        if self._ns is not None:
            return int(self._ns.owner.shape[0])
        return int(self.part.owner.shape[0])

    def _layout(self):
        if self._ns is not None:
            return self._ns.owner, self._ns.local
        return (trace.to_host(self.part.owner, "layout"),
                trace.to_host(self.part.local, "layout"))

    def to_global(self, values) -> np.ndarray:
        """[S, Np] shard layout -> [n_ids] gid order (via the name server,
        so dynamically added vertices resolve too).

        Dead ids (free capacity slots, deleted vertices) keep a stale
        slot mapping and may alias a live vertex's value — mask with
        :meth:`live_ids` when iterating the full id space."""
        owner, local = self._layout()
        return trace.to_host(values, "to_global")[owner, local]

    def live_ids(self) -> np.ndarray:
        """[n_ids] bool: ids currently naming a live vertex."""
        owner, local = self._layout()
        ok = trace.to_host(self.sg.node_ok, "live_ids")[owner, local]
        gid = trace.to_host(self.sg.gid, "live_ids")[owner, local]
        return ok & (gid == np.arange(owner.shape[0]))

    def edge_list(self):
        """Host copy of the live edge set as (src_gid, dst_gid, weight)."""
        sg = self.sg
        ok = np.asarray(sg.edge_ok)
        src_gid = np.asarray(sg.gid)[
            np.arange(sg.n_shards)[:, None], np.asarray(sg.src_local)
        ]
        return (src_gid[ok].astype(np.int32),
                np.asarray(sg.dst_gid)[ok].astype(np.int32),
                np.asarray(sg.weight)[ok].astype(np.float32))

    # ------------------------------------------------------------------
    # static queries
    # ------------------------------------------------------------------

    def _key(self, name: str, engine: str, kwargs: dict,
             backend: str = "xla", delta: float | None = None,
             sweep: str = "pull") -> tuple:
        # freeze_kwargs canonicalizes unhashable values (list-valued
        # ``sources`` etc.) into deterministic tuples
        key = (name, engine, freeze_kwargs(kwargs))
        # default (xla, ungated, pull) keys stay in the PR-1 shape so
        # adopt()/peek() callers keep working; variants get suffixed keys.
        # sweep variants are bitwise-identical fixed points, but they key
        # separately like backend so a caller can hold both warm.
        if backend != "xla":
            key = key + (backend,)
        if delta is not None:
            key = key + (("delta", delta),)
        if sweep != "pull":
            key = key + (("sweep", sweep),)
        if self.sg.replica_members is not None:
            # hub-replica graphs hold the same fixed points only up to
            # FP reassociation for sum monoids — keep their entries
            # distinct from an unsplit graph a caller might adopt() into
            key = key + (("replicas",),)
        return key

    def _cache_get(self, key) -> _Entry | None:
        """Cache lookup that refreshes recency (LRU via insertion order)."""
        entry = self._cache.pop(key, None)
        if entry is not None:
            self._cache[key] = entry
        return entry

    def _cache_put(self, key, entry: _Entry):
        """Insert most-recent; evict the least-recently-used entries
        beyond ``max_cache_entries`` (evictees just recompute on their
        next query and stop being repaired by commit())."""
        self._cache.pop(key, None)
        self._cache[key] = entry
        if self.max_cache_entries is not None:
            while len(self._cache) > self.max_cache_entries:
                self._cache.pop(next(iter(self._cache)))

    def _resolve(self, prog, kwargs: dict):
        """One registry path for every way of naming a program — a
        registry string, a :class:`ProgramHandle` (``sssp``), a
        :class:`BoundQuery` (``sssp(source=3)``), or a raw lowered
        :class:`VertexProgram` — used by ``query`` and ``peek`` alike.
        Returns (spec, name, merged kwargs, adhoc VertexProgram | None).
        """
        if isinstance(prog, VertexProgram):
            return None, None, kwargs, prog
        if isinstance(prog, BoundQuery):
            name, kwargs = prog.name, {**prog.kwargs, **kwargs}
        elif isinstance(prog, ProgramHandle):
            name = prog.name
        else:
            name = prog
        if name not in PROGRAMS:
            raise KeyError(
                f"unknown program {name!r}; registered: "
                f"{sorted(PROGRAMS)} (@diffusive or register_program to "
                f"add)")
        return PROGRAMS[name], name, kwargs, None

    def query(self, prog, engine: str | None = None,
              backend: str | None = None, sweep: str | None = None,
              refresh: bool = False, value_key: str | None = None,
              delta: float | None = None, validate: bool | None = None,
              **kwargs):
        """Run (or serve from cache) a named or ad-hoc vertex program.

        ``prog`` is a registry name ("sssp", "cc", "ppr", "pagerank",
        "bfs", "widest", "reach", "triangles", ...), a program handle or
        bound query from the :func:`~.programs.diffusive` decorator
        (``query(sssp(source=3))``), or a raw :class:`VertexProgram`
        (then ``value_key`` selects the result field).
        ``sharded``/``spmd`` fixed points are cached and repaired
        incrementally by later ``commit()`` calls; ``event`` (the host
        oracle) and custom ``run_fn`` queries recompute on every call —
        they always see the current graph and hold no device state to
        repair.

        **Multi-query lanes:** pluralizing a program's lane param —
        ``query("sssp", sources=[s0, s1, ...])`` or
        ``query(sssp(sources=[...]))`` — runs all B queries as lanes of a
        *single* diffusion (one edge sweep per sub-iteration serves every
        lane) and returns a list of per-source Results.  Each lane's
        fixed point is bitwise-identical to the corresponding
        single-source query, and each is cached under its single-source
        key, so later ``commit()`` repairs and ``peek``/``query`` hits
        treat lanes exactly like individually-issued queries.

        ``backend`` picks the relaxation kernel ("xla" | "pallas") and
        ``sweep`` the direction ("pull" | "push" | "auto" — dense,
        frontier-compacted, or per-round selected; all bitwise-identical);
        ``delta`` enables the delta-stepping priority gate for programs
        with a priority, and is remembered so commit()'s incremental
        repair re-diffuses under the same gate.

        A diffusion that exhausts ``max_rounds`` before quiescence
        surfaces ``stats.converged == False`` and triggers the session's
        ``on_budget`` policy ("raise" | "warn" | "partial").
        ``validate=`` (per-call override of the session default) checks
        the returned values against the program's Field schema — NaN and
        out-of-domain values on live vertices raise
        :class:`ValidationError` (DESIGN.md §2.13).

        Each call is the host span ``repro.query``; a diffusion inside it
        runs the stages ``prepare``, ``dispatch``, ``budget`` and
        ``result`` (:mod:`~.trace`).
        """
        with trace.stage(trace.QUERY):
            return self._query(prog, engine, backend, sweep, refresh,
                               value_key, delta, validate, kwargs)

    def _query(self, prog, engine, backend, sweep, refresh, value_key,
               delta, validate, kwargs):
        engine = engine or self.engine
        explicit_backend = backend
        backend = backend or self.backend
        explicit_sweep = sweep
        sweep = sweep or self.sweep
        if engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, "
                             f"got {engine!r}")
        if backend not in RELAX_BACKENDS:
            raise ValueError(f"backend must be one of {RELAX_BACKENDS}, "
                             f"got {backend!r}")
        if sweep not in RELAX_SWEEPS:
            raise ValueError(f"sweep must be one of {RELAX_SWEEPS}, "
                             f"got {sweep!r}")
        if delta is not None and engine != "sharded":
            raise ValueError(
                "delta-stepping is only gated on engine='sharded'; the "
                f"{engine!r} engine would silently run ungated")
        if explicit_backend is not None and engine == "event":
            raise ValueError(
                "the event oracle runs on the host and has no relax "
                "backend; backend= would be silently ignored")
        if explicit_sweep is not None and engine == "event":
            raise ValueError(
                "the event oracle runs on the host and has no sweep "
                "direction; sweep= would be silently ignored")

        spec, name, kwargs, adhoc = self._resolve(prog, kwargs)
        if adhoc is not None:
            if value_key is None:
                raise ValueError("value_key= is required for a raw "
                                 "VertexProgram")
            spec = ProgramSpec(f"adhoc:{id(adhoc)}", lambda: adhoc,
                               value_key)
            name = spec.name
        elif spec.run_fn is not None:
            # custom (non-diffusive) queries go through the same cache /
            # commit()-repair door: the Result is cached whole and
            # repaired by a restart-style rerun ("recount") on commit
            if (explicit_backend is not None or explicit_sweep is not None
                    or delta is not None):
                raise ValueError(
                    f"{name!r} is a custom run_fn query with no "
                    f"relaxation sweep; backend=/sweep=/delta= would be "
                    f"silently ignored")
            key = self._key(name, engine, kwargs)
            if not refresh:
                hit = self._cache_get(key)
                if hit is not None:
                    return hit.raw
            res = spec.run_fn(self, engine=engine, **kwargs)
            self._cache_put(key, _Entry(spec, None, spec.value_key,
                                        dict(kwargs), None, res.stats,
                                        engine, raw=res))
            return res

        lane_kw = spec.lane_param + "s" if spec.lane_param else None
        if lane_kw and lane_kw in kwargs:
            lane_vals = list(kwargs.pop(lane_kw))
            return self._query_lanes(spec, name, lane_vals, kwargs, engine,
                                     backend, refresh, delta, value_key,
                                     sweep, explicit_sweep, validate)

        key = self._key(name, engine, kwargs, backend, delta, sweep)
        if not refresh:
            hit = self._cache_get(key)
            if hit is not None:
                with trace.stage(trace.QUERY_RESULT):
                    res = self._result(hit)
                    # re-validate on every serve: a poisoned cached state
                    # (chaos.poison_vstate, a bad repair) is caught at
                    # read time, not just at compute time
                    self._maybe_validate(hit, res, validate,
                                         f"query {name!r} (cached)")
                return res

        if engine == "event":
            if spec.event_fn is not None:
                values, st = spec.event_fn(self, **kwargs)
            elif spec.factory is not None:
                # generic oracle: any @diffusive program runs
                # message-at-a-time on the host (event.py)
                from .event import event_diffuse

                program = (adhoc if adhoc is not None
                           else spec.factory(**kwargs))
                src, dst, w = self.edge_list()
                state, st = event_diffuse(program, src, dst, w, self.n_ids,
                                          node_ok=self.live_ids())
                vk = value_key or spec.value_key
                values = state[vk]
            else:
                raise ValueError(
                    f"program {name!r} has no event-engine oracle and no "
                    f"factory; use engine='sharded' or 'spmd'")
            return Result(values=values, stats=st,
                          extra={"live": self.live_ids()})

        with trace.stage(trace.QUERY_PREPARE):
            program = adhoc if adhoc is not None else spec.factory(**kwargs)
            run = self._prepare_diffusion(program, engine, backend, delta,
                                          sweep)
        with trace.stage(trace.QUERY_DISPATCH):
            vstate, stats = run()
        vk = value_key or spec.value_key
        entry = _Entry(spec, program, vk, dict(kwargs), vstate, stats,
                       engine, backend=backend, delta=delta,
                       sweep=explicit_sweep)
        self._cache_put(key, entry)
        with trace.stage(trace.QUERY_BUDGET):
            self._enforce_budget(stats, f"query {name!r}")
            if engine == "spmd":
                self._count_exchange(program, stats)
        with trace.stage(trace.QUERY_RESULT):
            res = self._result(entry)
            self._maybe_validate(entry, res, validate, f"query {name!r}")
        return res

    def _compact_for(self, program: VertexProgram | None):
        """Sum-combine diffusions must see compacted (delta-free) streams
        to stay bitwise-equal to a full rebuild (DESIGN.md §2.9) —
        delegate the policy to :func:`~.diffuse.exact_streams_for` and
        *persist* its result, so every later query and repair reuses the
        clean graph instead of re-sorting per call; min/max programs
        consume the incremental views directly and come back unchanged."""
        if program is not None:
            sg = exact_streams_for(self.sg, program)
            if sg is not self.sg:
                self._set_graph(sg)

    # -- the spmd engine's mesh and chip-resident cells ----------------------

    def _spmd_mesh(self):
        """The session's mesh, one compute cell per device (built once)."""
        if self._mesh is None:
            from ..launch.mesh import make_mesh

            S, devices = self.n_cells, jax.devices()
            if len(devices) < S:
                raise RuntimeError(
                    f"engine='spmd' needs >= {S} devices (one per compute "
                    f"cell); this process has {len(devices)}. Set "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{S} before importing jax, or use engine='sharded'.")
            self._mesh = make_mesh((S,), ("cells",), devices=devices[:S])
        return self._mesh

    def _set_graph(self, sg: ShardedGraph) -> None:
        """Adopt a new graph version (construction, commit, compaction).
        On an ``engine="spmd"`` session it is placed one cell per device,
        with its pull views, in the host span ``repro.place``: every
        array of cell s lives on device s from here on, so a dispatch
        moves no edge bytes between chips."""
        if self.engine != "spmd":
            self.part.sg = sg
            return
        with trace.stage(trace.PLACE):
            self.part.sg = dataclasses.replace(
                sg, **on_mesh(self._spmd_mesh(), sg.state_dict()))
            self._place_pull_views()

    def _place_pull_views(self) -> None:
        self._views = on_mesh(self._spmd_mesh(), engine_views(self.sg))
        self._views_of = self.sg

    def _spmd_views(self, with_push: bool) -> dict:
        """The engine-facing dict of the current graph on the mesh, built
        once per graph version; the push streams join it the first time
        a push or auto sweep needs them (each build a ``repro.place``)."""
        if self._views_of is not self.sg:
            with trace.stage(trace.PLACE):
                self._place_pull_views()
        if with_push and "push_src" not in self._views:
            with trace.stage(trace.PLACE):
                self._views = dict(self._views, **on_mesh(
                    self._spmd_mesh(),
                    engine_views(self.sg, pull=False, push=True)))
        if with_push:
            return self._views
        return {k: v for k, v in self._views.items()
                if not k.startswith("push_")}

    def _prepare_diffusion(self, program: VertexProgram, engine: str,
                           backend: str, delta, sweep: str = "pull",
                           state=None):
        """Compact, build the initial state (or take ``state``, a
        ``(vstate, active)`` to resume from) and, on spmd, the jitted
        shard_map function, and return the device call that runs the
        diffusion: ``() -> (vstate, stats)``."""
        self._compact_for(program)
        sg = self.sg
        vstate0, active0 = (initial_state(sg, program) if state is None
                            else state)
        if engine == "sharded":
            return lambda: diffuse_from(
                sg, program, vstate0, active0,
                max_local_iters=self.max_local_iters,
                max_rounds=self.max_rounds, delta=delta, backend=backend,
                sweep=sweep)
        mesh = self._spmd_mesh()
        sgd = self._spmd_views(with_push=sweep != "pull")
        # one function per program structure: the initial state enters as
        # a sharded input, so every source of a program shares its compile
        fkey = (program, backend, sweep, sg.csr_block, sg.delta_blocks)
        fn = self._spmd_fns.get(fkey)
        if fn is None:
            fn = self._spmd_fns[fkey] = make_spmd_diffuse(
                mesh, program, sgd, axis_name="cells",
                max_local_iters=self.max_local_iters,
                max_rounds=self.max_rounds, backend=backend,
                block_e=sg.csr_block, sweep=sweep,
                delta_blocks=max(sg.delta_blocks, 0))
        placed = on_mesh(mesh, {"vstate": vstate0, "active": active0})
        return lambda: fn(sgd, placed["vstate"], placed["active"])

    def _count_exchange(self, program: VertexProgram, stats) -> None:
        """The bytes an spmd diffusion's exchange sent between devices:
        ``DiffuseStats.rounds`` x :func:`~.diffuse.spmd_exchange_bytes`,
        as the scalar event ``/repro/exchange_bytes``."""
        rounds = int(trace.to_host(stats.rounds, "exchange"))
        jax.monitoring.record_scalar(
            trace.EXCHANGE_BYTES, rounds * spmd_exchange_bytes(
                program, self.n_cells, self.sg.n_per_shard))

    def _query_lanes(self, spec: ProgramSpec, name: str, lane_vals: list,
                     kwargs: dict, engine: str, backend: str,
                     refresh: bool, delta, value_key: str | None = None,
                     sweep: str = "pull",
                     explicit_sweep: str | None = None,
                     validate: bool | None = None) -> list:
        """Fan a pluralized lane param out into B lanes of one diffusion.

        The laned fixed point is split lane-by-lane into ordinary
        single-query cache entries (``vstate`` leaves [S, L, Np] ->
        [S, Np]), so commit()-time repair splices and re-diffuses each
        lane exactly like a query that was issued on its own.  A push /
        auto sweep ORs every lane's senders into one shared active set —
        one compaction serves all lanes.
        """
        per_lane = [dict(kwargs, **{spec.lane_param: v}) for v in lane_vals]
        keys = [self._key(name, engine, kw, backend, delta, sweep)
                for kw in per_lane]
        if not refresh and all(k in self._cache for k in keys):
            with trace.stage(trace.QUERY_RESULT):
                return [self._result(self._cache_get(k)) for k in keys]

        if engine == "event":
            # the host oracle is message-at-a-time; lanes degrade to a loop
            return [self.query(name, engine=engine, refresh=refresh,
                               value_key=value_key, **kw)
                    for kw in per_lane]

        with trace.stage(trace.QUERY_PREPARE):
            progs = tuple(spec.factory(**kw) for kw in per_lane)
            laned = make_laned(progs)
            run = self._prepare_diffusion(laned, engine, backend, delta,
                                          sweep)
        with trace.stage(trace.QUERY_DISPATCH):
            vstate, stats = run()
        with trace.stage(trace.QUERY_BUDGET):
            self._enforce_budget(stats, f"query {name!r} "
                                        f"({len(lane_vals)} lanes)")
            if engine == "spmd":
                self._count_exchange(laned, stats)

        vk = value_key or spec.value_key
        results = []
        with trace.stage(trace.QUERY_RESULT):
            for i, (kw, key) in enumerate(zip(per_lane, keys)):
                # slicing lane i uploads the literal index — an O(1) h2d
                # per lane, legal under the sanitizer (which guards d2h
                # syncs and retraces); keep the d2h direction guarded
                with jax.transfer_guard_host_to_device("allow"):
                    lane_state = jax.tree_util.tree_map(lambda a: a[:, i],
                                                        vstate)
                entry = _Entry(spec, progs[i], vk, kw, lane_state,
                               stats, engine, backend=backend, delta=delta,
                               sweep=explicit_sweep)
                self._cache_put(key, entry)
                res = self._result(entry)
                self._maybe_validate(entry, res, validate,
                                     f"query {name!r} lane {i}")
                results.append(res)
        return results

    def adopt(self, name: str, vstate, stats=None, engine: str = "sharded",
              backend: str | None = None, delta: float | None = None,
              sweep: str | None = None, **kwargs) -> tuple:
        """Register an existing fixed point with the session so commit()
        repairs it (on the session's backend unless overridden); returns
        the cache key."""
        spec = PROGRAMS[name]
        prog = spec.factory(**kwargs)
        backend = backend or self.backend
        key = self._key(name, engine, kwargs, backend, delta,
                        sweep or self.sweep)
        self._cache_put(key, _Entry(spec, prog, spec.value_key,
                                    dict(kwargs), vstate, stats, engine,
                                    backend=backend, delta=delta,
                                    sweep=sweep))
        return key

    def vertex_state(self, name: str, engine: str | None = None,
                     backend: str | None = None, delta: float | None = None,
                     sweep: str | None = None, **kwargs):
        """The cached [S, Np]-layout vertex-state pytree of a query."""
        key = self._key(name, engine or self.engine, kwargs,
                        backend or self.backend, delta,
                        sweep or self.sweep)
        entry = self._cache_get(key)    # reads keep the entry warm (LRU)
        if entry is None:
            raise KeyError(
                f"no cached fixed point for {name!r} with {kwargs} — "
                f"never queried, or evicted by max_cache_entries; "
                f"query() recomputes it")
        if entry.vstate is None:
            raise ValueError(
                f"{name!r} is a custom run_fn query; it caches a whole "
                f"Result (query() serves it), not a vertex-state pytree")
        return entry.vstate

    def _run_spmd(self, program: VertexProgram, backend: str = "xla",
                  sweep: str = "pull", state=None):
        vstate, stats = self._prepare_diffusion(program, "spmd", backend,
                                                None, sweep, state)()
        self._count_exchange(program, stats)
        return vstate, stats

    def _result(self, entry: _Entry) -> Result:
        values = self.to_global(entry.vstate[entry.value_key])
        extra = {k: self.to_global(v) for k, v in entry.vstate.items()
                 if k != entry.value_key}
        extra["live"] = self.live_ids()
        return Result(values=values, stats=entry.stats, extra=extra)

    # ------------------------------------------------------------------
    # the seven primitives, batched
    # ------------------------------------------------------------------

    def update(self) -> UpdateBatch:
        """The pending mutation batch (created lazily)."""
        if self._pending is None:
            self._pending = UpdateBatch(self.ns)
        return self._pending

    def add_vertex(self, shard: int | None = None) -> int:
        return self.update().add_vertex(shard)

    def delete_vertex(self, gid: int):
        self.update().delete_vertex(gid)
        return self

    def add_edge(self, u: int, v: int, w: float = 1.0):
        self.update().add_edge(u, v, w)
        return self

    def delete_edge(self, u: int, v: int):
        self.update().delete_edge(u, v)
        return self

    def touch(self, gid: int):
        self.update().touch_vertex(gid)
        return self

    def peek(self, u: int, prog="sssp", **kwargs):
        """The paper's peek primitive: u's per-out-edge neighbour values
        of a cached program's result (NaN on dead slots).

        ``prog`` goes through the same registry path as :meth:`query` —
        a name string, a program handle, or a bound query
        (``sess.peek(0, sssp(source=3))``) all resolve identically."""
        from .dynamic import peek as _peek

        engine = kwargs.pop("engine", None) or self.engine
        backend = kwargs.pop("backend", None) or self.backend
        sweep_kw = kwargs.pop("sweep", None)
        sweep = sweep_kw or self.sweep
        delta = kwargs.pop("delta", None)
        if engine == "event":
            raise ValueError(
                "peek reads a cached shard-layout state; the event oracle "
                "holds none — use engine='sharded' or 'spmd'")
        spec, name, kwargs, adhoc = self._resolve(prog, kwargs)
        if adhoc is not None:
            raise ValueError(
                "peek needs a registered program (name, handle, or bound "
                "query), not a raw VertexProgram")
        if spec.run_fn is not None:
            raise ValueError(
                f"peek reads a cached shard-layout vertex state; the "
                f"custom query {name!r} caches a whole Result and holds "
                f"none")
        lane_kw = spec.lane_param + "s" if spec.lane_param else None
        if lane_kw and lane_kw in kwargs:
            raise ValueError(
                f"peek reads one cached fixed point; a lane batch caches "
                f"per source — peek with {spec.lane_param}=<one of "
                f"{lane_kw}> instead")
        key = self._key(name, engine, kwargs, backend, delta, sweep)
        if key not in self._cache:
            # fall back to the unique cached variant of this program (and,
            # when kwargs were given, of these kwargs) — a delta/backend/
            # engine-variant entry serves a plain peek instead of paying a
            # fresh diffusion
            kw = freeze_kwargs(kwargs)
            same = [k for k in self._cache
                    if k[0] == name and (not kwargs or k[2] == kw)]
            if len(same) == 1:
                key = same[0]
            else:
                self.query(name, engine=engine, backend=backend,
                           sweep=sweep_kw, delta=delta, **kwargs)
        entry = self._cache_get(key)    # reads keep the entry warm (LRU)
        return _peek(self.sg, entry.vstate[entry.value_key], self.ns, u)

    # ------------------------------------------------------------------
    # commit: apply the batch + incremental repair
    # ------------------------------------------------------------------

    def commit(self, max_local_iters: int | None = None) -> CommitInfo:
        """Apply the pending UpdateBatch (vectorized) and repair every
        cached program fixed point by frontier re-diffusion.

        When a journal is armed (after :meth:`save`/:meth:`open`) the
        batch is journaled **before** it mutates any state — write-ahead
        logging.  A crash after the append but before the apply simply
        redoes the record at :meth:`open` (replay is deterministic, so
        redo converges to the same bits); an apply that *fails* (e.g. a
        full compute cell) rolls the record back so the journal never
        claims an op the store rejected."""
        return self._commit(max_local_iters, journal=True)

    def _commit(self, max_local_iters: int | None = None,
                journal: bool = True) -> CommitInfo:
        mli = max_local_iters or self.max_local_iters
        if self._pending is None or len(self._pending) == 0:
            applied = AppliedUpdates((), (), (), (), ())
        else:
            seq = None
            if journal and self._journal is not None:
                # snapshot the op lists BEFORE apply (apply clears them)
                with trace.stage(trace.COMMIT_JOURNAL):
                    rec = OpRecord.from_batch(self._pending)
                    seq = self._journal.append(rec)
                chaos.point("commit.journal-appended")
            try:
                with trace.stage(trace.COMMIT_APPLY):
                    sg, applied = self._pending.apply(self.part.sg)
                self._set_graph(sg)
            except Exception:
                # the store rejected the batch — un-journal it (ChaosKill
                # is a BaseException and deliberately escapes this)
                if seq is not None:
                    self._journal.rollback(seq)
                raise
            self._pending = None
            chaos.point("commit.applied")

        repairs = {}
        for key, entry in list(self._cache.items()):
            if applied.n_ops == 0:
                repairs[key] = ("noop", None)
                continue
            repairs[key] = self._repair_entry(entry, applied, mli)
        if applied.n_ops:
            chaos.point("commit.repaired")
        for key, (strategy, stats) in repairs.items():
            if stats is not None:
                self._enforce_budget(stats, f"commit repair ({strategy}) "
                                            f"of {key[0]!r}")
        return CommitInfo(applied=applied, repairs=repairs)

    def _repair_entry(self, entry: _Entry, applied: AppliedUpdates,
                      mli: int):
        """Repair one cached fixed point; the host stage
        ``repro.commit.repair.<strategy>``."""
        if entry.spec.run_fn is not None:
            strategy = "recount"
        else:
            strategy = entry.spec.repair
            if not applied.has_deletes and entry.spec.monotone:
                strategy = "frontier"
            elif strategy == "parents" and "parent" not in entry.vstate:
                strategy = "restart"
        with trace.stage(f"{trace.COMMIT_REPAIR}.{strategy}"):
            return strategy, self._repair(entry, applied, mli, strategy)

    def _repair(self, entry: _Entry, applied: AppliedUpdates, mli: int,
                strategy: str):
        """-> the repaired entry's stats."""
        sg = self.sg
        if strategy == "recount":
            # custom queries (triangles): restart-style recount against
            # the committed graph — cached and repaired like any program
            res = entry.spec.run_fn(self, engine=entry.engine,
                                    **entry.kwargs)
            entry.raw, entry.stats = res, res.stats
            return res.stats

        if strategy == "restart":
            with trace.stage(trace.COMMIT_COMPACT):
                self._compact_for(entry.prog)
            sg = self.sg            # _compact_for may have persisted
            if entry.engine == "spmd":
                vstate, stats = self._run_spmd(entry.prog, entry.backend,
                                               entry.sweep or self.sweep)
            else:
                vstate, stats = diffuse(sg, entry.prog,
                                        max_local_iters=mli,
                                        max_rounds=self.max_rounds,
                                        delta=entry.delta,
                                        backend=entry.backend,
                                        sweep=entry.sweep or self.sweep)
            entry.vstate, entry.stats = vstate, stats
            return stats

        vstate, active = self._warm_state(entry, applied, strategy)
        # resume under the entry's own engine, delta gate + kernel backend,
        # so the repair diffusion is work-gated exactly like the original
        # query.  Warm repairs resume from a tiny frontier, so they default
        # to the frontier-compacted push sweep (an explicit query sweep
        # wins) — bitwise-identical, O(frontier-adjacent edges) per round.
        if entry.engine == "spmd":
            vstate, stats = self._run_spmd(entry.prog, entry.backend,
                                           entry.sweep or "push",
                                           (vstate, active))
        else:
            vstate, stats = diffuse_from(sg, entry.prog, vstate, active,
                                         max_local_iters=mli,
                                         max_rounds=self.max_rounds,
                                         delta=entry.delta,
                                         backend=entry.backend,
                                         sweep=entry.sweep or "push")
        entry.vstate, entry.stats = vstate, stats
        return stats

    # -- repair state builders -------------------------------------------

    def _slots(self, gids) -> tuple[np.ndarray, np.ndarray]:
        s = np.array([self.ns.resolve(g)[0] for g in gids], np.int32)
        l = np.array([self.ns.resolve(g)[1] for g in gids], np.int32)
        return s, l

    def _splice_init(self, entry: _Entry, vstate, gids):
        """Reset the given vertices' state to the program's init values
        (fresh slots may hold stale state from a previously deleted
        occupant)."""
        if not gids:
            return vstate
        init_v, _ = entry.prog.init(logical_view(self.sg))
        s, l = self._slots(gids)
        return jax.tree_util.tree_map(
            lambda cur, ini: cur.at[s, l].set(ini[s, l]), vstate, init_v
        )

    def _base_frontier(self, applied: AppliedUpdates):
        """Insert source endpoints + touched + newly added vertices."""
        sg = self.sg
        active = jnp.zeros((sg.n_shards, sg.n_per_shard), bool)
        gids = ([u for u, _, _ in applied.edge_adds]
                + list(applied.touched)
                + [g for g, _, _ in applied.vertex_adds])
        if gids:
            s, l = self._slots(gids)
            active = active.at[s, l].set(True)
        return active & sg.node_ok

    def _warm_state(self, entry: _Entry, applied: AppliedUpdates,
                    strategy: str):
        sg = self.sg
        vstate = entry.vstate
        # new vertices (and reused slots) start from init state
        fresh = [g for g, _, _ in applied.vertex_adds]
        vstate = self._splice_init(entry, vstate, fresh)
        active = self._base_frontier(applied)

        if strategy == "frontier":
            return vstate, active

        if strategy == "parents":
            # roots: deleted tree edges + orphans of deleted vertices
            parent = vstate["parent"]
            roots = []
            dead = set(applied.vertex_deletes)
            for u, v in applied.edge_deletes:
                sv, lv = self.ns.resolve(v)
                if int(trace.to_host(parent[sv, lv], "repair.parents")) == u:
                    roots.append(v)
            if dead:
                par_np = self.to_global(parent)
                for v in range(par_np.shape[0]):
                    if int(par_np[v]) in dead and v not in dead:
                        roots.append(v)
            dist = vstate["dist"]
            parent_a = parent
            if roots or dead:
                all_roots = list(dict.fromkeys(roots)) + list(dead)
                invalid = _invalidate_subtrees(
                    self.part, self.ns, vstate, all_roots)
                dist = jnp.where(invalid, jnp.inf, dist)
                parent_a = jnp.where(invalid, -1, parent_a)
                # every still-finite vertex re-emits once; receivers'
                # predicates discard non-improvements (pure diffusion)
                active = active | (jnp.isfinite(dist) & sg.node_ok)
            out = dict(vstate)
            out["dist"], out["parent"] = dist, parent_a
            return out, active

        if strategy == "component":
            comp = vstate[entry.value_key]
            affected = set()
            for u, v in applied.edge_deletes:
                for g in (u, v):
                    s_, l_ = self.ns.resolve(g)
                    affected.add(int(trace.to_host(comp[s_, l_],
                                                   "repair.component")))
            for g in applied.vertex_deletes:
                s_, l_ = self.ns.resolve(g)
                affected.add(int(trace.to_host(comp[s_, l_],
                                               "repair.component")))
            if affected:
                init_v, _ = entry.prog.init(logical_view(sg))
                aff = jnp.isin(comp, jnp.asarray(sorted(affected),
                                                 comp.dtype))
                comp = jnp.where(aff, init_v[entry.value_key], comp)
                # all live vertices re-emit so cross-component inflow
                # re-arrives; min-combine discards non-improvements
                active = active | sg.node_ok
            out = dict(vstate)
            out[entry.value_key] = comp
            return out, active

        raise ValueError(f"unknown repair strategy {strategy!r}")

    # ------------------------------------------------------------------
    # convergence watchdog + result validation (DESIGN.md §2.13)
    # ------------------------------------------------------------------

    def _enforce_budget(self, stats, context: str) -> None:
        """Apply the on_budget policy to a diffusion's converged flag."""
        conv = getattr(stats, "converged", None)
        if conv is None or self.on_budget == "partial":
            return
        # explicit d2h transfer: legal under the runtime sanitizer's
        # transfer guard (same idiom as exact_streams_for)
        if bool(trace.to_host(conv, "budget")):
            return
        msg = (f"{context} exhausted max_rounds={self.max_rounds} before "
               f"quiescence — the fixed point is PARTIAL "
               f"(stats.converged=False); raise max_rounds, or accept "
               f"partial results with on_budget='partial'")
        if self.on_budget == "raise":
            raise ConvergenceError(msg)
        warnings.warn(msg, ConvergenceWarning)

    def _maybe_validate(self, entry: _Entry, res: Result,
                        validate: bool | None, context: str) -> None:
        on = self.validate if validate is None else validate
        if on:
            self._validate_result(entry, res, context)

    def _validate_result(self, entry: _Entry, res: Result,
                         context: str) -> None:
        """Schema-check a Result against its program's Field domains.

        Lowered from each Field declaration (programs.py): NaN is always
        invalid for float fields; a declared ``domain=(lo, hi)`` bounds
        the legal values (None = unbounded on that side); undeclared int
        domains default to the payload range ``[-1, n_ids)`` (gid
        payloads plus the -1 sentinel).  Only live vertices are checked —
        dead slots legitimately hold stale bits."""
        fields = getattr(entry.prog, "fields", None)
        if fields is None:
            return
        live = np.asarray(res.extra["live"])
        for fname, field in fields:
            if fname == entry.value_key:
                arr = res.values
            elif fname in res.extra:
                arr = res.extra[fname]
            else:
                continue
            a = np.asarray(arr)[live]
            if a.size == 0:
                continue
            lo = hi = None
            if np.issubdtype(a.dtype, np.floating):
                nan = np.isnan(a)
                if nan.any():
                    raise ValidationError(
                        f"{context}: field {fname!r} holds NaN on "
                        f"{int(nan.sum())} live vertices")
                if field.domain is not None:
                    lo, hi = field.domain
            else:
                lo, hi = (field.domain if field.domain is not None
                          else (-1, self.n_ids - 1))
            if lo is not None and bool((a < lo).any()):
                raise ValidationError(
                    f"{context}: field {fname!r} holds values below "
                    f"{lo} on live vertices (min {a.min()})")
            if hi is not None and bool((a > hi).any()):
                raise ValidationError(
                    f"{context}: field {fname!r} holds values above "
                    f"{hi} on live vertices (max {a.max()})")

    # ------------------------------------------------------------------
    # durability: snapshot + write-ahead journal (DESIGN.md §2.13)
    # ------------------------------------------------------------------

    def _attach(self, directory: str) -> None:
        # lazy import: checkpoint.manager imports core.chaos, which
        # executes core/__init__ (and therefore this module)
        from ..checkpoint.manager import CheckpointManager

        directory = os.path.abspath(directory)
        if self._dur_dir is not None:
            if directory != self._dur_dir:
                raise ValueError(
                    f"session is already durable at {self._dur_dir}; "
                    f"cannot re-home it to {directory}")
            return
        os.makedirs(directory, exist_ok=True)
        self._dur_dir = directory
        self._ckpt = CheckpointManager(directory, keep=self._snapshot_keep)
        self._journal = UpdateJournal(
            os.path.join(directory, _JOURNAL_FILE),
            fsync=self._journal_fsync)

    def save(self, directory: str | None = None) -> int:
        """Snapshot the full session and arm the write-ahead journal.

        The first call names the durability directory; later calls may
        omit it.  The snapshot captures everything :meth:`open` needs to
        resume **bitwise-equal**: the graph arrays (both CSR views,
        delta/tombstone state, replica maps), the partition, the name
        server (including free-list order), every reconstructible cached
        fixed point (vstate + stats), and the session's engine/backend/
        sweep/watchdog settings.  Writes go through
        :class:`CheckpointManager` (atomic tmp-dir rename + digest
        manifest + retention), so a crash mid-save never damages the
        previous snapshot.  After a successful save the journal head is
        garbage-collected up to the *oldest retained* snapshot —
        falling back past a corrupt snapshot still finds every record
        it needs.  Returns the snapshot step (= the journal seq the
        snapshot is consistent with).

        Uncommitted pending ops are **not** captured — commit() first to
        make them durable (they journal at commit).
        """
        if directory is None:
            directory = self._dur_dir
        if directory is None:
            raise ValueError(
                "save() needs a directory the first time "
                "(session.save('/path/to/dir'))")
        if self._pending is not None and len(self._pending):
            warnings.warn(
                "save() with uncommitted pending updates: the snapshot "
                "captures committed state only — commit() first to make "
                "the pending batch durable")
        self._attach(directory)
        step = self._journal.next_seq
        tree, meta = self._snapshot_tree()
        meta["format"] = SNAPSHOT_FORMAT
        meta_bytes = json.dumps(meta, default=_json_np).encode()
        tree["session_meta"] = np.frombuffer(meta_bytes, np.uint8).copy()
        self._ckpt.save(step, tree, wait=True)
        steps = self._ckpt.all_steps()
        if steps:
            self._journal.truncate(min(steps))
        return step

    def _snapshot_tree(self) -> tuple[dict, dict]:
        """-> (flat leaf dict, JSON-ready metadata) for one snapshot."""
        sg = self.sg
        tree: dict[str, Any] = {}
        for k, v in sg.state_dict().items():
            tree[f"graph/{k}"] = v
        tree["part/owner"] = np.asarray(self.part.owner)
        tree["part/local"] = np.asarray(self.part.local)
        rep = getattr(self.part, "replica", None)
        if rep is not None:
            for f in ReplicaInfo._fields:
                tree[f"replica/{f}"] = np.asarray(getattr(rep, f))
        if self._ns is not None:
            for k, v in self._ns.state_dict().items():
                tree[f"ns/{k}"] = v
        meta = {
            "engine": self.engine,
            "backend": self.backend,
            "sweep": self.sweep,
            "max_local_iters": self.max_local_iters,
            "max_rounds": self.max_rounds,
            "max_cache_entries": self.max_cache_entries,
            "on_budget": self.on_budget,
            "validate": self.validate,
            "snapshot_keep": self._snapshot_keep,
            "graph_meta": sg.meta_dict(),
            "n_real": int(self.part.n_real),
            "has_ns": self._ns is not None,
            "has_replica": rep is not None,
            "cache": [],
        }
        for i, entry in enumerate(self._cache.values()):
            name = entry.spec.name
            if name.startswith("adhoc:") or name not in PROGRAMS:
                warnings.warn(
                    f"snapshot skips cache entry {name!r}: ad-hoc "
                    f"programs are not reconstructible by name (the "
                    f"query recomputes after open())")
                continue
            em: dict[str, Any] = {
                "name": name,
                "value_key": entry.value_key,
                "kwargs": entry.kwargs,
                "engine": entry.engine,
                "backend": entry.backend,
                "delta": entry.delta,
                "sweep": entry.sweep,
                # the cache key resolved a defaulted sweep to the
                # session's — record the resolved value so open()
                # rebuilds the identical key
                "key_sweep": entry.sweep or self.sweep,
            }
            if entry.spec.run_fn is not None:
                em["kind"] = "run_fn"
                em["extra_scalars"] = {}
                em["extra_arrays"] = []
                tree[f"cache/{i}/raw"] = np.asarray(entry.raw.values)
                for k, v in entry.raw.extra.items():
                    if isinstance(v, np.ndarray):
                        em["extra_arrays"].append(k)
                        tree[f"cache/{i}/extra/{k}"] = v
                    else:
                        em["extra_scalars"][k] = v
            else:
                em["kind"] = "diffuse"
                em["vstate_fields"] = list(entry.vstate.keys())
                for f, leaf in entry.vstate.items():
                    tree[f"cache/{i}/vstate/{f}"] = leaf
                if isinstance(entry.stats, DiffuseStats):
                    em["stats"] = "diffuse"
                    for f in DiffuseStats._fields:
                        tree[f"cache/{i}/stats/{f}"] = getattr(
                            entry.stats, f)
                else:
                    em["stats"] = None
            meta["cache"].append(em)
        return tree, meta

    @classmethod
    def open(cls, directory: str, journal_fsync: str = "always",
             step: int | None = None) -> "DiffusionSession":
        """Recover a session: latest valid snapshot + journal-tail replay.

        A damaged latest snapshot (torn manifest, missing leaf, digest
        mismatch) falls back to the previous retained one; the journal's
        opening scan truncates any torn tail; then every journaled commit
        with ``seq >= snapshot step`` is redone through the same compiled
        apply + cache-repair path the live commits used.  The recovered
        session is bitwise-equal to one that never crashed — graph
        arrays, cache keys, and query results alike."""
        from ..checkpoint.manager import CheckpointManager

        directory = os.path.abspath(directory)
        ckpt = CheckpointManager(directory)
        arrays, loaded_step = ckpt.restore_flat(step=step)
        meta = json.loads(bytes(bytearray(arrays.pop("session_meta"))))
        if meta.get("format") != SNAPSHOT_FORMAT:
            raise IOError(
                f"snapshot format {meta.get('format')!r} is not "
                f"{SNAPSHOT_FORMAT} (newer writer?)")
        graph_arrays = {k.split("/", 1)[1]: v for k, v in arrays.items()
                        if k.startswith("graph/")}
        sg = ShardedGraph.from_state(graph_arrays, meta["graph_meta"])
        replica = None
        if meta["has_replica"]:
            replica = ReplicaInfo(*(np.asarray(arrays[f"replica/{f}"])
                                    for f in ReplicaInfo._fields))
        part = Partitioned(sg, arrays["part/owner"], arrays["part/local"],
                           n_real=meta["n_real"], replica=replica)
        ns = None
        if meta["has_ns"]:
            ns_arrays = {k.split("/", 1)[1]: v for k, v in arrays.items()
                         if k.startswith("ns/")}
            ns = NameServer.from_state(ns_arrays, sg.n_shards,
                                       replica=replica)
        sess = cls(part, ns=ns, engine=meta["engine"],
                   backend=meta["backend"], sweep=meta["sweep"],
                   max_local_iters=meta["max_local_iters"],
                   max_rounds=meta["max_rounds"],
                   max_cache_entries=meta["max_cache_entries"],
                   on_budget=meta["on_budget"], validate=meta["validate"],
                   journal_fsync=journal_fsync,
                   snapshot_keep=meta.get("snapshot_keep", 3))
        ckpt.keep = sess._snapshot_keep
        sess._restore_cache(meta["cache"], arrays)
        sess._dur_dir = directory
        sess._ckpt = ckpt
        sess._journal = UpdateJournal(
            os.path.join(directory, _JOURNAL_FILE), fsync=journal_fsync)
        sess._replay_journal(loaded_step)
        return sess

    def _restore_cache(self, cache_meta: list, arrays: dict) -> None:
        for i, em in enumerate(cache_meta):
            name = em["name"]
            if name not in PROGRAMS:
                warnings.warn(
                    f"snapshot cache entry {name!r} is no longer in the "
                    f"program registry; skipping (it recomputes on query)")
                continue
            spec = PROGRAMS[name]
            kwargs = dict(em["kwargs"])
            if em["kind"] == "run_fn":
                extra = dict(em["extra_scalars"])
                for k in em["extra_arrays"]:
                    extra[k] = np.asarray(arrays[f"cache/{i}/extra/{k}"])
                res = Result(values=np.asarray(arrays[f"cache/{i}/raw"]),
                             stats=None, extra=extra)
                key = self._key(name, em["engine"], kwargs)
                self._cache_put(key, _Entry(spec, None, em["value_key"],
                                            kwargs, None, None,
                                            em["engine"], raw=res))
                continue
            prog = spec.factory(**kwargs)
            vstate = {f: jnp.asarray(arrays[f"cache/{i}/vstate/{f}"])
                      for f in em["vstate_fields"]}
            stats = None
            if em["stats"] == "diffuse":
                stats = DiffuseStats(*(
                    jnp.asarray(arrays[f"cache/{i}/stats/{f}"])
                    for f in DiffuseStats._fields))
            key = self._key(name, em["engine"], kwargs, em["backend"],
                            em["delta"], em["key_sweep"])
            self._cache_put(key, _Entry(
                spec, prog, em["value_key"], kwargs, vstate, stats,
                em["engine"], backend=em["backend"], delta=em["delta"],
                sweep=em["sweep"]))

    def _replay_journal(self, from_seq: int) -> int:
        """Redo journaled commits on top of the snapshot (WAL recovery).

        Each record rebuilds an UpdateBatch and runs the normal commit
        path (journaling disabled), so NameServer allocation, replica
        routing, compaction policy, and cache repairs all re-run exactly
        as they did live.  Vertex adds allocate gids *eagerly* at
        ``add_vertex`` time — before the commit that journals them — so
        a snapshot may already contain a journaled allocation; those are
        verified and reused, anything newer is re-allocated and must
        come out identical (gids are monotonic, never reused)."""
        replayed = 0
        for _seq, rec in self._journal.replay(from_seq):
            batch = UpdateBatch(self.ns)
            for gid, s, l in rec.vadds.tolist():
                if gid < self.ns._next:
                    if self.ns.resolve(gid) != (s, l):
                        raise JournalReplayError(
                            f"replayed vertex add gid={gid} resolves to "
                            f"{self.ns.resolve(gid)}, journal says "
                            f"({s}, {l})")
                else:
                    got = self.ns.allocate(int(s))
                    if got != (gid, s, l):
                        raise JournalReplayError(
                            f"replayed allocation produced {got}, "
                            f"journal says ({gid}, {s}, {l})")
                batch._vadds.append((int(gid), int(s), int(l)))
            for g in rec.vdels.tolist():
                batch.delete_vertex(g)
            for (u, v), w in zip(rec.eadds.tolist(), rec.ea_w.tolist()):
                batch.add_edge(u, v, w)
            for u, v in rec.edels.tolist():
                batch.delete_edge(u, v)
            for g in rec.touch.tolist():
                batch.touch_vertex(g)
            self._pending = batch
            self._commit(journal=False)
            replayed += 1
        return replayed

    def close(self) -> None:
        """Flush + close the journal (snapshots need no close)."""
        if self._journal is not None:
            self._journal.close()
            self._journal = None
