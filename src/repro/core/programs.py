"""Diffusive programs — the ``hpx_diffuse`` contract as a declarative,
user-registrable spec.

The paper's Code Listing 3 primitive is::

    hpx_diffuse(vertex_id, vertex_func, args..., terminator, predicate)

PR 1/2 hardcoded five vectorized realizations of that contract as closure
factories only the engine authors could extend.  This module turns the
contract into a public extension point (DESIGN.md §2.7):

* :class:`DiffusiveProgram` — a *declarative spec*: a typed vertex-state
  schema (named :class:`Field`\\ s: dtype + init expression + dead-slot
  value), a first-class :class:`~.monoid.Monoid`, and pure
  ``emit / receive / on_send / priority`` functions over the named state;
* :func:`diffusive` — the registration decorator: a decorated factory is
  invocable by name through every engine (``sharded`` / ``event`` /
  ``spmd``), both kernel backends (``xla`` / ``pallas``), the session
  cache, and commit()-time repair, with zero engine changes;
* :func:`lower` — compiles a spec to the engine IR
  (:class:`VertexProgram`), whose function fields the relaxation kernels
  trace straight into their bodies;
* :func:`make_laned` — stacks B single-query programs into one program
  with a lane axis, so ``session.query(sssp(sources=[...]))`` amortizes
  B queries over a single edge sweep (multi-query lanes, DESIGN.md §2.7).

The five builtins (SSSP / BFS / CC / PPR / PageRank) are themselves
written on the public spec, as are the two proof-of-extensibility
programs ``widest`` (max-bottleneck path) and ``reach``
(multi-source reachability).

Messages are combined with an associative-commutative monoid so delivery
order cannot matter — this is what makes the paper's "no DAG, any path to
the fixed point" semantics sound under bulk-asynchronous execution.
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

import numpy as np

from .monoid import Monoid, as_monoid

__all__ = [
    "Field", "DiffusiveProgram", "VertexProgram", "SourceReads",
    "ProgramSpec",
    "BoundQuery", "ProgramHandle", "diffusive", "lower", "make_laned",
    "PROGRAMS", "register_program", "freeze_kwargs",
    "sssp", "bfs", "cc", "ppr", "pagerank", "widest", "reach",
    "sssp_program", "bfs_program", "cc_program", "ppr_program",
    "pagerank_program", "widest_program", "reach_program",
]


# --------------------------------------------------------------------------
# engine IR — what diffuse.py / the relax kernels consume
# --------------------------------------------------------------------------

def _closure_key(value):
    """Hashable identity of one captured value.  Nested functions key
    structurally; unhashable captures (arrays, Field schemas) fall back
    to object identity — the fallback can only *separate* two programs
    that structural equality would have merged, never wrongly merge
    them, so it is always trace-safe."""
    if callable(value) and hasattr(value, "__code__"):
        return _fn_key(value)
    try:
        hash(value)
    except TypeError:
        return ("id", id(value))
    return value


def _fn_key(fn):
    """Structural identity of a pure function: code object + captured
    closure values + defaults.  Two closures produced by re-running the
    same factory with the same parameters compare equal — they trace to
    the same jaxpr — which is what lets every ``sssp(source=k)`` share
    one ``_run_rounds`` jit cache entry (the source lives in ``init``,
    which is excluded from the program's trace identity)."""
    if fn is None:
        return None
    code = getattr(fn, "__code__", None)
    if code is None:
        return fn                       # builtins / partials: the object
    cells = tuple(_closure_key(c.cell_contents)
                  for c in (fn.__closure__ or ()))
    defaults = tuple(_closure_key(d) for d in (fn.__defaults__ or ()))
    return (code, cells, defaults)


class SourceReads(NamedTuple):
    """What one sweep reads of each edge's source vertex, from the
    program alone (:meth:`VertexProgram.source_reads`).

    ``leaves`` names the state fields ``emit`` or ``payload`` use;
    ``gid`` says whether they use ``src_gid`` (the engines then serve it
    as the stream field ``csr_src_gid``); ``flag_leaf`` names the read
    field whose word carries the sender flag in its sign bit — a signed
    32-bit field declared non-negative (:class:`Field` ``domain``), or
    None, and the flag then takes a gather of its own.  ``leaves`` is
    None for a program without a Field schema, which reads every leaf
    and packs none.
    """

    leaves: tuple | None
    gid: bool
    flag_leaf: str | None

    @property
    def gathers(self) -> int | None:
        """E-long source gathers of one dense sweep: one per read leaf,
        one for the sender flag unless it rides in ``flag_leaf``."""
        if self.leaves is None:
            return None
        return len(self.leaves) + (self.flag_leaf is None)


def _live_inputs(closed) -> list:
    """Which inputs of a closed jaxpr feed its outputs (an equation's
    inputs are live when any of its outputs is; nested jaxprs count
    whole)."""
    jaxpr = closed.jaxpr
    live = {id(v) for v in jaxpr.outvars}
    for eqn in reversed(jaxpr.eqns):  # analysis: allow(host-loop): trace-time walk of emit's jaxpr, once per program, not over cells
        if any(id(v) in live for v in eqn.outvars):
            live.update(id(v) for v in eqn.invars)
    return [id(v) in live for v in jaxpr.invars]


def _flag_capable(field) -> bool:
    """A field whose word has a spare sign bit: signed 32-bit, declared
    non-negative."""
    dt = np.dtype(field.dtype)
    lo = field.domain[0] if field.domain is not None else None
    return (dt.itemsize == 4 and dt.kind in "fi" and lo is not None
            and lo >= 0)


@dataclasses.dataclass(frozen=True, eq=False)
class VertexProgram:
    """Lowered (engine-facing) vertex program.

    Shapes (per shard): vertex-state leaves are [Np] — or [L, Np] when
    ``lanes`` is set (multi-query lanes; see :func:`make_laned`) — and
    edge args are [Ep].  Serves as the jit static argument, so its
    ``__eq__`` / ``__hash__`` are *structural over everything the trace
    reads* — monoid, msg dtype, the emit/on_send/receive/payload/
    priority function structure (:func:`_fn_key`), lanes, name — and
    deliberately exclude ``init``: the engines take the initial
    ``(vstate, active)`` as *traced* inputs, so programs differing only
    in their init closure (``sssp(source=0)`` vs ``sssp(source=1)``)
    share one compiled fixed-point loop instead of retracing per
    source.  Callers that do trace ``init`` (the laned stacker) must key
    their caches on ``_fn_key(prog.init)`` as well.
    """

    monoid: Monoid                 # first-class combine (min/max/sum class)
    msg_dtype: Any
    # (view) -> (vstate pytree of [.., Np] leaves, active [.., Np] bool)
    init: Callable
    # (src_state pytree [Ep], weight [Ep], src_gid [Ep], dst_gid [Ep]) -> msg
    emit: Callable
    # (vstate [Np] leaves, sent_mask [Np]) -> vstate
    on_send: Callable
    # (vstate, inbox [Np], has_msg [Np], payload [Np] int32|None, node_ok)
    #   -> (vstate, activated [Np] bool)
    receive: Callable
    # optional argbest payload: (src_state [Ep], src_gid [Ep]) -> int32 [Ep]
    payload: Callable | None = None
    # optional bucket priority (delta-stepping gate): (vstate) -> f32 [Np]
    priority: Callable | None = None
    lanes: int | None = None       # lane count; None = single-query program
    name: str = ""
    # the declarative Field schema this program was lowered from, as
    # ((name, Field), ...) — carried for the session's validate= guard
    # and for source_reads(); None for hand-built programs (which then
    # skip validation and pack no sender flag).  The trace key holds
    # only the part source_reads() decides from.
    fields: Any = None

    def __post_init__(self):
        if not isinstance(self.monoid, Monoid):
            object.__setattr__(self, "monoid", as_monoid(self.monoid))
        if self.payload is not None and self.monoid.payload != "argbest":
            raise ValueError(
                f"program {self.name!r} carries a payload but monoid "
                f"{self.monoid.name!r} has no 'argbest' payload rule")

    def _trace_key(self) -> tuple:
        """Everything the jitted fixed-point loop reads from this
        program (``init`` excluded — it enters as traced arrays).  Of
        the Field schema only what :meth:`source_reads` decides from:
        names, dtypes and which fields may carry the sender flag."""
        key = self.__dict__.get("_trace_key_cache")
        if key is None:
            schema = (None if self.fields is None else tuple(
                (n, np.dtype(f.dtype), _flag_capable(f))
                for n, f in self.fields))
            key = (self.monoid, np.dtype(self.msg_dtype),
                   _fn_key(self.emit), _fn_key(self.on_send),
                   _fn_key(self.receive), _fn_key(self.payload),
                   _fn_key(self.priority), self.lanes, self.name, schema)
            object.__setattr__(self, "_trace_key_cache", key)
        return key

    def source_reads(self) -> SourceReads:
        """The per-edge source reads of this program's sweep, found by
        tracing ``emit`` and ``payload`` once over abstract edge
        streams and keeping the inputs that reach their outputs."""
        reads = self.__dict__.get("_source_reads_cache")
        if reads is not None:
            return reads
        if self.fields is None:
            reads = SourceReads(None, True, None)
        else:
            names = tuple(n for n, _ in self.fields)
            edge = lambda dt: jax.ShapeDtypeStruct((8,), dt)
            state = {n: edge(f.dtype) for n, f in self.fields}

            def messages(s, weight, src_gid, dst_gid):
                msg = self.emit(s, weight, src_gid, dst_gid)
                pay = (self.payload(s, src_gid) if self.with_payload
                       else None)
                return msg, pay

            closed = jax.make_jaxpr(messages)(
                state, edge(jnp.float32), edge(jnp.int32), edge(jnp.int32))
            live = _live_inputs(closed)
            order = jax.tree_util.tree_leaves({n: n for n in names})
            used = {n for n, u in zip(order, live) if u}
            leaves = tuple(n for n in names if n in used)
            fields = dict(self.fields)
            flag = next((n for n in leaves if _flag_capable(fields[n])),
                        None)
            reads = SourceReads(leaves, live[len(names) + 1], flag)
        object.__setattr__(self, "_source_reads_cache", reads)
        return reads

    def __eq__(self, other):
        if not isinstance(other, VertexProgram):
            return NotImplemented
        return self is other or self._trace_key() == other._trace_key()

    def __hash__(self):
        return hash(self._trace_key())

    @property
    def combine(self) -> str:
        """Scatter class of the monoid — the kernels' dispatch string."""
        return self.monoid.kind

    @property
    def with_payload(self) -> bool:
        return self.payload is not None


# --------------------------------------------------------------------------
# declarative spec + lowering
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class Field:
    """One named vertex-state field: dtype + init expression.

    ``init`` is a scalar or a pure function of the graph view (an object
    with ``gid`` / ``node_ok`` / ``out_degree`` arrays); ``on_dead``, when
    given, overwrites dead/free vertex slots (deleted vertices and spare
    capacity) so stale slot contents can never leak into a fixed point.

    ``domain`` optionally declares the legal value range ``(lo, hi)`` of
    *live* vertices at a fixed point (None end = unbounded), consumed by
    the session's ``validate=`` post-query guard (DESIGN.md §2.13): NaN
    is always invalid for float fields; out-of-domain values (including
    an inf that the domain does not admit) fail validation.  Undeclared
    domains default to NaN-only checking for floats and the payload
    range ``[-1, n_ids)`` for ints (payloads carry gids or -1).

    A lower bound ``lo >= 0`` on a float32 or int32 field is also a
    promise for every value the field holds during a diffusion: its
    sign bit stays clear (no negative value, no ``-0.0``), so the sweep
    may carry the sender flag in it (:class:`SourceReads`).
    """

    dtype: Any
    init: Any = 0
    on_dead: Any = None
    domain: tuple | None = None


@dataclasses.dataclass(frozen=True, eq=False)
class DiffusiveProgram:
    """Declarative diffusive-program spec (see module docstring).

    ``emit`` / ``receive`` / ``on_send`` / ``priority`` are pure functions
    over the *named* state dict declared in ``state`` — the same
    vectorized signatures as :class:`VertexProgram` (they are traced into
    the relaxation kernels unchanged by :func:`lower`).
    """

    monoid: Monoid | str
    msg_dtype: Any
    state: Any                          # mapping name -> Field (ordered)
    emit: Callable
    receive: Callable
    init_active: Callable | None = None  # (view) -> bool mask; None = all
    on_send: Callable | None = None      # None = identity
    payload: Callable | None = None
    priority: Callable | None = None


def lower(spec: DiffusiveProgram, name: str = "") -> VertexProgram:
    """Compile a declarative spec to the engine IR.

    Builds the vectorized ``init`` from the state schema: evaluate each
    field's init expression over the graph view, cast to the declared
    dtype, splat ``on_dead`` over dead slots, and intersect the initial
    frontier with ``node_ok``.

    Every spec is verified against the §2.7 authoring contract on the
    way through (abstract traces of emit/receive/on_send/priority
    against the Field schema + a seeded monoid-law check — see
    :mod:`repro.analysis.verify`); a broken spec raises
    :class:`~repro.analysis.verify.ProgramVerificationError` here, at
    build/registration time, instead of mis-executing at query time.
    Set ``REPRO_VERIFY=0`` to skip.
    """
    from ..analysis import verify as _verify  # deferred: no import cycle

    if _verify.verification_enabled():
        _verify.verify_program(spec, name=name)

    monoid = as_monoid(spec.monoid)
    fields = tuple(spec.state.items())

    def init(view):
        shape = view.gid.shape
        vstate = {}
        for fname, f in fields:  # analysis: allow(host-loop): static unroll over the declared field schema, not shards
            v = f.init(view) if callable(f.init) else f.init
            v = jnp.broadcast_to(jnp.asarray(v), shape).astype(f.dtype)
            if f.on_dead is not None:
                v = jnp.where(view.node_ok, v,
                              jnp.asarray(f.on_dead, f.dtype))
            vstate[fname] = v
        mask = (spec.init_active(view) if spec.init_active is not None
                else jnp.ones(shape, bool))
        return vstate, mask & view.node_ok

    return VertexProgram(
        monoid=monoid,
        msg_dtype=spec.msg_dtype,
        init=init,
        emit=spec.emit,
        on_send=spec.on_send or (lambda vstate, sent: vstate),
        receive=spec.receive,
        payload=spec.payload,
        priority=spec.priority,
        name=name,
        fields=fields,
    )


# --------------------------------------------------------------------------
# registry — one lookup path for names, handles, and bound queries
# --------------------------------------------------------------------------

class ProgramSpec(NamedTuple):
    """Registry entry making a program invocable by name (DESIGN.md §2.4).

    ``lane_param`` names the kwarg whose plural form fans out into query
    lanes (``source`` -> ``sources``); lane-varying params may only
    influence the init schema / initial frontier, never emit/receive.
    """

    name: str
    factory: Callable | None     # (**kwargs) -> VertexProgram
    value_key: str
    repair: str = "restart"      # 'parents' | 'component' | 'restart'
    monotone: bool = False       # insert-only warm start is sound
    event_fn: Callable | None = None   # (session, **kwargs) -> (values, st)
    run_fn: Callable | None = None     # custom query (e.g. triangles)
    lane_param: str | None = None


PROGRAMS: dict[str, ProgramSpec] = {}


def register_program(spec: ProgramSpec) -> ProgramSpec:
    PROGRAMS[spec.name] = spec
    return spec


def freeze_kwargs(kwargs: dict) -> tuple:
    """Deterministic hashable form of query/program kwargs: lists, arrays,
    sets, and dicts become sorted/ordered tuples (so ``sources=[...]``
    can key a cache instead of raising TypeError)."""
    def _freeze(v):
        if isinstance(v, (list, tuple)):
            return tuple(_freeze(x) for x in v)
        if isinstance(v, (set, frozenset)):
            return tuple(sorted(_freeze(x) for x in v))
        if isinstance(v, (np.ndarray, jnp.ndarray)):
            a = np.asarray(v)
            return a.item() if a.ndim == 0 else tuple(
                _freeze(x) for x in a.tolist())
        if isinstance(v, dict):
            return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
        if isinstance(v, np.generic):
            return v.item()
        return v
    return tuple(sorted((k, _freeze(v)) for k, v in kwargs.items()))


# Stable-identity caches of lowered programs (a rebuilt program would jit
# afresh).  Bounded like PR 1/2's lru_cache(256): a serving process that
# sees millions of distinct sources must not retain every closure forever
# — evicting merely costs the evictee a recompile on its next use.
_PROGRAM_CACHE_SIZE = 256


def _evict_oldest(cache: dict, limit: int):
    while len(cache) >= limit:
        cache.pop(next(iter(cache)))


class BoundQuery(NamedTuple):
    """A program invocation bound to its kwargs — what a
    :class:`ProgramHandle` call returns, and what ``session.query`` /
    ``session.peek`` accept interchangeably with a registry name."""

    name: str
    kwargs: dict


class ProgramHandle:
    """The object a :func:`diffusive` decoration returns.

    Calling it binds kwargs into a :class:`BoundQuery` for
    ``session.query(sssp(source=3))`` / ``query(sssp(sources=[...]))``;
    :meth:`build` lowers the spec to a cached :class:`VertexProgram`
    (stable identity per canonicalized kwargs => no jit recompiles).
    """

    def __init__(self, name: str, fn: Callable, value_key: str,
                 lane_param: str | None = None):
        self.name = name
        self.fn = fn
        self.value_key = value_key
        self.lane_param = lane_param
        self._built: dict[tuple, VertexProgram] = {}
        self.__doc__ = fn.__doc__

    def __call__(self, **kwargs) -> BoundQuery:
        return BoundQuery(self.name, dict(kwargs))

    def build(self, *args, **kwargs) -> VertexProgram:
        bound = inspect.signature(self.fn).bind(*args, **kwargs)
        bound.apply_defaults()
        key = freeze_kwargs(bound.arguments)
        if key not in self._built:
            spec = self.fn(**bound.arguments)
            if not isinstance(spec, DiffusiveProgram):
                raise TypeError(
                    f"@diffusive factory {self.name!r} must return a "
                    f"DiffusiveProgram, got {type(spec).__name__}")
            _evict_oldest(self._built, _PROGRAM_CACHE_SIZE)
            self._built[key] = lower(spec, name=self.name)
        return self._built[key]

    def __repr__(self):
        return f"<diffusive program {self.name!r}>"


def diffusive(name: str, *, value_key: str, repair: str = "restart",
              monotone: bool = False, lane_param: str | None = None):
    """Register a user-defined diffusive program (DESIGN.md §2.7).

    Decorate a factory ``(**params) -> DiffusiveProgram``; the returned
    handle is callable (binding kwargs for ``session.query``) and the
    program becomes name-invocable across all engines, kernel backends,
    the session cache, and commit()-time repair::

        @diffusive("widest", value_key="width", monotone=True,
                   lane_param="source")
        def widest(source: int):
            return DiffusiveProgram(monoid="max", ...)

    ``repair`` picks the commit()-time strategy ('parents' | 'component'
    | 'restart'); ``monotone`` allows the warm-frontier path for
    insert-only batches; ``lane_param`` enables multi-query lanes over
    the pluralized kwarg.
    """
    def deco(fn: Callable) -> ProgramHandle:
        handle = ProgramHandle(name, fn, value_key, lane_param)
        register_program(ProgramSpec(
            name, handle.build, value_key, repair=repair, monotone=monotone,
            lane_param=lane_param,
        ))
        return handle
    return deco


# --------------------------------------------------------------------------
# multi-query lanes
# --------------------------------------------------------------------------

_LANED: dict[tuple, VertexProgram] = {}


def make_laned(progs) -> VertexProgram:
    """Stack B single-query programs into one laned program.

    Vertex-state leaves and the active mask gain a lane axis (per shard:
    [Np] -> [L, Np]); emit/receive/on_send/priority come from the first
    program and broadcast over lanes, so the lane-varying kwargs (the
    registry's ``lane_param``) may only influence the init schema and the
    initial frontier.  The engines then run one edge sweep per
    sub-iteration for all B queries (DESIGN.md §2.7).

    Cached on the program tuple => stable identity, no jit recompiles
    for a repeated batch shape (bounded — see ``_PROGRAM_CACHE_SIZE``).
    """
    progs = tuple(progs)
    if not progs:
        raise ValueError("make_laned needs at least one program")
    # the laned init stacks every lane's init, so the cache key must
    # carry each program's *init identity* on top of its (init-excluding)
    # structural equality — otherwise sssp lanes [0, 1] would serve
    # lanes [2, 3]
    lkey = tuple((p, _fn_key(p.init)) for p in progs)
    if lkey in _LANED:
        return _LANED[lkey]
    _evict_oldest(_LANED, _PROGRAM_CACHE_SIZE)
    base = progs[0]
    for p in progs[1:]:
        if (p.monoid != base.monoid or p.msg_dtype != base.msg_dtype
                or (p.payload is None) != (base.payload is None)):
            raise ValueError(
                "lane programs must share monoid, msg dtype, and "
                "payload-ness (only init may vary per lane)")

    def init(view):
        outs = [p.init(view) for p in progs]
        vstate = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=-2), *[o[0] for o in outs])
        active = jnp.stack([o[1] for o in outs], axis=-2)
        return vstate, active

    laned = dataclasses.replace(
        base, init=init, lanes=len(progs),
        name=f"{base.name or 'prog'}[x{len(progs)}]",
    )
    _LANED[lkey] = laned
    return laned


# --------------------------------------------------------------------------
# the builtins, written on the public spec
# --------------------------------------------------------------------------

@diffusive("sssp", value_key="dist", repair="parents", monotone=True,
           lane_param="source")
def sssp(source: int, track_parents: bool = True) -> DiffusiveProgram:
    """Diffusive SSSP: msg = dist(src) + w; predicate ``msg < dist(v)``
    (the paper's running example, Code Listings 1, 2, 4)."""
    state = {"dist": Field(jnp.float32,
                           init=lambda v: jnp.where(v.gid == source, 0.0,
                                                    jnp.inf),
                           on_dead=jnp.inf,
                           domain=(0.0, None))}   # +inf = unreachable: legal
    if track_parents:
        state["parent"] = Field(jnp.int32,
                                init=lambda v: jnp.where(v.gid == source,
                                                         source, -1))

    def receive(vstate, inbox, has_msg, payload, node_ok):
        better = has_msg & (inbox < vstate["dist"]) & node_ok
        out = dict(vstate)
        out["dist"] = jnp.where(better, inbox, vstate["dist"])
        if track_parents and payload is not None:
            out["parent"] = jnp.where(better, payload, vstate["parent"])
        return out, better

    return DiffusiveProgram(
        monoid="min",
        msg_dtype=jnp.float32,
        state=state,
        init_active=lambda v: v.gid == source,
        emit=lambda s, weight, src_gid, dst_gid: s["dist"] + weight,
        receive=receive,
        payload=(lambda s, src_gid: src_gid) if track_parents else None,
        priority=lambda vstate: vstate["dist"],
    )


@diffusive("bfs", value_key="dist", monotone=True, lane_param="source")
def bfs(source: int) -> DiffusiveProgram:
    """BFS = SSSP with unit edge messages (level = hops)."""
    def receive(vstate, inbox, has_msg, payload, node_ok):
        better = has_msg & (inbox < vstate["dist"]) & node_ok
        return {"dist": jnp.where(better, inbox, vstate["dist"])}, better

    return DiffusiveProgram(
        monoid="min",
        msg_dtype=jnp.float32,
        state={"dist": Field(jnp.float32,
                             init=lambda v: jnp.where(v.gid == source, 0.0,
                                                      jnp.inf),
                             on_dead=jnp.inf,
                             domain=(0.0, None))},
        init_active=lambda v: v.gid == source,
        emit=lambda s, weight, src_gid, dst_gid: s["dist"] + 1.0,
        receive=receive,
    )


@diffusive("cc", value_key="comp", repair="component", monotone=True)
def cc() -> DiffusiveProgram:
    """Connected components by min-label diffusion (all vertices start
    active)."""
    def receive(vstate, inbox, has_msg, payload, node_ok):
        better = has_msg & (inbox < vstate["comp"]) & node_ok
        return {"comp": jnp.where(better, inbox, vstate["comp"])}, better

    return DiffusiveProgram(
        monoid="min",
        msg_dtype=jnp.int32,
        state={"comp": Field(jnp.int32, init=lambda v: v.gid,
                             on_dead=jnp.iinfo(jnp.int32).max)},
        emit=lambda s, weight, src_gid, dst_gid: s["comp"],
        receive=receive,
    )


def _push_spec(residual_init, active_init, alpha: float, eps: float):
    """Shared forward-push schema for PPR / PageRank (sum-combine)."""
    def on_send(vstate, sent):
        rank = vstate["rank"] + jnp.where(sent, alpha * vstate["residual"],
                                          0.0)
        residual = jnp.where(sent, 0.0, vstate["residual"])
        return {"rank": rank, "residual": residual, "deg": vstate["deg"]}

    def receive(vstate, inbox, has_msg, payload, node_ok):
        residual = vstate["residual"] + jnp.where(has_msg, inbox, 0.0)
        residual = jnp.where(node_ok, residual, 0.0)
        out = dict(vstate)
        out["residual"] = residual
        return out, (residual > eps) & node_ok

    return DiffusiveProgram(
        monoid="sum",
        msg_dtype=jnp.float32,
        state={
            # domains are deliberately loose (total mass is 1, so 2.0
            # can never trip on legitimate float error) — the guard is
            # for Inf/NaN/garbage, not tight numerics
            "rank": Field(jnp.float32, init=0.0, domain=(0.0, 2.0)),
            "residual": Field(jnp.float32, init=residual_init, on_dead=0.0,
                              domain=(0.0, 2.0)),
            "deg": Field(jnp.float32,
                         init=lambda v: jnp.maximum(v.out_degree, 1),
                         domain=(1.0, None)),
        },
        init_active=active_init,
        emit=lambda s, weight, src_gid, dst_gid:
            (1.0 - alpha) * s["residual"] / s["deg"],
        on_send=on_send,
        receive=receive,
    )


@diffusive("ppr", value_key="rank", lane_param="source")
def ppr(source: int, alpha: float = 0.15, eps: float = 1e-4) -> DiffusiveProgram:
    """Personalized PageRank by forward push — a *sum-combine* diffusion.

    Active vertex v: rank += alpha * r(v); pushes (1-alpha) * r(v) /
    deg(v) to each neighbor; r(v) = 0.  Receiver activates when
    r(u) > eps.  Monotone-terminating because total residual shrinks by
    alpha per push."""
    return _push_spec(
        residual_init=lambda v: jnp.where(v.gid == source, 1.0, 0.0),
        active_init=lambda v: v.gid == source,
        alpha=alpha, eps=eps,
    )


@diffusive("pagerank", value_key="rank")
def pagerank(alpha: float = 0.15, eps: float = 1e-6) -> DiffusiveProgram:
    """Global PageRank by forward push from a uniform start distribution.

    Fixed point: rank = alpha * sum_k (1-alpha)^k (W^T)^k u, i.e.
    PageRank with teleport alpha.  A sum-combine diffusion where every
    vertex is a source — the densest operon traffic the engine
    generates."""
    def uniform(v):
        n = jnp.maximum(jnp.sum(v.node_ok.astype(jnp.float32)), 1.0)
        return jnp.where(v.node_ok, 1.0 / n, 0.0)

    return _push_spec(residual_init=uniform, active_init=None,
                      alpha=alpha, eps=eps)


# --------------------------------------------------------------------------
# proof of extensibility: two programs written purely through the public
# extension point (no engine, kernel, or session changes)
# --------------------------------------------------------------------------

@diffusive("widest", value_key="width", monotone=True, lane_param="source")
def widest(source: int, track_parents: bool = False) -> DiffusiveProgram:
    """Widest path (max-bottleneck): the best path maximizes the minimum
    edge weight along it.  A *max-combine* selection diffusion —
    msg = min(width(src), w); predicate ``msg > width(v)``."""
    state = {"width": Field(jnp.float32,
                            init=lambda v: jnp.where(v.gid == source,
                                                     jnp.inf, -jnp.inf),
                            on_dead=-jnp.inf)}
    if track_parents:
        state["parent"] = Field(jnp.int32,
                                init=lambda v: jnp.where(v.gid == source,
                                                         source, -1))

    def receive(vstate, inbox, has_msg, payload, node_ok):
        better = has_msg & (inbox > vstate["width"]) & node_ok
        out = dict(vstate)
        out["width"] = jnp.where(better, inbox, vstate["width"])
        if track_parents and payload is not None:
            out["parent"] = jnp.where(better, payload, vstate["parent"])
        return out, better

    return DiffusiveProgram(
        monoid="max",
        msg_dtype=jnp.float32,
        state=state,
        init_active=lambda v: v.gid == source,
        emit=lambda s, weight, src_gid, dst_gid:
            jnp.minimum(s["width"], weight),
        receive=receive,
        payload=(lambda s, src_gid: src_gid) if track_parents else None,
        priority=lambda vstate: -vstate["width"],
    )


@diffusive("reach", value_key="reached", monotone=True)
def reach(sources) -> DiffusiveProgram:
    """Reachability from a vertex set: reached(v) = 1 iff some source
    reaches v.  Logical-or over {0, 1} — a max-class monoid — seeded from
    every source at once (one diffusion, not |sources| BFS runs)."""
    srcs = tuple(int(s) for s in sources)

    def in_set(v):
        return jnp.isin(v.gid, jnp.asarray(srcs, jnp.int32))

    def receive(vstate, inbox, has_msg, payload, node_ok):
        better = has_msg & (inbox > vstate["reached"]) & node_ok
        return ({"reached": jnp.where(better, inbox, vstate["reached"])},
                better)

    return DiffusiveProgram(
        monoid="max",
        msg_dtype=jnp.int32,
        state={"reached": Field(jnp.int32,
                                init=lambda v: in_set(v).astype(jnp.int32),
                                on_dead=0)},
        init_active=in_set,
        emit=lambda s, weight, src_gid, dst_gid: s["reached"],
        receive=receive,
    )


# --------------------------------------------------------------------------
# factory aliases (PR 1/2 call style: ``sssp_program(0)``)
# --------------------------------------------------------------------------

sssp_program = sssp.build
bfs_program = bfs.build
cc_program = cc.build
ppr_program = ppr.build
pagerank_program = pagerank.build
widest_program = widest.build
reach_program = reach.build
