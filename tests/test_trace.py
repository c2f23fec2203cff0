"""Spans, host reads and device scopes of the served path (core/trace.py).

* every query is the host span ``repro.query`` holding the stages
  prepare, dispatch, budget and result, on the profiler's clock;
* every device-to-host read of the served path is one counted
  ``to_host`` event, so the reads per query are exact;
* the diffusion loop's ops carry the four ``diffuse.*`` scopes in their
  compiled ``op_name`` metadata, in the sharded and the spmd engine;
* a commit runs the stages journal, apply, repair.<strategy> and compact.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import trace
from repro.core.diffuse import _run_rounds, diffuse, initial_state
from repro.core.generators import make_graph_family
from repro.core.programs import PROGRAMS
from repro.core.session import DiffusionSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _session(n=256, **kw):
    src, dst, w, n = make_graph_family("graph500", n, seed=1)
    return DiffusionSession.from_edges(src, dst, n, w, **kw), (src, dst, w, n)


class _Events:
    """The trace module's jax.monitoring events while the block runs:
    host reads as (site, bytes), stages as their names."""

    def __enter__(self):
        self.reads: list[tuple[str, int]] = []
        self.stages: list[str] = []
        jax.monitoring.register_scalar_listener(self._on_scalar)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_scalar_listener(self._on_scalar)
        jax.monitoring.unregister_event_duration_listener(self._on_dur)

    def _on_scalar(self, event, value, **kw):
        if event == trace.HOST_READ_BYTES:
            self.reads.append((kw["site"], value))

    def _on_dur(self, event, secs, **_):
        if event.startswith(trace.EVENT_PREFIX):
            assert secs >= 0
            self.stages.append(event[len(trace.EVENT_PREFIX):])

    def sites(self):
        return collections.Counter(s for s, _ in self.reads)


_SCOPE_RE = re.compile("|".join(re.escape(s) for s in trace.SCOPES))


def _op_names(hlo_text: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def scope_of(op_name: str | None) -> str | None:
    """The innermost scope of an op's ``op_name`` metadata, or None."""
    found = _SCOPE_RE.findall(op_name or "")
    return found[-1] if found else None


# ---------------------------------------------------------------------------
# the helpers themselves
# ---------------------------------------------------------------------------

def test_stage_records_its_duration_even_when_the_block_raises():
    with _Events() as ev:
        with trace.stage("repro.test.ok"):
            pass
        with pytest.raises(KeyError):
            with trace.stage("repro.test.raised"):
                raise KeyError("boom")
    assert ev.stages == ["repro.test.ok", "repro.test.raised"]


def test_to_host_counts_device_reads_only():
    dev = jax.numpy.arange(6, dtype=jax.numpy.int32)
    host = np.arange(6, dtype=np.int32)
    with _Events() as ev:
        a = trace.to_host(dev, "here")
        b = trace.to_host(host, "there")
    assert isinstance(a, np.ndarray) and np.array_equal(a, host)
    assert b is host
    assert ev.reads == [("here", 24)]


# ---------------------------------------------------------------------------
# query spans on the profiler's clock
# ---------------------------------------------------------------------------

def _host_spans(log_dir: str):
    """(name, start, end) of the host's bench.* and repro.* events in the
    newest trace under ``log_dir``, by start time."""
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(("bench.", "repro."))]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


@pytest.mark.parametrize("prog, kw", [
    ("sssp", {"source": 3}),
    ("bfs", {"source": 3}),
    ("sssp", {"sources": [3, 5]}),
], ids=["sssp", "bfs", "sssp-lanes"])
def test_profiled_query_nests_its_stages_in_the_caller_span(tmp_path, prog,
                                                            kw):
    sess, _ = _session()
    sess.query(prog, **kw)                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.query"):
            sess.query(prog, refresh=True, **kw)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = [n for n, _, _ in spans]
    assert names == ["bench.query", trace.QUERY, *trace.QUERY_STAGES]
    (_, c0, c1), (_, q0, q1) = spans[:2]
    assert c0 <= q0 and q1 <= c1
    prev = q0
    for _, s0, s1 in spans[2:]:                 # in order, inside the query
        assert prev <= s0 <= s1 <= q1
        prev = s1


def test_cache_hit_runs_only_the_result_stage():
    sess, _ = _session()
    sess.query("sssp", source=0)
    with _Events() as ev:
        sess.query("sssp", source=0)
    assert ev.stages == [trace.QUERY_RESULT, trace.QUERY]


# ---------------------------------------------------------------------------
# the staged query is diffuse(), split at the dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name, sweep, delta", [
    ("sssp", "pull", None),
    ("bfs", "pull", None),
    ("sssp", "auto", None),
    ("sssp", "pull", 0.25),
], ids=["sssp", "bfs", "sssp-auto", "sssp-delta"])
def test_staged_query_is_diffuse_with_one_compile(name, sweep, delta):
    sess, _ = _session()
    spec = PROGRAMS[name]
    vstate, stats = diffuse(sess.sg, spec.factory(source=3),
                            max_local_iters=sess.max_local_iters,
                            max_rounds=sess.max_rounds, delta=delta,
                            backend=sess.backend, sweep=sweep)
    before = _run_rounds._cache_size()
    res = sess.query(name, source=3, sweep=sweep, delta=delta)
    assert _run_rounds._cache_size() == before
    assert np.array_equal(res.values, sess.to_global(vstate[spec.value_key]))
    assert (int(res.stats.rounds), int(res.stats.local_iters)) == (
        int(stats.rounds), int(stats.local_iters))


def test_spmd_restart_repair_runs_the_query_function():
    """A restart repair on the spmd engine reuses the shard_map function
    its query built, and reaches the fresh query's fixed point."""
    sess, (src, dst, w, n) = _session(n=128, n_cells=1)
    sess.query("ppr", engine="spmd", source=0, eps=1e-4)
    fns = dict(sess._spmd_fns)
    sess.delete_edge(int(src[0]), int(dst[0]))
    info = sess.commit()
    assert {v[0] for v in info.repairs.values()} == {"restart"}
    assert sess._spmd_fns == fns
    repaired = sess.query("ppr", engine="spmd", source=0, eps=1e-4)
    fresh = sess.query("ppr", engine="spmd", source=0, eps=1e-4,
                       refresh=True)
    assert np.array_equal(repaired.values, fresh.values)


# ---------------------------------------------------------------------------
# host reads per query, exactly
# ---------------------------------------------------------------------------

# Without a name server the layout (owner, local) lives on the device and
# every to_global / live_ids reads it again; sssp holds dist and parent.
READS = {
    "sssp": {"budget": 1, "to_global": 2, "live_ids": 2, "layout": 6},
    "bfs": {"budget": 1, "to_global": 1, "live_ids": 2, "layout": 4},
}


@pytest.mark.parametrize("prog", ["sssp", "bfs"])
def test_reads_per_query_are_exact(prog):
    sess, _ = _session()
    sess.query(prog, source=0)                  # compile
    with _Events() as miss:
        sess.query(prog, source=5)
    assert miss.sites() == READS[prog]
    with _Events() as hit:
        sess.query(prog, source=5)
    # a hit reads what _result reads and nothing else
    result_only = dict(READS[prog], budget=0)
    assert hit.sites() == {k: v for k, v in result_only.items() if v}
    nbytes = dict(hit.reads)
    assert nbytes["live_ids"] == sess.sg.gid.nbytes


def test_reads_with_a_name_server_skip_the_layout():
    sess, _ = _session()
    sess.ns                                     # host-resident layout
    sess.query("bfs", source=0)
    with _Events() as ev:
        sess.query("bfs", source=2)
    assert ev.sites() == {"budget": 1, "to_global": 1, "live_ids": 2}


def test_served_reads_pass_the_transfer_guard(sanitize):
    sess, _ = _session()
    sess.query("sssp", source=0)
    with _Events() as ev, sanitize():
        sess.query("sssp", source=9)
    assert sum(ev.sites().values()) == sum(READS["sssp"].values())


# ---------------------------------------------------------------------------
# device scopes in the compiled loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sweep", ["pull", "auto"])
def test_run_rounds_hlo_carries_all_four_scopes(sweep):
    sess, _ = _session()
    prog = PROGRAMS["sssp"].factory(source=0)
    v0, a0 = initial_state(sess.sg, prog)
    text = _run_rounds.lower(sess.sg, prog, v0, a0, 64, 10_000, None, "xla",
                             sweep).compile().as_text()
    scopes = collections.Counter(scope_of(n) for n in _op_names(text))
    assert set(trace.SCOPES) <= set(scopes)
    # the loop body is scoped: every op inside the inner while's body is
    relax_ops = [n for n in _op_names(text)
                 if "while/body/while/body" in n]
    assert relax_ops
    assert all(scope_of(n) for n in relax_ops
               if not n.endswith("/while"))


def test_spmd_step_hlo_carries_all_four_scopes_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.core.diffuse import (_sg_as_dict, initial_state,
                                        make_spmd_diffuse)
        from repro.core.generators import make_graph_family
        from repro.core.programs import PROGRAMS
        from repro.core.session import DiffusionSession
        from repro.launch.mesh import make_mesh

        src, dst, w, n = make_graph_family("graph500", 256, seed=1)
        sess = DiffusionSession.from_edges(src, dst, n, w, n_cells=4)
        prog = PROGRAMS["sssp"].factory(source=0)
        fn = make_spmd_diffuse(make_mesh((4,), ("cells",)), prog, sess.sg)
        print(fn.lower(_sg_as_dict(sess.sg), *initial_state(sess.sg, prog))
              .compile().as_text())
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr
    names = _op_names(out.stdout)
    assert set(trace.SCOPES) <= {scope_of(x) for x in names}
    # the exchange is the all_to_all between devices
    a2a = [x for x in names if "all_to_all" in x]
    assert a2a and all(scope_of(x) == trace.SCOPE_EXCHANGE for x in a2a)


# ---------------------------------------------------------------------------
# commit stages and the repair's per-delete reads
# ---------------------------------------------------------------------------

def test_commit_with_deletes_emits_its_stages_and_reads(tmp_path):
    sess, (src, dst, w, n) = _session(n=128, journal_fsync="always")
    sess.save(str(tmp_path / "store"))
    for name, kw in (("sssp", {"source": 0}), ("cc", {}),
                     ("ppr", {"source": 0, "eps": 1e-4})):
        sess.query(name, **kw)
    dels = list(dict.fromkeys(zip(src[:6].tolist(), dst[:6].tolist())))
    for u, v in dels:
        sess.delete_edge(u, v)
    with _Events() as ev:
        info = sess.commit()
    assert {v[0] for v in info.repairs.values()} == {
        "parents", "component", "restart"}
    stages = set(ev.stages)
    for name in (trace.COMMIT_JOURNAL, trace.COMMIT_APPLY,
                 trace.COMMIT_COMPACT,
                 f"{trace.COMMIT_REPAIR}.parents",
                 f"{trace.COMMIT_REPAIR}.component",
                 f"{trace.COMMIT_REPAIR}.restart"):
        assert name in stages, (name, stages)
    # one parent read per deleted edge, one label read per endpoint
    sites = ev.sites()
    assert sites["repair.parents"] == len(dels)
    assert sites["repair.component"] == 2 * len(dels)
    sess.close()


# ---------------------------------------------------------------------------
# spmd: placing a graph version, and the bytes of the exchange
# ---------------------------------------------------------------------------

_SPMD_TRACE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import numpy as np
    from repro.core import trace
    from repro.core.generators import make_graph_family
    from repro.core.programs import PROGRAMS
    from repro.core.session import DiffusionSession

    places, xbytes = [], []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, s, **kw: places.append(ev)
        if ev == trace.EVENT_PREFIX + trace.PLACE else None)
    jax.monitoring.register_scalar_listener(
        lambda ev, v, **kw: xbytes.append(v)
        if ev == trace.EXCHANGE_BYTES else None)

    src, dst, w, n = make_graph_family("graph500", 256, seed=1)
    sess = DiffusionSession.from_edges(src, dst, n, w, engine="spmd",
                                       sweep="pull", edge_slack=0.2)
    out = {"built": len(places), "np": sess.sg.n_per_shard, "queries": []}
    for name, kw in (("sssp", {"source": 0}), ("bfs", {"source": 3}),
                     ("sssp", {"source": 5})):
        k = len(xbytes)
        res = sess.query(name, sweep="pull", **kw)
        prog = PROGRAMS[name].factory(**kw)
        out["queries"].append({
            "name": name, "rounds": int(res.stats.rounds),
            "events": xbytes[k:],
            "slot": int(np.dtype(prog.msg_dtype).itemsize) + 1
            + 4 * (prog.payload is not None)})
    out["queried"] = len(places)
    sess.delete_edge(int(src[0]), int(dst[0]))
    sess.add_edge(0, 7, 0.5)
    sess.commit()
    out["committed"] = len(places)
    sess.query("ppr", source=0, eps=1e-4, sweep="pull")   # compacts first
    out["compacted"] = len(places)
    sess.query("ppr", source=1, eps=1e-4, sweep="pull")
    out["clean"] = len(places)
    print("SPMD_TRACE " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def spmd_trace():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SPMD_TRACE],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("SPMD_TRACE ")]
    assert line, out.stderr[-4000:]
    return json.loads(line[0].split(" ", 1)[1])


def test_spmd_place_span_once_per_compaction(spmd_trace):
    """Construction, a commit and a sum program's compaction each place
    the new graph version once; queries on a placed version place
    nothing."""
    t = spmd_trace
    assert (t["built"], t["queried"], t["committed"], t["compacted"],
            t["clean"]) == (1, 1, 2, 3, 3)


def test_spmd_exchange_bytes_are_rounds_times_shapes(spmd_trace):
    """One event per dispatch: rounds x 4 devices x 3 peers x Np slots x
    (value + has flag + payload)."""
    np_ = spmd_trace["np"]
    for q in spmd_trace["queries"]:
        assert q["events"] == [q["rounds"] * 4 * 3 * np_ * q["slot"]], q
    assert spmd_trace["queries"][0]["slot"] == 4 + 1 + 4   # dist + parent
