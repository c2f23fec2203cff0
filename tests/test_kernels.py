"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import attention, decode_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_attention.xla_flash import mea_attention
from repro.kernels.segment_reduce.ops import segment_sum
from repro.kernels.sssp_relax.ops import relax
from repro.kernels.sssp_relax.ref import relax_ref


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 4, 4, 128, 64),    # MHA
    (2, 8, 2, 200, 64),    # GQA + padding path
    (1, 8, 1, 256, 32),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, hq, hkv, s, d, dtype, causal):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, hq, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    out = attention(q, k, v, causal=causal, backend="interpret")
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


def test_flash_attention_softcap():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 4, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
    out = attention(q, k, v, softcap=20.0, backend="interpret")
    ref = attention_ref(q, k, v, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_mea_attention_grads_match_oracle():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 4, 96, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 96, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 96, 32)), jnp.float32)

    def loss(fn):
        return lambda q_, k_, v_: (fn(q_, k_, v_) ** 2).sum()

    g1 = jax.grad(loss(lambda a, b, c: mea_attention(a, b, c, True, 0.0, 32,
                                                     None)),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda a, b, c: attention_ref(a, b, c, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_decode_attention_matches_last_position():
    rng = np.random.default_rng(3)
    b, hq, hkv, s, d = 2, 8, 2, 64, 32
    q = jnp.asarray(rng.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    kc = jnp.pad(k, ((0, 0), (0, 0), (0, 16), (0, 0)))
    vc = jnp.pad(v, ((0, 0), (0, 0), (0, 16), (0, 0)))
    out = decode_attention(q[:, :, -1:], kc, vc, cache_len=s)
    ref = attention_ref(q, k, v, causal=True)[:, :, -1:]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("e,f,n", [(64, 8, 13), (1000, 32, 77),
                                   (257, 1, 300), (128, 128, 5)])
def test_segment_sum_sweep(e, f, n):
    rng = np.random.default_rng(4)
    ids = rng.integers(0, n, e).astype(np.int32)
    vals = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    out = segment_sum(vals, jnp.asarray(ids), n, backend="interpret")
    # unsorted wrapper sorts internally; compare against the raw jax oracle
    ref = jax.ops.segment_sum(vals, jnp.asarray(ids), num_segments=n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_segment_sum_gradient():
    rng = np.random.default_rng(5)
    ids = np.sort(rng.integers(0, 10, 100)).astype(np.int32)
    vals = jnp.asarray(rng.normal(size=(100, 4)), jnp.float32)

    def f(backend):
        return lambda v: (
            segment_sum(v, jnp.asarray(ids), 10, backend=backend) ** 2
        ).sum()

    g1 = jax.grad(f("interpret"))(vals)
    g2 = jax.grad(f("xla"))(vals)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


@pytest.mark.parametrize("family,prog_name", [
    ("erdos_renyi", "sssp"), ("scale_free", "ppr"), ("small_world", "cc"),
])
def test_edge_relax_backends_bitwise_per_cell(family, prog_name):
    """edge_relax: the Pallas kernel (interpret) and the XLA reference
    return bitwise-identical (table, cnt, pay) for one cell's relaxation
    sweep — the invariant the engine's backend= switch rests on."""
    from repro.core.diffuse import _sg_as_dict
    from repro.core.generators import make_graph_family
    from repro.core.programs import cc_program, ppr_program, sssp_program
    from repro.core import build
    from repro.kernels.edge_relax import edge_relax

    progs = {"sssp": sssp_program(0), "ppr": ppr_program(0),
             "cc": cc_program()}
    prog = progs[prog_name]
    rng = np.random.default_rng(11)
    src, dst, w, n = make_graph_family(family, 150, seed=11)
    part = build(src, dst, n, w, n_cells=3, edge_slack=0.2)
    sg = part.sg
    sgd = _sg_as_dict(sg)
    vstate, active = prog.init(sg)
    # a partially-active frontier exercises the send masking
    senders = jnp.asarray(rng.random((sg.n_shards, sg.n_per_shard)) < 0.6)
    senders = senders & active if prog_name != "sssp" else active
    n_keys = sg.n_shards * sg.n_per_shard
    for s in range(sg.n_shards):
        args = (jax.tree_util.tree_map(lambda a: a[s], vstate), senders[s],
                sgd["gid"][s], sgd["csr_key"][s], sgd["csr_src"][s],
                sgd["csr_weight"][s], sgd["csr_dst_gid"][s])
        tx, cx, px = edge_relax(prog, *args, n_keys=n_keys,
                                block_e=sg.csr_block, backend="xla")
        tp, cp, pp = edge_relax(prog, *args, n_keys=n_keys,
                                block_e=sg.csr_block, backend="pallas",
                                interpret=True)
        assert np.array_equal(np.asarray(cx), np.asarray(cp))
        ax, ap = np.asarray(tx), np.asarray(tp)
        both_inf = ~np.isfinite(ax) & ~np.isfinite(ap)
        assert np.array_equal(np.where(both_inf, 0, ax),
                              np.where(both_inf, 0, ap))
        assert (px is None) == (pp is None)
        if px is not None:
            assert np.array_equal(np.asarray(px), np.asarray(pp))


def test_edge_relax_empty_frontier_is_identity():
    from repro.core.diffuse import _sg_as_dict
    from repro.core.generators import make_graph_family
    from repro.core.msg import identity_for
    from repro.core.programs import sssp_program
    from repro.core import build
    from repro.kernels.edge_relax import edge_relax

    prog = sssp_program(0)
    src, dst, w, n = make_graph_family("erdos_renyi", 60, seed=2)
    part = build(src, dst, n, w, n_cells=2)
    sg = part.sg
    sgd = _sg_as_dict(sg)
    vstate, _ = prog.init(sg)
    n_keys = sg.n_shards * sg.n_per_shard
    none = jnp.zeros(sg.n_per_shard, bool)
    for backend in ("xla", "pallas"):
        t, c, p = edge_relax(
            prog, jax.tree_util.tree_map(lambda a: a[0], vstate), none,
            sgd["gid"][0], sgd["csr_key"][0], sgd["csr_src"][0],
            sgd["csr_weight"][0], sgd["csr_dst_gid"][0],
            n_keys=n_keys, block_e=sg.csr_block, backend=backend,
            interpret=True)
        ident = float(identity_for(prog.combine, prog.msg_dtype))
        assert (np.asarray(t) == ident).all()
        assert (np.asarray(c) == 0).all()
        assert (np.asarray(p) == -1).all()


@pytest.mark.parametrize("np_,e", [(50, 200), (300, 900), (128, 512)])
def test_relax_sweep(np_, e):
    rng = np.random.default_rng(6)
    dist = jnp.asarray(
        np.where(rng.random(np_) < 0.4, rng.random(np_) * 10, np.inf),
        jnp.float32,
    )
    active = jnp.asarray(rng.random(np_) < 0.5)
    src = jnp.asarray(rng.integers(0, np_, e), jnp.int32)
    dstv = np.sort(rng.integers(0, np_, e)).astype(np.int32)
    # mask some edges dead
    dstv[rng.random(e) < 0.1] = -1
    w = jnp.asarray(rng.random(e) * 5, jnp.float32)
    out = relax(dist, active, w, src, jnp.asarray(dstv), np_,
                backend="interpret")
    ref = relax_ref(dist, w, src, jnp.asarray(dstv), active, np_)
    both_inf = np.isinf(np.asarray(out)) & np.isinf(np.asarray(ref))
    diff = np.where(both_inf, 0, np.asarray(out) - np.asarray(ref))
    assert np.max(np.abs(diff)) < 1e-5


# --------------------------------------------------------------------------
# dense pull sweep on xla: segmented scan + run-end gathers, no scatters
# --------------------------------------------------------------------------

def _live_eqns(jaxpr, used=None):
    """The equations of ``jaxpr`` (and of the jaxprs nested in them) that
    feed its used outputs — dead source reads left out."""
    used = [True] * len(jaxpr.outvars) if used is None else used
    live = {id(v) for v, u in zip(jaxpr.outvars, used) if u}
    found = []
    for eqn in reversed(jaxpr.eqns):
        outs = [id(v) in live for v in eqn.outvars]
        if not any(outs):
            continue
        live.update(id(v) for v in eqn.invars)
        found.append(eqn)
        for p in eqn.params.values():
            if hasattr(p, "eqns"):
                inner = getattr(p, "jaxpr", p)
                found += _live_eqns(
                    inner, outs if len(inner.outvars) == len(outs) else None)
    return found


@pytest.mark.parametrize("delta_e", [0, 256])
@pytest.mark.parametrize("name,n_reads", [("sssp", 1), ("bfs", 1)])
def test_pull_sweep_has_no_stream_scatters(name, n_reads, delta_e):
    """The dense single-query pull sweep on xla reads the sorted region
    with elementwise scan levels and one gather per destination: its only
    E-long gather is the one source read — the sender flag rides in the
    sign bit of ``dist`` and the source gid arrives as the
    ``csr_src_gid`` stream — and only the delta segment takes
    scatters."""
    import types

    from repro.core.programs import PROGRAMS
    from repro.kernels.edge_relax import edge_relax

    prog = PROGRAMS[name].factory(source=0)
    np_, n_keys, es = 96, 2 * 96, 128 * 24
    e = es + delta_e
    sds = jax.ShapeDtypeStruct
    vstate = jax.eval_shape(
        lambda g: prog.init(types.SimpleNamespace(
            gid=g, node_ok=g >= 0, out_degree=g))[0],
        sds((1, np_), jnp.int32))
    vstate = jax.tree_util.tree_map(lambda a: sds((np_,), a.dtype), vstate)

    def sweep(vs, senders, gid, key, src, w, dg, run_end, src_gid):
        return edge_relax(prog, vs, senders, gid, key, src, w, dg,
                          n_keys=n_keys, block_e=128, backend="xla",
                          delta_e=delta_e, run_end=run_end,
                          src_gid=src_gid)

    closed = jax.make_jaxpr(sweep)(
        vstate, sds((np_,), bool), sds((np_,), jnp.int32),
        sds((e,), jnp.int32), sds((e,), jnp.int32), sds((e,), jnp.float32),
        sds((e,), jnp.int32), sds((n_keys,), jnp.int32),
        sds((e,), jnp.int32))
    eqns = _live_eqns(closed.jaxpr)
    n_idx = lambda eqn: int(np.prod(eqn.invars[1].aval.shape[:-1]))
    long_gathers = [q for q in eqns if q.primitive.name == "gather"
                    and n_idx(q) >= es]
    assert len(long_gathers) == n_reads == prog.source_reads().gathers
    scatters = [q for q in eqns if q.primitive.name.startswith("scatter")]
    if not delta_e:
        assert scatters == []
    else:
        assert scatters
        assert all(q.invars[2].aval.size == delta_e for q in scatters)


def _source_state(prog, rng, shape, np_):
    """A random vertex state of ``prog``'s Field schema: floats mix +inf,
    0.0 and positive values (and -inf and negatives where the field
    declares no lower bound), ints are ids."""
    vstate = {}
    for fname, f in prog.fields:
        if np.dtype(f.dtype).kind == "f":
            v = (rng.random(shape) * 4).astype(np.float32)
            v[rng.random(shape) < 0.25] = np.inf
            v[rng.random(shape) < 0.15] = 0.0
            if f.domain is None or f.domain[0] is None:
                v[rng.random(shape) < 0.2] *= -1
        else:
            v = rng.integers(0, np_, shape).astype(np.int32)
        vstate[fname] = jnp.asarray(v)
    return vstate


@pytest.mark.parametrize("name,kw,lanes,delta_e,n_gathers", [
    ("sssp", {"source": 0}, None, 0, 1),
    ("sssp", {"source": 0}, None, 256, 1),
    ("sssp", {"source": 0}, 3, 256, 1),
    ("bfs", {"source": 0}, None, 256, 1),
    ("bfs", {"source": 0}, 2, 0, 1),
    ("cc", {}, None, 256, 2),
    ("widest", {"source": 0, "track_parents": True}, None, 256, 2),
    ("ppr", {"source": 0}, None, 0, 2),
])
def test_packed_source_read_matches_unpacked(name, kw, lanes, delta_e,
                                             n_gathers):
    """The dense sweep's source read — the sender flag in the sign bit
    of a non-negative state word, the source gid from the
    ``csr_src_gid`` stream — returns bitwise the (table, cnt, pay) of
    the body that gathers every leaf, the flag and the gid apart; the
    programs without such a word (cc, widest) and the laned runs
    included.  ``/repro/source_gathers`` counts the live E-long source
    gathers of the sweep."""
    from repro.core import trace
    from repro.core.msg import run_ends
    from repro.core.programs import PROGRAMS, make_laned
    from repro.core.relax import make_relax
    from repro.kernels.edge_relax import edge_relax
    from repro.kernels.edge_relax.ref import emit_messages, stream_combine

    rng = np.random.default_rng(17)
    np_, s_, es = 160, 2, 128 * 12
    n_keys = s_ * np_
    prog = PROGRAMS[name].factory(**kw)
    if lanes:
        prog = make_laned([PROGRAMS[name].factory(**{**kw, "source": i})
                           for i in range(lanes)])
    shape = (lanes, np_) if lanes else (np_,)
    key, skey, src, w = _pull_cell(rng, np_, s_, es, delta_e, hub_run=300)
    gid = jnp.asarray(rng.permutation(np_ * 4)[:np_].astype(np.int32))
    dst_gid = jnp.where(skey >= 0, skey + 7, -1)
    vstate = _source_state(prog, rng, shape, np_)
    senders = jnp.asarray(rng.random(shape) < 0.6)
    flag = prog.source_reads().flag_leaf
    if flag is not None:
        word, sent = np.asarray(vstate[flag]), np.asarray(senders)
        assert (sent & np.isposinf(word)).any()
        assert (sent & (word == 0)).any() and (~sent & (word == 0)).any()
    run_end = run_ends(skey[:es], n_keys)

    def packed(vstate, senders, src_gid):
        return edge_relax(prog, vstate, senders, gid, key, src, w, dst_gid,
                          n_keys=n_keys, block_e=128, backend="xla",
                          skey=skey, delta_e=delta_e, run_end=run_end,
                          src_gid=src_gid)

    out = packed(vstate, senders, gid[src])
    src_state = jax.tree_util.tree_map(lambda a: a[..., src], vstate)
    cand, send, pay = emit_messages(prog, src_state, senders[..., src],
                                    gid[src], key, w, dst_gid)
    ref = stream_combine(prog, cand, send, pay, key, skey, n_keys, delta_e,
                         run_end)
    assert (out[2] is None) == (ref[2] is None) == (not prog.with_payload)
    for a, b in zip(out, ref):
        if b is not None:
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert int(np.asarray(ref[1]).sum()) > 0

    seen = []

    def on_scalar(event, value, **tags):
        if event == trace.SOURCE_GATHERS:
            seen.append((tags["program"], value))

    jax.monitoring.register_scalar_listener(on_scalar)
    try:
        make_relax(prog, s_, np_, 128, delta_e=delta_e)
    finally:
        jax.monitoring.unregister_scalar_listener(on_scalar)
    assert seen == [(prog.name, n_gathers)]
    closed = jax.make_jaxpr(packed)(vstate, senders, gid[src])
    long_gathers = [q for q in _live_eqns(closed.jaxpr)
                    if q.primitive.name == "gather"
                    and int(np.prod(q.invars[1].aval.shape[:-1])) >= es]
    assert len(long_gathers) == n_gathers


def _pull_cell(rng, np_, s_, es, delta_e, hub_run):
    """One cell's streams: a destination-sorted region of width ``es``
    holding a hub run of ``hub_run`` edges, trailing padding, tombstones,
    and an unsorted delta segment of ``delta_e`` positions."""
    n_keys = s_ * np_
    n_live = es - 100
    keys = np.sort(np.concatenate([
        np.full(hub_run, 37), rng.integers(0, n_keys, n_live - hub_run)]))
    skey = np.concatenate([keys, np.full(es - n_live, -1)])
    dkey = rng.integers(-1, n_keys, delta_e)
    skey = np.concatenate([skey, dkey]).astype(np.int32)
    key = np.where(rng.random(es + delta_e) < 0.1, -1, skey)   # tombstones
    key[es:] = dkey
    src = rng.integers(0, np_, es + delta_e).astype(np.int32)
    # small whole numbers: many exact ties in the combined value
    w = rng.integers(1, 4, es + delta_e).astype(np.float32)
    return (jnp.asarray(key), jnp.asarray(skey), jnp.asarray(src),
            jnp.asarray(w))


@pytest.mark.parametrize("delta_e", [0, 384])
@pytest.mark.parametrize("name", ["sssp", "widest"])
def test_pull_scan_matches_flat_combine(name, delta_e):
    """The xla pull sweep (segmented scan + precomputed run ends) returns
    bitwise the tables of the unsorted segment combine over the same
    messages: values, sending counts, and the payload tie-break (max
    payload among the winners)."""
    from repro.core.msg import run_ends
    from repro.core.programs import PROGRAMS
    from repro.kernels.edge_relax import edge_relax
    from repro.kernels.edge_relax.ref import edge_messages, flat_combine

    rng = np.random.default_rng(15)
    np_, s_, es = 200, 2, 128 * 14
    n_keys = s_ * np_
    prog = PROGRAMS[name].factory(source=0)
    key, skey, src, w = _pull_cell(rng, np_, s_, es, delta_e, hub_run=1200)
    dst_gid = skey                     # read by neither program's emit
    assert int((np.asarray(skey[:es]) == 37).sum()) > 8 * 128
    gid = jnp.arange(np_, dtype=jnp.int32) + 1000
    field = "dist" if name == "sssp" else "width"
    vals = rng.integers(0, 5, np_).astype(np.float32)
    vals[rng.random(np_) < 0.2] = np.inf if name == "sssp" else -np.inf
    vstate = {field: jnp.asarray(vals),
              "parent": jnp.full((np_,), -1, jnp.int32)}
    senders = jnp.asarray(rng.random(np_) < 0.7)
    run_end = run_ends(skey[:es], n_keys)

    out = edge_relax(prog, vstate, senders, gid, key, src, w, dst_gid,
                     n_keys=n_keys, block_e=128, backend="xla", skey=skey,
                     delta_e=delta_e, run_end=run_end)
    cand, send, pay = edge_messages(prog, vstate, senders, gid, key, src,
                                    w, dst_gid)
    ref = flat_combine(cand, send, pay,
                       jnp.where(send, key, n_keys), n_keys, prog.combine)
    for a, b in zip(out, ref):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    cnt = np.asarray(ref[1])
    assert cnt[37] > 8 * 128 * 0.5                   # the hub's run sends
    # ties: some destination has several winning senders
    table = np.asarray(ref[0])
    win = np.asarray(send) & (np.asarray(cand) == table[
        np.clip(np.asarray(key), 0, n_keys - 1)]) & (np.asarray(key) >= 0)
    assert np.bincount(np.asarray(key)[win], minlength=n_keys).max() > 1
    # the run layout computed in the sweep when not given
    again = edge_relax(prog, vstate, senders, gid, key, src, w, dst_gid,
                       n_keys=n_keys, block_e=128, backend="xla", skey=skey,
                       delta_e=delta_e)
    for a, b in zip(out, again):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _gather_carry_scan(comb, elems):
    """``_block_scan`` with each block's carry read by an E-long gather
    (its earlier form): the reference for the carry broadcast."""
    from repro.kernels.edge_relax.ref import SCAN_BLOCK as b

    e = elems[0].shape[-1]
    lane = jnp.arange(e, dtype=jnp.int32) % b
    d = 1
    while d < min(b, e):
        right = comb(tuple(a[..., :-d] for a in elems),
                     tuple(a[..., d:] for a in elems))
        keep = lane[d:] >= d
        elems = tuple(
            jnp.concatenate([a[..., :d], jnp.where(keep, r, a[..., d:])],
                            axis=-1)
            for a, r in zip(elems, right))
        d *= 2
    if e <= b:
        return elems
    nb = -(-e // b)
    last = jnp.minimum(jnp.arange(nb, dtype=jnp.int32) * b + (b - 1), e - 1)
    tot = _gather_carry_scan(comb, tuple(jnp.take(a, last, axis=-1)
                                         for a in elems))
    blk = jnp.arange(e, dtype=jnp.int32) // b
    carry = tuple(jnp.take(t, jnp.maximum(blk - 1, 0), axis=-1) for t in tot)
    folded = comb(carry, elems)
    return tuple(jnp.where(blk >= 1, f, a) for f, a in zip(folded, elems))


@pytest.mark.parametrize("e", [1, 300, 128 * 128 + 77, 3 * 128 * 128])
def test_block_scan_carry_broadcast_is_bitwise(e):
    """The broadcast carries give bitwise the floating sums of the
    gathered carries (same fold, same order) at one, two and three
    levels, laned, and the exact per-run counts."""
    from repro.core.monoid import MONOIDS
    from repro.kernels.edge_relax.ref import _block_scan, stream_scan

    rng = np.random.default_rng(e)
    key = jnp.asarray(np.sort(rng.integers(0, max(e // 50, 1), e)),
                      jnp.int32)
    cand = jnp.asarray(rng.standard_normal((3, e)), jnp.float32)
    send = jnp.asarray(rng.random((3, e)) < 0.8)
    v, c, _ = jax.jit(lambda *a: stream_scan(MONOIDS["sum"], *a, None))(
        cand, send, key)

    def comb(a, b):
        return (jnp.where(b[2], b[0], a[0] + b[0]),
                jnp.where(b[2], b[1], a[1] + b[1]), a[2] | b[2])

    start = jnp.broadcast_to(
        key != jnp.concatenate([jnp.full((1,), -2, jnp.int32), key[:-1]]),
        cand.shape)
    cnt = send.astype(jnp.int32)
    rv, rc, _ = jax.jit(lambda *a: _gather_carry_scan(comb, a))(
        cand, cnt, start)
    nv, nc, _ = jax.jit(lambda *a: _block_scan(comb, a))(cand, cnt, start)
    assert np.array_equal(np.asarray(v), np.asarray(rv))
    assert np.array_equal(np.asarray(nv), np.asarray(rv))
    assert np.array_equal(np.asarray(nc), np.asarray(rc))
    # counts are exact: the inclusive per-run prefix count
    k, s = np.asarray(key), np.asarray(send)
    first = np.searchsorted(k, k, side="left")
    csum = np.cumsum(s, axis=-1)
    before = np.where(first > 0, csum[:, np.maximum(first - 1, 0)], 0)
    assert np.array_equal(np.asarray(c), csum - before)
