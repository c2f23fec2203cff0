"""Model-level correctness: transformer serving equivalence, MoE
conservation, equivariance, chunked/SPMD path equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.sharding import sharding_context
from repro.launch.mesh import make_mesh
from repro.models.gnn.common import GraphBatch
from repro.models.gnn import equiformer_v2 as eqv2
from repro.models.gnn import gatedgcn, mace, meshgraphnet
from repro.models.moe import MoEConfig, init_moe, moe_ffn
from repro.models import recsys
from repro.models.transformer import (
    TransformerConfig,
    decode_step,
    forward,
    init_params,
    loss_fn,
    prefill,
)


def _graph(rng, n=40, e=128, with_geometry=True):
    kw = dict(
        senders=jnp.asarray(rng.integers(0, n, e), jnp.int32),
        receivers=jnp.asarray(rng.integers(0, n, e), jnp.int32),
        n_nodes=n,
    )
    if with_geometry:
        kw["positions"] = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
        kw["species"] = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
    return kw


@pytest.mark.parametrize("moe", [False, True])
@pytest.mark.parametrize("parallel_block", [False, True])
def test_transformer_decode_matches_forward(moe, parallel_block):
    cfg = TransformerConfig(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=97,
        parallel_block=parallel_block,
        moe=MoEConfig(4, 2, 96) if moe else None,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 97)
    lg_pre, cache = prefill(params, toks, cfg, max_len=16)
    nxt = toks[:, -1:] * 0 + 5
    lg_dec, _ = decode_step(params, nxt, cache, 12, cfg)
    full, _ = forward(params, jnp.concatenate([toks, nxt], 1), cfg)
    np.testing.assert_allclose(
        np.asarray(lg_dec[:, 0]), np.asarray(full[:, -1]), atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(lg_pre[:, 0]), np.asarray(full[:, -2]), atol=2e-5
    )


def test_moe_token_conservation_and_impl_equivalence():
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff=32, capacity_factor=4.0)
    p = init_moe(jax.random.PRNGKey(0), 16, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    y1, aux = moe_ffn(p, x, cfg)
    y2, _ = moe_ffn(p, x, dataclasses.replace(cfg, impl="ragged"))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    # routing conservation: fractions sum to 1
    assert np.isclose(float(aux["router_frac"].sum()), 1.0, atol=1e-5)
    assert np.isclose(float(aux["router_probs_mean"].sum()), 1.0, atol=1e-5)


def test_moe_capacity_drops_tokens_gracefully():
    # tiny capacity: output must stay finite and bounded
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff=32, capacity_factor=0.25)
    p = init_moe(jax.random.PRNGKey(0), 16, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 16))
    y, _ = moe_ffn(p, x, cfg)
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("model,make_cfg", [
    (mace, lambda: mace.MACEConfig(n_layers=2, d_hidden=8, n_species=5)),
    (eqv2, lambda: eqv2.EquiformerV2Config(
        n_layers=2, d_hidden=16, l_max=3, n_heads=2, n_species=5, d_out=2)),
])
def test_rotation_invariance(model, make_cfg):
    rng = np.random.default_rng(0)
    cfg = make_cfg()
    kw = _graph(rng)
    Q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
    if np.linalg.det(Q) < 0:
        Q[:, 0] *= -1
    b1 = GraphBatch(**kw, graph_ids=jnp.zeros(40, jnp.int32), n_graphs=1)
    kw2 = dict(kw)
    kw2["positions"] = jnp.asarray(np.asarray(kw["positions"]) @ Q.T)
    b2 = GraphBatch(**kw2, graph_ids=jnp.zeros(40, jnp.int32), n_graphs=1)
    p = model.init_params(jax.random.PRNGKey(0), cfg)
    o1, o2 = model.apply(p, b1, cfg), model.apply(p, b2, cfg)
    scale = max(1.0, float(jnp.max(jnp.abs(o1))))
    assert float(jnp.max(jnp.abs(o1 - o2))) / scale < 1e-3


def test_eqv2_chunked_and_spmd_paths_match():
    rng = np.random.default_rng(1)
    cfg = eqv2.EquiformerV2Config(n_layers=2, d_hidden=16, l_max=2,
                                  n_heads=2, n_species=5, d_out=3,
                                  channel_groups=4)
    kw = _graph(rng, n=32, e=96)
    b = GraphBatch(**kw, labels=jnp.asarray(rng.integers(0, 3, 32),
                                            jnp.int32))
    p = eqv2.init_params(jax.random.PRNGKey(0), cfg)
    o1 = eqv2.apply(p, b, cfg)
    o2 = eqv2.apply(p, b, dataclasses.replace(cfg, edge_chunks=4))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg_s = dataclasses.replace(cfg, edge_chunks=4, spmd_edges=True)
    rules = {"nodes": ("data",), "edges": ("data",), "channels": "model"}
    with jax.set_mesh(mesh):
        with sharding_context(mesh, rules):
            o3 = jax.jit(lambda pp, bb: eqv2.apply(pp, bb, cfg_s))(p, b)
            g3 = jax.jit(
                lambda pp, bb: jax.grad(eqv2.loss_fn)(pp, bb, cfg_s)
            )(p, b)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o3), atol=1e-5)
    g_ref = jax.grad(eqv2.loss_fn)(p, b, cfg)
    err = jax.tree_util.tree_reduce(
        lambda a, t: max(a, float(jnp.max(jnp.abs(t)))),
        jax.tree_util.tree_map(lambda a, b_: a - b_, g_ref, g3), 0.0,
    )
    assert err < 1e-4


def test_mace_spmd_path_matches():
    rng = np.random.default_rng(2)
    cfg = mace.MACEConfig(n_layers=2, d_hidden=8, n_species=5,
                          channel_groups=4)
    kw = _graph(rng, n=32, e=96)
    b = GraphBatch(**kw, graph_ids=jnp.zeros(32, jnp.int32), n_graphs=1,
                   labels=jnp.ones(1, jnp.float32))
    p = mace.init_params(jax.random.PRNGKey(0), cfg)
    e_ref = mace.apply(p, b, cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg_s = dataclasses.replace(cfg, edge_chunks=4, spmd_edges=True)
    rules = {"nodes": ("data",), "edges": ("data",), "channels": "model"}
    with jax.set_mesh(mesh):
        with sharding_context(mesh, rules):
            e_s = jax.jit(lambda pp, bb: mace.apply(pp, bb, cfg_s))(p, b)
    np.testing.assert_allclose(np.asarray(e_ref), np.asarray(e_s),
                               rtol=1e-4, atol=1e-4)


def test_scalar_gnns_train_step_decreases_loss():
    rng = np.random.default_rng(3)
    n, e = 48, 160
    for model, cfg, batch in [
        (gatedgcn,
         gatedgcn.GatedGCNConfig(n_layers=2, d_hidden=16, d_in=8,
                                 n_classes=3),
         GraphBatch(
             senders=jnp.asarray(rng.integers(0, n, e), jnp.int32),
             receivers=jnp.asarray(rng.integers(0, n, e), jnp.int32),
             n_nodes=n,
             nodes=jnp.asarray(rng.normal(size=(n, 8)), jnp.float32),
             labels=jnp.asarray(rng.integers(0, 3, n), jnp.int32))),
        (meshgraphnet,
         meshgraphnet.MeshGraphNetConfig(n_layers=2, d_hidden=16,
                                         d_node_in=8, d_edge_in=4, d_out=2),
         GraphBatch(
             senders=jnp.asarray(rng.integers(0, n, e), jnp.int32),
             receivers=jnp.asarray(rng.integers(0, n, e), jnp.int32),
             n_nodes=n,
             nodes=jnp.asarray(rng.normal(size=(n, 8)), jnp.float32),
             edges=jnp.asarray(rng.normal(size=(e, 4)), jnp.float32),
             labels=jnp.asarray(rng.normal(size=(n, 2)), jnp.float32))),
    ]:
        p = model.init_params(jax.random.PRNGKey(0), cfg)
        l0 = float(model.loss_fn(p, batch, cfg))
        for _ in range(15):
            g = jax.grad(model.loss_fn)(p, batch, cfg)
            p = jax.tree_util.tree_map(lambda a, b: a - 0.05 * b, p, g)
        l1 = float(model.loss_fn(p, batch, cfg))
        assert l1 < l0, (model.__name__, l0, l1)


def test_embedding_bag_matches_onehot_matmul():
    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.normal(size=(50, 8)), jnp.float32)
    ids = jnp.asarray(rng.integers(-1, 50, size=(6, 5)), jnp.int32)
    out = recsys.embedding_bag(table, ids)
    onehot = jnp.where(
        (ids >= 0)[..., None],
        jax.nn.one_hot(jnp.maximum(ids, 0), 50), 0.0,
    )
    ref = jnp.einsum("blv,vd->bd", onehot, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # ragged variant agrees
    flat = ids.reshape(-1)
    bags = jnp.repeat(jnp.arange(6), 5)
    out2 = recsys.embedding_bag_ragged(table, flat, bags, 6)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out), atol=1e-5)


def test_two_tower_training_and_retrieval():
    cfg = recsys.TwoTowerConfig(embed_dim=16, tower_mlp=(32, 16),
                                n_user_fields=3, bag_len=4, user_vocab=300,
                                item_vocab=300, n_dense=5)
    p = recsys.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    batch = dict(
        user_ids=jnp.asarray(rng.integers(-1, 300, (16, 3, 4)), jnp.int32),
        user_dense=jnp.asarray(rng.normal(size=(16, 5)), jnp.float32),
        item_ids=jnp.asarray(rng.integers(0, 300, 16), jnp.int32),
        item_dense=jnp.asarray(rng.normal(size=(16, 5)), jnp.float32),
        item_logq=jnp.zeros(16, jnp.float32),
    )
    l0 = float(recsys.loss_fn(p, batch, cfg))
    for _ in range(20):
        g = jax.grad(recsys.loss_fn)(p, batch, cfg)
        p = jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)
    l1 = float(recsys.loss_fn(p, batch, cfg))
    assert l1 < l0
    cand = jnp.asarray(rng.normal(size=(500, 16)), jnp.float32)
    vals, idx = recsys.retrieval_topk(
        p, dict(user_ids=batch["user_ids"][:1],
                user_dense=batch["user_dense"][:1], cand_emb=cand),
        cfg, k=7,
    )
    assert vals.shape == (7,) and idx.shape == (7,)
    assert bool(jnp.all(vals[:-1] >= vals[1:]))


def test_int8_kv_cache_decode_accuracy():
    """int8-quantized KV decode: logits within 5% of full precision and
    identical argmax (the decode cells' bandwidth optimization)."""
    from repro.models.transformer import init_cache, kv_quantize

    cfg = TransformerConfig(n_layers=3, d_model=64, n_heads=4,
                            n_kv_heads=2, d_ff=128, vocab=97)
    cfgq = dataclasses.replace(cfg, kv_quant=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 97)
    _, cache = prefill(params, toks, cfg, max_len=24)
    qk, sk = kv_quantize(cache["k"])
    qv, sv = kv_quantize(cache["v"])
    qcache = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    nxt = toks[:, -1:] * 0 + 5
    lg_q, qc2 = decode_step(params, nxt, qcache, 16, cfgq)
    assert qc2["k"].dtype == jnp.int8
    full, _ = forward(params, jnp.concatenate([toks, nxt], 1), cfg)
    err = float(jnp.max(jnp.abs(lg_q[:, 0] - full[:, -1])))
    scale = float(jnp.max(jnp.abs(full[:, -1])))
    assert err / scale < 0.05
    assert bool((jnp.argmax(lg_q[:, 0], -1)
                 == jnp.argmax(full[:, -1], -1)).all())
    # init_cache produces the right structure
    c0 = init_cache(cfgq, 2, 24)
    assert set(c0) == {"k", "v", "k_scale", "v_scale"}
