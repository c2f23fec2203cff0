"""EquiformerV2 (arXiv:2306.12059): equivariant graph attention with
eSCN-style SO(2) convolutions (l_max=6, m_max=2, 8 heads, 12 blocks).

TPU adaptations (DESIGN.md §2):

* eSCN rotation trick — per-edge Wigner alignment turns the O(L^6) tensor
  product into per-m SO(2) mixes (equivariant.py).
* **Channel-grouped (block-diagonal) mixing** (``channel_groups``): with
  groups == the tensor-axis size, a channel shard never communicates.
* **Edge streaming** (``edge_chunks``): edges flow through the layer in
  chunks with an online-softmax (flash-attention) recurrence, so peak edge
  memory is O(E / chunks).
* **SPMD edge routing** (``spmd_edges``): the aggregation runs under
  shard_map — each device owns an edge shard + a channel shard, scatters
  locally into a full-node partial accumulator, and one
  pmax/psum-combine per layer merges the per-device online-softmax states.
  This is the diffusive-operon pattern: compute moves to where the edges
  live, partial results merge once per round, replacing GSPMD's
  replicate-and-all-reduce fallback (measured in EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from ...dist.sharding import current_context, logical_constraint
from ..common import dense_init
from .common import GraphBatch, edge_softmax_agg, mlp_init, mlp_apply
from .equivariant import (
    bessel_basis,
    irrep_slices,
    n_sph,
    poly_cutoff,
    wigner_blocks,
    rotate_irreps,
)

__all__ = ["EquiformerV2Config", "init_params", "apply", "loss_fn"]


@dataclasses.dataclass(frozen=True)
class EquiformerV2Config:
    name: str = "equiformer-v2"
    n_layers: int = 12
    d_hidden: int = 128           # channels per irrep component
    l_max: int = 6
    m_max: int = 2
    n_heads: int = 8
    n_rbf: int = 8
    r_cut: float = 5.0
    n_species: int = 10
    d_out: int = 1
    dtype: object = jnp.float32
    edge_chunks: int = 1          # >1: stream edges, online-softmax agg
    remat: bool = False           # checkpoint each block (big graphs)
    channel_groups: int = 1       # block-diag channel mixing (TPU scaling)
    spmd_edges: bool = False      # shard_map operon-routed aggregation


def _m_layout(l_max, m_max):
    pos = {m: [] for m in range(0, m_max + 1)}
    neg = {m: [] for m in range(1, m_max + 1)}
    for l in range(l_max + 1):
        base = l * l + l
        pos[0].append(base)
        for m in range(1, min(l, m_max) + 1):
            pos[m].append(base + m)
            neg[m].append(base - m)
    return pos, neg


def init_params(key, cfg: EquiformerV2Config):
    c = cfg.d_hidden
    g = cfg.channel_groups
    assert c % g == 0 and c % cfg.n_heads == 0
    cg = c // g
    pos, neg = _m_layout(cfg.l_max, cfg.m_max)
    n0 = len(pos[0])
    ks = jax.random.split(key, cfg.n_layers + 2)
    layers = []
    for t in range(cfg.n_layers):
        lk = jax.random.split(ks[t], 8 + 2 * (cfg.m_max + 1))
        so2 = {
            "w0": dense_init(lk[0], (g, 2 * n0 * cg, n0 * cg), 1,
                             dtype=cfg.dtype)
        }
        for m in range(1, cfg.m_max + 1):
            nm = len(pos[m])
            so2[f"w{m}_r"] = dense_init(
                lk[2 * m], (g, 2 * nm * cg, nm * cg), 1, dtype=cfg.dtype
            )
            so2[f"w{m}_i"] = dense_init(
                lk[2 * m + 1], (g, 2 * nm * cg, nm * cg), 1, dtype=cfg.dtype
            )
        layers.append({
            "so2": so2,
            # radial MLP: final layer emits C channels (channel-shardable)
            "radial": mlp_init(lk[-6], (cfg.n_rbf, 64, c), dtype=cfg.dtype),
            # attention logits: per-group partial contraction + combine
            "alpha_w1": dense_init(lk[-5], (g, (n0 + 1) * cg, 64), 1,
                                   dtype=cfg.dtype),
            "alpha_b1": jnp.zeros((64,), cfg.dtype),
            "alpha_w2": dense_init(lk[-4], (64, cfg.n_heads), 0,
                                   dtype=cfg.dtype),
            "ffn_gate": {
                "w1": dense_init(lk[-3], (g, cg, cg), 1, dtype=cfg.dtype),
                "w2": dense_init(jax.random.fold_in(lk[-3], 1),
                                 (c, cfg.l_max + 1), 0, dtype=cfg.dtype),
            },
            "ffn_scalar": {
                "w1": dense_init(lk[-2], (g, cg, 2 * cg), 1,
                                 dtype=cfg.dtype),
                "w2": dense_init(jax.random.fold_in(lk[-2], 1),
                                 (g, 2 * cg, cg), 1, dtype=cfg.dtype),
            },
            "w_out": dense_init(lk[-1], (g, cg, cg), 1, dtype=cfg.dtype),
        })
    return {
        "embed": dense_init(ks[-2], (cfg.n_species, c), 0, dtype=cfg.dtype)
        * 3.0,
        "head": mlp_init(ks[-1], (c, c, cfg.d_out), dtype=cfg.dtype),
        "layers": layers,
    }


def _grouped(x, g):
    """[E, n, C] -> [E, g, n*Cg]."""
    e, n, c = x.shape
    return x.reshape(e, n, g, c // g).transpose(0, 2, 1, 3).reshape(
        e, g, n * (c // g)
    )


def _ungrouped(y, g, n, c):
    e = y.shape[0]
    return y.reshape(e, g, n, c // g).transpose(0, 2, 1, 3).reshape(e, n, c)


def _so2_conv(p, x_src, x_dst, pos, neg, m_max, g):
    c = x_src.shape[-1]
    out = jnp.zeros_like(x_src)
    idx0 = jnp.asarray(pos[0])
    n0 = len(pos[0])
    f0 = jnp.concatenate(
        [_grouped(x_src[:, idx0, :], g), _grouped(x_dst[:, idx0, :], g)],
        axis=-1,
    )
    y0 = jnp.einsum("egi,gio->ego", f0, p["w0"])
    out = out.at[:, idx0, :].set(_ungrouped(y0, g, n0, c))
    for m in range(1, m_max + 1):
        ip, im = jnp.asarray(pos[m]), jnp.asarray(neg[m])
        nm = len(pos[m])
        xp_ = jnp.concatenate(
            [_grouped(x_src[:, ip, :], g), _grouped(x_dst[:, ip, :], g)],
            axis=-1,
        )
        xm_ = jnp.concatenate(
            [_grouped(x_src[:, im, :], g), _grouped(x_dst[:, im, :], g)],
            axis=-1,
        )
        yp = (jnp.einsum("egi,gio->ego", xp_, p[f"w{m}_r"])
              - jnp.einsum("egi,gio->ego", xm_, p[f"w{m}_i"]))
        ym = (jnp.einsum("egi,gio->ego", xp_, p[f"w{m}_i"])
              + jnp.einsum("egi,gio->ego", xm_, p[f"w{m}_r"]))
        out = out.at[:, ip, :].set(_ungrouped(yp, g, nm, c))
        out = out.at[:, im, :].set(_ungrouped(ym, g, nm, c))
    return out


def _layer_params_local(p, g_local):
    """Slice of per-layer params for a channel shard (g_local groups)."""
    return p  # shard_map in_specs do the slicing; helper kept for clarity


def _edge_messages(p, x, snd_c, rcv_c, vec_c, emask_c, cfg, g, psum_axis=None):
    """Per-edge-chunk messages on (possibly channel-local) features.

    Returns (logits [Ec,H] f32, vals [Ec, nsph, C_local] f32 rotated back,
    geom_ok mask)."""
    pos, neg = _m_layout(cfg.l_max, cfg.m_max)
    r = jnp.linalg.norm(vec_c, axis=-1)
    geom_ok = (r > 1e-6) & emask_c
    rbf = (bessel_basis(r, cfg.n_rbf, cfg.r_cut)
           * poly_cutoff(r, cfg.r_cut)[..., None]).astype(cfg.dtype)
    D = wigner_blocks(cfg.l_max, vec_c)
    x_src = rotate_irreps(x[snd_c], D, cfg.l_max)
    x_dst = rotate_irreps(x[rcv_c], D, cfg.l_max)
    radial = mlp_apply(p["radial"], rbf)                   # [Ec, C_local]
    msg = _so2_conv(p["so2"], x_src, x_dst, pos, neg, cfg.m_max, g)
    msg = msg * radial[:, None, :]
    # attention logits: per-group partial + (optional cross-shard) combine
    idx0 = jnp.asarray(pos[0])
    inv = jnp.concatenate([msg[:, idx0, :], radial[:, None, :]], axis=1)
    inv_g = _grouped(inv, g)                               # [Ec,g,(n0+1)cg]
    part = jnp.einsum("egi,gio->eo", inv_g, p["alpha_w1"])
    if psum_axis is not None:
        part = lax.psum(part, psum_axis)
    hidden = jax.nn.silu(part + p["alpha_b1"])
    logits = (hidden @ p["alpha_w2"]).astype(jnp.float32)
    logits = jnp.where(geom_ok[:, None], logits, -jnp.inf)
    vals = rotate_irreps(msg, D, cfg.l_max, inverse=True).astype(jnp.float32)
    return logits, vals, geom_ok


def _heads_split(vals, h):
    """[E, nsph, C] -> [E, H, nsph*(C/H)]."""
    e, ns, c = vals.shape
    return vals.reshape(e, ns, h, c // h).transpose(0, 2, 1, 3).reshape(
        e, h, ns * (c // h)
    )


def _heads_merge(agg, h, ns, c):
    n = agg.shape[0]
    return agg.reshape(n, h, ns, c // h).transpose(0, 2, 1, 3).reshape(
        n, ns, c
    )


def _chunk_scan(p, x, snd, rcv, vec, emask, cfg, g, n, nch, psum_axis=None):
    """Online-softmax edge streaming; returns per-shard (m, l, acc)."""
    e = snd.shape[0]
    c_local = x.shape[-1]
    assert c_local % cfg.n_heads == 0, (
        "channel shard must keep whole heads (C/shards % n_heads == 0)"
    )
    h_eff = cfg.n_heads
    k_ = n_sph(cfg.l_max) * (c_local // h_eff)
    ec = e // nch
    xs = (snd.reshape(nch, ec), rcv.reshape(nch, ec),
          vec.reshape(nch, ec, 3), emask.reshape(nch, ec))

    def body(carry, inp):
        m, l, acc = carry
        snd_c, rcv_c, vec_c, em_c = inp
        logits, vals, ok = _edge_messages(p, x, snd_c, rcv_c, vec_c, em_c,
                                          cfg, g, psum_axis)
        vals = _heads_split(vals, h_eff)
        rcv_s = jnp.where(ok, rcv_c, n)
        # softmax shift: stability-only, gradient-neutral => stop_gradient
        m_chunk = lax.stop_gradient(
            jax.ops.segment_max(logits, rcv_s, num_segments=n + 1)[:n]
        )
        m_new = jnp.maximum(m, m_chunk)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        scale = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - m_safe))
        w = jnp.exp(logits - m_safe[rcv_s.clip(0, n - 1)])
        w = jnp.where(ok[:, None], w, 0.0)
        l = l * scale + jax.ops.segment_sum(w, rcv_s, num_segments=n + 1)[:n]
        acc = acc * scale[..., None] + jax.ops.segment_sum(
            w[..., None] * vals, rcv_s, num_segments=n + 1
        )[:n]
        return (m_new, l, acc), None

    m0 = jnp.full((n, h_eff), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((n, h_eff), jnp.float32)
    acc0 = jnp.zeros((n, h_eff, k_), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, acc0), xs)
    return m, l, acc, h_eff



def _zero_tan(a):
    import numpy as _np
    return _np.zeros(a.shape, jax.dtypes.float0)


def _heads_split_nodes(a, h):
    """[N, ns, C] -> [N, H, ns*(C/H)] (node-side twin of _heads_split)."""
    n, ns, c = a.shape
    return a.reshape(n, ns, h, c // h).transpose(0, 2, 1, 3).reshape(
        n, h, ns * (c // h)
    )


def _make_spmd_agg(cfg, mesh, data_axes, model_axis, layer_specs, n, nch,
                   g_local):
    """Receiver-partitioned SPMD graph attention (custom VJP at pjit level).

    Contract: the edge arrays are partitioned so device d's shard only
    contains edges whose RECEIVER lies in node block d (the diffusive
    partitioning from core/partition.py, applied at data ingest).  Then:

    * every node's softmax lives on exactly one device — no cross-shard
      softmax combine at all;
    * the scatter is local; accumulators are node-block sized;
    * only the sender table x is replicated (one all-gather per layer,
      transient); residuals saved for backward are all node-SHARDED
      (lse + agg per block), so backward re-gathers x but never stores a
      full-node tensor across layers.
    """
    from jax.sharding import PartitionSpec as P

    h = cfg.n_heads
    ns = n_sph(cfg.l_max)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    block = n // n_data
    mspec = model_axis if model_axis else None
    espec = P(data_axes)

    def _offset():
        idx = jnp.zeros((), jnp.int32)
        for a in data_axes:
            idx = idx * mesh.shape[a] + lax.axis_index(a)
        return idx * block

    def fwd_body(pl, x_full, snd, rcv, vec, emask):
        off = _offset()
        rcv_l = rcv - off
        ok0 = (rcv_l >= 0) & (rcv_l < block) & emask
        rcv_l = jnp.clip(rcv_l, 0, block - 1)
        m, l, acc, h_eff = _chunk_scan(
            pl, x_full, snd, rcv_l, vec, ok0, cfg, g_local, block, nch,
            psum_axis=model_axis,
        )
        shift = jnp.where(jnp.isneginf(m), 0.0, m)
        l = jnp.maximum(l, 1e-20)
        agg = acc / l[..., None]                       # [block, H, K]
        lse = shift + jnp.log(l)
        return _heads_merge(agg, h_eff, ns, x_full.shape[-1]), lse

    fwd_sm = jax.shard_map(
        fwd_body, mesh=mesh,
        in_specs=(layer_specs, P(None, None, mspec), espec, espec,
                  P(data_axes, None), espec),
        out_specs=(P(data_axes, None, mspec), P(data_axes, None)),
        check_vma=False,
    )

    def bwd_body(pl, x_full, snd, rcv, vec, emask, lse, agg_l, d_agg_l):
        off = _offset()
        rcv_l0 = rcv - off
        ok0 = (rcv_l0 >= 0) & (rcv_l0 < block) & emask
        rcv_l = jnp.clip(rcv_l0, 0, block - 1)
        e_l = snd.shape[0]
        ec = e_l // nch
        c_local = x_full.shape[-1]
        agg_h = _heads_split_nodes(agg_l.astype(jnp.float32), h)
        d_agg_h = _heads_split_nodes(d_agg_l.astype(jnp.float32), h)
        delta = (agg_h * d_agg_h).sum(-1)              # [block, H]
        xs = (snd.reshape(nch, ec), rcv_l.reshape(nch, ec),
              vec.reshape(nch, ec, 3), ok0.reshape(nch, ec))
        dp0 = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), pl)
        dx0 = jnp.zeros(x_full.shape, jnp.float32)

        def body(carry, inp):
            dp, dx = carry
            snd_c, rcv_c, vec_c, ok_c = inp

            def fwd_chunk(p_, x_, vec_):
                lo, va, _ok = _edge_messages(
                    p_, x_, snd_c, rcv_c, vec_, ok_c, cfg, g_local,
                    model_axis,
                )
                return lo, va

            (logits, vals), vjp = jax.vjp(fwd_chunk, pl, x_full, vec_c)
            valid = jnp.isfinite(logits[:, 0])
            w = jnp.exp(logits - lse[rcv_c])
            w = jnp.where(valid[:, None], w, 0.0)
            vals_h = _heads_split(vals, h)
            dyr = d_agg_h[rcv_c]
            d_vals_h = jnp.where(valid[:, None, None],
                                 w[..., None] * dyr, 0.0)
            d_logits = jnp.where(
                valid[:, None],
                w * ((vals_h * dyr).sum(-1) - delta[rcv_c]), 0.0)
            d_vals = d_vals_h.reshape(ec, h, ns, c_local // h).transpose(
                0, 2, 1, 3).reshape(ec, ns, c_local)
            dpc, dxc, dvecc = vjp((d_logits, d_vals))
            dp = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), dp, dpc)
            return (dp, dx + dxc.astype(jnp.float32)), \
                dvecc.astype(jnp.float32)

        (dp, dx), dvecs = lax.scan(body, (dp0, dx0), xs)
        # edge shards each produced partial param/node cotangents
        dp = lax.psum(dp, data_axes)
        dx = lax.psum(dx, data_axes)
        dp = jax.tree_util.tree_map(lambda a, b: a.astype(b.dtype), dp, pl)
        return dp, dx, dvecs.reshape(e_l, 3)

    bwd_sm = jax.shard_map(
        bwd_body, mesh=mesh,
        in_specs=(layer_specs, P(None, None, mspec), espec, espec,
                  P(data_axes, None), espec, P(data_axes, None),
                  P(data_axes, None, mspec), P(data_axes, None, mspec)),
        out_specs=(layer_specs, P(None, None, mspec), P(data_axes, None)),
        check_vma=False,
    )

    @jax.custom_vjp
    def agg_fn(p, x, snd, rcv, vec, emask):
        return fwd_sm(p, x, snd, rcv, vec, emask)[0]

    def fwd(p, x, snd, rcv, vec, emask):
        agg, lse = fwd_sm(p, x, snd, rcv, vec, emask)
        return agg, (p, x, snd, rcv, vec, emask, lse, agg)

    def bwd(res, d_agg):
        p, x, snd, rcv, vec, emask, lse, agg = res
        dp, dx, dvec = bwd_sm(p, x, snd, rcv, vec, emask, lse, agg, d_agg)
        return (dp, dx.astype(x.dtype), _zero_tan(snd), _zero_tan(rcv),
                dvec.astype(vec.dtype), _zero_tan(emask))

    agg_fn.defvjp(fwd, bwd)
    return agg_fn


def _attention_agg(p, x, batch, cfg):
    """Returns agg [N, nsph, C(-local)] (softmax-weighted messages)."""
    n = batch.n_nodes
    snd, rcv = batch.senders, batch.receivers
    e = snd.shape[0]
    emask = (batch.edge_mask if batch.edge_mask is not None
             else jnp.ones((e,), bool))
    vec = batch.positions[rcv] - batch.positions[snd]
    g = cfg.channel_groups
    nch = max(cfg.edge_chunks, 1)
    c = cfg.d_hidden
    ns = n_sph(cfg.l_max)

    ctx = current_context()
    if cfg.spmd_edges and ctx is not None:
        from jax.sharding import PartitionSpec as P

        mesh = ctx["mesh"]
        rules = ctx["rules"]
        data_axes = rules.get("edges") or ("data",)
        model_axis = rules.get("channels")
        n_data = 1
        for a in data_axes:
            n_data *= mesh.shape[a]
        n_model = mesh.shape[model_axis] if model_axis else 1
        g_local = max(1, g // n_model)

        mspec = model_axis if model_axis else None
        layer_specs = jax.tree_util.tree_map(lambda _: P(), p)
        # channel-sharded leaves: group-dim or channel-dim sharding
        layer_specs = {
            "so2": jax.tree_util.tree_map(lambda _: P(mspec), p["so2"]),
            "radial": [
                {"w": P(None, None), "b": P(None)},
                {"w": P(None, mspec), "b": P(mspec)},
            ],
            "alpha_w1": P(mspec, None, None),
            "alpha_b1": P(None),
            "alpha_w2": P(None, None),
            "ffn_gate": {"w1": P(mspec, None, None), "w2": P(mspec, None)},
            "ffn_scalar": {"w1": P(mspec, None, None),
                           "w2": P(mspec, None, None)},
            "w_out": P(mspec, None, None),
        }
        agg_fn = _make_spmd_agg(cfg, mesh, data_axes, model_axis,
                                layer_specs, n, nch, g_local)
        return agg_fn(p, x, snd, rcv, vec, emask)

    if nch <= 1:
        logits, vals, ok = _edge_messages(p, x, snd, rcv, vec, emask, cfg, g)
        vals = _heads_split(vals, cfg.n_heads)
        agg = edge_softmax_agg(logits, vals, rcv, n, edge_mask=ok)
        return _heads_merge(agg, cfg.n_heads, ns, c)
    # hoist node-table replication out of the chunk scan
    x = logical_constraint(x, None, None, "channels")
    m, l, acc, h_eff = _chunk_scan(p, x, snd, rcv, vec, emask, cfg, g, n,
                                   nch)
    agg = acc / jnp.maximum(l, 1e-20)[..., None]
    return _heads_merge(agg, h_eff, ns, c)


def _eqv_rmsnorm(x, l_max, eps=1e-6):
    outs = []
    for sl in irrep_slices(l_max):
        blk = x[:, sl, :]
        nrm = jnp.sqrt(jnp.mean(jnp.square(blk), axis=(1, 2),
                                keepdims=True) + eps)
        outs.append(blk / nrm)
    return jnp.concatenate(outs, axis=1)


def _block(p, x, batch, cfg):
    n = batch.n_nodes
    c = cfg.d_hidden
    g = cfg.channel_groups
    ns = n_sph(cfg.l_max)
    agg = _attention_agg(p, x, batch, cfg)                  # [N, ns, C]
    aggd = agg.astype(cfg.dtype).reshape(n, ns, g, c // g)
    x = x + jnp.einsum("nagk,gkm->nagm", aggd, p["w_out"]).reshape(n, ns, c)
    x = _eqv_rmsnorm(x, cfg.l_max).astype(cfg.dtype)
    x = logical_constraint(x, "nodes", None, "channels")
    # gated feed-forward (block-diag over channel groups)
    s = x[:, 0, :]
    sg = s.reshape(n, g, c // g)
    gate_h = jax.nn.silu(
        jnp.einsum("ngk,gkm->ngm", sg, p["ffn_gate"]["w1"]).reshape(n, c)
    )
    gate = jax.nn.sigmoid(gate_h @ p["ffn_gate"]["w2"])     # [N, L+1]
    hid = jax.nn.silu(jnp.einsum("ngk,gkm->ngm", sg, p["ffn_scalar"]["w1"]))
    s_out = s + jnp.einsum("ngk,gkm->ngm", hid,
                           p["ffn_scalar"]["w2"]).reshape(n, c)
    outs = [s_out[:, None, :]]
    for l, sl in enumerate(irrep_slices(cfg.l_max)):
        if l == 0:
            continue
        outs.append(x[:, sl, :] * gate[:, l, None, None])
    return jnp.concatenate(outs, axis=1)


def apply(params, batch: GraphBatch, cfg: EquiformerV2Config):
    n = batch.n_nodes
    c = cfg.d_hidden
    x = jnp.zeros((n, n_sph(cfg.l_max), c), cfg.dtype)
    x = x.at[:, 0, :].set(params["embed"][batch.species])
    x = logical_constraint(x, "nodes", None, "channels")

    block = _block
    if cfg.remat:
        block = jax.checkpoint(_block, static_argnums=(3,),
                               prevent_cse=False)
    for p in params["layers"]:
        x = block(p, x, batch, cfg)

    scalars = x[:, 0, :]
    out = mlp_apply(params["head"], scalars)                # [N, d_out]
    if batch.node_mask is not None:
        out = jnp.where(batch.node_mask[:, None], out, 0)
    return out


def loss_fn(params, batch: GraphBatch, cfg: EquiformerV2Config):
    pred = apply(params, batch, cfg)
    if batch.labels.ndim == 1 and cfg.d_out > 1:
        logp = jax.nn.log_softmax(pred.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, batch.labels[:, None], -1)[:, 0]
        if batch.node_mask is not None:
            nll = jnp.where(batch.node_mask, nll, 0)
            return nll.sum() / jnp.maximum(batch.node_mask.sum(), 1)
        return nll.mean()
    gids = batch.graph_ids if batch.graph_ids is not None else jnp.zeros(
        (batch.n_nodes,), jnp.int32
    )
    pooled = jax.ops.segment_sum(
        pred[:, 0].astype(jnp.float32), gids, num_segments=batch.n_graphs
    )
    return jnp.mean(jnp.square(pooled - batch.labels.astype(jnp.float32)))
