"""MACE (arXiv:2206.07697): higher-order equivariant message passing.

Structure per interaction layer (l_max=2, correlation order 3, n_rbf=8):

1. Edge basis: phi_ij = R_path(r_ij) * Y_l2(r_hat_ij), Bessel radial + cutoff.
2. A-basis (one-particle): A_i^{l3} = sum_j sum_paths W CG(h_j^{l1}, phi^{l2})
3. B-basis (higher order, ACE): nu=1: A; nu=2: CG(A, A); nu=3: CG(CG(A,A), A)
   — symmetric contractions with learnable path weights, all l <= l_max.
4. Message m_i = sum_nu W_nu B_i^(nu);  update h' = Linear(m) + Res(h).
5. Site energy readout from invariants (l=0) per layer; total = sum.

Features are uniform-multiplicity irreps: h [N, (l_max+1)^2, C].
CG tensors come from equivariant.cg_coupling (numerically exact).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ...dist.sharding import logical_constraint
from ..common import dense_init
from .common import GraphBatch, mlp_init, mlp_apply
from .equivariant import (
    bessel_basis,
    cg_coupling,
    irrep_slices,
    n_sph,
    poly_cutoff,
    sph_harm,
)

__all__ = ["MACEConfig", "init_params", "apply", "loss_fn"]


@dataclasses.dataclass(frozen=True)
class MACEConfig:
    name: str = "mace"
    n_layers: int = 2
    d_hidden: int = 128          # channels per irrep component
    l_max: int = 2
    correlation: int = 3
    n_rbf: int = 8
    r_cut: float = 5.0
    n_species: int = 10
    dtype: object = jnp.float32
    edge_chunks: int = 1         # >1: stream edges through the A-basis
    remat: bool = False
    channel_groups: int = 1      # block-diag channel mixing (TPU scaling)
    spmd_edges: bool = False     # shard_map operon-routed A-basis


def _paths(l_max):
    out = []
    for l1 in range(l_max + 1):
        for l2 in range(l_max + 1):
            for l3 in range(l_max + 1):
                if cg_coupling(l1, l2, l3) is not None:
                    out.append((l1, l2, l3))
    return out


def init_params(key, cfg: MACEConfig):
    paths = _paths(cfg.l_max)
    c = cfg.d_hidden
    ks = jax.random.split(key, cfg.n_layers * 6 + 2)
    layers = []
    for t in range(cfg.n_layers):
        lk = jax.random.split(ks[t], 8)
        layers.append({
            # radial MLP: bessel -> hidden; explicit [64, P, C] head so a
            # channel shard slices the LAST dim cleanly
            "radial": mlp_init(lk[0], (cfg.n_rbf, 64, 64), dtype=cfg.dtype),
            "radial_out": dense_init(lk[7], (64, len(paths), c), 0,
                                     dtype=cfg.dtype),
            "w_A": dense_init(
                lk[1], (len(paths), cfg.channel_groups,
                        c // cfg.channel_groups, c // cfg.channel_groups),
                2, dtype=cfg.dtype,
            ),
            "w_B2": dense_init(lk[2], (len(paths), c), 0, dtype=cfg.dtype)
            * 0.1,
            "w_B3": dense_init(lk[3], (len(paths), c), 0, dtype=cfg.dtype)
            * 0.1,
            "w_msg": dense_init(
                lk[4], (3, n_sph(cfg.l_max), cfg.channel_groups,
                        c // cfg.channel_groups, c // cfg.channel_groups),
                3, dtype=cfg.dtype,
            ),
            "w_res": dense_init(
                lk[5], (cfg.n_species, cfg.channel_groups,
                        c // cfg.channel_groups, c // cfg.channel_groups),
                2, dtype=cfg.dtype,
            ),
            "readout": mlp_init(lk[6], (c, 32, 1), dtype=cfg.dtype),
        })
    return {
        "embed": dense_init(ks[-2], (cfg.n_species, c), 0, dtype=cfg.dtype)
        * 5.0,
        "layers": layers,  # NOT stacked: CG paths differ in no way, but
        # 2 layers only — python loop keeps einsums simple
    }


def _cg_apply(u, v, l1, l2, l3):
    """u [N, 2l1+1, C], v [N, 2l2+1, C] -> [N, 2l3+1, C] channelwise."""
    C = jnp.asarray(cg_coupling(l1, l2, l3), u.dtype)
    return jnp.einsum("abc,nbk,nck->nak", C, u, v)


def _sym_contract(x, y, paths, l_max, weights):
    """All CG paths of x (x) y, weighted per path+channel, summed into
    a fresh irrep stack [N, (l_max+1)^2, C]."""
    sl = irrep_slices(l_max)
    n, _, c = x.shape
    out = jnp.zeros((n, n_sph(l_max), c), x.dtype)
    for pi, (l1, l2, l3) in enumerate(paths):
        term = _cg_apply(x[:, sl[l1], :], y[:, sl[l2], :], l1, l2, l3)
        out = out.at[:, sl[l3], :].add(term * weights[pi][None, None, :])
    return out


def _a_basis_chunk(p, h, snd_c, rcv_c, vec_c, emask_c, cfg, paths, sl):
    """One edge chunk's contribution to the A-basis [N-block scatter]."""
    n = h.shape[0]
    c = h.shape[-1]                  # local channels under a channel shard
    r = jnp.linalg.norm(vec_c, axis=-1)
    ok = (r > 1e-6) & emask_c
    Y = sph_harm(cfg.l_max, vec_c).astype(cfg.dtype)
    rbf = bessel_basis(r, cfg.n_rbf, cfg.r_cut) * poly_cutoff(
        r, cfg.r_cut
    )[..., None]
    hrad = mlp_apply(p["radial"], rbf.astype(cfg.dtype), final_act=True)
    Rw = jnp.einsum("eh,hpc->epc", hrad, p["radial_out"])
    h_src = h[snd_c]
    rcv_safe = jnp.where(ok, rcv_c, n)
    A = jnp.zeros((n, n_sph(cfg.l_max), c), cfg.dtype)
    for pi, (l1, l2, l3) in enumerate(paths):
        Ct = jnp.asarray(cg_coupling(l1, l2, l3), cfg.dtype)
        msg = jnp.einsum(
            "abc,nbk,nc->nak", Ct, h_src[:, sl[l1], :], Y[:, sl[l2]]
        )
        msg = msg * Rw[:, pi, None, :]
        msg = jnp.where(ok[:, None, None], msg, 0)
        agg = jax.ops.segment_sum(msg, rcv_safe, num_segments=n + 1)[:n]
        gg = max(1, cfg.channel_groups // (cfg.d_hidden // c))
        aggd = agg.reshape(agg.shape[0], agg.shape[1], gg, c // gg)
        mixed = jnp.einsum("nagk,gkm->nagm", aggd, p["w_A"][pi])
        A = A.at[:, sl[l3], :].add(
            mixed.reshape(agg.shape[0], agg.shape[1], c)
        )
    return A


def _layer(p, h, batch: GraphBatch, cfg: MACEConfig, paths, sl):
    n = batch.n_nodes
    snd, rcv = batch.senders, batch.receivers
    e = snd.shape[0]
    emask = (batch.edge_mask if batch.edge_mask is not None
             else jnp.ones((e,), bool))
    vec = batch.positions[rcv] - batch.positions[snd]
    nch = cfg.edge_chunks

    from ...dist.sharding import current_context
    ctx = current_context()
    if cfg.spmd_edges and ctx is not None:
        from jax.sharding import PartitionSpec as P

        mesh, rules = ctx["mesh"], ctx["rules"]
        data_axes = rules.get("edges") or ("data",)
        mspec = rules.get("channels")

        def _zero_tan(a):
            import numpy as _np
            return _np.zeros(a.shape, jax.dtypes.float0)

        def _scan_A(pl, hl, snd_l, rcv_l, vec_l, em_l):
            e_l = snd_l.shape[0]
            ec = e_l // nch
            xs_l = (snd_l.reshape(nch, ec), rcv_l.reshape(nch, ec),
                    vec_l.reshape(nch, ec, 3), em_l.reshape(nch, ec))

            def body(Acc, inp):
                s_, r_, v_, m_ = inp
                return Acc + _a_basis_chunk(pl, hl, s_, r_, v_, m_, cfg,
                                            paths, sl), None

            A0 = jnp.zeros((n, n_sph(cfg.l_max), hl.shape[-1]), cfg.dtype)
            Al, _ = jax.lax.scan(body, A0, xs_l)
            # merge the per-edge-shard partial A's: one psum per layer
            return jax.lax.psum(Al, data_axes)

        # custom VJP: the A-sum is linear per chunk, so the backward is a
        # second chunk scan pushing the SAME d_A through each chunk's vjp —
        # no O(chunks x N x C) scan-carry checkpoints.
        @jax.custom_vjp
        def per_device(pl, hl, snd_l, rcv_l, vec_l, em_l):
            return _scan_A(pl, hl, snd_l, rcv_l, vec_l, em_l)

        def _fwd(pl, hl, snd_l, rcv_l, vec_l, em_l):
            A = _scan_A(pl, hl, snd_l, rcv_l, vec_l, em_l)
            return A, (pl, hl, snd_l, rcv_l, vec_l, em_l)

        def _bwd(res, dA):
            pl, hl, snd_l, rcv_l, vec_l, em_l = res
            e_l = snd_l.shape[0]
            ec = e_l // nch
            xs_l = (snd_l.reshape(nch, ec), rcv_l.reshape(nch, ec),
                    vec_l.reshape(nch, ec, 3), em_l.reshape(nch, ec))
            dp0 = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), pl
            )
            dh0 = jnp.zeros(hl.shape, jnp.float32)

            def body(carry, inp):
                dp, dh = carry
                s_, r_, v_, m_ = inp
                _, vjp = jax.vjp(
                    lambda P_, H_, V_: _a_basis_chunk(
                        P_, H_, s_, r_, V_, m_, cfg, paths, sl
                    ),
                    pl, hl, v_,
                )
                dpc, dhc, dvc = vjp(dA)
                dp = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), dp, dpc
                )
                return (dp, dh + dhc.astype(jnp.float32)), \
                    dvc.astype(jnp.float32)

            (dp, dh), dvecs = jax.lax.scan(body, (dp0, dh0), xs_l)
            dp = jax.tree_util.tree_map(
                lambda a, b: a.astype(b.dtype), dp, pl
            )
            return (dp, dh.astype(hl.dtype), _zero_tan(snd_l),
                    _zero_tan(rcv_l),
                    dvecs.reshape(e_l, 3).astype(vec_l.dtype),
                    _zero_tan(em_l))

        per_device.defvjp(_fwd, _bwd)

        pl_specs = {
            "radial": jax.tree_util.tree_map(lambda _: P(), p["radial"]),
            "radial_out": P(None, None, mspec),
            "w_A": P(None, mspec, None, None),
        }
        espec = P(data_axes)
        pl = {k: p[k] for k in ("radial", "radial_out", "w_A")}
        A = jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(pl_specs, P(None, None, mspec), espec, espec,
                      P(data_axes, None), espec),
            out_specs=P(None, None, mspec),
            check_vma=False,
        )(pl, h, snd, rcv, vec, emask)
    elif nch <= 1:
        A = _a_basis_chunk(p, h, snd, rcv, vec, emask, cfg, paths, sl)
    else:
        assert e % nch == 0, "pad edges to a multiple of edge_chunks"
        ec = e // nch
        # hoist the node-table replication out of the chunk scan (one
        # all-gather per layer, not per chunk)
        h = logical_constraint(h, None, None, "channels")
        xs = (snd.reshape(nch, ec), rcv.reshape(nch, ec),
              vec.reshape(nch, ec, 3), emask.reshape(nch, ec))

        def body(A, inp):
            snd_c, rcv_c, vec_c, em_c = inp
            return A + _a_basis_chunk(p, h, snd_c, rcv_c, vec_c, em_c, cfg,
                                      paths, sl), None

        A0 = jnp.zeros((n, n_sph(cfg.l_max), cfg.d_hidden), cfg.dtype)
        A, _ = jax.lax.scan(body, A0, xs)
    A = logical_constraint(A, "nodes", None, "channels")
    return A


def apply(params, batch: GraphBatch, cfg: MACEConfig):
    """Returns per-graph energies [n_graphs]."""
    n = batch.n_nodes
    paths = _paths(cfg.l_max)
    sl = irrep_slices(cfg.l_max)
    c = cfg.d_hidden

    # initial features: species embedding in l=0
    h = jnp.zeros((n, n_sph(cfg.l_max), c), cfg.dtype)
    h = h.at[:, 0, :].set(params["embed"][batch.species])
    energies = jnp.zeros((n,), jnp.float32)

    layer_fn = _layer
    if cfg.remat:
        layer_fn = jax.checkpoint(_layer, static_argnums=(3, 4, 5),
                                  prevent_cse=False)

    for p in params["layers"]:
        A = layer_fn(p, h, batch, cfg, paths, sl)
        # B-basis: symmetric contractions up to correlation order
        B1 = A
        B2 = _sym_contract(A, A, paths, cfg.l_max, p["w_B2"])
        B3 = _sym_contract(B2, A, paths, cfg.l_max, p["w_B3"])
        gg = cfg.channel_groups
        cg = c // gg
        nsph = n_sph(cfg.l_max)

        def _mix(B, w):                     # w [comps, G, Cg, Cg]
            Bd = B.reshape(n, nsph, gg, cg)
            return jnp.einsum("nagk,agkm->nagm", Bd, w).reshape(n, nsph, c)

        m = (_mix(B1, p["w_msg"][0]) + _mix(B2, p["w_msg"][1])
             + _mix(B3, p["w_msg"][2]))
        hd = h.reshape(n, nsph, gg, cg)
        res = jnp.einsum("nagk,ngkm->nagm", hd,
                         p["w_res"][batch.species]).reshape(n, nsph, c)
        h = m + res
        # per-layer site-energy readout from invariants
        e_site = mlp_apply(p["readout"], h[:, 0, :])[:, 0]
        energies = energies + e_site.astype(jnp.float32)

    if batch.node_mask is not None:
        energies = jnp.where(batch.node_mask, energies, 0.0)
    gids = batch.graph_ids if batch.graph_ids is not None else jnp.zeros(
        (n,), jnp.int32
    )
    return jax.ops.segment_sum(energies, gids,
                               num_segments=batch.n_graphs)


def loss_fn(params, batch: GraphBatch, cfg: MACEConfig):
    e = apply(params, batch, cfg)
    target = batch.labels.astype(jnp.float32)
    return jnp.mean(jnp.square(e - target))
