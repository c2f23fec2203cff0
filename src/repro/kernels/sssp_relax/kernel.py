"""Pallas TPU fused SSSP relaxation — the paper's hot loop as one kernel.

Fuses the three memory passes of a relaxation sweep into one VMEM-resident
pipeline: gather ``dist[src]``, add the edge weight, and segment-min by
destination.  The per-cell distance array stays pinned in VMEM across the
whole edge stream (a vertex block of 512k nodes is 2 MB — the "memory-driven"
layout: compute moves to the distances, not the other way).  The segment-min
uses the same sorted-run dense-rank trick as segment_reduce, with a masked
min instead of a matmul.

Phase 2 (XLA) min-combines the per-block partial tables — O(blocks*block_e).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["relax_sorted"]

INF = jnp.inf


def _kernel(dist_ref, active_ref, w_ref, src_ref, dst_ref, part_ref, uniq_ref,
            *, block_e: int):
    src = src_ref[0]                                  # [Be]
    dst = dst_ref[0]                                  # [Be] sorted, -1 pad
    valid = dst >= 0

    d_src = dist_ref[0, src]                          # VMEM gather
    act = active_ref[0, src]
    cand = jnp.where(valid & act, d_src + w_ref[0], INF)

    prev = jnp.concatenate([jnp.full((1,), -2, jnp.int32), dst[:-1]])
    new_seg = (dst != prev) & valid
    rank = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
    rank = jnp.where(valid, rank, -1)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_e), 1)
    onehot = rank[:, None] == lanes                   # [Be, W]
    part = jnp.min(
        jnp.where(onehot, cand[:, None], INF), axis=0
    )                                                 # [W]
    uniq = jnp.max(jnp.where(onehot, dst[:, None], -1), axis=0)
    part_ref[0] = part
    uniq_ref[0] = uniq


@functools.partial(
    jax.jit, static_argnames=("n_nodes", "block_e", "interpret")
)
def relax_sorted(
    dist: jnp.ndarray,        # [Np] float32 — cell-resident distances
    active: jnp.ndarray,      # [Np] bool
    weight: jnp.ndarray,      # [E] float32, edges sorted by dst
    src: jnp.ndarray,         # [E] int32 local source index
    dst_sorted: jnp.ndarray,  # [E] int32 sorted ascending, -1 = dead/pad
    n_nodes: int,
    block_e: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    e = weight.shape[0]
    assert e % block_e == 0, "pad via ops.relax"
    nblocks = e // block_e
    np_ = dist.shape[0]

    part, uniq = pl.pallas_call(
        functools.partial(_kernel, block_e=block_e),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, np_), lambda i: (0, 0)),     # dist: whole cell
            pl.BlockSpec((1, np_), lambda i: (0, 0)),     # active
            pl.BlockSpec((1, block_e), lambda i: (0, i)),
            pl.BlockSpec((1, block_e), lambda i: (0, i)),
            pl.BlockSpec((1, block_e), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_e), lambda i: (i, 0)),
            pl.BlockSpec((1, block_e), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, block_e), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, block_e), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(
        dist[None], active[None], weight[None].astype(jnp.float32),
        src[None], dst_sorted[None],
    )

    flat_ids = jnp.where(uniq.reshape(-1) < 0, n_nodes, uniq.reshape(-1))
    out = jnp.full((n_nodes + 1,), INF, jnp.float32)
    out = out.at[flat_ids].min(part.reshape(-1))
    return out[:n_nodes]
