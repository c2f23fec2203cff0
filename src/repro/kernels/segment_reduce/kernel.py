"""Pallas TPU sorted-segment-sum — the GNN / embedding-bag / diffusion
scatter hot path, re-thought for the MXU (DESIGN.md hardware adaptation).

GPU scatter-add relies on atomics; the TPU has none, but it has a 128x128
systolic array.  With edge values sorted by destination, each edge block
touches at most ``block_e`` distinct segments, so the in-block scatter is a
dense one-hot matmul::

    partial[w, f] = one_hot(rank(ids))[e, w]^T @ values[e, f]

where ``rank`` is the within-block dense rank of each segment id (a cheap
cumsum over sorted ids).  Phase 2 (XLA) scatter-adds the tiny per-block
partial tables into the [N, F] output — O(blocks * block_e) work instead of
O(E).  All the O(E*F) flow goes through the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["segment_sum_sorted"]


def _kernel(vals_ref, ids_ref, part_ref, uniq_ref, *, block_e: int):
    vals = vals_ref[...].astype(jnp.float32)          # [Be, F]
    ids = ids_ref[0]                                  # [Be] int32, sorted, -1 pad
    valid = ids >= 0

    prev = jnp.concatenate([jnp.full((1,), -2, jnp.int32), ids[:-1]])
    new_seg = (ids != prev) & valid
    # dense within-block rank of each segment (first valid segment = 0)
    rank = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
    rank = jnp.where(valid, rank, -1)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_e), 1)
    onehot = (rank[:, None] == lanes) & valid[:, None]     # [Be, W=Be]
    part = jax.lax.dot_general(
        onehot.astype(jnp.float32), vals,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                      # [W, F]
    part_ref[0] = part
    # the segment id belonging to each rank lane (-1 where unused)
    uniq = jnp.max(
        jnp.where(onehot, ids[:, None], -1), axis=0
    )                                                      # [W]
    uniq_ref[0] = uniq


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_e", "interpret")
)
def segment_sum_sorted(
    values: jnp.ndarray,       # [E, F] float; E % block_e == 0 (pad with -1 ids)
    seg_ids: jnp.ndarray,      # [E] int32 sorted ascending; -1 = padding
    num_segments: int,
    block_e: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    e, f = values.shape
    assert e % block_e == 0, "pad via ops.segment_sum"
    nblocks = e // block_e

    part, uniq = pl.pallas_call(
        functools.partial(_kernel, block_e=block_e),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_e, f), lambda i: (i, 0)),
            pl.BlockSpec((1, block_e), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_e, f), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, block_e), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, block_e, f), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, block_e), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(values, seg_ids.reshape(nblocks, block_e))

    # phase 2: tiny cross-block combine (O(blocks*block_e) rows)
    flat_ids = jnp.where(uniq.reshape(-1) < 0, num_segments, uniq.reshape(-1))
    out = jnp.zeros((num_segments + 1, f), jnp.float32)
    out = out.at[flat_ids].add(part.reshape(-1, f))
    return out[:num_segments]
