"""The shared sparse message substrate.

One abstraction — ``gather -> per-edge compute -> segment-combine -> route`` —
underlies everything in this framework: diffusive graph algorithms, GNN
message passing, MoE token dispatch, and recsys embedding bags.  This module
holds the segment-combine primitives (with a Pallas fast path for the sorted
case) and the identity elements per combine monoid.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "block_any",
    "run_ends",
    "segment_combine",
    "identity_for",
    "segment_softmax",
    "COMBINES",
]

COMBINES = ("sum", "min", "max", "mean")


def identity_for(combine: str, dtype=jnp.float32):
    if combine in ("sum", "mean"):
        return jnp.zeros((), dtype)
    if combine == "min":
        if jnp.issubdtype(dtype, jnp.integer):
            return jnp.array(jnp.iinfo(dtype).max, dtype)
        return jnp.array(jnp.inf, dtype)
    if combine == "max":
        if jnp.issubdtype(dtype, jnp.integer):
            return jnp.array(jnp.iinfo(dtype).min, dtype)
        return jnp.array(-jnp.inf, dtype)
    raise ValueError(f"unknown combine {combine!r}")


def block_any(mask: jnp.ndarray, block: int) -> jnp.ndarray:
    """[..., E] bool -> [..., E // block]: whether any element of each
    run of ``block`` consecutive elements is set.  A windowed reduction
    rather than a reshape to [..., E // block, block]: on a TPU that
    reshape is a relayout whose compile time grows with E (~90 s at
    [4, 2^24.3], v5e target); the window compiles in seconds."""
    win = (1,) * (mask.ndim - 1) + (block,)
    return jax.lax.reduce_window(mask, False, jax.lax.bitwise_or, win, win,
                                 "VALID")


def run_ends(skey: jnp.ndarray, n_keys: int) -> jnp.ndarray:
    """The run layout of a key-sorted stream ``skey`` [E] (``-1`` padding
    trailing): [n_keys] int32, the position of the last element of key
    k's run, or ``-1`` where k has no element.  Depends only on the
    stream's structure, so a sweep over an unchanged stream computes it
    once and reads each key's run total with one gather."""
    key2 = jnp.where(skey < 0, n_keys, skey).astype(jnp.int32)
    ks = jnp.arange(n_keys, dtype=jnp.int32)
    last = jnp.searchsorted(key2, ks, side="right").astype(jnp.int32) - 1
    ok = (last >= 0) & (key2[jnp.clip(last, 0)] == ks)
    return jnp.where(ok, last, -1)


def segment_combine(
    values: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    combine: str = "sum",
    indices_are_sorted: bool = False,
):
    """Segment-reduce ``values`` by ``segment_ids`` with the given monoid.

    Values may have trailing feature dims; segment_ids indexes the leading
    axis.  Out-of-range segment ids are dropped (used for masking).
    """
    kw = dict(
        num_segments=num_segments,
        indices_are_sorted=indices_are_sorted,
    )
    if combine == "sum":
        return jax.ops.segment_sum(values, segment_ids, **kw)
    if combine == "min":
        return jax.ops.segment_min(values, segment_ids, **kw)
    if combine == "max":
        return jax.ops.segment_max(values, segment_ids, **kw)
    if combine == "mean":
        tot = jax.ops.segment_sum(values, segment_ids, **kw)
        cnt = jax.ops.segment_sum(
            jnp.ones(values.shape[: segment_ids.ndim], values.dtype),
            segment_ids,
            **kw,
        )
        cnt = jnp.maximum(cnt, 1)
        return tot / cnt.reshape(cnt.shape + (1,) * (tot.ndim - cnt.ndim))
    raise ValueError(f"unknown combine {combine!r}")


def segment_softmax(
    logits: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: jnp.ndarray | None = None,
):
    """Numerically stable softmax within segments (GAT-style edge softmax)."""
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    seg_max = jax.ops.segment_max(logits, segment_ids, num_segments=num_segments)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    shifted = logits - seg_max[segment_ids]
    expd = jnp.exp(shifted)
    if mask is not None:
        expd = jnp.where(mask, expd, 0.0)
    denom = jax.ops.segment_sum(expd, segment_ids, num_segments=num_segments)
    denom = jnp.maximum(denom, 1e-20)
    return expd / denom[segment_ids]
