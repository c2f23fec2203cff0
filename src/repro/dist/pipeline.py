"""GPipe pipeline parallelism over a mesh axis (DESIGN.md §3).

One stage per device along ``axis``; micro-batches stream through the
stages with a ``ppermute`` shift per tick.  The schedule runs
``n_micro + n_stages - 1`` ticks; the classic bubble fraction is
``(S - 1) / (M + S - 1)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["make_pipeline_fn", "bubble_fraction"]


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """Idle fraction of the GPipe schedule (fill + drain)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


def make_pipeline_fn(mesh, stage_fn, n_stages: int, n_micro: int,
                     axis: str = "pod"):
    """Build ``(ws [S, ...], xs [M, ...]) -> ys [M, ...]`` running
    ``stage_fn(w_s, x)`` for stages s = 0..S-1 in sequence over every
    micro-batch.

    ``ws`` is stage-sharded over ``axis``; ``xs`` is replicated (stage 0
    injects micro-batches, the last stage collects outputs, merged with a
    psum so the result is replicated).
    """
    S, M = n_stages, n_micro
    fwd = [(i, (i + 1) % S) for i in range(S)]

    def per_device(ws, xs):
        w = ws[0]                       # my stage's weights
        stage = lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])     # activation arriving from stage-1
        ys = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, ys = carry
            mb_in = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(stage == 0, xs[mb_in], buf)
            out = stage_fn(w, x_in)
            mb_out = t - (S - 1)
            write = (stage == S - 1) & (mb_out >= 0)
            slot = jnp.clip(mb_out, 0, M - 1)
            ys = ys.at[slot].set(jnp.where(write, out, ys[slot]))
            buf = lax.ppermute(out, axis, fwd)
            return (buf, ys), None

        (_, ys), _ = lax.scan(tick, (buf, ys), jnp.arange(M + S - 1))
        # only the last stage wrote outputs; psum replicates them
        return lax.psum(ys, axis)

    return jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
