"""Pallas TPU flash attention (GQA, causal, optional logit softcap).

Online-softmax kernel in the FlashAttention-2 style, adapted to the TPU
memory hierarchy: Q/K/V blocks staged HBM->VMEM via BlockSpec, the score
matmul and the PV matmul hit the MXU with 128-aligned tiles, and the running
(max, sum, acc) state lives in VMEM scratch persisted across the innermost
(KV) grid axis — the TPU analogue of CUDA's SRAM accumulators.

Grid: (batch, q_heads, q_blocks, kv_blocks), kv innermost/sequential.
GQA is free: the K/V index_map folds the query head onto its KV head, so no
head replication is ever materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = float("-inf")


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, softcap: float, kv_len: int,
            block_q: int, block_k: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [Bq, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [Bk, D]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # [Bq, Bk]
    if softcap and softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < kv_len                                 # padding mask
    if causal:
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0
        ) + q_offset
        mask = mask & (kpos <= qpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]                                 # [Bq]
    l_prev = l_ref[:, 0]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    # fully-masked-so-far rows keep m = -inf; make alpha/p well-defined
    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
    alpha = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_safe))
    p = jnp.exp(jnp.where(mask, s - m_safe[:, None], NEG_INF))
    l_new = alpha * l_prev + p.sum(axis=-1)
    v = v_ref[0, 0].astype(jnp.float32)                  # [Bk, D]
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
    m_ref[:, 0] = m_new
    l_ref[:, 0] = l_new

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0], 1e-20)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "softcap", "block_q", "block_k", "kv_len",
                     "interpret"),
)
def flash_attention(
    q: jnp.ndarray,            # [B, Hq, Sq, D]
    k: jnp.ndarray,            # [B, Hkv, Skv, D]
    v: jnp.ndarray,            # [B, Hkv, Skv, D]
    causal: bool = True,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    kv_len: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, "GQA requires q_heads % kv_heads == 0"
    assert sq % block_q == 0 and skv % block_k == 0, "pad via ops.attention"
    g = hq // hkv
    scale = 1.0 / (d ** 0.5)
    kv_len = skv if kv_len is None else kv_len
    q_offset = skv - sq  # decode-style alignment of the causal diagonal

    grid = (b, hq, sq // block_q, skv // block_k)
    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        softcap=softcap,
        kv_len=kv_len,
        block_q=block_q,
        block_k=block_k,
        q_offset=q_offset,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bb, h, iq, ik: (bb, h, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bb, h, iq, ik, g=g: (bb, h // g, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bb, h, iq, ik, g=g: (bb, h // g, ik, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bb, h, iq, ik: (bb, h, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
