"""Flash attention Pallas TPU kernel (tiled online softmax).

TPU-native redesign of the CUDA flash algorithm: block sizes are chosen for
VMEM residency and MXU alignment (multiples of 128), not warp/shared-memory
occupancy. Grid is (batch*heads, q_blocks, kv_blocks) with the kv dimension
innermost and ARBITRARY (sequential), so the running max / denominator /
accumulator live in VMEM scratch across kv steps. Fully-masked causal
blocks are skipped via predication.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,          # VMEM blocks
    o_ref,                        # output block
    acc_ref, m_ref, l_ref,        # scratch (f32)
    *, scale: float, causal: bool, block_q: int, block_k: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip kv blocks strictly above the diagonal band
    q_start = iq * block_q
    k_start = ik * block_k

    def _body():
        q = q_ref[0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0].astype(jnp.float32)           # (bk, d)
        v = v_ref[0].astype(jnp.float32)           # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                   # (bq, bk)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,                  # (BH, S, D)
    k: jax.Array,                  # (BH, T, D)
    v: jax.Array,                  # (BH, T, D)
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    bh, s, d = q.shape
    t = k.shape[1]
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    grid = (bh, s // block_q, t // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, iq, ik: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
