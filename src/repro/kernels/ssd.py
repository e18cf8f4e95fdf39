"""Mamba-2 SSD Pallas TPU kernel.

Fuses the whole chunked-SSD pipeline for one (batch, head) pair in VMEM:
intra-chunk dense terms (the MXU-heavy L x L / L x N / L x P matmuls) AND the
inter-chunk state recurrence, carried across the sequential chunk grid
dimension in a VMEM scratch state (P, N). This avoids materializing per-chunk
states and decay matrices in HBM, which is what the pure-XLA path does.

Grid: (B, H, num_chunks) with chunks ARBITRARY (sequential).
Blocks: x (L, P), dt (L,), B/C (L, N) per chunk; y (L, P) out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssd_kernel(
    a_ref,                       # (1,) per-head A (negative), SMEM-ish block
    x_ref, dt_ref, b_ref, c_ref, # VMEM chunk blocks
    y_ref,                       # output chunk block
    state_ref,                   # scratch (P, N) f32: carried chunk state
    *, chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    a = a_ref[0]                                   # scalar A_h (negative)
    x = x_ref[0, 0].astype(jnp.float32)            # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (L,)
    bm = b_ref[0, 0].astype(jnp.float32)           # (L, N)
    cm = c_ref[0, 0].astype(jnp.float32)           # (L, N)

    dA = dt * a                                    # (L,)
    cum = jnp.cumsum(dA)                           # (L,)
    # intra-chunk decay: Lmat[i, j] = exp(cum[i] - cum[j]) for j <= i
    diff = cum[:, None] - cum[None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmat = jnp.where(cols <= rows, jnp.exp(diff), 0.0)

    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (L, L)
    gate = cb * lmat * dt[None, :]
    y = jax.lax.dot_general(
        gate, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (L, P) intra-chunk

    # inter-chunk: y += diag(exp(cum)) C @ state_prev^T
    prev = state_ref[...]                          # (P, N)
    y_inter = jax.lax.dot_general(
        cm, prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (L, P)
    y = y + y_inter * jnp.exp(cum)[:, None]

    # state update: state = exp(sum dA) * prev + sum_j exp(cum[-1]-cum[j]) dt_j x_j B_j^T
    decay_to_end = jnp.exp(cum[-1] - cum) * dt     # (L,)
    xw = x * decay_to_end[:, None]                 # (L, P)
    new_contrib = jax.lax.dot_general(
        xw, bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (P, N)
    state_ref[...] = prev * jnp.exp(cum[-1]) + new_contrib

    y_ref[0, 0, ...] = y.astype(y_ref.dtype)


def ssd(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H) post-softplus
    A: jax.Array,    # (H,) negative
    Bm: jax.Array,   # (B, S, G, N) — G must divide H; expanded by the wrapper
    Cm: jax.Array,   # (B, S, G, N)
    chunk: int = 256,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns y (B, S, H, P). Head-major layout internally."""
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = h // g

    # head-major: (B, H, S, ...)
    xh = x.transpose(0, 2, 1, 3)                       # (B,H,S,P)
    dth = dt.transpose(0, 2, 1)                        # (B,H,S)
    bh = jnp.repeat(Bm, rep, axis=2).transpose(0, 2, 1, 3)  # (B,H,S,N)
    ch = jnp.repeat(Cm, rep, axis=2).transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    yh = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
            pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, chunk), lambda ib, ih, ic: (ib, ih, ic)),
            pl.BlockSpec((1, 1, chunk, n), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda ib, ih, ic: (ib, ih, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nc * chunk, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(A, xh, dth, bh, ch)
    return yh.transpose(0, 2, 1, 3)                    # (B,S,H,P)
