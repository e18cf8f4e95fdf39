"""Checkpoint delta-encoding Pallas TPU kernel (DSE-adjacent).

The paper's Fig. 10 shows persistence *bandwidth* is a first-order cost of
speculative services. For the training instantiation, successive checkpoint
versions differ by one optimizer step; this kernel block-quantizes the delta
(new - prev) to int8 with a per-block fp32 scale, cutting checkpoint bytes
~4x (fp32 -> int8 + 4B/block). The decoder fuses dequant+add on restore.

Layout: 1D parameter stream reshaped to (nblocks, block); one quantization
block is one row. A grid step covers ``ROWS`` rows (Mosaic tiles int8 in 32
rows), or the whole array when it has at most ``ROWS`` rows. Scales travel
as an (nblocks, 1) column inside the kernel: scale = max|delta| / 127 per row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

#: rows (quantization blocks) per grid step; a multiple of the int8 tile (32)
ROWS = 256


def _encode_kernel(new_ref, prev_ref, code_ref, scale_ref):
    delta = new_ref[...].astype(jnp.float32) - prev_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(delta), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    scale_ref[...] = scale
    code_ref[...] = jnp.clip(jnp.round(delta / scale), -127, 127).astype(jnp.int8)


def _decode_kernel(code_ref, scale_ref, prev_ref, out_ref):
    delta = code_ref[...].astype(jnp.float32) * scale_ref[...]
    out_ref[...] = (prev_ref[...].astype(jnp.float32) + delta).astype(out_ref.dtype)


def _row_block(nb: int) -> int:
    rows = min(ROWS, nb)
    if nb % rows:
        raise ValueError(f"{nb} blocks: pad to a multiple of {ROWS} rows")
    return rows


def delta_encode(
    new: jax.Array,    # (nblocks, block)
    prev: jax.Array,   # (nblocks, block)
    *,
    interpret: bool = False,
):
    """Returns (codes (nblocks, block) int8, scales (nblocks,) f32)."""
    nb, blk = new.shape
    rows = _row_block(nb)
    row_spec = pl.BlockSpec((rows, blk), lambda i: (i, 0))
    codes, scales = pl.pallas_call(
        _encode_kernel,
        grid=(nb // rows,),
        in_specs=[row_spec, row_spec],
        out_specs=[row_spec, pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((nb, blk), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(new, prev)
    return codes, scales.reshape(nb)


def delta_decode(
    codes: jax.Array,   # (nblocks, block) int8
    scales: jax.Array,  # (nblocks,) f32
    prev: jax.Array,    # (nblocks, block)
    dtype=jnp.bfloat16,
    *,
    interpret: bool = False,
) -> jax.Array:
    nb, blk = codes.shape
    rows = _row_block(nb)
    row_spec = pl.BlockSpec((rows, blk), lambda i: (i, 0))
    return pl.pallas_call(
        _decode_kernel,
        grid=(nb // rows,),
        in_specs=[row_spec, pl.BlockSpec((rows, 1), lambda i: (i, 0)), row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((nb, blk), dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(codes, scales.reshape(nb, 1), prev)
