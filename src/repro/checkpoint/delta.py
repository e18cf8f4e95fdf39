"""Delta-compressed checkpoint codec.

Persistence bandwidth is the resource the paper's Fig. 10 shows DSE saving;
for the training instantiation we additionally compress successive versions:

  * PARAMS: a full fp32 base every ``base_every`` versions, int8 deltas with
    per-block scales in between (Pallas delta_encode kernel). Parameters are
    magnitude-homogeneous enough for block quantization of their step deltas.
  * OPTIMIZER MOMENTS: stored raw — m as fp16, v as fp32. Adam's second
    moment spans ~8 orders of magnitude and sits next to first-moment blocks
    in any flat stream; block-quantizing its deltas rounds small v entries
    to zero and the next update explodes (m/(sqrt(0)+eps)). Measured before
    this split: post-restore loss 6.2 -> 13+.

Restore replays base + deltas for params and loads moments directly.
"""
from __future__ import annotations

import io
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..kernels.delta_encode import ROWS as _ROWS

_BLOCK = 1024


def _flatten(tree) -> Tuple[np.ndarray, List, List]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    arrs = [np.asarray(l) for l in leaves]
    flat = (
        np.concatenate([a.ravel().astype(np.float32) for a in arrs])
        if arrs
        else np.zeros(0, np.float32)
    )
    shapes = [(a.shape, a.dtype.str) for a in arrs]
    return flat, shapes, treedef


def _unflatten(flat: np.ndarray, shapes, treedef):
    out, off = [], 0
    for shape, dt in shapes:
        n = int(np.prod(shape)) if shape else 1
        out.append(flat[off : off + n].astype(np.dtype(dt)).reshape(shape))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def padded_blocks(n: int) -> int:
    """Rows of ``_BLOCK`` floats holding ``n`` values, rounded up to the
    kernel's row tile so every grid step is a full tile."""
    nb = max(1, -(-n // _BLOCK))
    return -(-nb // _ROWS) * _ROWS


def _pad_blocks(flat: np.ndarray) -> np.ndarray:
    nb = padded_blocks(len(flat))
    padded = np.zeros(nb * _BLOCK, np.float32)
    padded[: len(flat)] = flat
    return padded.reshape(nb, _BLOCK)


class DeltaCheckpointCodec:
    def __init__(self, base_every: int = 8) -> None:
        self.base_every = base_every

    def encode(self, version: int, state, prev_flat: Optional[np.ndarray]):
        """state = (params, opt_state). Returns (blob, new_params_flat).
        prev_flat None => full params base."""
        params, opt = state
        p_flat, _, _ = _flatten(params)
        o_leaves, _ = jax.tree_util.tree_flatten(opt)
        opt_arrays: Dict[str, np.ndarray] = {
            f"o{i}": np.asarray(leaf) for i, leaf in enumerate(o_leaves)
        }
        # dtype policy: an fp32 moment leaf is stored as fp16 only when it
        # round-trips within 1e-3 relative error (in practice the first
        # moment); the rest (v, step) stay at full precision.
        for k, a in list(opt_arrays.items()):
            if a.dtype == np.float32:
                a16 = a.astype(np.float16)
                denom = np.maximum(np.abs(a), 1e-12)
                if float(np.max(np.abs(a16.astype(np.float32) - a) / denom)) < 1e-3:
                    opt_arrays[k] = a16

        buf = io.BytesIO()
        is_base = prev_flat is None or len(prev_flat) != len(p_flat)
        if is_base:
            np.savez_compressed(buf, kind=np.array(0), flat=p_flat, **opt_arrays)
        else:
            codes, scales = kops.delta_encode(
                jnp.asarray(_pad_blocks(p_flat)), jnp.asarray(_pad_blocks(prev_flat))
            )
            codes, scales = np.asarray(codes), np.asarray(scales)
            np.savez_compressed(
                buf, kind=np.array(1), codes=codes, scales=scales,
                n=np.array(len(p_flat)), **opt_arrays,
            )
        return buf.getvalue(), p_flat

    def decode_chain(self, blobs: List[bytes], p_shapes, p_treedef,
                     o_shapes, o_treedef):
        """Replay [base, delta, ...]; the LAST blob carries the opt moments.
        Returns ((params, opt_state), params_flat)."""
        flat: Optional[np.ndarray] = None
        last = None
        for blob in blobs:
            z = np.load(io.BytesIO(blob))
            last = z
            if int(z["kind"]) == 0:
                flat = z["flat"]
            else:
                assert flat is not None, "delta before base"
                prev_b = _pad_blocks(flat)
                dec = kops.delta_decode(
                    jnp.asarray(z["codes"]), jnp.asarray(z["scales"]),
                    jnp.asarray(prev_b), dtype=jnp.float32,
                )
                flat = np.asarray(dec).ravel()[: int(z["n"])]
        assert flat is not None and last is not None
        params = _unflatten(flat, p_shapes, p_treedef)
        o_leaves = []
        for i, (shape, dt) in enumerate(o_shapes):
            a = np.asarray(last[f"o{i}"]).astype(np.dtype(dt)).reshape(shape)
            o_leaves.append(a)
        opt = jax.tree_util.tree_unflatten(o_treedef, o_leaves)
        return (params, opt), flat
