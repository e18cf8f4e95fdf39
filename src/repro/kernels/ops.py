"""Jitted public wrappers for the Pallas kernels.

``interpret`` is chosen here and nowhere else: on a TPU the call sites
compile the Mosaic kernels, on the CPU backend (the test suite) they run in
the Pallas interpreter, and any other backend is an error.
Model code selects ``attn_impl``/``ssd_impl`` in {"xla", "pallas"}; the
dry-run/roofline path uses "xla" so HLO cost analysis reflects the
production XLA pipeline (see DESIGN.md §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import delta_encode as _de
from . import flash_attention as _fa
from . import ssd as _ssd


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas TPU kernels compile only for a TPU (or interpret on the "
            f"CPU); the default backend is {backend!r}"
        )
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128, interpret=None):
    """GQA flash attention. q: (B,S,Nq,H); k/v: (B,T,Nkv,H). Returns (B,S,Nq,H)."""
    interpret = _default_interpret() if interpret is None else interpret
    b, s, nq, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    if nq != nkv:
        k = jnp.repeat(k, nq // nkv, axis=2)
        v = jnp.repeat(v, nq // nkv, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * nq, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * nq, t, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * nq, t, hd)
    bq = min(block_q, s)
    bk = min(block_k, t)
    o = _fa.flash_attention(qf, kf, vf, causal=causal, block_q=bq, block_k=bk,
                            interpret=interpret)
    return o.reshape(b, nq, s, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, Bm, Cm, chunk=256, interpret=None):
    """Mamba-2 SSD: returns y (B,S,H,P) (final state stays in-kernel)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _ssd.ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


def ssd_model_impl(x, dt, A, Bm, Cm, chunk=256):
    """Adapter matching models/ssm.py's ssd_impl signature (y, state)."""
    y = ssd(x, dt, A, Bm, Cm, chunk=chunk)
    return y, None


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_encode(new, prev, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _de.delta_encode(new, prev, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def delta_decode(codes, scales, prev, dtype=jnp.bfloat16, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _de.delta_decode(codes, scales, prev, dtype=dtype, interpret=interpret)
