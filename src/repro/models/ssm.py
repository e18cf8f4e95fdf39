"""Mamba-2 mixer via state-space duality (SSD), pure-JAX chunked form.

Train/prefill use the chunked SSD algorithm (arXiv:2405.21060 §6): the
sequence is split into chunks; intra-chunk terms are dense matmuls (MXU
friendly — this is the part the Pallas kernel in kernels/ssd.py targets),
inter-chunk terms are a first-order recurrence over chunk states carried by
``lax.scan``. Decode keeps O(1) state per layer: a conv ring and the
(H, P, N) SSM state.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .params import PDesc
from .scan_utils import _scan

F32 = jnp.float32


def ssm_descs(cfg: ModelConfig) -> Dict[str, PDesc]:
    s, d = cfg.ssm, cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    conv_ch = di + 2 * gn
    return {
        "w_z": PDesc((d, di), ("embed", "ffn")),
        "w_x": PDesc((d, di), ("embed", "ffn")),
        "w_B": PDesc((d, gn), ("embed", None)),
        "w_C": PDesc((d, gn), ("embed", None)),
        "w_dt": PDesc((d, nh), ("embed", None)),
        "conv_w": PDesc((s.d_conv, conv_ch), (None, "ffn")),
        "conv_b": PDesc((conv_ch,), ("ffn",), init="zeros"),
        "A_log": PDesc((nh,), (None,), init="zeros"),
        "D": PDesc((nh,), (None,), init="ones"),
        "dt_bias": PDesc((nh,), (None,), init="zeros"),
        "norm_w": PDesc((di,), ("ffn",), init="zeros"),
        "out_proj": PDesc((di, d), ("ffn", "embed")),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv: x (B,S,C), w (K,C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):  # K is tiny (4): unrolled adds fuse well
        out = out + xp[:, i : i + x.shape[1], :] * w[i]
    return out + b


def _segsum(dA: jax.Array) -> jax.Array:
    """Lower-triangular pairwise decay logits within a chunk.
    dA: (..., L) -> (..., L, L) with out[i, j] = sum_{j < t <= i} dA[t]."""
    L = dA.shape[-1]
    cs = jnp.cumsum(dA, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    i = jnp.arange(L)[:, None]
    j = jnp.arange(L)[None, :]
    return jnp.where(j <= i, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)  (post-softplus)
    A: jax.Array,      # (H,)       (negative)
    Bm: jax.Array,     # (B, S, G, N)
    Cm: jax.Array,     # (B, S, G, N)
    chunk: int = 256,
    initial_state: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    xr = x.reshape(Bsz, nc, chunk, H, P)
    dtr = dt.reshape(Bsz, nc, chunk, H)
    Br = Bm.reshape(Bsz, nc, chunk, G, N)
    Cr = Cm.reshape(Bsz, nc, chunk, G, N)
    dA = dtr * A  # (B,nc,L,H)

    # intra-chunk (dense; the Pallas kernel computes exactly this term)
    Lmat = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))        # (B,nc,H,L,L)
    CB = jnp.einsum("bclgn,bcsgn->bcgls", Cr, Br)            # (B,nc,G,L,L)
    CB = jnp.repeat(CB, rep, axis=2)                          # (B,nc,H,L,L)
    gate = (CB * Lmat).astype(x.dtype)
    y_diag = jnp.einsum("bchls,bcsh,bcshp->bclhp", gate, dtr.astype(x.dtype), xr)

    # chunk states: decay-to-chunk-end weighted outer products
    dA_cum = jnp.cumsum(dA, axis=2)                           # (B,nc,L,H)
    decay_to_end = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)     # (B,nc,L,H)
    Bh = jnp.repeat(Br, rep, axis=3)                          # (B,nc,L,H,N)
    Bx = jnp.einsum(
        "bclhn,bclh,bclhp->bchpn",
        Bh.astype(F32),
        (dtr * decay_to_end).astype(F32),
        xr.astype(F32),
    )  # (B,nc,H,P,N)

    # inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))                # (B,nc,H)
    init = (
        jnp.zeros((Bsz, H, P, N), F32)
        if initial_state is None
        else initial_state.astype(F32)
    )

    def step(state, inp):
        bx_c, dec_c = inp
        new_state = state * dec_c[:, :, None, None] + bx_c
        return new_state, state  # emit the state seen by this chunk's queries

    # scan over chunks: move nc to leading axis
    bx_s = jnp.moveaxis(Bx, 1, 0)
    dec_s = jnp.moveaxis(chunk_decay, 1, 0)
    final_state, prev_states = _scan(step, init, (bx_s, dec_s), unrollable=False)
    prev_states = jnp.moveaxis(prev_states, 0, 1)             # (B,nc,H,P,N)

    # inter-chunk contribution: y += C_t · decayed prev chunk state
    in_decay = jnp.exp(dA_cum)                                # (B,nc,L,H)
    Ch = jnp.repeat(Cr, rep, axis=3)                          # (B,nc,L,H,N)
    y_inter = jnp.einsum("bclhn,bchpn->bclhp", Ch.astype(F32), prev_states)
    y_inter = y_inter * in_decay[..., None]

    y = (y_diag.astype(F32) + y_inter).reshape(Bsz, S, H, P)
    return y.astype(x.dtype), final_state


def ssd_decode_step(
    x: jax.Array,     # (B, 1, H, P)
    dt: jax.Array,    # (B, 1, H)
    A: jax.Array,     # (H,)
    Bm: jax.Array,    # (B, 1, G, N)
    Cm: jax.Array,    # (B, 1, G, N)
    state: jax.Array,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    H = x.shape[2]
    G = Bm.shape[2]
    rep = H // G
    dA = jnp.exp(dt[:, 0, :] * A)                             # (B,H)
    Bh = jnp.repeat(Bm[:, 0], rep, axis=1)                    # (B,H,N)
    Ch = jnp.repeat(Cm[:, 0], rep, axis=1)
    upd = jnp.einsum("bh,bhp,bhn->bhpn", dt[:, 0].astype(F32), x[:, 0].astype(F32), Bh.astype(F32))
    new_state = state.astype(F32) * dA[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch.astype(F32))
    return y[:, None].astype(x.dtype), new_state.astype(state.dtype)


def mamba2_mixer(
    p: Dict[str, jax.Array],
    x: jax.Array,                 # (B, S, D)
    cfg: ModelConfig,
    *,
    cache: Optional[Dict[str, jax.Array]] = None,  # {"conv": (B,K-1,C), "state": (B,H,P,N)}
    ssd_impl=None,                # optional kernel override (kernels/ops.py)
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    s = cfg.ssm
    B, S, D = x.shape
    di = s.d_inner(D)
    nh = s.n_heads(D)
    gn = s.n_groups * s.d_state

    z = jnp.einsum("bsd,di->bsi", x, p["w_z"])
    xs = jnp.einsum("bsd,di->bsi", x, p["w_x"])
    Bm = jnp.einsum("bsd,dg->bsg", x, p["w_B"])
    Cm = jnp.einsum("bsd,dg->bsg", x, p["w_C"])
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x, p["w_dt"]).astype(F32) + p["dt_bias"].astype(F32)
    )
    A = -jnp.exp(p["A_log"].astype(F32))

    xbc = jnp.concatenate([xs, Bm, Cm], axis=-1)              # (B,S,C)
    new_cache = None
    if cache is None:
        xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    else:
        k = s.d_conv
        window = jnp.concatenate([cache["conv"], xbc], axis=1)  # (B,K-1+S,C)
        conv_out = jnp.einsum("bkc,kc->bc", window[:, -k:], p["conv_w"]) + p["conv_b"]
        xbc = jax.nn.silu(conv_out)[:, None]                  # (B,1,C)
        new_conv = window[:, -(k - 1) :]

    xs = xbc[..., :di].reshape(B, S, nh, s.head_dim)
    Bm = xbc[..., di : di + gn].reshape(B, S, s.n_groups, s.d_state)
    Cm = xbc[..., di + gn :].reshape(B, S, s.n_groups, s.d_state)

    if cache is None:
        run = ssd_impl or ssd_chunked
        y, _state = run(xs, dt.astype(x.dtype), A.astype(F32), Bm, Cm, s.chunk_size)
    else:
        y, new_state = ssd_decode_step(xs, dt.astype(F32), A, Bm, Cm, cache["state"])
        new_cache = {"conv": new_conv, "state": new_state}

    y = y + xs * p["D"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(B, S, di)
    # gated RMSNorm then down-projection (Mamba-2 block epilogue)
    y = gated_rms_norm(y, z, p["norm_w"], s.n_groups, cfg.norm_eps)
    return jnp.einsum("bsi,id->bsd", y, p["out_proj"]), new_cache


def gated_rms_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int, eps: float) -> jax.Array:
    """RMSNorm of y * silu(z) over each of ``groups`` equal slices of the
    last axis apart (Zamba2RMSNormGated, group_size = d_inner / ngroups),
    scaled by 1 + w."""
    y = y * jax.nn.silu(z)
    shape = y.shape
    g = y.reshape(shape[:-1] + (groups, shape[-1] // groups))
    var = jnp.mean(jnp.square(g.astype(F32)), axis=-1, keepdims=True)
    g = (g.astype(F32) * jax.lax.rsqrt(var + eps)).astype(y.dtype)
    return g.reshape(shape) * (1.0 + w.astype(y.dtype))
