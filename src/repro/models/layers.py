"""Transformer building blocks (pure JAX, GSPMD-friendly).

Conventions:
  * activations: x (B, S, D); masks are built from iota comparisons inside
    attention (never materialized globally);
  * GQA einsums keep the (kv_heads, group) split so sharding by kv_heads
    propagates: q (B,S,N,G,H), k/v (B,T,N,H);
  * decode caches are (B, Smax, N, H) ring/linear buffers updated with
    dynamic_update_slice at the current index.

Logical sharding axis names used in descriptors: "embed", "heads",
"kv_heads", "head_dim", "ffn", "vocab", "experts", "expert_ffn",
"layers" (scan dim, never sharded), "state", "batch", "seq".
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .params import PDesc
from .tuning import constrain_replicated_heads, constrain_seq_sharded, get_tuning

F32 = jnp.float32


# --------------------------------------------------------------------------- #
# norms / rope                                                                 #
# --------------------------------------------------------------------------- #
def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    return (x.astype(F32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * (1.0 + w.astype(x.dtype))


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H) with H even; positions broadcastable to (..., S)."""
    h = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, h, 2, dtype=F32) / h))
    angles = positions[..., None].astype(F32) * freqs  # (..., S, H/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., : h // 2], x[..., h // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _soft_cap(logits: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(logits / cap) * cap if cap > 0 else logits


# --------------------------------------------------------------------------- #
# attention                                                                    #
# --------------------------------------------------------------------------- #
def attn_descs(cfg: ModelConfig, cross: bool = False, d_in: Optional[int] = None) -> Dict[str, PDesc]:
    """Projections from an input of width ``d_in`` (default d_model) to the
    heads, and from the heads back to d_model."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    a = d_in or d
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    descs = {
        "wq": PDesc((a, nq, hd), ("embed", "heads", None)),
        "wk": PDesc((a, nkv, hd), ("embed", "kv_heads", None)),
        "wv": PDesc((a, nkv, hd), ("embed", "kv_heads", None)),
        "wo": PDesc((nq, hd, d), ("heads", None, "embed")),
    }
    if cross:
        descs["gate"] = PDesc((1,), (None,), init="zeros")  # tanh-gated (VLM)
    return descs


def _sdpa(
    q: jax.Array,        # (B, S, N, H)  — N = full query heads
    k: jax.Array,        # (B, T, N, H)  — kv repeated to N (GQA)
    v: jax.Array,        # (B, T, N, H)
    mask: Optional[jax.Array],  # broadcastable to (B, N, S, T) or None
    softcap: float,
    scale: float,
) -> jax.Array:
    # NOTE (sharding): GQA is computed in repeat-kv form on purpose — a
    # (kv_heads, groups) split of the head dim is unshardable whenever
    # kv_heads < |model| (GSPMD would replicate the S x T logits). Repeating
    # K/V keeps every attention tensor sharded on the full head dim.
    logits = jnp.einsum("bsnh,btnh->bnst", q, k).astype(F32) * scale
    logits = _soft_cap(logits, softcap)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnh->bsnh", probs, v)


def attention(
    p: Dict[str, jax.Array],
    x: jax.Array,                       # (B, S, D)
    cfg: ModelConfig,
    positions: jax.Array,               # (B, S) absolute positions of x
    *,
    window: Optional[int] = None,       # sliding-window size (local attn)
    cache: Optional[Dict[str, jax.Array]] = None,  # decode: {"k","v"} (B,Smax,N,H)
    cache_index: Optional[jax.Array] = None,       # scalar int32 write offset
    ring: bool = False,                 # cache is a ring buffer of size window
    cross_src: Optional[jax.Array] = None,         # (B, Ssrc, D) encoder/image
    causal: bool = True,
    scale: Optional[float] = None,      # score scale (default head_dim ** -0.5)
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    B, S, D = x.shape
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    groups = nq // nkv
    if scale is None:
        scale = 1.0 / np.sqrt(hd)

    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    kv_in = cross_src if cross_src is not None else x
    k = jnp.einsum("bsd,dnh->bsnh", kv_in, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", kv_in, p["wv"])

    if cross_src is None:
        q = rope(q.transpose(0, 2, 1, 3), positions[:, None, :], cfg.rope_theta).transpose(0, 2, 1, 3)
        k = rope(k.transpose(0, 2, 1, 3), positions[:, None, :], cfg.rope_theta).transpose(0, 2, 1, 3)

    new_cache = None
    if cross_src is not None:
        kv_pos = None
        mask = None  # full attention over the (stub) modality tokens
        t_len = cross_src.shape[1]
    elif cache is not None:
        smax = cache["k"].shape[1]
        if ring:
            idx = (cache_index % smax).astype(jnp.int32)
        else:
            idx = cache_index.astype(jnp.int32)
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, idx, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, idx, 0, 0))
        if get_tuning().decode_seq_constraint:
            # flash-decode sharding: K/V stay sequence-sharded AND q is
            # replicated over the model axis (q is (B,1,N,H) — tiny), so
            # QK^T/PV contract locally per T-shard; GSPMD inserts only
            # small stat/partial-sum all-reduces instead of gathering the
            # repeated cache per layer.
            ck = constrain_seq_sharded(ck, 1)
            cv = constrain_seq_sharded(cv, 1)
            q = constrain_replicated_heads(q)
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        t_len = smax
        slot = jnp.arange(smax, dtype=jnp.int32)
        if ring:
            # slot holds absolute position cache_index - ((idx - slot) mod smax)
            age = (idx - slot) % smax
            abs_pos = cache_index - age
            valid = (abs_pos >= 0) & (abs_pos <= cache_index)
            if window is not None:
                valid &= abs_pos > cache_index - window
            mask = valid[None, None, None, :]
        else:
            valid = slot <= cache_index
            if window is not None:
                valid &= slot > cache_index - window
            mask = valid[None, None, None, :]
    else:
        t_len = S
        qpos = positions[:, None, :, None]                # (B,1,S,1)
        kpos = positions[:, None, None, :]                # (B,1,1,T)
        mask = jnp.ones((B, 1, S, S), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window

    if cache is not None and cross_src is None and get_tuning().decode_seq_constraint:
        # flash-decode: NO kv repeat (the repeat is a broadcast GSPMD would
        # shard on heads, forcing a full seq all-gather of the cache).
        # q is replicated, K/V stay seq-sharded; the grouped einsum
        # contracts locally per T-shard and GSPMD inserts only small
        # softmax-stat / partial-sum all-reduces.
        qg = q.reshape(B, S, nkv, groups, hd)
        logits = jnp.einsum("bsngh,btnh->bngst", qg, k).astype(F32) * scale
        logits = _soft_cap(logits, cfg.logit_softcap)
        if mask is not None:
            logits = jnp.where(mask[:, :, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        out = jnp.einsum("bngst,btnh->bsngh", probs, v).reshape(B, S, nq, hd)
    else:
        if groups > 1:
            k = jnp.repeat(k, groups, axis=2)
            v = jnp.repeat(v, groups, axis=2)
        out = _sdpa(q, k, v, mask, cfg.logit_softcap, scale)
    out = jnp.einsum("bsnh,nhd->bsd", out, p["wo"])
    return out, new_cache


# --------------------------------------------------------------------------- #
# MLA (DeepSeek-V2 multi-head latent attention)                                #
# --------------------------------------------------------------------------- #
def mla_descs(cfg: ModelConfig) -> Dict[str, PDesc]:
    m, d, nq = cfg.mla, cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    descs: Dict[str, PDesc] = {}
    if m.q_lora_rank:
        descs["w_dq"] = PDesc((d, m.q_lora_rank), ("embed", None))
        descs["w_uq"] = PDesc((m.q_lora_rank, nq, qk), (None, "heads", None))
    else:
        descs["w_q"] = PDesc((d, nq, qk), ("embed", "heads", None))
    descs["w_dkv"] = PDesc((d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", None))
    descs["w_uk"] = PDesc((m.kv_lora_rank, nq, m.qk_nope_head_dim), (None, "heads", None))
    descs["w_uv"] = PDesc((m.kv_lora_rank, nq, m.v_head_dim), (None, "heads", None))
    descs["wo"] = PDesc((nq, m.v_head_dim, d), ("heads", None, "embed"))
    return descs


def mla_attention(
    p: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    *,
    cache: Optional[Dict[str, jax.Array]] = None,   # {"ckv": (B,Smax,R), "kpe": (B,Smax,P)}
    cache_index: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    m = cfg.mla
    B, S, D = x.shape
    nq = cfg.num_heads

    if m.q_lora_rank:
        q = jnp.einsum("bsd,dr->bsr", x, p["w_dq"])
        q = jnp.einsum("bsr,rnh->bsnh", q, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dnh->bsnh", x, p["w_q"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_pe = q[..., m.qk_nope_head_dim :]
    q_pe = rope(q_pe.transpose(0, 2, 1, 3), positions[:, None, :], cfg.rope_theta).transpose(0, 2, 1, 3)

    dkv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    ckv, k_pe = dkv[..., : m.kv_lora_rank], dkv[..., m.kv_lora_rank :]
    k_pe = rope(k_pe, positions, cfg.rope_theta)  # (B,S,P): shared across heads

    new_cache = None
    if cache is not None:
        idx = cache_index.astype(jnp.int32)
        cckv = jax.lax.dynamic_update_slice(cache["ckv"], ckv, (0, idx, 0))
        ckpe = jax.lax.dynamic_update_slice(cache["kpe"], k_pe, (0, idx, 0))
        new_cache = {"ckv": cckv, "kpe": ckpe}
        ckv, k_pe = cckv, ckpe
        t = ckv.shape[1]
        valid = jnp.arange(t, dtype=jnp.int32) <= cache_index
        mask = valid[None, None, :, None]  # (1,1,T,1) -> used below as (B,N,S,T)
        mask = valid[None, None, None, :]
    else:
        qpos = positions[:, None, :, None]
        kpos = positions[:, None, None, :]
        mask = kpos <= qpos  # (B,1,S,T)

    # expand compressed cache: k_nope (B,T,N,Hn), v (B,T,N,Hv)
    k_nope = jnp.einsum("btr,rnh->btnh", ckv, p["w_uk"])
    val = jnp.einsum("btr,rnh->btnh", ckv, p["w_uv"])

    scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    logits = (
        jnp.einsum("bsnh,btnh->bnst", q_nope, k_nope)
        + jnp.einsum("bsnh,bth->bnst", q_pe, k_pe)
    ).astype(F32) * scale
    logits = jnp.where(mask if mask.ndim == 4 else mask[:, :, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bnst,btnh->bsnh", probs, val)
    out = jnp.einsum("bsnh,nhd->bsd", out, p["wo"])
    return out, new_cache


# --------------------------------------------------------------------------- #
# MLPs                                                                         #
# --------------------------------------------------------------------------- #
def mlp_descs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, PDesc]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    return {
        "wi_gate": PDesc((d, f), ("embed", "ffn")),
        "wi_up": PDesc((d, f), ("embed", "ffn")),
        "wo": PDesc((f, d), ("ffn", "embed")),
    }


_ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,  # tanh approximation (gemma)
    "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),  # erf form (zamba2)
}


def mlp(p: Dict[str, jax.Array], x: jax.Array, activation: str,
        adapter: Optional[Dict[str, jax.Array]] = None) -> jax.Array:
    """Gated MLP; ``adapter`` ({"lora_a": (d, r), "lora_gate", "lora_up":
    (r, f)}) adds a low-rank term to the gate and up projections."""
    gate = jnp.einsum("bsd,df->bsf", x, p["wi_gate"])
    up = jnp.einsum("bsd,df->bsf", x, p["wi_up"])
    if adapter is not None:
        low = jnp.einsum("bsd,dr->bsr", x, adapter["lora_a"])
        gate = gate + jnp.einsum("bsr,rf->bsf", low, adapter["lora_gate"])
        up = up + jnp.einsum("bsr,rf->bsf", low, adapter["lora_up"])
    return jnp.einsum("bsf,fd->bsd", _ACTIVATIONS[activation](gate) * up, p["wo"])


# --------------------------------------------------------------------------- #
# MoE (GShard-style grouped einsum dispatch; EP-a2a path lives in             #
# parallel/ep_moe.py as a perf alternative)                                   #
# --------------------------------------------------------------------------- #
def moe_descs(cfg: ModelConfig) -> Dict[str, PDesc]:
    mo, d = cfg.moe, cfg.d_model
    descs = {
        "router": PDesc((d, mo.num_experts), ("embed", None), init="small"),
        "w_gate": PDesc((mo.num_experts, d, mo.d_expert), ("experts", "embed", "expert_ffn")),
        "w_up": PDesc((mo.num_experts, d, mo.d_expert), ("experts", "embed", "expert_ffn")),
        "w_down": PDesc((mo.num_experts, mo.d_expert, d), ("experts", "expert_ffn", "embed")),
    }
    if mo.num_shared:
        descs["shared"] = mlp_descs(cfg, d_ff=mo.num_shared * mo.d_expert)
    return descs


def moe(
    p: Dict[str, jax.Array],
    x: jax.Array,                    # (B, S, D)
    cfg: ModelConfig,
    group_size: int = 2048,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out, aux_loss). Token groups bound the dispatch tensor to
    (G, Tg, E, C) with Tg = group_size (GShard §3.2); groups shard over the
    batch axes, experts over the model axis."""
    mo = cfg.moe
    if get_tuning().moe_impl == "ep":
        from ..parallel.ep_moe import ep_moe, get_ep_mesh

        if get_ep_mesh() is not None:
            out, aux = ep_moe(
                {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}, x, cfg
            )
            if mo.num_shared:
                out = out + mlp(p["shared"], x, cfg.activation)
            return out, aux

    B, S, D = x.shape
    T = B * S
    tg = min(group_size, T)
    G = T // tg
    xf = x.reshape(G, tg, D)

    logits = jnp.einsum("gtd,de->gte", xf, p["router"]).astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, ids = jax.lax.top_k(probs, mo.top_k)            # (G,tg,k)
    gate_w = gate_w / jnp.clip(gate_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): mean prob vs mean assignment per expert
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(
        (jax.nn.one_hot(ids, mo.num_experts, dtype=F32)).sum(2), axis=(0, 1)
    ) / mo.top_k
    aux = mo.num_experts * jnp.sum(me * ce) * mo.router_aux_weight

    capacity = int(np.ceil(tg * mo.top_k / mo.num_experts * mo.capacity_factor))
    onehot = jax.nn.one_hot(ids, mo.num_experts, dtype=F32)  # (G,tg,k,E)
    # position of each (token, slot) within its expert, in (t, k) priority order
    flat = onehot.reshape(G, tg * mo.top_k, mo.num_experts)
    pos = (jnp.cumsum(flat, axis=1) - flat).astype(jnp.int32)  # (G,tg*k,E)
    pos = pos.reshape(G, tg, mo.top_k, mo.num_experts)
    # slot of each (token, k) within its CHOSEN expert; overflow slots drop
    pos_sel = jnp.take_along_axis(pos, ids[..., None], axis=-1)[..., 0]  # (G,tg,k)
    keep = (pos_sel < capacity).astype(x.dtype)
    oh_e = jax.nn.one_hot(ids, mo.num_experts, dtype=x.dtype) * keep[..., None]
    oh_c = jax.nn.one_hot(pos_sel, capacity, dtype=x.dtype)   # (G,tg,k,C)
    # contract k: never materializes the 5-D (t,k,E,C) tensor
    dispatch = jnp.einsum("gtke,gtkc->gtec", oh_e, oh_c)
    combine = jnp.einsum("gtk,gtke,gtkc->gtec", gate_w.astype(x.dtype), oh_e, oh_c)

    xin = jnp.einsum("gtec,gtd->gecd", dispatch, xf)          # (G,E,C,D)
    act = jax.nn.gelu if cfg.activation == "gelu" else jax.nn.silu
    h = act(jnp.einsum("gecd,edf->gecf", xin, p["w_gate"])) * jnp.einsum(
        "gecd,edf->gecf", xin, p["w_up"]
    )
    xout = jnp.einsum("gecf,efd->gecd", h, p["w_down"])       # (G,E,C,D)
    out = jnp.einsum("gtec,gecd->gtd", combine, xout).reshape(B, S, D)

    if mo.num_shared:
        out = out + mlp(p["shared"], x, cfg.activation)
    return out, aux
