"""Model assembly for the 10 assigned architectures.

Every architecture is described by the same ``ModelConfig``; this module
builds (a) the parameter-descriptor tree, (b) ``forward`` for train/prefill,
(c) ``decode_step`` against explicit caches, and (d) the LM loss. Stacks use
``lax.scan`` over layer-stacked parameters; heterogeneous stacks (gemma3
local:global, vision cross-attention) scan over *groups* whose inner
structure is homogeneous, so the HLO stays compact at any depth; zamba2
scans each run of Mamba layers between its shared-block sites.

Family map:
  dense / moe    -> forward_dense     (MLA and MoE are per-block options)
  gemma3         -> grouped local/global stack (ring caches for local layers)
  ssm            -> forward_ssm       (Mamba-2 SSD)
  hybrid         -> forward_hybrid    (zamba2: shared attn+MLP blocks at listed sites)
  encdec         -> forward_encdec    (seamless: stub audio frames -> encoder)
  vlm            -> forward_vlm       (llama-3.2-vision: gated cross-attn groups)
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import (
    attention,
    attn_descs,
    mla_attention,
    mla_descs,
    mlp,
    mlp_descs,
    moe,
    moe_descs,
    rms_norm,
)
from .params import PDesc, stack_tree
from .scan_utils import _scan, scan_unroll
from .ssm import mamba2_mixer, ssm_descs
from .tuning import constrain_batch_sharded, get_tuning

F32 = jnp.float32


# --------------------------------------------------------------------------- #
# blocks                                                                       #
# --------------------------------------------------------------------------- #
def _block_descs(cfg: ModelConfig, *, kind: str, dense_ff: Optional[int] = None) -> Dict:
    """kind: attn | local_attn | mla | attn_moe | mla_moe | ssm | cross | attn_dense"""
    d = cfg.d_model
    descs: Dict[str, Any] = {"ln1": PDesc((d,), ("embed",), init="zeros")}
    if kind == "ssm":
        descs["mixer"] = ssm_descs(cfg)
        return descs  # mamba block has its own epilogue norm
    if kind == "cross":
        descs["attn"] = attn_descs(cfg, cross=True)
        descs["ln2"] = PDesc((d,), ("embed",), init="zeros")
        descs["mlp"] = mlp_descs(cfg)
        descs["mlp_gate"] = PDesc((1,), (None,), init="zeros")
        return descs
    descs["attn"] = mla_descs(cfg) if kind.startswith("mla") else attn_descs(cfg)
    descs["ln2"] = PDesc((d,), ("embed",), init="zeros")
    if kind.endswith("moe"):
        descs["moe"] = moe_descs(cfg)
    else:
        descs["mlp"] = mlp_descs(cfg, d_ff=dense_ff)
    return descs


def _block_apply(
    cfg: ModelConfig,
    lp: Dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    kind: str,
    window: Optional[int] = None,
    cache: Optional[Dict] = None,
    cache_index: Optional[jax.Array] = None,
    ring: bool = False,
    cross_src: Optional[jax.Array] = None,
    causal: bool = True,
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    x = constrain_batch_sharded(x)  # §Perf B3 knob; no-op unless tuned on
    aux = jnp.zeros((), F32)
    if kind == "ssm":
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        out, new_cache = mamba2_mixer(lp["mixer"], h, cfg, cache=cache)
        return x + out, new_cache, aux

    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if kind == "cross":
        out, new_cache = attention(
            lp["attn"], h, cfg, positions, cross_src=cross_src, causal=False
        )
        out = out * jnp.tanh(lp["attn"]["gate"].astype(x.dtype))
        x = x + out
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        m = mlp(lp["mlp"], h2, cfg.activation)
        m = m * jnp.tanh(lp["mlp_gate"].astype(x.dtype))
        return x + m, None, aux

    if kind.startswith("mla"):
        out, new_cache = mla_attention(
            lp["attn"], h, cfg, positions, cache=cache, cache_index=cache_index
        )
    else:
        out, new_cache = attention(
            lp["attn"],
            h,
            cfg,
            positions,
            window=window,
            cache=cache,
            cache_index=cache_index,
            ring=ring,
            cross_src=cross_src,
            causal=causal,
        )
    x = x + out
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if kind.endswith("moe"):
        m, aux = moe(lp["moe"], h2, cfg)
    else:
        m = mlp(lp["mlp"], h2, cfg.activation)
    return x + m, new_cache, aux


def _maybe_remat(fn: Callable, policy: str) -> Callable:
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(fn)


# --------------------------------------------------------------------------- #
# embedding / head                                                             #
# --------------------------------------------------------------------------- #
def _embed_descs(cfg: ModelConfig) -> Dict:
    descs = {
        "embed": PDesc((cfg.vocab_padded, cfg.d_model), ("vocab", "embed")),
        "ln_f": PDesc((cfg.d_model,), ("embed",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        descs["lm_head"] = PDesc((cfg.d_model, cfg.vocab_padded), ("embed", "vocab"))
    return descs


def _embed(cfg: ModelConfig, params: Dict, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.activation == "gelu":  # gemma family scales embeddings
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x


def apply_head(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])


def _logits(cfg: ModelConfig, params: Dict, x: jax.Array, last_only: bool = False) -> jax.Array:
    if last_only:
        x = x[:, -1:]
    elif get_tuning().loss_chunk:
        # §Perf knob: leave hidden states; the head is applied chunk-wise
        # inside chunked_lm_loss to bound the fp32 logits working set.
        return x
    return apply_head(cfg, params, x)


def chunked_lm_loss(
    cfg: ModelConfig,
    params: Dict,
    hidden: jax.Array,   # (B, S, D) — forward output under loss_chunk tuning
    labels: jax.Array,   # (B, S)
    aux: jax.Array,
    chunk: int,
) -> jax.Array:
    """LM head + cross-entropy over sequence chunks (rematerialized): the
    (B, chunk, V) fp32 logits are the only head-sized live tensor."""
    B, S, D = hidden.shape
    if S % chunk != 0:
        return lm_loss(cfg, apply_head(cfg, params, hidden), labels, aux)
    nc = S // chunk
    xr = jnp.moveaxis(hidden.reshape(B, nc, chunk, D), 1, 0)
    yr = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)

    def body(carry, xs):
        xc, yc = xs
        logits = apply_head(cfg, params, xc).astype(F32)
        if cfg.vocab_padded != cfg.vocab_size:
            pad = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
            logits = jnp.where(pad[None, None, :], -1e30, logits)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(logz - ll), None

    body = jax.checkpoint(body)
    total, _ = _scan(body, jnp.zeros((), F32), (xr, yr))
    return total / (B * S) + aux


# --------------------------------------------------------------------------- #
# family: dense / moe / gemma3                                                 #
# --------------------------------------------------------------------------- #
def _dense_plan(cfg: ModelConfig) -> Dict:
    """Segments of homogeneous stacks for dense/moe/mla archs."""
    if cfg.global_period:  # gemma3: groups of (p-1) local + 1 global, + tail
        p = cfg.global_period
        n_groups = cfg.num_layers // p
        tail = cfg.num_layers - n_groups * p
        return {"kind": "gemma3", "groups": n_groups, "locals": p - 1, "tail": tail}
    if cfg.moe is not None and cfg.moe.first_k_dense:
        return {
            "kind": "deepseek",
            "dense": cfg.moe.first_k_dense,
            "moe": cfg.num_layers - cfg.moe.first_k_dense,
        }
    return {"kind": "flat", "layers": cfg.num_layers}


def _attn_kind(cfg: ModelConfig) -> str:
    if cfg.mla is not None:
        return "mla_moe" if cfg.moe is not None else "mla"
    return "attn_moe" if cfg.moe is not None else "attn"


def dense_descs(cfg: ModelConfig) -> Dict:
    plan = _dense_plan(cfg)
    descs = _embed_descs(cfg)
    if plan["kind"] == "flat":
        descs["layers"] = stack_tree(_block_descs(cfg, kind=_attn_kind(cfg)), plan["layers"])
    elif plan["kind"] == "deepseek":
        dense_block = _block_descs(cfg, kind="mla", dense_ff=cfg.moe.dense_d_ff)
        descs["dense_layers"] = stack_tree(dense_block, plan["dense"])
        descs["moe_layers"] = stack_tree(_block_descs(cfg, kind="mla_moe"), plan["moe"])
    else:  # gemma3
        local = _block_descs(cfg, kind="attn")
        descs["group_locals"] = stack_tree(stack_tree(local, plan["locals"]), plan["groups"])
        descs["group_global"] = stack_tree(_block_descs(cfg, kind="attn"), plan["groups"])
        if plan["tail"]:
            descs["tail_locals"] = stack_tree(local, plan["tail"])
    return descs


def _scan_stack(
    cfg: ModelConfig,
    stacked: Dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    kind: str,
    window: Optional[int] = None,
    cache: Optional[Dict] = None,
    cache_index: Optional[jax.Array] = None,
    ring: bool = False,
    causal: bool = True,
    remat: str = "none",
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    def body(carry, xs):
        h, aux = carry
        lp, c = xs
        h, new_c, a = _block_apply(
            cfg, lp, h, positions,
            kind=kind, window=window, cache=c, cache_index=cache_index,
            ring=ring, causal=causal,
        )
        return (h, aux + a), new_c

    body = _maybe_remat(body, remat)
    (x, aux), new_cache = _scan(body, (x, jnp.zeros((), F32)), (stacked, cache))
    return x, new_cache, aux


def forward_dense(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,
    last_only: bool = False,
    *,
    remat: str = "none",
    cache: Optional[Dict] = None,
    cache_index: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Train/prefill when cache is None; single-token decode otherwise."""
    plan = _dense_plan(cfg)
    B, S = tokens.shape
    if cache is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        decode = False
    else:
        positions = jnp.broadcast_to(cache_index[None, None].astype(jnp.int32), (B, S))
        decode = True
    x = _embed(cfg, params, tokens)
    aux = jnp.zeros((), F32)

    if plan["kind"] == "flat":
        x, new_cache, aux = _scan_stack(
            cfg, params["layers"], x, positions,
            kind=_attn_kind(cfg),
            cache=cache["layers"] if decode else None,
            cache_index=cache_index, remat=remat,
        )
        new_cache = {"layers": new_cache} if decode else None
    elif plan["kind"] == "deepseek":
        x, nc_d, a1 = _scan_stack(
            cfg, params["dense_layers"], x, positions, kind="mla",
            cache=cache["dense_layers"] if decode else None,
            cache_index=cache_index, remat=remat,
        )
        x, nc_m, a2 = _scan_stack(
            cfg, params["moe_layers"], x, positions, kind="mla_moe",
            cache=cache["moe_layers"] if decode else None,
            cache_index=cache_index, remat=remat,
        )
        aux = a1 + a2
        new_cache = {"dense_layers": nc_d, "moe_layers": nc_m} if decode else None
    else:  # gemma3 grouped local/global
        def group_body(carry, xs):
            h, aux = carry
            gl, gg, cl, cg = xs
            h, ncl, a1 = _scan_stack(
                cfg, gl, h, positions, kind="attn", window=cfg.sliding_window,
                cache=cl, cache_index=cache_index, ring=decode,
            )
            h, ncg, a2 = _block_apply(
                cfg, gg, h, positions, kind="attn",
                cache=cg, cache_index=cache_index,
            )
            return (h, aux + a1 + a2), (ncl, ncg)

        group_body = _maybe_remat(group_body, remat)
        xs = (
            params["group_locals"], params["group_global"],
            cache["group_locals"] if decode else None,
            cache["group_global"] if decode else None,
        )
        (x, aux), (ncl, ncg) = _scan(group_body, (x, aux), xs)
        nct = None
        if plan["tail"]:
            x, nct, a3 = _scan_stack(
                cfg, params["tail_locals"], x, positions,
                kind="attn", window=cfg.sliding_window,
                cache=cache["tail_locals"] if decode else None,
                cache_index=cache_index, ring=decode, remat=remat,
            )
            aux = aux + a3
        new_cache = (
            {"group_locals": ncl, "group_global": ncg, "tail_locals": nct}
            if decode else None
        )
        if decode and not plan["tail"]:
            new_cache.pop("tail_locals")

    logits = _logits(cfg, params, x, last_only)
    return logits, new_cache, aux


# --------------------------------------------------------------------------- #
# family: ssm / hybrid                                                         #
# --------------------------------------------------------------------------- #
def ssm_descs_tree(cfg: ModelConfig) -> Dict:
    descs = _embed_descs(cfg)
    descs["layers"] = stack_tree(_block_descs(cfg, kind="ssm"), cfg.num_layers)
    return descs


def hybrid_plan(cfg: ModelConfig) -> List[Tuple[Optional[int], int]]:
    """The hybrid stack as segments of Mamba layers: (site, layers), where
    ``site`` is the index into ``hybrid_layer_ids`` of the shared-block site
    that runs before the segment's first layer (None for the layers before
    the first site)."""
    ids = list(cfg.hybrid_layer_ids)
    assert ids == sorted(set(ids)) and 0 <= ids[0] and ids[-1] < cfg.num_layers, ids
    bounds = ids + [cfg.num_layers]
    plan: List[Tuple[Optional[int], int]] = [(None, ids[0])] if ids[0] else []
    return plan + [(k, bounds[k + 1] - bounds[k]) for k in range(len(ids))]


def hybrid_descs(cfg: ModelConfig) -> Dict:
    """Zamba2: the Mamba layers as one stack per segment of ``hybrid_plan``
    (a scan over a slice of one stack would copy the slice), the shared
    blocks stacked once each, and every site's adapter and linear stacked
    in site order."""
    d, r = cfg.d_model, cfg.adapter_rank
    a = 2 * d  # the shared block reads concat(h, embedding)
    descs = _embed_descs(cfg)
    block = {
        "ln_in": PDesc((a,), ("embed",), init="zeros"),
        "attn": attn_descs(cfg, d_in=a),
        "ln_ff": PDesc((d,), ("embed",), init="zeros"),
        "mlp": mlp_descs(cfg),
    }
    site = {
        "lora_a": PDesc((d, r), ("embed", None)),
        "lora_gate": PDesc((r, cfg.d_ff), (None, "ffn")),
        "lora_up": PDesc((r, cfg.d_ff), (None, "ffn")),
        "linear": PDesc((d, d), ("embed", None)),
    }
    descs["blocks"] = stack_tree(block, cfg.num_mem_blocks)
    descs["sites"] = stack_tree(site, len(cfg.hybrid_layer_ids))
    descs["mamba"] = [stack_tree(_block_descs(cfg, kind="ssm"), n) for _, n in hybrid_plan(cfg)]
    return descs


def forward_ssm(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,
    last_only: bool = False,
    *,
    remat: str = "none",
    cache: Optional[Dict] = None,
    cache_index: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    B, S = tokens.shape
    decode = cache is not None
    positions = (
        jnp.broadcast_to(cache_index[None, None].astype(jnp.int32), (B, S))
        if decode
        else jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    )
    x = _embed(cfg, params, tokens)
    x, new_cache, aux = _scan_stack(
        cfg, params["layers"], x, positions, kind="ssm",
        cache=cache["layers"] if decode else None,
        cache_index=cache_index, remat=remat,
    )
    return _logits(cfg, params, x, last_only), ({"layers": new_cache} if decode else None), aux


def _shared_block(
    cfg: ModelConfig,
    params: Dict,
    site: int,
    h: jax.Array,
    emb: jax.Array,
    positions: jax.Array,
    cache: Optional[Dict],
    cache_index: Optional[jax.Array],
) -> Tuple[jax.Array, Optional[Dict]]:
    """Zamba2's shared block at one site (``Zamba2HybridLayer`` up to its
    Mamba layer): RMSNorm of concat(h, embedding), attention scaled by
    (head_dim / 2) ** -0.5, RMSNorm, the gated MLP with the site's LoRA on
    gate and up, no residual; then the site's linear."""
    take = lambda tree, i: jax.tree_util.tree_map(lambda w: w[i], tree)
    bp = take(params["blocks"], site % cfg.num_mem_blocks)
    sp = take(params["sites"], site)
    with jax.named_scope("zamba2.shared_block"):
        t = rms_norm(jnp.concatenate([h, emb], axis=-1), bp["ln_in"], cfg.norm_eps)
        t, new_cache = attention(
            bp["attn"], t, cfg, positions, cache=cache, cache_index=cache_index,
            scale=(cfg.resolved_head_dim / 2) ** -0.5,
        )
        t = mlp(bp["mlp"], rms_norm(t, bp["ln_ff"], cfg.norm_eps), cfg.activation, adapter=sp)
    with jax.named_scope("zamba2.site_linear"):
        t = jnp.einsum("bsd,de->bse", t, sp["linear"])
    return t, new_cache


def _mamba_segment(
    cfg: ModelConfig,
    stacked: Dict,
    x: jax.Array,
    t: Optional[jax.Array],
    cache: Optional[Dict],
    remat: str,
) -> Tuple[jax.Array, Optional[Dict]]:
    """Zamba2 Mamba layers, h + mixer(norm(h + t)), with the site's output
    ``t`` added to the first layer's input only."""
    def body(carry, xs):
        h, t = carry
        lp, c = xs
        hin = h if t is None else h + t
        out, new_c = mamba2_mixer(lp["mixer"], rms_norm(hin, lp["ln1"], cfg.norm_eps), cfg, cache=c)
        return (h + out, None if t is None else jnp.zeros_like(t)), new_c

    with jax.named_scope("zamba2.mamba"):
        (x, _), new_cache = _scan(_maybe_remat(body, remat), (x, t), (stacked, cache))
    return x, new_cache


def forward_hybrid(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,
    last_only: bool = False,
    *,
    remat: str = "none",
    cache: Optional[Dict] = None,
    cache_index: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Zamba2 (``Zamba2Model``): Mamba-2 layers, with a shared block's
    output added to the input of each site's layer."""
    B, S = tokens.shape
    decode = cache is not None
    positions = (
        jnp.broadcast_to(cache_index[None, None].astype(jnp.int32), (B, S))
        if decode
        else jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    )
    emb = _embed(cfg, params, tokens)

    def run_block(site, h, c):
        return _shared_block(cfg, params, site, h, emb, positions, c, cache_index)

    x = emb
    new_mamba, new_kv = [], []
    for j, (site, _) in enumerate(hybrid_plan(cfg)):
        t = None
        if site is not None:
            t, nkv = _maybe_remat(functools.partial(run_block, site), remat)(
                x, cache["kv"][site] if decode else None)
            new_kv.append(nkv)
        x, nm = _mamba_segment(
            cfg, params["mamba"][j], x, t, cache["mamba"][j] if decode else None, remat,
        )
        new_mamba.append(nm)
    new_cache = {"mamba": new_mamba, "kv": new_kv} if decode else None
    return _logits(cfg, params, x, last_only), new_cache, jnp.zeros((), F32)


# --------------------------------------------------------------------------- #
# family: encoder-decoder (seamless-m4t)                                       #
# --------------------------------------------------------------------------- #
def encdec_descs(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    descs = _embed_descs(cfg)
    enc_block = _block_descs(cfg, kind="attn")
    descs["encoder"] = stack_tree(enc_block, cfg.encoder_layers)
    dec_block = _block_descs(cfg, kind="attn")
    dec_block["ln_cross"] = PDesc((d,), ("embed",), init="zeros")
    dec_block["cross_attn"] = attn_descs(cfg)
    descs["decoder"] = stack_tree(dec_block, cfg.num_layers)
    return descs


def _decoder_block(cfg, lp, x, positions, enc_out, cache, cache_index):
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    out, new_cache = attention(
        lp["attn"], h, cfg, positions, cache=cache, cache_index=cache_index
    )
    x = x + out
    hc = rms_norm(x, lp["ln_cross"], cfg.norm_eps)
    out, _ = attention(lp["cross_attn"], hc, cfg, positions, cross_src=enc_out, causal=False)
    x = x + out
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    return x + mlp(lp["mlp"], h2, cfg.activation), new_cache


def forward_encdec(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,
    last_only: bool = False,            # decoder text tokens (B, S)
    *,
    frames: jax.Array,            # stub audio frontend output (B, Ssrc, D)
    remat: str = "none",
    cache: Optional[Dict] = None,
    cache_index: Optional[jax.Array] = None,
    enc_out: Optional[jax.Array] = None,  # reuse encoder output during decode
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    B, S = tokens.shape
    decode = cache is not None

    if enc_out is None:
        src_pos = jnp.broadcast_to(
            jnp.arange(frames.shape[1], dtype=jnp.int32)[None], frames.shape[:2]
        )
        def enc_body(carry, lp):
            h, _ = carry
            h, _, _ = _block_apply(cfg, lp, h, src_pos, kind="attn", causal=False)
            return (h, jnp.zeros((), F32)), None
        enc_body = _maybe_remat(enc_body, remat)
        (enc_out, _), _ = _scan(enc_body, (frames, jnp.zeros((), F32)), params["encoder"])

    positions = (
        jnp.broadcast_to(cache_index[None, None].astype(jnp.int32), (B, S))
        if decode
        else jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    )
    x = _embed(cfg, params, tokens)

    def dec_body(carry, xs):
        h, _ = carry
        lp, c = xs
        h, nc = _decoder_block(cfg, lp, h, positions, enc_out, c, cache_index)
        return (h, jnp.zeros((), F32)), nc

    dec_body = _maybe_remat(dec_body, remat)
    (x, _), new_cache = _scan(
        dec_body, (x, jnp.zeros((), F32)),
        (params["decoder"], cache["decoder"] if decode else None),
    )
    nc = {"decoder": new_cache, "enc_out": enc_out} if decode else None
    return _logits(cfg, params, x, last_only), nc, jnp.zeros((), F32)


# --------------------------------------------------------------------------- #
# family: vision-language (llama-3.2-vision)                                   #
# --------------------------------------------------------------------------- #
def vlm_descs(cfg: ModelConfig) -> Dict:
    p = cfg.cross_attn_period
    n_groups = cfg.num_layers // p
    descs = _embed_descs(cfg)
    self_block = _block_descs(cfg, kind="attn")
    descs["group_selfs"] = stack_tree(stack_tree(self_block, p - 1), n_groups)
    descs["group_cross"] = stack_tree(_block_descs(cfg, kind="cross"), n_groups)
    return descs


def forward_vlm(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,
    last_only: bool = False,
    *,
    image_embeds: jax.Array,      # stub vision frontend output (B, Nimg, D)
    remat: str = "none",
    cache: Optional[Dict] = None,
    cache_index: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    B, S = tokens.shape
    decode = cache is not None
    positions = (
        jnp.broadcast_to(cache_index[None, None].astype(jnp.int32), (B, S))
        if decode
        else jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    )
    x = _embed(cfg, params, tokens)

    def group_body(carry, xs):
        h, _ = carry
        gs, gc, cs = xs
        h, ncs, _ = _scan_stack(
            cfg, gs, h, positions, kind="attn",
            cache=cs, cache_index=cache_index,
        )
        h, _, _ = _block_apply(
            cfg, gc, h, positions, kind="cross", cross_src=image_embeds
        )
        return (h, jnp.zeros((), F32)), ncs

    group_body = _maybe_remat(group_body, remat)
    xs = (
        params["group_selfs"], params["group_cross"],
        cache["group_selfs"] if decode else None,
    )
    (x, _), ncs = _scan(group_body, (x, jnp.zeros((), F32)), xs)
    new_cache = {"group_selfs": ncs} if decode else None
    return _logits(cfg, params, x, last_only), new_cache, jnp.zeros((), F32)


# --------------------------------------------------------------------------- #
# unified entry points                                                         #
# --------------------------------------------------------------------------- #
_FORWARD = {
    "dense": forward_dense,
    "moe": forward_dense,
    "ssm": forward_ssm,
    "hybrid": forward_hybrid,
    "encdec": forward_encdec,
    "vlm": forward_vlm,
}

_DESCS = {
    "dense": dense_descs,
    "moe": dense_descs,
    "ssm": ssm_descs_tree,
    "hybrid": hybrid_descs,
    "encdec": encdec_descs,
    "vlm": vlm_descs,
}


def param_descs(cfg: ModelConfig) -> Dict:
    return _DESCS[cfg.family](cfg)


def forward(cfg: ModelConfig, params: Dict, tokens: jax.Array, *, extras=None, **kw):
    extras = extras or {}
    fwd = _FORWARD[cfg.family]
    if cfg.family == "encdec":
        return fwd(cfg, params, tokens, frames=extras["frames"], **kw)
    if cfg.family == "vlm":
        return fwd(cfg, params, tokens, image_embeds=extras["image_embeds"], **kw)
    return fwd(cfg, params, tokens, **kw)


def lm_loss(
    cfg: ModelConfig,
    logits: jax.Array,      # (B, S, Vp)
    labels: jax.Array,      # (B, S)
    aux: jax.Array,
) -> jax.Array:
    logits = logits.astype(F32)
    if cfg.vocab_padded != cfg.vocab_size:
        pad = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
        logits = jnp.where(pad[None, None, :], -1e30, logits)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - ll) + aux


# --------------------------------------------------------------------------- #
# decode caches                                                                #
# --------------------------------------------------------------------------- #
def _attn_cache_desc(cfg: ModelConfig, batch: int, length: int) -> Dict[str, PDesc]:
    nkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": PDesc((batch, length, nkv, hd), ("batch", "seq", "kv_heads", None), init="zeros"),
        "v": PDesc((batch, length, nkv, hd), ("batch", "seq", "kv_heads", None), init="zeros"),
    }


def _mla_cache_desc(cfg: ModelConfig, batch: int, length: int) -> Dict[str, PDesc]:
    m = cfg.mla
    return {
        "ckv": PDesc((batch, length, m.kv_lora_rank), ("batch", "seq", None), init="zeros"),
        "kpe": PDesc((batch, length, m.qk_rope_head_dim), ("batch", "seq", None), init="zeros"),
    }


def _ssm_cache_desc(cfg: ModelConfig, batch: int) -> Dict[str, PDesc]:
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    conv_ch = di + 2 * s.n_groups * s.d_state
    return {
        "conv": PDesc((batch, s.d_conv - 1, conv_ch), ("batch", None, "ffn"), init="zeros"),
        "state": PDesc((batch, nh, s.head_dim, s.d_state), ("batch", "heads", None, None), init="zeros"),
    }


def cache_descs(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """Decode-cache descriptor tree matching the family's scan layout."""
    if cfg.family in ("dense", "moe"):
        plan = _dense_plan(cfg)
        mk = _mla_cache_desc if cfg.mla is not None else _attn_cache_desc
        if plan["kind"] == "flat":
            return {"layers": stack_tree(mk(cfg, batch, max_len), plan["layers"])}
        if plan["kind"] == "deepseek":
            return {
                "dense_layers": stack_tree(mk(cfg, batch, max_len), plan["dense"]),
                "moe_layers": stack_tree(mk(cfg, batch, max_len), plan["moe"]),
            }
        # gemma3: ring caches (window-sized) for locals, full for globals
        w = min(cfg.sliding_window, max_len)
        out = {
            "group_locals": stack_tree(
                stack_tree(_attn_cache_desc(cfg, batch, w), plan["locals"]), plan["groups"]
            ),
            "group_global": stack_tree(_attn_cache_desc(cfg, batch, max_len), plan["groups"]),
        }
        if plan["tail"]:
            out["tail_locals"] = stack_tree(_attn_cache_desc(cfg, batch, w), plan["tail"])
        return out
    if cfg.family == "ssm":
        return {"layers": stack_tree(_ssm_cache_desc(cfg, batch), cfg.num_layers)}
    if cfg.family == "hybrid":
        # each segment's SSM state stacked as its layers are; one KV cache a site
        return {
            "mamba": [stack_tree(_ssm_cache_desc(cfg, batch), n) for _, n in hybrid_plan(cfg)],
            "kv": [_attn_cache_desc(cfg, batch, max_len) for _ in cfg.hybrid_layer_ids],
        }
    if cfg.family == "encdec":
        return {
            "decoder": stack_tree(_attn_cache_desc(cfg, batch, max_len), cfg.num_layers),
            "enc_out": PDesc(
                (batch, cfg.source_len, cfg.d_model), ("batch", None, "embed"), init="zeros"
            ),
        }
    if cfg.family == "vlm":
        p = cfg.cross_attn_period
        n_groups = cfg.num_layers // p
        return {
            "group_selfs": stack_tree(
                stack_tree(_attn_cache_desc(cfg, batch, max_len), p - 1), n_groups
            )
        }
    raise ValueError(cfg.family)


def decode_step(
    cfg: ModelConfig,
    params: Dict,
    cache: Dict,
    tokens: jax.Array,        # (B, 1)
    cache_index: jax.Array,   # scalar int32
    *,
    extras=None,
) -> Tuple[jax.Array, Dict]:
    extras = dict(extras or {})
    if cfg.family == "encdec":
        logits, new_cache, _ = forward_encdec(
            cfg, params, tokens,
            frames=extras.get("frames"),
            cache=cache, cache_index=cache_index,
            enc_out=cache.get("enc_out"),
        )
        return logits, new_cache
    logits, new_cache, _ = forward(
        cfg, params, tokens, extras=extras, cache=cache, cache_index=cache_index
    )
    return logits, new_cache
