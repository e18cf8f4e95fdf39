"""Expert-parallel MoE dispatch via shard_map + all_to_all (beyond-paper).

The GShard grouped-einsum dispatch (layers.moe) is GSPMD-native but costs
O(T·E·C·D) einsum flops — measured 40-50x the experts themselves for
granite's tiny d_expert=512 (useful_ratio 0.02, EXPERIMENTS §Roofline).
This module is the DeepSeek-style alternative: tokens are routed LOCALLY
per data shard (scatter into per-expert capacity buckets — O(T·D), no
one-hot einsums), exchanged with the expert owners via all_to_all over the
"model" axis, transformed, and returned. Dispatch cost collapses to
gather/scatter + 2 all_to_alls of (E, C_loc, D).

Enabled per-cell with tuning(moe_impl="ep"); numerically equivalent to the
einsum path when nothing overflows capacity (tests/test_ep_moe.py).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..models.config import ModelConfig

F32 = jnp.float32

# ambient mesh for shard_map (set by the dry-run / launcher around lowering)
_EP_MESH = None


class ep_mesh:
    def __init__(self, mesh) -> None:
        self.mesh = mesh

    def __enter__(self):
        global _EP_MESH
        self._prev = _EP_MESH
        _EP_MESH = self.mesh
        return self.mesh

    def __exit__(self, *exc):
        global _EP_MESH
        _EP_MESH = self._prev


def get_ep_mesh():
    return _EP_MESH


def _local_moe(xf, router, w_gate, w_up, w_down, *, cfg: ModelConfig,
               model_axis: str, e_pad: int) -> Tuple[jax.Array, jax.Array]:
    """Per-device block code. xf: (T_loc, D); expert weights: (E_pad/M, D, F).
    e_pad >= num_experts is the padded expert count (multiple of M); padded
    experts receive no tokens (router never selects them)."""
    mo = cfg.moe
    T, D = xf.shape
    E, k = mo.num_experts, mo.top_k
    M = jax.lax.psum(1, model_axis)

    logits = (xf @ router).astype(F32)                      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, ids = jax.lax.top_k(probs, k)                   # (T, k)
    gate_w = gate_w / jnp.clip(gate_w.sum(-1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(ids, E, dtype=F32).sum(1), axis=0) / k
    aux = E * jnp.sum(me * ce) * mo.router_aux_weight

    cap = int(np.ceil(T * k / E * mo.capacity_factor))
    # slot within the chosen expert, (t, k)-priority — O(T·E) ints, no einsum
    onehot = jax.nn.one_hot(ids, E, dtype=jnp.int32).reshape(T * k, E)
    pos = (jnp.cumsum(onehot, axis=0) - onehot).reshape(T, k, E)
    pos_sel = jnp.take_along_axis(pos, ids[..., None], axis=-1)[..., 0]  # (T,k)
    keep = pos_sel < cap
    slot = jnp.where(keep, ids * cap + pos_sel, e_pad * cap)  # e_pad*cap = drop

    tok_idx = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k))
    buf = jnp.zeros((e_pad * cap, D), xf.dtype)
    buf = buf.at[slot.ravel()].add(xf[tok_idx.ravel()], mode="drop")
    buf = buf.reshape(e_pad, cap, D)

    # ship each expert's bucket to its owner shard; receive M buckets for
    # each local expert: (E, C, D) -> (E/M, M*C, D)
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1,
                             tiled=True)

    act = jax.nn.gelu if cfg.activation == "gelu" else jax.nn.silu
    h = act(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up
    )
    out = jnp.einsum("ecf,efd->ecd", h, w_down)             # (E/M, M*C, D)

    out = jax.lax.all_to_all(out, model_axis, split_axis=1, concat_axis=0,
                             tiled=True)                    # (E_pad, C, D)
    out = out.reshape(e_pad * cap, D)
    y_tk = jnp.take(out, jnp.where(keep, slot, 0), axis=0)  # (T, k, D)
    y_tk = y_tk * (keep[..., None] * gate_w[..., None]).astype(xf.dtype)
    return y_tk.sum(axis=1), aux


def ep_moe(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig):
    """Drop-in for layers.moe's routed part. Requires an ep_mesh context.

    Tokens shard over (batch_axes, "model"): each model shard routes its
    OWN sequence slice (otherwise every shard would build and process an
    identical full dispatch buffer — M-fold duplicated expert work,
    observed as a 2x compute regression on deepseek before this layout).
    Experts pad up to a multiple of |model| (granite: 40 -> 48); padded
    experts are never routed to."""
    mesh = get_ep_mesh()
    assert mesh is not None, "ep_moe requires an ep_mesh(...) context"
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    m_size = mesh.shape["model"]
    B, S, D = x.shape
    E = cfg.moe.num_experts
    e_pad = ((E + m_size - 1) // m_size) * m_size
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    if e_pad != E:
        padn = e_pad - E
        wg = jnp.pad(wg, ((0, padn), (0, 0), (0, 0)))
        wu = jnp.pad(wu, ((0, padn), (0, 0), (0, 0)))
        wd = jnp.pad(wd, ((0, padn), (0, 0), (0, 0)))
    seq_shardable = S % m_size == 0
    x_spec = (
        P(batch_axes, "model", None) if seq_shardable else P(batch_axes, None, None)
    )

    def body(xb, router, wg, wu, wd):
        T = xb.shape[0] * xb.shape[1]
        y, aux = _local_moe(
            xb.reshape(T, D), router, wg, wu, wd,
            cfg=cfg, model_axis="model", e_pad=e_pad,
        )
        # aux is per-shard; average across the whole mesh
        aux = jax.lax.pmean(aux, batch_axes + ("model",))
        return y.reshape(xb.shape), aux

    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            x_spec,                      # x: batch (and seq) sharded
            P(None, None),               # router: replicated
            P("model", None, None),      # experts: sharded over model
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], wg, wu, wd)
    return y, aux
