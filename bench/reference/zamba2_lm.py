"""Plain reference of the Zamba2 language model (Zyphra Zamba2-7B-Instruct;
arXiv:2411.15242, shared-block design arXiv:2405.16712) the benchmark serves.
Written from the equations of the published implementation
(``transformers``' ``modeling_zamba2.py``), in plain ``jax.numpy``: no
kernel, no cache, no chunking. Every layer is a Mamba-2 layer,

    h <- h + mamba(rmsnorm(h + t)),

where ``t`` is zero except at a hybrid site k, whose ``t`` is the shared
block (k mod num_mem_blocks) applied to concat(h, embedding):

    u = rmsnorm(concat(h, e));  u = attention(u);  u = rmsnorm(u)
    u = down(gelu(gate(u) + B_g A u) * (up(u) + B_u A u));  t = linear_k(u)

with the site's own rank-r adapter (A, B_g, B_u) and ``linear``, attention
scores scaled by (head_dim / 2) ** -0.5 and RoPE over the whole head. The
Mamba mixer's gated RMSNorm normalises each of its ``ngroups`` slices
apart, and its SSM is the quadratic (attention-like) form over the whole
sequence, as in ``ssm_lm.py``. Attention runs over blocks of query rows so
long sessions fit.

Sizes come from the benchmark's configuration file, never from the
program. Weights come from the seed by the program's recipe: leaves in
sorted-path order (the Mamba layers as one stack per run of layers between
sites, the shared blocks and the sites each stacked), one key each from
splitting the seed's key, zeros, ones, or a normal draw over 1/sqrt(fan-in),
the fan-in being the dimensions the weight contracts with its input.

``dtype`` float32 under ``jax.default_matmul_precision("highest")`` is the
reference. The same code in bfloat16 at the default precision is the
control (norm statistics and softmax stay float32).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: bytes one (heads, S, S) decay block may take
_BLOCK_BYTES = 1 << 28
#: query rows per attention block
_Q_BLOCK = 512


# --------------------------------------------------------------------------- #
# weights                                                                      #
# --------------------------------------------------------------------------- #
def segments(c: Dict) -> List[Tuple[Optional[int], int]]:
    """Runs of Mamba layers: (the site before the run or None, its layers)."""
    ids = list(c["hybrid_layer_ids"])
    bounds = ids + [c["n_layer"]]
    out = [(None, ids[0])] if ids[0] else []
    return out + [(k, bounds[k + 1] - bounds[k]) for k in range(len(ids))]


def _w(shape, fan_in):
    return (tuple(shape), "normal", fan_in)


def _z(shape, init="zeros"):
    return (tuple(shape), init, 0)


def _mamba_leaves(c: Dict) -> Dict:
    d = c["d_model"]
    di = c["expand"] * d
    nh = di // c["headdim"]
    gn = c["ngroups"] * c["d_state"]
    conv_ch = di + 2 * gn
    return {
        "ln1": _z((d,)),
        "mixer": {
            "w_z": _w((d, di), d), "w_x": _w((d, di), d),
            "w_B": _w((d, gn), d), "w_C": _w((d, gn), d), "w_dt": _w((d, nh), d),
            "conv_w": _w((c["d_conv"], conv_ch), c["d_conv"]),
            "conv_b": _z((conv_ch,)),
            "A_log": _z((nh,)), "D": _z((nh,), "ones"), "dt_bias": _z((nh,)),
            "norm_w": _z((di,)),
            "out_proj": _w((di, d), di),
        },
    }


def _block_leaves(c: Dict) -> Dict:
    d, a, f = c["d_model"], c["attention_hidden_size"], c["ffn"]
    n, hd = c["n_heads_attn"], c["attn_head_dim"]
    return {
        "ln_in": _z((a,)),
        "attn": {"wq": _w((a, n, hd), a), "wk": _w((a, n, hd), a), "wv": _w((a, n, hd), a),
                 "wo": _w((n, hd, d), n * hd)},
        "ln_ff": _z((d,)),
        "mlp": {"wi_gate": _w((d, f), d), "wi_up": _w((d, f), d), "wo": _w((f, d), f)},
    }


def _site_leaves(c: Dict) -> Dict:
    d, r, f = c["d_model"], c["adapter_rank"], c["ffn"]
    return {"lora_a": _w((d, r), d), "lora_gate": _w((r, f), r), "lora_up": _w((r, f), r),
            "linear": _w((d, d), d)}


def _stack(tree, n: int):
    if isinstance(tree, dict):
        return {k: _stack(v, n) for k, v in tree.items()}
    shape, init, fan_in = tree
    return ((n,) + shape, init, fan_in)


def layout(c: Dict) -> Dict:
    """Leaf shapes, init kinds and fan-ins, nested as the model's weights are."""
    d, v = c["d_model"], c["vocab_padded"]
    tree: Dict = {"embed": _w((v, d), v), "ln_f": _z((d,))}
    if not c["tie_embeddings"]:
        tree["lm_head"] = _w((d, v), d)
    tree["blocks"] = _stack(_block_leaves(c), c["num_mem_blocks"])
    tree["sites"] = _stack(_site_leaves(c), len(c["hybrid_layer_ids"]))
    tree["mamba"] = [_stack(_mamba_leaves(c), n) for _, n in segments(c)]
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def init_weights(c: Dict, seed: jax.Array, dtype=F32) -> Dict:
    """Weights from the seed (an int32 scalar). Jit it: one call makes them
    all on the device."""
    leaves, treedef = jax.tree_util.tree_flatten(layout(c), is_leaf=_is_leaf)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for (shape, init, fan_in), k in zip(leaves, keys):
        if init == "zeros":
            out.append(jnp.zeros(shape, dtype))
        elif init == "ones":
            out.append(jnp.ones(shape, dtype))
        else:
            out.append((jax.random.normal(k, shape, F32) * (1.0 / np.sqrt(fan_in))).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------- #
# layers                                                                       #
# --------------------------------------------------------------------------- #
def rmsnorm(x, w, eps: float, groups: int = 1):
    """RMSNorm with scale 1 + w, over each of ``groups`` equal slices of the
    last axis apart."""
    shape = x.shape
    xf = x.astype(F32).reshape(shape[:-1] + (groups, shape[-1] // groups))
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.reshape(shape).astype(x.dtype) * (1.0 + w.astype(x.dtype))


def causal_conv(x, w, b):
    """Depthwise causal conv: out_t = b + sum_j w[K-1-j] x_{t-j}."""
    K, S = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x) + b
    for j in range(K):
        shifted = jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, :S]
        out = out + shifted * w[K - 1 - j]
    return out


def segsum(a):
    """a (..., S) -> (..., S, S): out[t, s] = sum_{s<r<=t} a[r] for s <= t,
    -inf above the diagonal, by a masked cumulative sum."""
    S = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (S,))
    r = jnp.arange(S)[:, None]
    s = jnp.arange(S)[None, :]
    x = jnp.where(r > s, x, 0.0)
    cs = jnp.cumsum(x, axis=-2)
    return jnp.where(r >= s, cs, -jnp.inf)


def ssd(x, dt, A, Bm, Cm):
    """y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s, head h
    reading group h // (H / G). x (b,S,H,P), dt (b,S,H) float32, A (H,)."""
    b, S, H, P = x.shape
    G = Bm.shape[2]
    rep = H // G
    hb = rep
    while hb > 1 and b * hb * S * S * 4 > _BLOCK_BYTES:
        hb //= 2
    while rep % hb:
        hb -= 1
    nb = H // hb
    CB = jnp.einsum("btgn,bsgn->bgts", Cm, Bm)
    xs = jnp.moveaxis(x.reshape(b, S, nb, hb, P), 2, 0)
    dts = jnp.moveaxis(dt.reshape(b, S, nb, hb), 2, 0)
    As = A.reshape(nb, hb)
    grp = jnp.arange(nb) * hb // rep

    @jax.checkpoint
    def block(args):
        xb, dtb, Ab, g = args
        decay = jnp.exp(segsum(jnp.moveaxis(dtb * Ab, 2, 1)))
        cb = jnp.take(CB, g, axis=1)
        M = cb[:, None].astype(F32) * decay * jnp.moveaxis(dtb, 2, 1)[:, :, None, :]
        return jnp.einsum("bhts,bshp->bthp", M.astype(x.dtype), xb)

    ys = jax.lax.map(block, (xs, dts, As, grp))
    return jnp.moveaxis(ys, 0, 2).reshape(b, S, H, P)


def mamba_layer(p: Dict, h, t, c: Dict):
    """h + mixer(rmsnorm(h + t)); ``t`` None where no site feeds the layer."""
    d = c["d_model"]
    di = c["expand"] * d
    P, N, G = c["headdim"], c["d_state"], c["ngroups"]
    nh = di // P
    eps = c["norm_eps"]
    m = p["mixer"]
    b, S, _ = h.shape
    u = rmsnorm(h if t is None else h + t, p["ln1"], eps)
    z = u @ m["w_z"]
    xbc = jnp.concatenate([u @ m["w_x"], u @ m["w_B"], u @ m["w_C"]], axis=-1)
    dt = jax.nn.softplus((u @ m["w_dt"]).astype(F32) + m["dt_bias"].astype(F32))
    xbc = jax.nn.silu(causal_conv(xbc, m["conv_w"], m["conv_b"]))
    xs = xbc[..., :di].reshape(b, S, nh, P)
    Bm = xbc[..., di:di + G * N].reshape(b, S, G, N)
    Cm = xbc[..., di + G * N:].reshape(b, S, G, N)
    A = -jnp.exp(m["A_log"].astype(F32))
    y = ssd(xs, dt, A, Bm, Cm) + xs * m["D"].astype(h.dtype)[:, None]
    y = rmsnorm(y.reshape(b, S, di) * jax.nn.silu(z), m["norm_w"], eps, groups=G)
    return h + y @ m["out_proj"]


def rope(x, theta: float):
    """x (b, S, n, hd): rotate the two halves of each head by position."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = np.arange(S)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(np.concatenate([ang, ang], -1)), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(np.concatenate([ang, ang], -1)), F32)[None, :, None, :]
    xf = x.astype(F32)
    half = jnp.concatenate([-xf[..., hd // 2:], xf[..., : hd // 2]], axis=-1)
    return (xf * cos + half * sin).astype(x.dtype)


def attention(p: Dict, u, c: Dict):
    """Causal multi-head attention over the whole sequence, query rows in
    blocks: softmax(q k^T (head_dim / 2) ** -0.5) v, then the output
    projection."""
    b, S, _ = u.shape
    hd = c["attn_head_dim"]
    q = rope(jnp.einsum("bsa,anh->bsnh", u, p["wq"]), c["rope_theta"])
    k = rope(jnp.einsum("bsa,anh->bsnh", u, p["wk"]), c["rope_theta"])
    v = jnp.einsum("bsa,anh->bsnh", u, p["wv"])
    scale = (hd / 2) ** -0.5
    qb = _Q_BLOCK if S % _Q_BLOCK == 0 else S

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = jnp.einsum("bqnh,btnh->bnqt", qi, k).astype(F32) * scale
        mask = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1).astype(u.dtype)
        return jnp.einsum("bnqt,btnh->bqnh", pr, v)

    o = jax.lax.map(rows, jnp.arange(S // qb))              # (S/qb, b, qb, n, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(b, S, *q.shape[2:])
    return jnp.einsum("bsnh,nhd->bsd", o, p["wo"])


def shared_block(w: Dict, k: int, h, e, c: Dict):
    """The site k's t: shared block k mod num_mem_blocks over concat(h, e),
    with the site's adapter on gate and up, then the site's linear."""
    take = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    bp = take(w["blocks"], k % c["num_mem_blocks"])
    sp = take(w["sites"], k)
    eps = c["norm_eps"]
    u = rmsnorm(jnp.concatenate([h, e], axis=-1), bp["ln_in"], eps)
    u = rmsnorm(attention(bp["attn"], u, c), bp["ln_ff"], eps)
    low = u @ sp["lora_a"]
    gate = u @ bp["mlp"]["wi_gate"] + low @ sp["lora_gate"]
    up = u @ bp["mlp"]["wi_up"] + low @ sp["lora_up"]
    u = (jax.nn.gelu(gate, approximate=False) * up) @ bp["mlp"]["wo"]
    return u @ sp["linear"]


def logits(w: Dict, tokens, c: Dict):
    """Logits (b, S, vocab_padded), float32, of input tokens (b, S)."""
    e = w["embed"][tokens]
    h = e
    for j, (site, n) in enumerate(segments(c)):
        t = None if site is None else shared_block(w, site, h, e, c)
        for i in range(n):
            h = mamba_layer(jax.tree_util.tree_map(lambda a: a[i], w["mamba"][j]), h, t, c)
            t = None
    h = rmsnorm(h, w["ln_f"], c["norm_eps"])
    head = w["embed"].T if c["tie_embeddings"] else w["lm_head"]
    return (h @ head).astype(F32)


def serve_logits_fn(c: Dict):
    """A function (weights, tokens) -> teacher-forced logits (n, vocab) over
    one session's inputs, padded at the end to ``pad_to`` positions so
    every session shares one program (causality keeps the padding out of
    earlier positions)."""
    f = jax.jit(lambda w, t: logits(w, t, c))

    def run(w: Dict, tokens: np.ndarray, pad_to: int) -> np.ndarray:
        buf = np.zeros((1, pad_to), np.int32)
        buf[0, : len(tokens)] = tokens
        return np.asarray(f(w, jnp.asarray(buf))[0, : len(tokens), : c["vocab_size"]])

    return run
