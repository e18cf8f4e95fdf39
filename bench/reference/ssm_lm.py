"""Plain reference of the Mamba-2 language model (arXiv:2405.21060) the
benchmark runs. Written from the equations, in plain ``jax.numpy``: no
kernel, no cache, no chunking. The SSM layer is the quadratic
(attention-like) form of the state-space model over the whole sequence,

    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t,

with the decay sums taken by the stable masked cumulative sum.

Sizes come from the benchmark's configuration file, never from the
program. Weights come from the seed by the program's published recipe:
leaves in sorted-path order, one key each from splitting the seed's key,
zeros, ones, or a normal draw over 1/sqrt(second-to-last dimension).

``dtype`` float32 under ``jax.default_matmul_precision("highest")`` is the
reference. The same code in bfloat16 at the default precision is the
control (norm statistics, softmax and the loss stay float32, as a bfloat16
implementation keeps them).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: bytes one (heads, S, S) decay block may take; larger head counts are
#: processed in blocks of heads so a long sequence fits
_BLOCK_BYTES = 1 << 28


# --------------------------------------------------------------------------- #
# weights                                                                      #
# --------------------------------------------------------------------------- #
def _ssm_leaves(c: Dict) -> Dict:
    d = c["d_model"]
    di = c["expand"] * d
    nh = di // c["headdim"]
    gn = c["ngroups"] * c["d_state"]
    conv_ch = di + 2 * gn
    return {
        "ln1": ((d,), "zeros"),
        "mixer": {
            "w_z": ((d, di), "normal"), "w_x": ((d, di), "normal"),
            "w_B": ((d, gn), "normal"), "w_C": ((d, gn), "normal"),
            "w_dt": ((d, nh), "normal"),
            "conv_w": ((c["d_conv"], conv_ch), "normal"),
            "conv_b": ((conv_ch,), "zeros"),
            "A_log": ((nh,), "zeros"), "D": ((nh,), "ones"), "dt_bias": ((nh,), "zeros"),
            "norm_w": ((di,), "zeros"),
            "out_proj": ((di, d), "normal"),
        },
    }


def _stack(tree, n: int):
    if isinstance(tree, dict):
        return {k: _stack(v, n) for k, v in tree.items()}
    shape, init = tree
    return ((n,) + shape, init)


def layout(c: Dict) -> Dict:
    """Leaf shapes and init kinds, nested as the model's weights are."""
    d, v = c["d_model"], c["vocab_padded"]
    tree: Dict = {"embed": ((v, d), "normal"), "ln_f": ((d,), "zeros")}
    if not c["tie_embeddings"]:
        tree["lm_head"] = ((d, v), "normal")
    tree["layers"] = _stack(_ssm_leaves(c), c["n_layer"])
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_weights(c: Dict, seed: jax.Array, dtype=F32) -> Dict:
    """Weights from the seed (an int32 scalar). Jit it: one call makes them
    all on the device."""
    leaves, treedef = jax.tree_util.tree_flatten(layout(c), is_leaf=_is_leaf)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for (shape, init), k in zip(leaves, keys):
        if init == "zeros":
            out.append(jnp.zeros(shape, dtype))
        elif init == "ones":
            out.append(jnp.ones(shape, dtype))
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            std = 1.0 / np.sqrt(max(fan_in, 1))
            out.append((jax.random.normal(k, shape, F32) * std).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------- #
# layers                                                                       #
# --------------------------------------------------------------------------- #
def _mm(eq: str, a, b):
    return jnp.einsum(eq, a, b)


def rmsnorm(x, w, eps: float):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * (1.0 + w.astype(x.dtype))


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
    -inf above the diagonal; by a masked cumulative sum, so no difference of
    two long sums loses precision."""
    S = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (S,))  # x[..., r, s] = a[r]
    r = jnp.arange(S)[:, None]
    s = jnp.arange(S)[None, :]
    x = jnp.where(r > s, x, 0.0)
    cs = jnp.cumsum(x, axis=-2)
    return jnp.where(r >= s, cs, -jnp.inf)


def ssd(x, dt, A, Bm, Cm):
    """x (b,S,H,P), dt (b,S,H) float32, A (H,) float32, Bm/Cm (b,S,G,N)."""
    b, S, H, P = x.shape
    G = Bm.shape[2]
    rep = H // G
    hb = rep
    while hb > 1 and b * hb * S * S * 4 > _BLOCK_BYTES:
        hb //= 2
    while rep % hb:
        hb -= 1
    nb = H // hb
    CB = _mm("btgn,bsgn->bgts", Cm, Bm)  # (b,G,S,S)
    xs = jnp.moveaxis(x.reshape(b, S, nb, hb, P), 2, 0)
    dts = jnp.moveaxis(dt.reshape(b, S, nb, hb), 2, 0)
    As = A.reshape(nb, hb)
    grp = jnp.arange(nb) * hb // rep

    @jax.checkpoint
    def block(args):
        xb, dtb, Ab, g = args
        dA = jnp.moveaxis(dtb * Ab, 2, 1)                # (b,hb,S)
        decay = jnp.exp(segsum(dA))                       # (b,hb,t,s)
        cb = jnp.take(CB, g, axis=1)                      # (b,t,s)
        M = cb[:, None].astype(F32) * decay * jnp.moveaxis(dtb, 2, 1)[:, :, None, :]
        return _mm("bhts,bshp->bthp", M.astype(x.dtype), xb)

    ys = jax.lax.map(block, (xs, dts, As, grp))           # (nb,b,S,hb,P)
    return jnp.moveaxis(ys, 0, 2).reshape(b, S, H, P)


def ssm_layer(p: Dict, x, c: Dict):
    d = c["d_model"]
    di = c["expand"] * d
    P, N, G = c["headdim"], c["d_state"], c["ngroups"]
    nh = di // P
    eps = c["norm_eps"]
    m = p["mixer"]
    b, S, _ = x.shape
    h = rmsnorm(x, p["ln1"], eps)
    z = _mm("bsd,di->bsi", h, m["w_z"])
    xbc = jnp.concatenate([_mm("bsd,di->bsi", h, m["w_x"]),
                           _mm("bsd,dg->bsg", h, m["w_B"]),
                           _mm("bsd,dg->bsg", h, m["w_C"])], axis=-1)
    dt = jax.nn.softplus(_mm("bsd,dh->bsh", h, m["w_dt"]).astype(F32)
                         + m["dt_bias"].astype(F32))
    xbc = jax.nn.silu(causal_conv(xbc, m["conv_w"], m["conv_b"]))
    xs = xbc[..., :di].reshape(b, S, nh, P)
    Bm = xbc[..., di:di + G * N].reshape(b, S, G, N)
    Cm = xbc[..., di + G * N:].reshape(b, S, G, N)
    A = -jnp.exp(m["A_log"].astype(F32))
    y = ssd(xs, dt, A, Bm, Cm) + xs * m["D"].astype(x.dtype)[:, None]
    y = y.reshape(b, S, di) * jax.nn.silu(z)
    y = rmsnorm(y, m["norm_w"], eps)
    return x + _mm("bsi,id->bsd", y, m["out_proj"])


def _scan_layers(stacked, x, c: Dict, remat: bool):
    body = lambda h, lp: (ssm_layer(lp, h, c), None)
    if remat:
        body = jax.checkpoint(body)
    return jax.lax.scan(body, x, stacked)[0]


def hidden(w: Dict, tokens, c: Dict, remat: bool = False):
    """Final hidden states (b, S, d) for input tokens (b, S)."""
    return _scan_layers(w["layers"], w["embed"][tokens], c, remat)


def logits(w: Dict, tokens, c: Dict, remat: bool = False):
    x = rmsnorm(hidden(w, tokens, c, remat), w["ln_f"], c["norm_eps"])
    head = w["embed"].T if c["tie_embeddings"] else w["lm_head"]
    return _mm("bsd,dv->bsv", x, head).astype(F32)


def loss_sum(w: Dict, tokens, c: Dict):
    """Summed next-token cross-entropy of tokens (b, S+1), padded vocabulary
    entries excluded."""
    lg = logits(w, tokens[:, :-1], c, remat=True)
    lg = jnp.where(jnp.arange(lg.shape[-1]) < c["vocab_size"], lg, -jnp.inf)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(logz - ll)


# --------------------------------------------------------------------------- #
# training: gradients by rows, AdamW                                           #
# --------------------------------------------------------------------------- #
def leaf_norms(tree) -> Dict[str, float]:
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(l.astype(F32))))
                               for l in jax.tree_util.tree_leaves(t)])(tree)
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(paths, norms)}


def make_trainer(c: Dict, opt: Dict, dtype=F32):
    """(grad_fn, update_fn): the loss and gradient of a batch (b, S+1),
    taken one row at a time and averaged over all b*S tokens, and one AdamW
    step with global-norm clipping (decoupled weight decay on every leaf).
    Returns the gradient as the optimizer takes it (after clipping) too."""
    row = jax.jit(jax.value_and_grad(lambda w, t: loss_sum(w, t, c)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=0)

    def grad_fn(w, tokens):
        total, g = None, None
        for r in range(tokens.shape[0]):
            l, gr = row(w, tokens[r:r + 1])
            gr = jax.tree_util.tree_map(lambda x: x.astype(F32), gr)
            total = l if total is None else total + l
            g = gr if g is None else add(g, gr)
        n = tokens.shape[0] * (tokens.shape[1] - 1)
        return float(total) / n, jax.tree_util.tree_map(lambda x: x / n, g)

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(w, g, m, v, step):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-12))
        g = jax.tree_util.tree_map(lambda x: x * scale, g)
        m = jax.tree_util.tree_map(lambda a, x: opt["b1"] * a + (1 - opt["b1"]) * x, m, g)
        v = jax.tree_util.tree_map(lambda a, x: opt["b2"] * a + (1 - opt["b2"]) * x * x, v, g)
        c1 = 1 - opt["b1"] ** step
        c2 = 1 - opt["b2"] ** step

        def new(p, a, b):
            pf = p.astype(F32)
            d = (a / c1) / (jnp.sqrt(b / c2) + opt["eps"]) + opt["weight_decay"] * pf
            return (pf - opt["lr"] * d).astype(p.dtype)

        return jax.tree_util.tree_map(new, w, m, v), g, m, v

    return grad_fn, update


def train_readings(c: Dict, opt: Dict, seed: int, batches: List[np.ndarray],
                   dtype=F32) -> Dict[str, object]:
    """Follow ``len(batches)`` optimizer steps from the seed's weights:
    each step's loss, the per-leaf norms of the first gradient as clipped
    for the optimizer and before clipping, and the per-leaf norms of the
    weights' change over all the steps."""
    w = jax.jit(lambda s: init_weights(c, s, dtype))(jnp.int32(seed))
    w0 = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x.astype(F32) + 0, t))(w)
    grad_fn, update = make_trainer(c, opt, dtype)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), t))
    m, v = zeros(w), zeros(w)
    losses, g_first, g_raw = [], None, None
    for i, tokens in enumerate(batches):
        loss, g = grad_fn(w, jnp.asarray(tokens))
        losses.append(loss)
        if i == 0:
            g_raw = leaf_norms(g)
        w, g_used, m, v = update(w, g, m, v, jnp.float32(i + 1))
        if i == 0:
            g_first = leaf_norms(g_used)
        del g, g_used
    change = leaf_norms(jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: x.astype(F32) - y, a, b))(w, w0))
    return {"losses": losses, "grad": g_first, "grad_raw": g_raw, "change": change}


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
