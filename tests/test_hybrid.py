"""The Zamba2 hybrid's pieces in the program: the weight draw's fan-in, the
Mamba mixer's per-group gated norm, the parameter layout, and what a
session's restore replays."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import LocalCluster, spans
from repro.models import cache_descs, init_params, param_descs
from repro.models.layers import attention, attn_descs
from repro.models.params import PDesc, fan_in, stack
from repro.models.ssm import gated_rms_norm
from repro.models.transformer import hybrid_plan
from repro.train.serve import DecodeSessionStateObject


# --------------------------------------------------------------------------- #
# weight draw                                                                  #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("desc,want", [
    (PDesc((7168, 32, 224), ("embed", "heads", None)), 7168),
    (PDesc((7168, 8, 224), ("embed", "kv_heads", None)), 7168),
    (PDesc((32, 224, 3584), ("heads", None, "embed")), 32 * 224),
    (PDesc((3584, 7168), ("embed", "ffn")), 3584),
    (PDesc((40, 1024, 512), ("experts", "embed", "expert_ffn")), 1024),
])
def test_fan_in_is_the_contracted_width(desc, want):
    assert fan_in(desc) == want
    assert fan_in(stack(desc, 12)) == want  # a stacked layer draws as one


def test_a_seeded_attention_layers_scores_stay_order_one():
    """Zamba2's attention reads 7168 wide in 32 heads of 224: drawn over the
    contracted width, its queries and keys are unit-scale, so the scaled
    scores are O(1) and the softmax is far from one-hot."""
    cfg = get_config("zamba2-7b")
    a, hd = 2 * cfg.d_model, cfg.resolved_head_dim  # concat(h, embedding)
    p = init_params(attn_descs(cfg, d_in=a), jax.random.key(0), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 64, a), jnp.float32)  # an RMSNorm's output
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
    scores = jnp.einsum("bsnh,btnh->bnst", q, k) * (hd / 2) ** -0.5
    assert 0.5 < float(jnp.std(q)) < 2.0
    assert 0.5 < float(jnp.std(scores)) < 3.0
    out, _ = attention(p, x, cfg, jnp.arange(64)[None], scale=(hd / 2) ** -0.5)
    assert out.shape == (1, 64, cfg.d_model) and float(jnp.std(out)) < 3.0


# --------------------------------------------------------------------------- #
# the Mamba mixer's gated norm                                                 #
# --------------------------------------------------------------------------- #
def _norm_inputs(di=128):
    ks = jax.random.split(jax.random.key(5), 3)
    y = jax.random.normal(ks[0], (2, 16, di), jnp.float32)
    z = jax.random.normal(ks[1], (2, 16, di), jnp.float32)
    w = jax.random.normal(ks[2], (di,), jnp.float32) * 0.1
    return y, z, w


def test_one_group_norms_as_the_whole_width_did():
    """With ngroups 1 the per-group norm is the whole-width gated RMSNorm
    the mixer had before, bit for bit."""
    y, z, w = _norm_inputs()
    eps = 1e-6

    def whole_width(y, z, w):
        y = y * jax.nn.silu(z)
        var = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1, keepdims=True)
        return (y.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(y.dtype) * (1.0 + w)

    got = jax.jit(lambda *a: gated_rms_norm(*a, 1, eps))(y, z, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(jax.jit(whole_width)(y, z, w)))


def test_two_groups_norm_each_half_apart():
    """ngroups 2: each half of d_inner is normalised by its own mean square
    (Zamba2RMSNormGated's group_size = d_inner / ngroups)."""
    y, z, w = _norm_inputs()
    got = gated_rms_norm(y, z, w, 2, 1e-5)
    scaled = gated_rms_norm(y * jnp.concatenate([jnp.full(64, 5.0), jnp.ones(64)]), z, w, 2, 1e-5)
    # the second half does not see the first half's scale; the first half's
    # norm takes it out, up to eps (1e-5 against a mean square of ~0.3)
    np.testing.assert_array_equal(np.asarray(scaled[..., 64:]), np.asarray(got[..., 64:]))
    np.testing.assert_allclose(np.asarray(scaled), np.asarray(got), rtol=1e-4, atol=1e-5)
    g = (y * jax.nn.silu(z))[..., 64:]
    want = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5) * (1 + w[64:])
    np.testing.assert_allclose(np.asarray(got[..., 64:]), np.asarray(want), rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(got - gated_rms_norm(y, z, w, 1, 1e-5)))) > 1e-3


# --------------------------------------------------------------------------- #
# layout                                                                       #
# --------------------------------------------------------------------------- #
def test_the_layout_holds_each_shared_weight_once():
    """Shared blocks once each, adapters and linear once per site in site
    order, the Mamba layers as one stack per run between sites, and one KV
    cache per site beside each run's SSM state."""
    cfg = get_config("zamba2_7b_l12")
    assert (cfg.num_layers, cfg.hybrid_layer_ids) == (12, (6, 11))
    assert hybrid_plan(cfg) == [(None, 6), (0, 5), (1, 1)]
    d = param_descs(cfg)
    assert d["blocks"]["attn"]["wq"].shape == (2, 7168, 32, 224)
    assert d["blocks"]["attn"]["wo"].shape == (2, 32, 224, 3584)
    assert d["blocks"]["mlp"]["wi_gate"].shape == (2, 3584, 14336)
    assert d["sites"]["lora_a"].shape == (2, 3584, 128)
    assert d["sites"]["lora_gate"].shape == (2, 128, 14336)
    assert d["sites"]["linear"].shape == (2, 3584, 3584)
    assert [m["mixer"]["w_z"].shape[0] for m in d["mamba"]] == [6, 5, 1]
    assert "lm_head" not in d  # tied
    c = cache_descs(cfg, batch=1, max_len=4096)
    assert [kv["k"].shape for kv in c["kv"]] == [(1, 4096, 32, 224)] * 2
    assert [m["state"].shape for m in c["mamba"]] == [(n, 1, 112, 64, 64) for n in (6, 5, 1)]
    full = get_config("zamba2_7b")
    assert hybrid_plan(full)[0] == (None, 6) and hybrid_plan(full)[-1] == (12, 4)


# --------------------------------------------------------------------------- #
# a session's restore                                                          #
# --------------------------------------------------------------------------- #
def test_a_hybrid_sessions_restore_rebuilds_kv_and_state(tmp_path):
    """Restore replays the surviving tokens through the decode step: the
    KV caches and the Mamba state come back as they were, and the replay is
    one ``session.rebuild`` span that counts both."""
    cfg = get_config("zamba2_7b", smoke=True)
    params = init_params(param_descs(cfg), jax.random.key(0), jnp.float32)
    spans.enable()
    spans.clear()
    try:
        with LocalCluster(tmp_path, group_commit_interval=0.01) as cluster:
            mk = lambda: DecodeSessionStateObject(tmp_path / "s", cfg, params, max_len=16)
            sess = cluster.add("s", mk)
            sess.generate(6)
            assert sess.stream_durable(timeout=30) is not None
            before = jax.tree_util.tree_map(np.asarray, sess._cache)
            tokens = list(sess.tokens)
            cluster.kill("s")
            assert sess.params is None and sess._cache is None  # the dead one holds no device memory
            back = cluster.get("s")
            assert back.tokens == tokens
            jax.tree_util.tree_map(
                lambda a, b: np.testing.assert_allclose(a, np.asarray(b), rtol=1e-5, atol=1e-6),
                before, back._cache)
        recs = [r for r in spans.records() if r.name == "session.rebuild"]
    finally:
        spans.disable()
        spans.clear()
    last = recs[-1]
    assert last.attrs["tokens"] == len(tokens) == 6
    assert last.attrs["kv_bytes"] == 4 * 2 * 16 * 4 * 32 * 4  # 4 sites, k and v, 16 x 4 x 32 floats
    mamba = [n for _, n in hybrid_plan(cfg)]
    di = 128
    per_layer = (3 * (di + 2 * 2 * 16) + 8 * 16 * 16) * 4  # conv window and SSM state
    assert last.attrs["state_bytes"] == sum(mamba) * per_layer
