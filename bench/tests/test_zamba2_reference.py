"""The Zamba2 cell's yardsticks that need no chip: the plain reference
against the program (weights, prefill, decode through the cache) and
against the published implementation (``transformers``' Zamba2), the work
counts against the program's parameter count, and the roofline reader on
a synthetic trace. All at smoke size on the CPU, seeded.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_zamba2_reference.py
"""
from __future__ import annotations

import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, work, work_hybrid  # noqa: E402
from bench.reference import zamba2_lm  # noqa: E402

CONFIG = "zamba2-7b"


@pytest.fixture(scope="module")
def smoke():
    c = harness.config(CONFIG, rehearsal=True)
    cfg = harness.program_config(c)
    w = jax.jit(lambda s: zamba2_lm.init_weights(c, s))(jnp.int32(11))
    return c, cfg, w


# --------------------------------------------------------------------------- #
# the configuration file against the program                                  #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("rehearsal", [False, True])
def test_the_file_holds_the_programs_hybrid_sizes(rehearsal):
    c = harness.config(CONFIG, rehearsal=rehearsal)
    cfg = harness.program_config(c)
    # the program's shared block reads concat(h, embedding): twice d_model wide
    assert (c["attention_hidden_size"], c["n_heads_attn"], c["attn_head_dim"], c["ffn"]) == (
        2 * cfg.d_model, cfg.num_heads, cfg.resolved_head_dim, cfg.d_ff)
    assert cfg.num_kv_heads == cfg.num_heads
    assert (c["adapter_rank"], c["num_mem_blocks"], tuple(c["hybrid_layer_ids"])) == (
        cfg.adapter_rank, cfg.num_mem_blocks, cfg.hybrid_layer_ids)
    assert c["rope_theta"] == cfg.rope_theta and cfg.activation == "gelu_exact"


def test_the_published_keys_agree_with_the_sizes_run():
    """The file copies the published config.json's keys (the cut ones
    listed in ``reduced``); they say what the program's names say."""
    c = harness.config(CONFIG)
    same = {"hidden_size": "d_model", "num_hidden_layers": "n_layer", "mamba_d_state": "d_state",
            "mamba_d_conv": "d_conv", "mamba_expand": "expand", "mamba_headdim": "headdim",
            "mamba_ngroups": "ngroups", "rms_norm_eps": "norm_eps", "intermediate_size": "ffn",
            "num_attention_heads": "n_heads_attn", "attention_head_dim": "attn_head_dim"}
    for pub, ours in same.items():
        assert c[pub] == c[ours], pub
    assert c["n_mamba_heads"] * c["mamba_headdim"] == c["expand"] * c["d_model"]
    assert c["attention_hidden_size"] == 2 * c["d_model"] == c["n_heads_attn"] * c["attn_head_dim"]
    hybrid = [i for i, t in enumerate(c["layers_block_type"]) if t == "hybrid"]
    assert hybrid == c["hybrid_layer_ids"] and len(c["layers_block_type"]) == c["n_layer"]
    p = c["published"]
    assert [i for i, t in enumerate(p["layers_block_type"]) if t == "hybrid"] == p["hybrid_layer_ids"]
    # the cut is one whole period of the published pattern: its sites are the first two
    assert c["hybrid_layer_ids"] == p["hybrid_layer_ids"][:2]


def test_the_smoke_model_reuses_each_block_at_two_sites():
    cfg = harness.program_config(harness.config(CONFIG, rehearsal=True))
    uses = Counter(k % cfg.num_mem_blocks for k in range(len(cfg.hybrid_layer_ids)))
    assert cfg.num_mem_blocks == 2 and min(uses.values()) >= 2
    assert cfg.ssm.n_groups == 2


# --------------------------------------------------------------------------- #
# counts                                                                      #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("rehearsal,count", [(False, 1_760_605_632), (True, 595_224)])
def test_work_counts_match_the_program(rehearsal, count):
    from repro.models import param_count, param_descs

    c = harness.config(CONFIG, rehearsal=rehearsal)
    assert work_hybrid.param_count(c) == c["param_count"] == count
    assert param_count(param_descs(harness.program_config(c))) == count
    leaves = jax.tree_util.tree_leaves(zamba2_lm.layout(c), is_leaf=zamba2_lm._is_leaf)
    assert sum(int(np.prod(s)) for s, _, _ in leaves) == count


def test_the_published_model_counts_its_published_parameters():
    from repro.configs import get_config
    from repro.models import param_count, param_descs

    c = harness.config(CONFIG)
    pub = {**c, **c["published"]}
    assert work_hybrid.param_count(pub) == pub["param_count"] == 7_356_749_648
    cfg = get_config("zamba2-7b")
    assert (cfg.num_layers, len(cfg.hybrid_layer_ids), cfg.num_mem_blocks) == (81, 13, 2)
    # the program pads the vocabulary to 32768 rows; the published table has 32000
    padding = (cfg.vocab_padded - cfg.vocab_size) * cfg.d_model
    assert param_count(param_descs(cfg)) - padding == 7_356_749_648


def test_decode_bytes_at_the_cut():
    c = harness.config(CONFIG)
    # bench/work.py counts the Mamba part: its weights, the head's table, the
    # logits, and the 12 layers' conv windows and states read and written
    assert work.decode_bytes(c) == (work.param_count(c) + 32768) * 4 + 46_178_304
    assert work_hybrid.kv_bytes_per_position(c) == 114_688
    # weights read once each (each block at one site), the logits, the
    # states, and the cache's positions 0..p read and one written
    at = lambda p: 1_760_605_632 * 4 + 32768 * 4 + 46_178_304 + 114_688 * (p + 2)
    assert work_hybrid.decode_bytes(c, 0) == at(0)
    assert work_hybrid.decode_bytes(c, 999) == at(999)


def _serve_run(**over):
    c = harness.config(CONFIG)
    run = {
        "driver": "serve", "conf": c, "device_kind": "TPU v5 lite",
        "trace": {"modules": {"jit__lambda(7)": {"count": 7, "seconds": 0.07},
                              "jit_argmax(3)": {"count": 7, "seconds": 0.001}}},
        "decode_module": "jit__lambda", "traced_decodes": 7,
        # s0: window turns to position 10, then 3 traced decodes (10, 11, 12);
        # s1: to 5, then 2 (5, 6); s1.1 replaced s1 while traced: 2 (0, 1)
        "sessions": {"s0": list(range(13)), "s1": list(range(7)), "s1.1": [4, 4]},
        "window_turns": [{"sid": "s0", "start": 1, "tokens": [0] * 5},
                         {"sid": "s0", "start": 6, "tokens": [0] * 4},
                         {"sid": "s1", "start": 1, "tokens": [0] * 4}],
    }
    run.update(over)
    return run


def test_the_roofline_reader_counts_each_traced_decode_at_its_position():
    reader = harness.reader("decode_hbm_roofline.hybrid")
    run = _serve_run()
    assert sorted(reader.traced_positions(run)) == [0, 1, 5, 6, 10, 11, 12]
    moved = sum(work_hybrid.decode_bytes(run["conf"], p) for p in (0, 1, 5, 6, 10, 11, 12))
    assert reader.read(run) == pytest.approx(100 * moved / 819e9 / 0.07)
    assert reader.read(_serve_run(rehearsal=True)) is None
    assert reader.read(_serve_run(trace=None)) is None


@pytest.mark.parametrize("wrong", [{"decode_module": "jit_decode_step"}, {"traced_decodes": 6}])
def test_the_roofline_reader_needs_the_decode_program_once_per_step(wrong):
    reader = harness.reader("decode_hbm_roofline.hybrid")
    with pytest.raises(RuntimeError, match="executions"):
        reader.read(_serve_run(**wrong))


def test_the_roofline_reader_needs_a_position_per_traced_decode():
    reader = harness.reader("decode_hbm_roofline.hybrid")
    run = _serve_run()
    run["sessions"] = dict(run["sessions"], s0=list(range(12)))
    with pytest.raises(RuntimeError, match="positions"):
        reader.read(run)


# --------------------------------------------------------------------------- #
# the reference against the program                                           #
# --------------------------------------------------------------------------- #
def test_reference_weights_follow_the_programs_recipe(smoke):
    from repro.models import init_params, param_descs

    c, cfg, w = smoke
    prog = init_params(param_descs(cfg), jax.random.key(11), jnp.float32)
    assert jax.tree_util.tree_structure(prog) == jax.tree_util.tree_structure(w)
    for a, b in zip(jax.tree_util.tree_leaves(prog), jax.tree_util.tree_leaves(w)):
        # the same draws over the same fan-in; jit fuses the scaling, so the
        # last bit may differ
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2.4e-7, atol=0)


def test_reference_forward_matches_the_programs_prefill(smoke):
    from repro.models import forward

    c, cfg, w = smoke
    tokens = np.random.default_rng(0).integers(0, c["vocab_size"], (2, 32)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog, _, _ = forward(cfg, w, jnp.asarray(tokens))
        ref = zamba2_lm.logits(w, jnp.asarray(tokens), c)
    # float32 on the CPU; the chunked SSD and the quadratic form, and the
    # two attentions, sum in different orders: agreement to float32
    # rounding of O(1) logits
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_reference_matches_the_programs_decode_through_the_cache(smoke):
    from repro.models import cache_descs, decode_step
    from repro.models.params import is_desc

    c, cfg, w = smoke
    n = 24
    cache = jax.tree_util.tree_map(lambda d: jnp.zeros(d.shape, jnp.float32),
                                   cache_descs(cfg, batch=1, max_len=32), is_leaf=is_desc)
    assert len(cache["kv"]) == 4 and len(cache["mamba"]) == 5  # a KV cache a site
    step = jax.jit(lambda p, ca, t, i: decode_step(cfg, p, ca, t, i))
    tokens = np.random.default_rng(1).integers(0, c["vocab_size"], n).astype(np.int32)
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(n):
            lg, cache = step(w, cache, jnp.asarray([[tokens[i]]]), jnp.asarray(i, jnp.int32))
            out.append(np.asarray(lg[0, 0, : c["vocab_size"]]))
        ref = zamba2_lm.serve_logits_fn(c)(w, tokens, 32)
    # the recurrent decode and the KV cache against the whole-sequence
    # form: float32 rounding
    np.testing.assert_allclose(np.stack(out), ref, atol=2e-4, rtol=2e-4)


# --------------------------------------------------------------------------- #
# the reference against the published implementation                          #
# --------------------------------------------------------------------------- #
def _hf_model(c, w, chunk_size):
    """``transformers``' Zamba2ForCausalLM at the smoke sizes, holding the
    reference's weights (RMSNorm scales are 1 + w; projections transposed;
    Mamba's in_proj is [z, x, B, C, dt]; an adapter's up-projection is
    [gate, up])."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    ids = c["hybrid_layer_ids"]
    hc = tf.Zamba2Config(
        vocab_size=c["vocab_size"], hidden_size=c["d_model"], num_hidden_layers=c["n_layer"],
        layers_block_type=["hybrid" if i in ids else "mamba" for i in range(c["n_layer"])],
        mamba_d_state=c["d_state"], mamba_d_conv=c["d_conv"], mamba_expand=c["expand"],
        mamba_ngroups=c["ngroups"], n_mamba_heads=c["expand"] * c["d_model"] // c["headdim"],
        chunk_size=chunk_size, intermediate_size=c["ffn"], hidden_act="gelu",
        num_attention_heads=c["n_heads_attn"], num_key_value_heads=c["n_heads_attn"],
        num_mem_blocks=c["num_mem_blocks"], use_shared_attention_adapter=False,
        adapter_rank=c["adapter_rank"], use_mem_rope=True, rope_theta=c["rope_theta"],
        rms_norm_eps=c["norm_eps"], tie_word_embeddings=True, max_position_embeddings=4096,
        attn_implementation="eager")
    torch.manual_seed(0)
    model = tf.Zamba2ForCausalLM(hc).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    one = lambda a: t(1.0 + np.asarray(a))
    given = {}

    def put(param, value):
        assert tuple(param.shape) == tuple(value.shape), (param.shape, value.shape)
        with torch.no_grad():
            param.copy_(value)
        given[id(param)] = True

    m = model.model
    put(m.embed_tokens.weight, t(w["embed"][: c["vocab_size"]]))
    put(m.final_layernorm.weight, one(w["ln_f"]))
    layer = 0
    for j, (site, n) in enumerate(zamba2_lm.segments(c)):
        for i in range(n):
            lp = jax.tree_util.tree_map(lambda a: np.asarray(a[i]), w["mamba"][j])
            hl = m.layers[layer]
            mamba = hl.mamba_decoder if i == 0 and site is not None else hl
            mx = lp["mixer"]
            put(mamba.input_layernorm.weight, one(lp["ln1"]))
            mm = mamba.mamba
            put(mm.in_proj.weight, t(np.concatenate(
                [mx["w_z"], mx["w_x"], mx["w_B"], mx["w_C"], mx["w_dt"]], axis=1).T))
            put(mm.conv1d.weight, t(mx["conv_w"].T[:, None, :]))
            put(mm.conv1d.bias, t(mx["conv_b"]))
            for k in ("dt_bias", "A_log", "D"):
                put(getattr(mm, k), t(mx[k]))
            put(mm.norm.weight, one(mx["norm_w"]))
            put(mm.out_proj.weight, t(mx["out_proj"].T))
            if i == 0 and site is not None:
                bp = jax.tree_util.tree_map(lambda a: np.asarray(a[site % c["num_mem_blocks"]]),
                                            w["blocks"])
                sp = jax.tree_util.tree_map(lambda a: np.asarray(a[site]), w["sites"])
                put(hl.linear.weight, t(sp["linear"].T))
                blk = hl.shared_transformer
                at = blk.self_attn
                heads = c["n_heads_attn"] * c["attn_head_dim"]
                put(blk.input_layernorm.weight, one(bp["ln_in"]))
                for name in ("q", "k", "v"):
                    wt = bp["attn"]["w" + name].reshape(-1, heads)
                    put(getattr(at, f"{name}_proj").weight, t(wt.T))
                put(at.o_proj.weight, t(bp["attn"]["wo"].reshape(heads, -1).T))
                put(blk.pre_ff_layernorm.weight, one(bp["ln_ff"]))
                ff = blk.feed_forward
                put(ff.gate_up_proj.weight, t(np.concatenate(
                    [bp["mlp"]["wi_gate"], bp["mlp"]["wi_up"]], axis=1).T))
                put(ff.down_proj.weight, t(bp["mlp"]["wo"].T))
                ad = ff.gate_up_proj_adapter_list[site]
                put(ad[0].weight, t(sp["lora_a"].T))
                put(ad[1].weight, t(np.concatenate([sp["lora_gate"], sp["lora_up"]], axis=1).T))
            layer += 1
    model.tie_weights()
    missing = [n for n, p in model.named_parameters() if id(p) not in given]
    assert not missing, missing
    return model, torch


# transformers' torch path (the one without the CUDA kernels) mixes up the
# axes of its chunk-to-chunk recurrence (``Zamba2MambaMixer.torch_forward``
# sums ``decay_chunk`` times the states over the target chunk, where
# ``modeling_mamba2.py`` sums over the source chunk), so a prompt longer
# than one chunk reads wrong there. The comparisons below keep its prefill
# to one chunk and carry the rest through its recurrent decode path, which
# is the SSD recurrence itself. It also clamps dt below at time_step_min
# (1e-3), which the reference does not (the published kernel path runs with
# time_step_limit null); with these weights dt is softplus of a unit-scale
# input and never that small. Tolerances: float32 in torch and in JAX on
# the CPU, summed in different orders, so float32 rounding of O(1) logits.
def test_reference_matches_the_published_prefill(smoke):
    c, _, w = smoke
    model, torch = _hf_model(c, w, chunk_size=32)
    tokens = np.random.default_rng(2).integers(0, c["vocab_size"], (1, 32))
    with torch.no_grad():
        hf = model(torch.tensor(tokens), use_cache=False).logits.numpy()
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(zamba2_lm.logits(w, jnp.asarray(tokens, jnp.int32), c))[..., : c["vocab_size"]]
    np.testing.assert_allclose(ref, hf, atol=2e-4, rtol=2e-4)


def test_reference_matches_the_published_decode_through_its_cache(smoke):
    c, _, w = smoke
    model, torch = _hf_model(c, w, chunk_size=c["chunk_size"])
    from transformers.models.zamba2.modeling_zamba2 import Zamba2HybridDynamicCache

    n, first = 32, c["chunk_size"]
    tokens = np.random.default_rng(3).integers(0, c["vocab_size"], (1, n))
    cache = Zamba2HybridDynamicCache(model.config, 1, dtype=torch.float32)
    out = []
    with torch.no_grad():
        lg = model(torch.tensor(tokens[:, :first]), past_key_values=cache, use_cache=True,
                   cache_position=torch.arange(first)).logits
        out.append(lg[0].numpy())
        for i in range(first, n):
            lg = model(torch.tensor(tokens[:, i:i + 1]), past_key_values=cache, use_cache=True,
                       cache_position=torch.tensor([i])).logits
            out.append(lg[0].numpy())
    hf = np.concatenate(out)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(zamba2_lm.logits(w, jnp.asarray(tokens, jnp.int32), c))[0, :, : c["vocab_size"]]
    np.testing.assert_allclose(ref, hf, atol=2e-4, rtol=2e-4)


def test_the_serve_control_is_not_correct():
    """At every served position, the token the bfloat16 reference puts
    first lies further below the float32 reference's best than the limit
    allows. The smoke model is too small for bfloat16 to reorder its
    logits much, so this runs the reference alone at width 128, 6 layers
    and 2 sites, over two 512-token sessions."""
    from bench.drivers import serve

    c = dict(harness.config(CONFIG, rehearsal=True), d_model=128, n_layer=6, hybrid_layer_ids=[2, 4],
             attention_hidden_size=256, attn_head_dim=64, ffn=512, headdim=32, d_state=32,
             vocab_size=2048, vocab_padded=2048)
    rng = np.random.default_rng(6)
    sessions = {s: rng.integers(0, c["vocab_size"], 512).tolist() for s in ("a", "b")}
    turns = [{"sid": s, "start": 0, "tokens": t} for s, t in sessions.items()]
    gap, n = serve.served_gap(zamba2_lm, c, 6, sessions, turns, dtype=jnp.bfloat16)
    assert n == 1024 and gap > c["limits"]["serve"]["logit_gap"], gap
