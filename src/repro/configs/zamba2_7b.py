"""zamba2-7b [hybrid] (Zyphra Zamba2-7B-Instruct, config.json; arXiv:2411.15242,
shared-block design arXiv:2405.16712): 81 Mamba-2 layers, d_model=3584
(112 heads of 64, d_state=64, ngroups=2, d_conv=4, chunk 256). Before the
13 layers in ``hybrid_layer_ids`` one of 2 shared attention+MLP blocks
(site k: block k % 2) runs over concat(h, embedding), 7168 wide: attention
with 32 heads of 224, RoPE over all 224 dims, scores scaled by
(224 / 2) ** -0.5; an exact-GELU gated MLP 3584 -> 2 x 14336 -> 3584 with
the site's rank-128 LoRA on gate and up; the site's 3584 x 3584 linear;
the result added to the input of the site's Mamba layer. Vocab 32000, tied
head, eps 1e-5."""
from repro.models.config import ModelConfig, SSMConfig

SITES = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)


def config() -> ModelConfig:
    return ModelConfig(
        name="zamba2-7b",
        family="hybrid",
        num_layers=81,
        d_model=3584,
        num_heads=32,
        num_kv_heads=32,
        head_dim=224,
        d_ff=14336,
        vocab_size=32000,
        rope_theta=10000.0,
        activation="gelu_exact",
        norm_eps=1e-5,
        tie_embeddings=True,
        ssm=SSMConfig(d_state=64, d_conv=4, expand=2, head_dim=64, n_groups=2, chunk_size=256),
        hybrid_layer_ids=SITES,
        num_mem_blocks=2,
        adapter_rank=128,
        notes=(
            "vocab 32000 padded to 32768 (16*2048); logits past 32000 masked",
            "tied head: tie_word_embeddings is absent from config.json, so "
            "PretrainedConfig's default (True) applies",
            "use_shared_mlp_adapter: one rank-128 LoRA per site on the MLP's "
            "gate/up; use_shared_attention_adapter is false, so none on q/k/v",
            "dt is softplus(x + dt_bias) with no clamp, as the published "
            "kernel path runs with time_step_limit null; the HF torch path "
            "clamps it below at time_step_min (0.001)",
            "the Mamba gated RMSNorm uses norm_eps (1e-5), as HF's fixed 1e-5",
            "RMSNorm weights are stored as w with scale 1 + w (HF: the scale)",
        ),
    )


def smoke_config() -> ModelConfig:
    """Same structure at CPU size: 2 blocks over 4 sites (each block at two
    sites, with distinct adapters and caches), ngroups 2, uneven segments."""
    return ModelConfig(
        name="zamba2-7b-smoke",
        family="hybrid",
        num_layers=9,
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        head_dim=32,
        d_ff=128,
        vocab_size=512,
        activation="gelu_exact",
        norm_eps=1e-5,
        tie_embeddings=True,
        ssm=SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16, n_groups=2, chunk_size=8),
        hybrid_layer_ids=(2, 4, 5, 7),
        num_mem_blocks=2,
        adapter_rank=8,
    )
