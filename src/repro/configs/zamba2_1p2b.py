"""zamba2-1.2b [hybrid] (arXiv:2411.15242): Mamba-2 backbone with one shared
attention+MLP block (the Zamba2 block of ``configs/zamba2_7b.py``).
38L d_model=2048, attention over concat(h, embedding) 4096 wide in 32 heads
of 128, d_ff=8192, ssm_state=64, vocab=32000; sites every 6 layers.

Unverified: no published config.json of the 1.2B model is at hand (the
catalog holds Zamba2 only at 7B), so these widths, the sites, ngroups 1 and
the adapters are recalled, not checked. The published 1.2B may also carry
LoRA adapters on q/k/v (use_shared_attention_adapter), which the program
does not build."""
from repro.models.config import ModelConfig, SSMConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="zamba2-1.2b",
        family="hybrid",
        num_layers=38,
        d_model=2048,
        num_heads=32,
        num_kv_heads=32,
        head_dim=128,
        d_ff=8192,
        vocab_size=32000,
        activation="gelu_exact",
        norm_eps=1e-5,
        tie_embeddings=True,
        ssm=SSMConfig(d_state=64, d_conv=4, expand=2, head_dim=64, n_groups=1),
        hybrid_layer_ids=(6, 12, 18, 24, 30, 36),
        num_mem_blocks=1,
        adapter_rank=128,
        notes=(
            "vocab 32000 padded to 32768 (16*2048)",
            "one shared block at 6 sites, each with its own KV cache, LoRA "
            "and linear",
            "widths, sites and adapters unverified (no published config here)",
        ),
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="zamba2-smoke",
        family="hybrid",
        num_layers=5,
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        head_dim=32,
        d_ff=128,
        vocab_size=512,
        activation="gelu_exact",
        norm_eps=1e-5,
        tie_embeddings=True,
        ssm=SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16, n_groups=1, chunk_size=8),
        hybrid_layer_ids=(2, 4),
        num_mem_blocks=1,
        adapter_rank=8,
    )
