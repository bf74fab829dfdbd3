"""kimi-k2-1t-a32b — trillion-parameter MoE LM [hf:moonshotai/
Kimi-K2-Instruct config.json, ``model_type`` kimi_k2].

61 layers, d_model 7,168, 64 heads of multi-head latent attention
(``q_lora_rank`` 1,536, ``kv_lora_rank`` 512, q/k no-PE 128 + RoPE 64,
v 128; RoPE θ 50,000 with YaRN scaling: factor 32 over 4,096
positions, β_fast = β_slow = 1, mscale 1), RMSNorm ε 1e-6, layer 0 a
dense SwiGLU of width 18,432, then 384 routed experts of width 2,048,
8 per token, and 1 shared expert; a sigmoid ``noaux_tc`` router (one
group) with routed scaling 2.827; an untied vocabulary of 163,840.
~1.03T total / ~32.7B active parameters. Full attention → ``long_500k``
skip; latent attention has no cache path (prefill and decode raise).

Scale policy: Adafactor (factored second moments — AdamW's 8 TB of f32
moments cannot exist), bf16 params, EP over ``model`` + FSDP over
``data`` for expert weights, microbatched train step. The bias update
rate γ = 0.001 and balance-loss weight 1e-4 are DeepSeek-V3's
(arXiv:2412.19437 §2.1.2).
"""
from repro.configs.common import ArchSpec, lm_shapes, register
from repro.models.layers import YarnScaling
from repro.models.moe import MoEConfig
from repro.models.transformer import MLAConfig, TransformerConfig


def _moe(n_experts, top_k, d_ff):
    return MoEConfig(
        n_experts=n_experts,
        top_k=top_k,
        d_ff=d_ff,
        n_shared_experts=1,
        router="sigmoid",
        routed_scaling=2.827,
        aux_loss_weight=1e-4,
    )


def make_config(shape_name: str = "train_4k") -> TransformerConfig:
    return TransformerConfig(
        vocab=163840,
        n_layers=61,
        n_dense_layers=1,
        d_model=7168,
        n_heads=64,
        n_kv_heads=64,
        head_dim=192,
        d_ff=18432,
        rope_theta=50000.0,
        rope_scaling=YarnScaling(factor=32.0,
                                 original_max_position_embeddings=4096,
                                 beta_fast=1.0, beta_slow=1.0,
                                 mscale=1.0, mscale_all_dim=1.0),
        norm_eps=1e-6,
        tie_embeddings=False,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128,
                      q_lora_rank=1536),
        moe=_moe(384, 8, 2048),
        dtype="bfloat16",
        remat=True,
    )


def make_smoke_config() -> TransformerConfig:
    return TransformerConfig(
        vocab=512,
        n_layers=3,
        n_dense_layers=1,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        head_dim=24,
        d_ff=96,
        rope_theta=50000.0,
        rope_scaling=YarnScaling(factor=32.0,
                                 original_max_position_embeddings=64,
                                 beta_fast=1.0, beta_slow=1.0),
        tie_embeddings=False,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=24),
        moe=_moe(8, 2, 64),
        dtype="float32",
        remat=False,
    )


ARCH = register(
    ArchSpec(
        name="kimi-k2-1t-a32b",
        family="lm",
        paper_ref="hf:moonshotai/Kimi-K2-Instruct",
        make_config=make_config,
        make_smoke_config=make_smoke_config,
        shapes=lm_shapes(
            long_ctx_skip=(
                "pure full-attention arch: 500k-token decode skipped "
                "per task spec (DESIGN.md §5)"
            )
        ),
        optimizer="adafactor",
        train_loss="sce",
        eval_protocol="token-rank",
        dtype="bfloat16",
        fsdp=True,
        microbatches={"train_4k": 16},
        accum_dtype="bfloat16",
        sce_bucket_size_y=1024,
    )
)
