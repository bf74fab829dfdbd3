"""moonlight-16b-a3b — DeepSeek-V3-architecture MoE LM [hf:moonshotai/
Moonlight-16B-A3B config.json, ``model_type`` deepseek_v3].

Published: 27 layers, d_model 2,048, 16 heads of multi-head latent
attention (``q_lora_rank`` null, ``kv_lora_rank`` 512, q/k no-PE 128 +
RoPE 64, v 128; RoPE θ 50,000, no scaling), RMSNorm ε 1e-5, layer 0 a
dense SwiGLU of width 11,264 (``first_k_dense_replace`` 1), then 64
routed experts of width 1,408, 6 per token, and 2 shared experts; a
sigmoid router selecting by ``σ(x·W_r) + b`` (``noaux_tc``, one group),
weights normalised over the chosen six and scaled by 2.446; an untied
vocabulary of 163,840.

``make_config`` is one chip's share of a deployment in which 8 chips
share each layer: expert-parallel 8 over the 64 experts and
vocabulary-parallel 8 over the 163,840 rows, the layers past the sixth
being further pipeline stages. This chip holds the dense layer 0 and 5
MoE layers, 8 experts of each (``experts_held``; the router keeps its
64 outputs), and a 20,480-row vocabulary slice for the embedding and
the head. Every width is as published. That is 669 M parameters, or
10.7 GB of float32 weights, gradients and AdamW state.

Assumed (not in the config): AdamW (lr 3e-4, β 0.9/0.95, weight decay
0.1) in place of the Muon optimizer Moonlight was trained with; the
bias update rate γ = 0.001 and the sequence-wise balance-loss weight
1e-4 (DeepSeek-V3, arXiv:2412.19437 §2.1.2).
"""
from repro.configs.common import ArchSpec, ShapeSpec, register
from repro.models.moe import MoEConfig
from repro.models.transformer import MLAConfig, TransformerConfig


def make_config(shape_name: str = "train_8k") -> TransformerConfig:
    return TransformerConfig(
        vocab=163840 // 8,
        n_layers=6,
        n_dense_layers=1,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        head_dim=192,
        d_ff=11264,
        rope_theta=50000.0,
        norm_eps=1e-5,
        tie_embeddings=False,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        moe=MoEConfig(
            n_experts=64,
            top_k=6,
            d_ff=1408,
            n_shared_experts=2,
            router="sigmoid",
            routed_scaling=2.446,
            aux_loss_weight=1e-4,
            experts_held=8,
        ),
        dtype="float32",
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
        norm_eps=1e-5,
        tie_embeddings=False,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=MoEConfig(
            n_experts=8,
            top_k=3,
            d_ff=32,
            n_shared_experts=2,
            router="sigmoid",
            routed_scaling=2.446,
            aux_loss_weight=1e-4,
            experts_held=4,
        ),
        dtype="float32",
        remat=False,
    )


ARCH = register(
    ArchSpec(
        name="moonlight-16b-a3b",
        family="lm",
        paper_ref="hf:moonshotai/Moonlight-16B-A3B",
        make_config=make_config,
        make_smoke_config=make_smoke_config,
        shapes=(ShapeSpec("train_8k", "train",
                          {"seq_len": 8192, "global_batch": 1}),),
        optimizer="adamw",
        optimizer_args={"lr": 3e-4, "b2": 0.95, "weight_decay": 0.1},
        train_loss="sce",
        eval_protocol="token-rank",
        dtype="float32",
        sce_bucket_size_y=256,
    )
)
