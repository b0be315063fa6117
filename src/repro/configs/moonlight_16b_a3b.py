"""Moonlight-16B-A3B (moonshotai; ``model_type: deepseek_v3``), at the
values of its published config.json
(https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json):
27 layers, hidden 2048, vocab 163840, untied head, RMSNorm eps 1e-5, rope
theta 50000, context 8192.

- Latent attention: ``q_lora_rank`` null (q is one 2048 -> 16 x 192
  projection), ``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128,
  ``qk_rope_head_dim`` 64 (one rotary key shared by all heads),
  ``v_head_dim`` 128; softmax scale 192^-0.5.
- Layer 0 (``first_k_dense_replace`` 1) is a dense SwiGLU of width 11264;
  the other 26 are MoE: 64 routed experts of width 1408, top-6, sigmoid
  scores, ``noaux_tc`` (a bias chooses experts but does not weight
  them), ``n_group`` / ``topk_group`` 1, ``norm_topk_prob`` true,
  ``routed_scaling_factor`` 2.446, and 2 shared experts (one SwiGLU of
  width 2816).  No token is dropped.
"""
from repro.models.config import BlockSpec, MLAConfig, ModelConfig, MoEConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b",
        arch_type="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,              # v_head_dim (the heads' output width)
        d_ff=1408,
        vocab_size=163840,
        mlp_type="swiglu",
        pattern=(BlockSpec("mla", "moe"),),
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        first_dense=1,
        d_ff_dense=11264,
        moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                      num_shared=2, d_ff_shared=2816, impl="dropless",
                      router="sigmoid", routed_scaling=2.446),
        rope_theta=50000.0,
        norm_eps=1e-5,
        tie_embeddings=False,
    )


def smoke() -> ModelConfig:
    """CPU size: one leading dense layer and one MoE layer, 4 experts
    (top-2, one shared), a 32-wide latent."""
    return config().replace(
        num_layers=2, d_model=128, num_heads=4, num_kv_heads=4, head_dim=32,
        d_ff=64, vocab_size=512, dtype="float32", remat=False,
        d_ff_dense=256,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=32,
                      qk_rope_head_dim=16, v_head_dim=32),
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64,
                      num_shared=1, d_ff_shared=64, impl="dropless",
                      router="sigmoid", routed_scaling=2.446),
    )
