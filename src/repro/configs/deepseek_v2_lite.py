"""DeepSeek-V2-Lite as the agentic-RAG generator (search and chat), one
chip's share of a 4-way expert-parallel deployment on a 2x2 v5e host.

[huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]
27 layers at d_model 2,048; layer 0 dense (FFN 10,944), layers 1-26 MoE
with 64 routed experts of width 1,408 (softmax, greedy top-6, gates not
renormalised, scaling 1) and 2 shared experts; MLA with 16 heads,
kv_lora_rank 512, no q-LoRA, qk head 128 + 64 rope, v head 128, YaRN
(factor 40 over 4,096 positions, mscale 0.707); untied vocabulary of
102,400.  Attention, the dense layer, the shared experts, the router, the
embedding and the head are replicated over the four chips; each chip
holds 16 of each MoE layer's 64 routed experts, and this one holds
experts 0-15: 4,910,345,728 parameters (15,706,484,224 whole).
"""
from repro.configs.base import MLAConfig, MoEConfig, ModelConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,            # v head dim; the qk dims come from MLA
    d_ff=10944,              # intermediate_size: the dense layer's width
    vocab_size=102400,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    moe=MoEConfig(num_experts=64, num_shared_experts=2, top_k=6, d_ff=1408,
                  first_k_dense=1, dense_d_ff=10944, norm_topk_prob=False,
                  routed_scaling_factor=1.0, num_experts_held=16,
                  first_expert=0),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128,
                  yarn=YarnConfig(factor=40.0,
                                  original_max_position_embeddings=4096,
                                  beta_fast=32.0, beta_slow=1.0,
                                  mscale=0.707, mscale_all_dim=0.707)),
)

