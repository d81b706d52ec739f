"""DeepSeek-V3 671B-A37B [arXiv:2412.19437; hf deepseek-ai/DeepSeek-V3].

61 layers at d=7168, all multi-head latent attention (MLA, DeepSeek-V2
arXiv:2405.04434 §2.1): 128 heads, q latent 1536, kv latent 512, per-head
q/k 128 (no rope) + 64 (rope, shared across heads for k), v 128. The first
3 layers run a dense FFN of width 18432; the other 58 are MoE with 256
routed experts of width 2048, top-8, plus 1 shared expert. Vocab 129280.
The MTP module (``num_nextn_predict_layers`` 1) only drafts tokens for
speculative decoding and is not part of this config.
"""

from repro.configs.registry import ArchConfig

CONFIG = ArchConfig(
    name="deepseek_v3",
    n_layers=61,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,
    head_dim=128,  # qk_nope_head_dim
    d_ff=18432,
    vocab_size=129280,
    stage_pattern=(("mla", "moe"),),
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    v_head_dim=128,
    first_k_dense=3,
    num_experts=256,
    top_k=8,
    num_shared_experts=1,
    moe_d_ff=2048,
)
