"""deepseek-v2-lite [moe] — MLA (kv_lora 512, no q compression), 2 shared +
64 routed experts top-6, YaRN x40 [arXiv:2405.04434; hf
deepseek-ai/DeepSeek-V2-Lite config.json].

As one chip of an expert-parallel deployment trains it: each MoE layer's
64 experts divided over 8 chips (experts 0-7 held here, the router
keeping all 64), the depth cut to the dense layer and five MoE layers
(the other 21 on further pipeline stages) and an eighth of the
vocabulary.  Every width is as published."""
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=6, d_model=2048, n_heads=16, n_kv=16, d_ff=10944,
    vocab=12800, norm_eps=1e-6,
    mla=MLAConfig(kv_lora=512, q_lora=None, d_nope=128, d_rope=64, d_v=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  first_dense=1, n_held=8,
                  norm_topk_prob=False, seq_aux=True, aux_alpha=0.001),
    rope_scaling=YaRNConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    remat="full", train_microbatches=2,
)
