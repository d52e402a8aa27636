"""mellum2-12b-a2.5b [moe]: 28L d_model=2304 32H (GQA kv=4, head 128),
64 experts of width 896, top-8 with renormalised gates, no shared expert,
vocab=98304 untied.  Attention repeats a period of four layers: three
1024-token sliding-window layers (RoPE θ 5e5), then one full layer (YaRN
×16 over 8192 original positions).
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct]

Set here where the config is silent: a softmax router; no QK-norm; no
router balance loss (the config names no coefficient)."""

from repro.configs.base import ModelConfig, Yarn, register

CONFIG = register(ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=7168,               # intermediate_size: unused, every layer sparse
    moe_d_ff=896,
    vocab_size=98_304,
    norm_eps=1e-6,
    tie_embeddings=False,
    rope_theta=500_000.0,
    sliding_window=1024,
    global_every=4,          # 3 sliding : 1 full
    global_rope_theta=500_000.0,
    global_rope_yarn=Yarn(factor=16.0, original_max_position=8192,
                          beta_fast=32.0, beta_slow=1.0,
                          attention_factor=1.2772588722239782),
    num_experts=64,
    top_k=8,
    num_shared_experts=0,
    router_aux_coef=0.0,
))
