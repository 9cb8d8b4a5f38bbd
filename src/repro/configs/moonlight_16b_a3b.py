"""moonlight-16b-a3b [moe, latent attention] — DeepSeek-V3 block: one dense
SwiGLU layer, then 26 layers of 64 routed experts (6 per token, sigmoid
scores chosen with a correction bias, normalized and scaled by 2.446) plus
a shared expert of 2 x 1408; multi-head latent attention with a 512-wide
latent and a 64-wide rotary key shared by 16 heads
[hf:moonshotai/Moonlight-16B-A3B config.json].

``CHIP_SHARE`` is one chip's share of an 8-chip deployment: the dense
layer and 12 expert layers, experts 0-7 of 64 held (the router still
scores all 64), and the eighth of the vocabulary that holds ids 0-20479."""
from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", n_layers=27, d_model=2048, n_heads=16,
    n_kv_heads=16, d_ff=11264, vocab_size=163840, block_pattern=("attn",),
    attn_kind="mla", kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128, rope_theta=50000.0,
    n_experts=64, top_k=6, first_dense=1, d_ff_expert=1408, d_ff_shared=2816,
    router_score="sigmoid", router_bias=True, routed_scale=2.446, mlp_type="swiglu", norm="rmsnorm", norm_eps=1e-5,
    tie_embeddings=False,
)

CHIP_SHARE = CONFIG.replace(n_layers=13, n_held=8, vocab_size=20480)

# every mechanism at test width: a leading dense layer, 16 experts of which
# 4 (experts 4-7) are held, top 3, a shared expert, a 32 + 16 latent
REDUCED = CONFIG.replace(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
                         d_ff=128, d_ff_expert=32, d_ff_shared=32,
                         kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=16,
                         v_head_dim=16, n_experts=16, top_k=3, expert_lo=4,
                         n_held=4, vocab_size=512)
