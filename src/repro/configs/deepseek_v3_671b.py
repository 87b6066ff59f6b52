"""deepseek-v3-671b [moe] — MLA, 1 shared + 256 routed top-8, MTP.
61L d_model=7168 128H dense d_ff=18432, expert 2048, vocab=129280
[arXiv:2412.19437; hf:deepseek-ai/DeepSeek-V3 config.json].
First 3 layers dense (MLA + SwiGLU 18432); MLA latent KV (kv_lora 512,
q_lora 1536, qk_nope 128, qk_rope 64, v 128) with RMSNorms on both
latents and YaRN rotary scaling (factor 40 from 4096 positions); routing
noaux_tc: sigmoid scores, a correction bias that only chooses, 8 groups of
which the top 4 (by the sum of each group's 2 best) are searched, gates
renormalised and scaled by 2.5; multi-token-prediction head (depth 1)."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,          # assignment: GQA kv=128 — realized via MLA
    d_ff=18432,
    vocab=129280,
    head_dim=128,
    v_head_dim=128,
    norm_eps=1e-6,
    rope_theta=10000.0,
    pattern=("moe",),
    first_dense=3,
    n_experts=256,
    top_k=8,
    n_shared_experts=1,
    d_expert=2048,
    router_score="sigmoid",
    n_group=8,
    topk_group=4,
    routed_scaling_factor=2.5,
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=1536,
    rope_head_dim=64,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    mtp_depth=1,
    dtype="bfloat16",
    param_dtype="bfloat16",
    remat="full",
    attn_chunk=1024,
)

# every mechanism of CONFIG at a small size: q compression and its norm,
# YaRN from 8 positions (so that the tests' prompts pass through the
# scaled band), groups of 2 experts of which 2 of 4 groups are searched
SMOKE = CONFIG.replace(
    name="dsv3-smoke",
    n_layers=3,
    first_dense=1,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    head_dim=16,
    v_head_dim=16,
    d_ff=96,
    d_expert=64,
    vocab=128,
    n_experts=8,
    top_k=2,
    n_group=4,
    topk_group=2,
    kv_lora_rank=32,
    q_lora_rank=24,
    rope_head_dim=16,
    yarn_original_max_pos=8,
    dtype="float32",
    param_dtype="float32",
    remat="none",
    attn_chunk=0,
)
