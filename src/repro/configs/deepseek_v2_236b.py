"""DeepSeek-V2 236B (arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2 config.json).

60 layers of latent attention (MLA: q_lora 1536, kv_lora 512, qk 128 nope +
64 rope, v 128, YaRN rope factor 40); layer 0 is a dense SwiGLU of width
12288 (``first_k_dense_replace`` 1), layers 1-59 are MoE: 160 routed experts
of width 1536, top-6 by group-limited greedy routing (softmax scores, 8
groups, 3 kept), gates not renormalised and scaled by 16, plus 2 shared
experts.

Experts shard over the data axis (160/16=10) with per-expert d_ff over the
model axis (1536/16=96): pure model-axis EP would leave 28 GB of expert
weights per chip (> v5e HBM). MLA's latent cache makes long_500k deployable.
"""
from .base import LMConfig, LM_SHAPES, MLASpec, MoESpec, YarnSpec, reduced

CONFIG = LMConfig(
    name="deepseek-v2-236b",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,
    d_head=192,  # nope 128 + rope 64
    d_ff=12288,
    vocab=102400,
    moe=MoESpec(
        n_experts=160, top_k=6, n_shared=2, d_ff_expert=1536,
        n_group=8, topk_group=3, norm_topk=False, routed_scale=16.0,
    ),
    mla=MLASpec(
        kv_lora_rank=512, q_lora_rank=1536, rope_head_dim=64,
        nope_head_dim=128, v_head_dim=128,
    ),
    rope_scaling=YarnSpec(
        factor=40.0, beta_fast=32.0, beta_slow=1.0, original_max_position=4096,
        mscale=0.707, mscale_all_dim=0.707,
    ),
    first_k_dense=1,
    rope_theta=10_000.0,
    sub_quadratic=True,  # MLA latent cache -> long_500k runs
    shard_overrides=(("experts", ("data",)),),
)

# every mechanism at a CPU size: a dense first layer, 4 groups of 2 experts
# with 2 kept, a routed scale, YaRN, and 4 of the 8 routed experts held
SMOKE = reduced(
    CONFIG, name="deepseek-v2-smoke", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_head=24, d_ff=128, vocab=256,
    moe=MoESpec(
        n_experts=8, top_k=3, n_shared=2, d_ff_expert=32, n_group=4,
        topk_group=2, norm_topk=False, routed_scale=2.5, held_first=2, n_held=4,
    ),
    mla=MLASpec(kv_lora_rank=16, q_lora_rank=24, rope_head_dim=8,
                nope_head_dim=16, v_head_dim=16),
    rope_scaling=YarnSpec(factor=4.0, beta_fast=32.0, beta_slow=1.0,
                          original_max_position=16, mscale=0.707,
                          mscale_all_dim=0.707),
)

SHAPES = LM_SHAPES
