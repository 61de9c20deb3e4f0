"""trinity-mini — afmoe: GQA 32 over 4 heads of 128 with QK norm and a
sigmoid output gate, three sliding-window (2048) layers with RoPE then
one full NoPE layer, sandwich norms, 2 leading dense layers, then 128
routed experts top-8 (sigmoid router with a selection bias) plus one
shared expert.  [hf:arcee-ai/Trinity-Mini, config.json]"""
from ..models.config import MoEConfig, ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="trinity-mini", family="moe",
        n_layers=32, d_model=2048, n_heads=32, n_kv_heads=4, head_dim=128,
        d_ff=6144, vocab=200192, rope_theta=10000.0, norm_eps=1e-5,
        window=2048, full_attn_every=4, rope_full=False,
        qk_norm=True, attn_gate=True, sandwich_norm=True, scale_embed=True,
        moe=MoEConfig(n_experts=128, top_k=8, d_ff_expert=1024, n_shared=1,
                      first_dense=2, impl="held", score_func="sigmoid",
                      route_scale=2.826),
    )


def smoke_config() -> ModelConfig:
    # the 2 dense layers sit in the first (unscanned) period of 4, then
    # two scanned periods; top-2 of 8 experts with 4 held here
    return ModelConfig(
        name="trinity-smoke", family="moe",
        n_layers=12, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=96, vocab=256, max_seq=128,
        window=8, full_attn_every=4, rope_full=False,
        qk_norm=True, attn_gate=True, sandwich_norm=True, scale_embed=True,
        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, n_shared=1,
                      first_dense=2, impl="held", score_func="sigmoid",
                      route_scale=2.826, n_held=4),
    )
