"""granite-4.0-h-small [hybrid]: 40L d4096, Mamba-2 (128 heads of 64,
d_state 128, 1 group, conv 4, expand 2) with GQA attention (32 heads,
8 KV, head 128, no position embedding) at layers 5, 15, 25, 35; an MoE
FFN on every layer, 72 routed experts of width 768 top-10 and one shared
expert of width 1536; vocab 100352, tied embeddings; embedding x12,
residual x0.22, attention scale 1/128, logits /16
[hf:ibm-granite/granite-4.0-h-small config.json]."""
import dataclasses

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    arch_id="granite-4.0-h-small", family="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8,
    d_ff=768, vocab=100352, head_dim=128,
    moe_experts=72, moe_top_k=10, moe_every=1, moe_shared_ff=1536,
    ssm_state=128, ssm_conv=4, ssm_expand=2, ssm_head_dim=64, ssm_groups=1,
    attn_every=10, attn_offset=5, rope=False, tie_embeddings=True,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0078125, logits_scaling=16.0,
    subquadratic=True,
)

#: the deployment whose one chip ``chip_share`` holds
EXPERT_PARALLEL = 9       # 72 experts over 9 chips, 8 on each
PIPELINE_STAGES = 4       # one period of 10 layers a stage


def chip_share(cfg: ModelConfig = CONFIG, chips: int = EXPERT_PARALLEL) -> dict:
    """The parameter specs of the first chip's share of ``cfg`` when
    ``chips`` share each layer's experts and each pipeline stage holds one
    period of layers: for ``CONFIG``, 10 layers (published: 40,
    ``PIPELINE_STAGES`` stages) and 72 / 9 = 8 routed experts of each
    layer in the expert banks (published: 72).  The router keeps its 72
    outputs; attention, Mamba, the shared expert and the whole vocabulary
    are held whole."""
    import jax

    from repro.dist.sharding import ParamSpec
    from repro.models import model

    def cut(s: ParamSpec) -> ParamSpec:
        if "expert_in" not in s.axes:  # the router, and all else outside the expert banks
            return s
        i = s.axes.index("experts")
        return dataclasses.replace(s, shape=s.shape[:i] + (s.shape[i] // chips,) + s.shape[i + 1:])

    stage = dataclasses.replace(cfg, n_layers=cfg.attn_every)
    return jax.tree.map(cut, model.param_specs(stage), is_leaf=lambda s: isinstance(s, ParamSpec))


SMOKE = ModelConfig(
    arch_id="granite-4.0-h-small-smoke", family="hybrid",
    n_layers=10, d_model=64, n_heads=4, n_kv_heads=2,
    d_ff=32, vocab=256, head_dim=16,
    moe_experts=8, moe_top_k=3, moe_every=1, moe_shared_ff=48,
    ssm_state=16, ssm_conv=4, ssm_expand=2, ssm_head_dim=16, ssm_groups=1,
    ssm_chunk=16, attn_every=10, attn_offset=5, rope=False, tie_embeddings=True,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0078125, logits_scaling=16.0,
    subquadratic=True, remat="none",
    param_dtype="float32", compute_dtype="float32",
)
